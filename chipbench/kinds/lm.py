"""Decoder LMs trained the way users call them (DeepSeek-V2 first):

    cfg = repro.configs.get_config(arch)
    model = repro.models.api.get_model(cfg)
    step, *_ = repro.launch.steps.build_train_step(model, mesh, shape,
                                                   TrainSettings(...))
    for batch in Batcher({"tokens": ..., "labels": ...}, batch_size=B,
                         mesh=mesh):
        state, metrics = step(state, batch)

A sample is one sequence.  The configuration's file holds the published
keys (HF ``config.json`` names) as this chip runs them, and names the
program's architecture (``program``: ``arch`` and ``size``, ``full`` or
``smoke``); ``build`` cuts the program's configuration as the file's
``reduced`` keys say (``cut``) and holds it to every key the file
states.  The traffic (``chipbench/traffic/``) gives the sequences the
host holds, their length, the batch and the Zipf exponent of
``repro.data.token_stream``, whose ids the vocabulary slice bounds.  The
leaves are named by their tree paths (``layers/moe/w_gate``), as the
reference (``references/<reference>.py``) names them.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh

from chipbench import checks, spec, traffic, work
from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.data import Batcher, token_stream
from repro.kernels import ops
from repro.launch import steps
from repro.models.api import get_model

# the model, its mesh and training settings, and the step's state layout
Program = collections.namedtuple("Program", "model mesh settings shardings")


def batch(cell) -> int:
    return int(cell.traffic["batch"])


def seq_len(cell) -> int:
    return int(cell.traffic["seq_len"])


# the program's field for each key a configuration's file may cut
CUTS = {
    "num_hidden_layers": lambda c: {"n_layers": c["num_hidden_layers"]},
    "vocab_size": lambda c: {"vocab_size": c["vocab_size"]},
    "n_routed_experts": lambda c: {"expert_range": tuple(c["experts_held"])},
}


def cut(cfg, conf: dict):
    """``cfg`` cut as the configuration's file ``reduced`` says."""
    fields = {}
    for key in conf.get("reduced", {}):
        fields.update(CUTS[key](conf))
    return cfg.replace(**fields)


def model_config(cell):
    """The program's configuration of the architecture the file names,
    cut as the file says."""
    prog = cell.config["program"]
    get = smoke_config if prog["size"] == "smoke" else get_config
    return cut(get(prog["arch"]), cell.config)


def stated(cfg) -> dict:
    """The configuration's file keys that the program's configuration
    fixes, as the program holds them."""
    lo, hi = cfg.held_experts
    return {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "experts_routed_over": cfg.n_experts,
        "experts_held": [lo, hi],
        "n_routed_experts": hi - lo,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_hidden_layers": cfg.n_layers,
        "vocab_size": cfg.vocab_size,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": 1,     # the program scales by none
        "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim},
        "tie_word_embeddings": cfg.tie_embeddings,
        "aux_loss_alpha": cfg.router_aux_coef,
        "init_std": cfg.init_std,
        "dtype": cfg.dtype,
        "param_dtype": cfg.param_dtype,
    }


def dataset(cell, seed: int) -> dict[str, np.ndarray]:
    """{"tokens", "labels"}: (sequences, seq_len) int32 each, the labels
    the next token, from one ``token_stream`` over the vocabulary slice."""
    t = cell.traffic
    n, length = int(t["sequences"]), seq_len(cell)
    stream = token_stream(n * (length + 1), int(cell.config["vocab_size"]),
                          seed=seed, zipf_a=float(t["zipf_a"]))
    rows = stream.reshape(n, length + 1)
    return {"tokens": np.ascontiguousarray(rows[:, :-1]),
            "labels": np.ascontiguousarray(rows[:, 1:])}


def build(cell, devices: list, kernel_mode: str):
    """(program, step), the program's configuration checked against the
    configuration's file."""
    cfg = model_config(cell)
    want = stated(cfg)
    wrong = {k: (cell.config[k], v) for k, v in want.items()
             if cell.config[k] != v}
    if wrong:
        raise SystemExit(f"chipbench: {cfg.name} differs from "
                         f"{cell.config_name} (file, program): {wrong}")
    mode = ops.resolve_mode(None)
    if mode != kernel_mode:
        raise SystemExit(f"chipbench: dispatch chose kernel mode {mode!r}, "
                         f"not {kernel_mode!r}")
    o = cell.config["optimizer"]
    settings = steps.TrainSettings(
        learning_rate=o["lr"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"], b2=o["b2"])
    if (o["b1"], o["eps"]) != (0.9, 1e-8):
        raise SystemExit("chipbench: the training step's AdamW has b1 0.9 "
                         "and eps 1e-8")
    model = get_model(cfg)
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shape = ShapeSpec(cell.name, seq_len(cell), batch(cell), "train")
    step, st_sh, _, _ = steps.build_train_step(model, mesh, shape, settings)
    print(f"{cell.name}: {cfg.name}, {cfg.n_layers} layers, experts "
          f"{cfg.held_experts} of {cfg.n_experts}, batch {batch(cell)} x "
          f"{seq_len(cell)}, kernel mode {mode}", flush=True)
    return Program(model, mesh, settings, st_sh), step


def init_state(cell, program: Program, seed: int):
    return jax.jit(lambda k: steps.init_train_state(
        program.model, program.settings, k),
        out_shardings=program.shardings)(traffic.weights_key(seed))


def feed(cell, program: Program, data):
    return Batcher(data, batch_size=batch(cell), mesh=program.mesh)


def _named(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree, scale):
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for a in jax.tree.leaves(tree)]) * scale


def first_steps(cell, step, state, feed):
    """Take the first CHECK_STEPS steps through the window's own step and
    feed; return (state, readings).  The parameters' change is taken on
    the host in float64, against a copy made before the first step."""
    b1 = cell.config["optimizer"]["b1"]
    names = _named(state["params"])
    before = jax.device_get(state["params"])
    losses = []
    for t in range(checks.CHECK_STEPS):
        state, metrics = step(state, next(feed))
        losses.append(metrics["loss"])
        if t == 0:
            # AdamW's first moment after one step is (1 - b1) * g, g the
            # clipped gradient; read it before the next step takes it
            grads = _norms(state["opt"]["m"], jnp.float32(1.0 / (1.0 - b1)))
    grads = dict(zip(names, map(float, jax.device_get(grads))))
    after = jax.device_get(state["params"])
    change = {n: float(np.linalg.norm(np.asarray(a, np.float64)
                                      - np.asarray(b, np.float64)))
              for n, a, b in zip(names, jax.tree.leaves(after),
                                 jax.tree.leaves(before))}
    del before, after
    log_routing(metrics)
    readings = checks.Readings(
        tuple(float(v) for v in jax.device_get(losses)), grads, change)
    return state, readings


def log_routing(metrics) -> None:
    """The step's routing imbalance: tokens to the busiest held expert
    and to a held expert on average."""
    if "expert_tokens_max" in metrics:
        top, mean = jax.device_get((metrics["expert_tokens_max"],
                                    metrics["expert_tokens_mean"]))
        print(f"routing: expert_tokens_max {float(top)!r}, "
              f"expert_tokens_mean {float(mean)!r}", flush=True)


def reference(cell, seed: int, data, device, planted: str | None = None):
    """The plain reference over the first steps' sequences; ``planted``
    puts the bf16 control (``"control"``) or a fault of the reference's
    in the program's place."""
    n = checks.CHECK_STEPS * batch(cell)
    shape = (checks.CHECK_STEPS, batch(cell), seq_len(cell))
    kw = ({"dtype": "bfloat16"} if planted == "control"
          else {"fault": planted})
    return spec.load_reference(cell).train(
        traffic.weights_key(seed), cell.config,
        data["tokens"][:n].reshape(shape), data["labels"][:n].reshape(shape),
        device=device, **kw)


def samples_per_step(cell) -> int:
    return batch(cell)


def flops_per_sample(cell) -> float:
    """Model operations of one training sequence: 6 × the forward
    matmuls' multiply-adds (dgrad and wgrad twice the forward), causal
    attention over (L + 1) / 2 keys a query, the routed experts at the
    expected k · held / E of them a token, no recomputation."""
    c = cell.config
    d, h, length = c["hidden_size"], c["num_attention_heads"], seq_len(cell)
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r = c["kv_lora_rank"]
    lo, hi = c["experts_held"]
    f = c["moe_intermediate_size"]
    attn = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d + h * (dn + dr + dv) * (length + 1) / 2)
    dense = 3 * d * c["intermediate_size"]
    routed = (c["num_experts_per_tok"] * (hi - lo) / c["experts_routed_over"]
              * 3 * d * f)
    moe = d * c["experts_routed_over"] + 3 * d * c["n_shared_experts"] * f
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    macs = (c["num_hidden_layers"] * attn + n_dense * dense
            + n_moe * (moe + routed) + d * c["vocab_size"])
    return 6.0 * macs * length


def ragged_dot_calls(m: float, k: int, n: int, groups: int) -> list[work.Call]:
    """One (m, k) x (groups, k, n) grouped matmul of the held experts and
    its two backward passes: 2·m·k·n operations each; operands read once
    in bf16, the result written once in f32."""
    return [work.Call("ragged_dot", 2.0 * m * k * n, bytes_)
            for bytes_ in (2 * m * k + 2 * groups * k * n + 4 * m * n,
                           2 * m * n + 2 * groups * k * n + 4 * m * k,
                           2 * m * k + 2 * m * n + 4 * groups * k * n)]


def kernel_calls(cell) -> list[work.Call]:
    """The step's kernels (``tpu_custom_call``s): the loss's softmax
    cross-entropy over every token, forward and backward, and XLA's own
    grouped-matmul kernel for the held experts' ``ragged_dot``s (gate, up
    and down, forward, dgrad and wgrad, in every MoE layer) over the
    (token, slot) pairs routed here, at their expected count
    tokens · k · held / E."""
    c = cell.config
    tokens = batch(cell) * seq_len(cell)
    lo, hi = c["experts_held"]
    pairs = tokens * c["num_experts_per_tok"] * (hi - lo) / c[
        "experts_routed_over"]
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    per_layer = (2 * ragged_dot_calls(pairs, d, f, hi - lo)
                 + ragged_dot_calls(pairs, f, d, hi - lo))
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return ([work.xent_fwd(tokens, int(c["vocab_size"])),
             work.xent_bwd(tokens, int(c["vocab_size"]))]
            + n_moe * per_layer)
