"""Multi-pod dry-run: prove the distribution config is coherent by
lowering + compiling every (architecture × input shape × mesh) cell and
extracting the roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json

Results are merged into the --out JSON (incremental across invocations).
"""

# The VERY FIRST lines — before ANY other import, jax locks device count
# on first init.  512 host devices cover both the 16x16 pod and the
# 2x16x16 multi-pod mesh.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, shape_cells  # noqa: E402
from repro.configs.base import ModelConfig, ShapeSpec  # noqa: E402
from repro.core.planner import TPUTarget  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.parallel.sharding import AxisRules, DEFAULT_RULES  # noqa: E402


# ---------------------------------------------------------------- helpers

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather-start|all-gather|all-reduce-start|all-reduce"
    r"|reduce-scatter|all-to-all|collective-permute-start"
    r"|collective-permute)\b")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
                       r"\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
          "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1}


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, float]:
    """Sum RESULT sizes of every collective op in the (per-device) HLO.

    Lines look like:  %ag = bf16[8,1024]{1,0} all-gather(...), ...
    The result shape of an op line is the first shape on the line; for
    started async pairs we count the -start op only.
    """
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done." in line:
            continue  # async pairs: count the -start half only
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group(1).replace("-start", "")
        sm = _SHAPE_RE.search(line)
        if not sm:
            continue
        out[op] = out.get(op, 0.0) + _shape_bytes(sm.group(1), sm.group(2))
    return out


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n * tokens


def param_count(cfg: ModelConfig) -> float:
    """Total parameters (approximate closed form per family)."""
    d, l, v = cfg.d_model, cfg.n_layers, cfg.vocab_size
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family in ("dense", "vlm"):
        hd = cfg.resolved_head_dim
        attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * hd * d
        mlp = 3 * d * cfg.d_ff
        return emb + l * (attn + mlp)
    if cfg.family == "moe":
        hd = cfg.resolved_head_dim
        attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * hd * d
        moe = cfg.n_experts * 3 * d * cfg.moe_d_ff + d * cfg.n_experts
        shared = 3 * d * cfg.n_shared_experts * cfg.moe_d_ff
        return emb + l * (attn + moe + shared)
    if cfg.family == "ssm":
        di, g, n_s, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
        proj = d * (2 * di + 2 * g * n_s + h) + di * d
        return emb + l * proj
    if cfg.family == "hybrid":
        di, g, n_s, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
        mamba = d * (2 * di + 2 * g * n_s + h) + di * d
        hd = cfg.resolved_head_dim
        shared = (2 * d) * d + d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * hd * d + 3 * d * cfg.d_ff
        return emb + l * mamba + shared
    if cfg.family == "encdec":
        hd = cfg.resolved_head_dim
        attn = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * hd * d
        mlp = 3 * d * cfg.d_ff
        enc = cfg.n_encoder_layers * (attn + mlp)
        dec = cfg.n_layers * (2 * attn + mlp)
        return emb + enc + dec
    raise ValueError(cfg.family)


def active_param_count(cfg: ModelConfig) -> float:
    """Active params per token (MoE: top-k of E experts)."""
    total = param_count(cfg)
    if cfg.family != "moe":
        return total
    d, l = cfg.d_model, cfg.n_layers
    all_experts = l * cfg.n_experts * 3 * d * cfg.moe_d_ff
    active_experts = l * cfg.experts_per_token * 3 * d * cfg.moe_d_ff
    return total - all_experts + active_experts


# ---------------------------------------------------------------- lowering

# The §Perf-winning recipes, applied by ``--plan optimized``.  Family-aware
# (validated per cell, EXPERIMENTS.md §Perf):
#   train/dense+vlm+ssm+hybrid+encdec — pure-FSDP layout (batch over every
#     mesh axis, no TP activations, 2D-sharded weights) + fused CE +
#     one-hot embed + chunked flash attention: 2–19×.
#   train/moe — pure-FSDP breaks the grouped expert dispatch (measured
#     0.13×); kv-replication only (1.6×).
#   prefill — already memory-bound; overrides are a wash (±1%): baseline.
#   decode/dense+vlm — 2D-TP weights, replicated per-token activations
#     (flash-decoding cache rules from _rules_for still apply): 1.3–5.3×.
#   decode/ssm+hybrid+moe+encdec — baseline already near-optimal; the
#     serve overrides regressed them (0.2–0.9×): baseline.
_TRAIN_PURE_FSDP = (
    {"activation_batch": ("pod", "data", "model"),
     "cache_batch": ("pod", "data", "model"),
     "activation_heads": None, "activation_kv_heads": None,
     "activation_mlp": None, "activation_vocab": None,
     "activation_exp": None, "kv_heads": None, "table_embed": None},
    {"attn_chunk_threshold": 2048 * 2048, "fused_ce": True,
     "embed_onehot": True},
)
_TRAIN_KV_REP = (
    {"kv_heads": None, "activation_kv_heads": None},
    {},
)
_DECODE_SERVE = (
    {"embed": None, "table_embed": None, "mlp": ("model", "data"),
     "activation_mlp": ("model", "data"), "activation_batch": None,
     "activation_vocab": ("model", "data"), "vocab": ("model", "data")},
    {},
)
_BASELINE = ({}, {})


def optimized_plan(kind: str, family: str,
                   n_kv_heads: int = 0, model_ways: int = 16
                   ) -> tuple[dict, dict]:
    if kind == "train":
        if family == "moe":
            # kv replication only pays when kv-heads don't divide the TP
            # axis (measured: 1.6× for granite-moe kv=8, 0.85× for
            # qwen2-moe kv=16)
            if n_kv_heads and n_kv_heads % model_ways != 0:
                return _TRAIN_KV_REP
            return _BASELINE
        return _TRAIN_PURE_FSDP
    if kind == "decode" and family in ("dense", "vlm"):
        return _DECODE_SERVE
    return _BASELINE


def _rules_for(cfg: ModelConfig, shape: ShapeSpec, mesh) -> AxisRules:
    rules = DEFAULT_RULES
    data_ways = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            data_ways *= mesh.shape[a]
    if shape.global_batch < data_ways:
        # batch too small to shard (long_500k b=1): replicate batch axes
        rules = rules.override(activation_batch=None, cache_batch=None)
    model_ways = mesh.shape.get("model", 1)
    if (shape.kind == "decode" and cfg.n_kv_heads
            and cfg.n_kv_heads % model_ways != 0):
        # GQA kv-heads don't divide the model axis: head-sharded decode
        # attention would force GSPMD to all-reduce (B, S_cache, D)-sized
        # partials per layer.  Shard the cache on LENGTH instead — the
        # flash-decoding split-KV layout: each model shard scores its cache
        # slice, softmax becomes a distributed (max, sum) pair and PV a
        # partial-sum all-reduce, all of per-token size.  The cache divides
        # 16 ways so it fits HBM.
        rules = rules.override(activation_heads=None,
                               activation_kv_heads=None,
                               cache_kv_heads=None,
                               cache_length="model")
    return rules


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg: ModelConfig | None = None,
               rule_overrides: dict | None = None,
               settings=None):
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)
    rules = _rules_for(cfg, shape, mesh)
    if rule_overrides:
        rules = rules.override(**rule_overrides)

    from repro.parallel.sharding import use_rules
    from repro.models.layers import use_accum_dtype

    with (jax.sharding.use_abstract_mesh(mesh.abstract_mesh),
          use_rules(rules), use_accum_dtype(cfg.accum_dtype)):
        if shape.kind == "train":
            settings = settings or steps_lib.TrainSettings()
            step, st_sh, b_sh, state_spec = steps_lib.build_train_step(
                model, mesh, shape, settings, rules)
            lowered = step.lower(state_spec, model.input_specs(shape))
        elif shape.kind == "prefill":
            step, p_sh, b_sh, c_sh = steps_lib.build_prefill_step(
                model, mesh, shape, rules=rules)
            p_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            lowered = step.lower(p_spec, model.input_specs(shape))
        else:  # decode: one token against a seq_len-deep cache
            step, p_sh, b_sh, c_sh = steps_lib.build_decode_step(
                model, mesh, shape, rules=rules)
            p_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            kw = {}
            if cfg.family == "encdec":
                kw["enc_len"] = shape.seq_len // 2
            c_spec = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         **kw))
            lowered = step.lower(p_spec, c_spec, model.input_specs(shape))
    return lowered, mesh, cfg, shape


def _metrics_of(compiled) -> dict[str, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = cost or {}
    m = {"flops": float(cost.get("flops", 0.0)),
         "bytes": float(cost.get("bytes accessed", 0.0))}
    for k, v in collective_bytes_from_hlo(compiled.as_text()).items():
        m[f"coll:{k}"] = v
    return m


def _lin(*terms: tuple[float, dict]) -> dict[str, float]:
    out: dict[str, float] = {}
    for c, m in terms:
        for k, v in m.items():
            out[k] = out.get(k, 0.0) + c * v
    return {k: max(0.0, v) for k, v in out.items()}


def _probe_correct(arch: str, shape_name: str, multi_pod: bool,
                   cfg: ModelConfig,
                   rule_overrides: dict | None = None,
                   settings=None) -> dict[str, float]:
    """Exact loop-trip correction for XLA's count-loop-bodies-once cost
    analysis: compile 2-3 tiny fully-unrolled probe variants, solve the
    linear system for per-layer body cost, reconstruct the full total.
    (Validated: scan bodies are counted once; unroll=True is exact.)"""

    def probe(**over) -> dict[str, float]:
        pcfg = cfg.replace(probe_unroll=True, **over)
        lowered, *_ = lower_cell(arch, shape_name, multi_pod, cfg=pcfg,
                                 rule_overrides=rule_overrides,
                                 settings=settings)
        return _metrics_of(lowered.compile())

    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "ssm"):
        a = probe(n_layers=1)
        b = probe(n_layers=2)
        l = cfg.n_layers
        return _lin((2.0 - l, a), (l - 1.0, b))
    if fam == "hybrid":
        a = probe(n_layers=2, shared_attn_every=2)   # o + 2x + y
        b = probe(n_layers=4, shared_attn_every=2)   # o + 4x + 2y
        c = probe(n_layers=2, shared_attn_every=3)   # o + 2x
        # x = (b - 2a + c)/2 ; y = a - c ; total = c + (L-2)x + inv·y
        l = cfg.n_layers
        inv = l // (cfg.shared_attn_every or l)
        return _lin((1.0, c),
                    ((l - 2) / 2.0, b), (-(l - 2), a), ((l - 2) / 2.0, c),
                    (inv, a), (-inv, c))
    if fam == "encdec":
        a = probe(n_layers=1, n_encoder_layers=1)
        b = probe(n_layers=1, n_encoder_layers=2)
        c = probe(n_layers=2, n_encoder_layers=1)
        le, ld = cfg.n_encoder_layers, cfg.n_layers
        return _lin((1.0, a), (le - 1.0, b), (-(le - 1.0), a),
                    (ld - 1.0, c), (-(ld - 1.0), a))
    raise ValueError(fam)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             tpu: TPUTarget = TPUTarget(),
             cfg: ModelConfig | None = None,
             rule_overrides: dict | None = None,
             settings=None, plan: str = "baseline") -> dict:
    if plan == "optimized":
        base = cfg or get_config(arch)
        rules_ov, cfg_ov = optimized_plan(SHAPES[shape_name].kind,
                                          base.family, base.n_kv_heads)
        rule_overrides = {**rules_ov, **(rule_overrides or {})}
        cfg = base.replace(**cfg_ov)
    t0 = time.time()
    lowered, mesh, cfg, shape = lower_cell(
        arch, shape_name, multi_pod, cfg=cfg,
        rule_overrides=rule_overrides, settings=settings)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    chips = mesh.size
    mem = compiled.memory_analysis()
    raw = _metrics_of(compiled)

    t0 = time.time()
    try:
        corrected = _probe_correct(arch, shape_name, multi_pod, cfg,
                                   rule_overrides=rule_overrides,
                                   settings=settings)
        probe_ok = True
    except Exception as e:  # noqa: BLE001
        print(f"  probe correction failed ({type(e).__name__}: {e}); "
              "using raw loop-once metrics")
        corrected, probe_ok = raw, False
    t_probe = time.time() - t0

    flops_dev = corrected["flops"]
    bytes_dev = corrected["bytes"]
    coll = {k.split(":", 1)[1]: v for k, v in corrected.items()
            if k.startswith("coll:")}
    coll_bytes_dev = float(sum(coll.values()))

    compute_s = flops_dev / tpu.peak_flops
    memory_s = bytes_dev / tpu.hbm_bw
    collective_s = coll_bytes_dev / tpu.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * chips
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes_dev,
        "collectives": coll,
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "model_flops": mf,
        "useful_flops_ratio": mf / hlo_flops_global if hlo_flops_global else 0.0,
        "peak_memory_per_device": _mem_bytes(mem),
        "raw_loop_once": raw,
        "probe_corrected": probe_ok,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "probe_s": round(t_probe, 1),
        "ok": True,
    }
    return result


def _mem_bytes(mem) -> float:
    """Live per-device bytes: args + outputs + temps − aliased (donated
    buffers are both argument and output; counting them twice would report
    2× for the KV cache / train state)."""
    if mem is None:
        return 0.0
    total = (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "output_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)
             - getattr(mem, "alias_size_in_bytes", 0))
    return float(total)


# ---------------------------------------------------------------- driver

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--plan", choices=["baseline", "optimized"],
                    default="baseline")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        for shape_name, runnable, reason in shape_cells(cfg):
            if args.shape and shape_name != args.shape:
                continue
            cells.append((arch, shape_name) if runnable
                         else (arch, f"SKIP:{shape_name}:{reason}"))

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch, shape_name in cells:
        if shape_name.startswith("SKIP:"):
            _, sname, reason = shape_name.split(":", 2)
            for mp in meshes:
                key = f"{arch}|{sname}|{'2x16x16' if mp else '16x16'}"
                results[key] = {"arch": arch, "shape": sname,
                                "mesh": "2x16x16" if mp else "16x16",
                                "ok": True, "skipped": True, "reason": reason}
                print(f"[skip] {key}: {reason}")
            continue
        for mp in meshes:
            key = f"{arch}|{shape_name}|{'2x16x16' if mp else '16x16'}"
            if results.get(key, {}).get("ok") and not results[key].get("skipped"):
                print(f"[cached] {key}")
                continue
            print(f"[run] {key} ...", flush=True)
            try:
                res = run_cell(arch, shape_name, mp, plan=args.plan)
                results[key] = res
                print(f"  ok: compute={res['compute_s']*1e3:.2f}ms "
                      f"memory={res['memory_s']*1e3:.2f}ms "
                      f"collective={res['collective_s']*1e3:.2f}ms "
                      f"bottleneck={res['bottleneck']} "
                      f"(lower {res['lower_s']}s compile {res['compile_s']}s)",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                results[key] = {"arch": arch, "shape": shape_name,
                                "mesh": "2x16x16" if mp else "16x16",
                                "ok": False, "error": f"{type(e).__name__}: {e}"}
                print(f"  FAIL: {type(e).__name__}: {e}")
                traceback.print_exc()
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells ok -> {args.out}")


if __name__ == "__main__":
    main()
