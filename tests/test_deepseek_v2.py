"""DeepSeek-V2 (``models/deepseek_v2.py``) and the dropless held-expert
layer (``models/moe.py``) against the benchmark's plain reference
(``chipbench/references/dsv2lite.py``), on the CPU at the smoke size with
seeded random weights in float32."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.kernels import ops
from repro.launch import steps
from repro.models import deepseek_v2 as dsv2
from repro.models import layers as L
from repro.models import moe
from repro.models.api import get_model
from repro.parallel.sharding import shape_aware_shardings

ROOT = Path(__file__).resolve().parents[1]
KEY = jax.random.PRNGKey(7)
CFG = smoke_config("deepseek-v2-lite")


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(ROOT / "chipbench/references/dsv2lite.py", "dsv2lite_reference")
KIND = _load(ROOT / "chipbench/kinds/lm.py", "lm_kind")
NO_FAULT = dict.fromkeys(REF.FAULTS, 0.0)


def _ref_config(cfg) -> dict:
    """The reference's configuration keys for a program configuration."""
    conf = json.loads((ROOT / "chipbench/configs/dsv2lite.json").read_text())
    conf.update(KIND.stated(cfg))
    return conf


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)))


def test_program_configuration_is_the_benchmarks():
    """The registered configuration is the published model; cut as the
    benchmark's file says, it states every key of the file that the
    program fixes, at the published widths."""
    conf = json.loads((ROOT / "chipbench/configs/dsv2lite.json").read_text())
    published = get_config("deepseek-v2-lite")
    assert conf["published"] == {
        "num_hidden_layers": published.n_layers,
        "n_routed_experts": published.held_experts[1],
        "vocab_size": published.vocab_size}
    assert published.held_experts == (0, published.n_experts)
    cfg = KIND.cut(published, conf)
    assert {k: conf[k] for k in KIND.stated(cfg)} == KIND.stated(cfg)
    assert dsv2.softmax_scale(cfg) == pytest.approx(0.114722, abs=1e-6)
    assert dsv2.rope_scale(cfg) == 1.0
    np.testing.assert_allclose(dsv2.rope_inv_freq(cfg), REF.inv_freq(conf),
                               rtol=1e-12)


@pytest.mark.parametrize("length,chunked", [(32, False), (1024, True)])
def test_mla_with_yarn_matches_reference(length, chunked):
    cfg = CFG.replace(attn_chunk_threshold=(16 if chunked else 4096 ** 2))
    params = dsv2.init(KEY, cfg)
    attn = jax.tree.map(lambda a: a[0], params["dense"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, length, cfg.d_model))
    positions = jnp.arange(length)[None]
    got = dsv2.mla(attn, x, positions, cfg)
    with jax.default_matmul_precision("highest"):
        want = REF._mla(REF._Ops("float32"), attn, x, _ref_config(cfg),
                        NO_FAULT)
    _close(got, want, 1e-4)


def _skewed(p, favourite: int, x):
    """Router weights and inputs under which every token chooses
    ``favourite``: the inputs share a positive mean, which the favourite's
    router column weighs up."""
    router = p["router"].at[:, favourite].add(1.0)
    return {**p, "router": router}, x + 1.0


@pytest.mark.parametrize("skewed", [False, True])
def test_held_experts_match_dense_masked_reference(skewed):
    params = dsv2.init(KEY, CFG)
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    lo, hi = CFG.held_experts
    b, s = 2, 48
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, CFG.d_model))
    if skewed:
        p, x = _skewed(p, lo + 1, x)
    y, aux, counts = moe.moe_mlp(p, x, CFG)
    with jax.default_matmul_precision("highest"):
        want, want_aux = REF._moe(REF._Ops("float32"), p,
                                  x.reshape(b * s, -1), b,
                                  _ref_config(CFG), NO_FAULT)
    _close(y.reshape(b * s, -1), want, 1e-5)
    _close(aux, want_aux, 1e-6)
    experts = moe.route(p["router"], x.reshape(b * s, -1), CFG).experts
    routed_here = np.bincount(np.asarray(experts).ravel(),
                              minlength=CFG.n_experts)[lo:hi]
    np.testing.assert_array_equal(np.asarray(counts), routed_here)
    if skewed:
        # one expert takes every token, far past any capacity bound, and
        # none of them is dropped
        assert int(counts[1]) == b * s


def test_held_expert_shares_add_up_to_the_whole_layer():
    """The routed parts that holding [0,4), [4,8), ... each give, plus the
    shared experts counted once, are the layer holding every expert."""
    whole_cfg = CFG.replace(expert_range=())
    key = jax.random.PRNGKey(3)
    whole = dsv2.init(key, whole_cfg)
    p = jax.tree.map(lambda a: a[0], whole["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, CFG.d_model))
    want, _, _ = moe.moe_mlp(p, x, whole_cfg)
    shared = L.mlp(p["shared"], x)
    n_held = CFG.held_experts[1] - CFG.held_experts[0]
    total = shared
    for lo in range(0, CFG.n_experts, n_held):
        cfg = CFG.replace(expert_range=(lo, lo + n_held))
        part = {**p, **{k: p[k][lo:lo + n_held]
                        for k in ("w_gate", "w_up", "w_down")}}
        y, _, _ = moe.moe_mlp(part, x, cfg)
        total = total + (y - shared)
    _close(total, want, 1e-5)


@pytest.mark.parametrize("arch,ways", [("deepseek-v2-lite", 4),
                                       ("qwen2-moe-a2.7b", 2),
                                       ("granite-moe-1b-a400m", 4)])
def test_expert_parallel_layer_matches_one_device(arch, ways):
    """Under a mesh that splits the experts over ``ways`` devices of
    "model", the layer runs expert-parallel: its output, balance loss,
    counts and gradients are the one-device layer's."""
    cfg = smoke_config(arch)
    p = moe.init_moe_mlp(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16, cfg.d_model))

    def f(p, x):
        y, aux, counts = moe.moe_mlp(p, x, cfg)
        return jnp.sum(y * y) + aux, (y, aux, counts)

    step = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
    (want_loss, want), want_grads = jax.jit(step)(p, x)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8 // ways, ways),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    p_sh = shape_aware_shardings(p, moe.moe_mlp_axes(cfg), mesh)
    assert p_sh["w_gate"].spec[0] == "model"
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert "shard_map" in str(jax.make_jaxpr(f)(p, x))
        (loss, got), grads = jax.jit(step, in_shardings=(
            p_sh, NamedSharding(mesh, P("data"))))(p, x)
    _close(loss, want_loss, 1e-5)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "granite-moe-1b-a400m"])
def test_moe_configs_drop_no_token(arch):
    """Every (token, slot) pair reaches its expert, however skewed."""
    cfg = smoke_config(arch)
    p, x = _skewed(moe.init_moe_mlp(KEY, cfg), 0, jax.random.normal(
        jax.random.PRNGKey(5), (2, 64, cfg.d_model)))
    _, _, counts = moe.moe_mlp(p, x, cfg)
    assert int(jnp.sum(counts)) == 2 * 64 * cfg.experts_per_token
    assert int(counts[0]) == 2 * 64


def test_training_step_matches_reference():
    """Loss, the clipped gradient AdamW got and the update of one step of
    ``launch/steps.build_train_step`` against the reference's."""
    conf = _ref_config(CFG)
    model = get_model(CFG)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    o = conf["optimizer"]
    settings = steps.TrainSettings(learning_rate=o["lr"],
                                   weight_decay=o["weight_decay"],
                                   grad_clip=o["grad_clip"], b2=o["b2"])
    step, st_sh, _, _ = steps.build_train_step(
        model, mesh, ShapeSpec("t", 32, 2, "train"), settings)
    key = jax.random.PRNGKey(11)
    state = jax.device_put(steps.init_train_state(model, settings, key),
                           st_sh)
    before = jax.device_get(state["params"])
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 33), 0,
                                CFG.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    state, metrics = step(state, batch)
    want = REF.train(key, conf, np.asarray(batch["tokens"])[None],
                     np.asarray(batch["labels"])[None])
    assert float(metrics["loss"]) == pytest.approx(want.losses[0], rel=1e-6)
    names = REF.leaf_names(conf)
    grads = [np.linalg.norm(np.asarray(m)) / (1 - o["b1"])
             for m in jax.tree.leaves(state["opt"]["m"])]
    change = [np.linalg.norm(np.asarray(a, np.float64) - b)
              for a, b in zip(jax.tree.leaves(state["params"]),
                              jax.tree.leaves(before))]
    for name, g, c in zip(names, grads, change):
        assert g == pytest.approx(want.grad_norms[name], rel=1e-4), name
        assert c == pytest.approx(want.update_norms[name], rel=1e-4), name
    assert float(metrics["expert_tokens_max"]) >= float(
        metrics["expert_tokens_mean"]) > 0


def test_chunked_attention_with_v_dim_and_scale_matches_sdpa():
    """The kv-chunked causal path with a v head dim unlike q's and a given
    scale, values and gradients, against the plain path."""
    b, length, h, d, dv, scale = 2, 64, 4, 24, 16, 0.3
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (b, length, h, d))
    k = jax.random.normal(ks[1], (b, length, h, d))
    v = jax.random.normal(ks[2], (b, length, h, dv))
    cot = jax.random.normal(ks[3], (b, length, h, dv))
    idx = jnp.arange(length)
    mask = (idx[None, :] <= idx[:, None])[None, None, None]

    def chunked(q, k, v):
        return L._sdpa_chunked_causal(q, k, v, 16, 1, scale)

    def plain(q, k, v):
        return L._sdpa(q, k, v, mask, scale)

    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(chunked, q, k, v)
        want, want_vjp = jax.vjp(plain, q, k, v)
        _close(got, want, 1e-5)
        for a, w in zip(got_vjp(cot), want_vjp(cot)):
            _close(a, w, 1e-4)
    # no scale given: 1/sqrt(d), the path's old behaviour
    _close(L._sdpa_chunked_causal(q, k, q, 16, 1),
           L._sdpa_chunked_causal(q, k, q, 16, 1, 1 / np.sqrt(d)), 0.0)


def test_mla_on_flash_kernel_matches_chunked_path(monkeypatch):
    """mla at smoke widths and 1,024 positions (a multiple of the kernel's
    blocks and of the scan's chunk) on the Pallas flash kernel, in
    interpret mode, against mla on the kv-chunked scan: the output and the
    gradients of the parameters and the input.  The counters name the
    path each call was traced onto."""
    cfg = CFG.replace(attn_chunk_threshold=16)
    length = 1024
    params = dsv2.init(KEY, cfg)
    attn = jax.tree.map(lambda a: a[0], params["dense"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, cfg.d_model))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    positions = jnp.broadcast_to(jnp.arange(length), (2, length))

    def run(mode):
        monkeypatch.setattr(ops, "resolve_mode", lambda force: mode)
        before = obs.counters()
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(lambda p, x: dsv2.mla(p, x, positions, cfg),
                               attn, x)
            grads = vjp(cot)
        after = obs.counters()
        traced = {n: after.get(n, 0) - before.get(n, 0)
                  for n in (obs.ATTENTION_FLASH, obs.ATTENTION_CHUNKED)}
        return out, grads, traced

    got, got_grads, flash = run("pallas_interpret")
    want, want_grads, chunked = run("ref")
    assert flash == {obs.ATTENTION_FLASH: 1, obs.ATTENTION_CHUNKED: 0}
    assert chunked == {obs.ATTENTION_FLASH: 0, obs.ATTENTION_CHUNKED: 1}
    _close(got, want, 1e-4)
    for a, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        _close(a, w, 1e-4)


def _undefined_outside_groups(ragged_dot):
    """``ragged_dot`` that leaves NaN in every row outside the groups, in
    its output and in its lhs gradient, as a kernel may leave them
    undefined."""
    def f(lhs, rhs, group_sizes, **kw):
        grouped = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)

        @jax.custom_vjp
        def dot(lhs, rhs):
            out = ragged_dot(lhs, rhs, group_sizes, **kw)
            return jnp.where(grouped[:, None], out, jnp.nan)

        def fwd(lhs, rhs):
            return dot(lhs, rhs), (lhs, rhs)

        def bwd(res, ct):
            _, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, group_sizes, **kw),
                             *res)
            d_lhs, d_rhs = vjp(ct)
            return jnp.where(grouped[:, None], d_lhs, jnp.nan), d_rhs

        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)
    return f


def test_held_experts_ignore_rows_outside_the_groups(monkeypatch):
    """Values and gradients do not depend on what the grouped matmul
    leaves in the rows of the pairs bound for absent experts."""
    params = dsv2.init(KEY, CFG)
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, CFG.d_model))

    def f(p, x):
        y, aux, _ = moe.moe_mlp(p, x, CFG)
        return jnp.sum(y * jnp.cos(y)) + aux

    want = jax.value_and_grad(f, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _undefined_outside_groups(jax.lax.ragged_dot))
    got = jax.value_and_grad(f, argnums=(0, 1))(p, x)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        _close(a, w, 1e-6)
