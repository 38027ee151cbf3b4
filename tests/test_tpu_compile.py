"""Compiles of the FCNN training path, of DeepSeek-V2's latent
attention, held-expert layer and loss at published widths, and of an
expert-parallel MoE training step on four chips, for a described TPU
v5e, no chip attached: the TPU compiler installed with JAX compiles for
a ``v5e:2x2`` topology description, so what Mosaic or XLA would refuse
on the chip is refused here.  Nothing runs; these tests say nothing about values or
times (tests/test_kernels.py checks values in interpret mode).

All chip compiles live in this one file: the topology is described once,
in a module fixture, by the worker that runs the file.  Only one process
at a time may load the TPU library, so it is never described at import.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    AxisType,
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

import repro.exec as rexec
from repro import obs
from hlo_scopes import REQUIRED, present, unscoped
from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config, workload
from repro.kernels.fcnn_layer import (
    fcnn_layer,
    fcnn_layer_dgrad,
    fcnn_layer_wgrad,
)
from repro.kernels import ops
from repro.kernels.flash_attention import select_blocks as flash_select_blocks
from repro.kernels.softmax_xent import softmax_xent_dlogits, softmax_xent_fwd
from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.launch import steps
from repro.models.api import get_model
from repro.models import deepseek_v2, fcnn, moe
from repro.optim import adam

# every (n_in, n_out) layer of the paper's NN1 and NN6
LAYER_SHAPES = sorted({
    (a, b) for name in ("NN1", "NN6")
    for a, b in zip(NN_BENCHMARKS[name][:-1], NN_BENCHMARKS[name][1:])})


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*?)\)")


def _fcnn_values_in_vmem(text: str) -> list[str]:
    """``fcnn_layer*`` kernel calls of compiled HLO text with an operand or
    a result that XLA placed in VMEM (memory space ``S(1)``)."""
    shapes, calls = {}, []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, operands = m.groups()
        shapes[name] = shape
        if op == "custom-call" and name.startswith("fcnn_layer"):
            calls.append((name, shape, operands))
    return [name for name, shape, operands in calls
            if "S(1)" in shape or any(
                "S(1)" in shapes.get(o.strip().lstrip("%"), "")
                for o in operands.split(","))]


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text   # the Pallas kernel is in the program
    return text


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("batch", [64, 128, 1024])
@pytest.mark.parametrize("n_in,n_out", LAYER_SHAPES)
@pytest.mark.parametrize("kernel", ["fwd", "dgrad", "wgrad"])
def test_fcnn_layer_compiles(one_chip, kernel, n_in, n_out, batch):
    def f32(*shape):
        return _sds(shape, one_chip)

    if kernel == "fwd":
        lowered = fcnn_layer.lower(f32(batch, n_in), f32(n_in, n_out),
                                   f32(n_out), activation="sigmoid")
    elif kernel == "dgrad":
        lowered = fcnn_layer_dgrad.lower(
            f32(batch, n_out), f32(batch, n_out), f32(n_in, n_out),
            activation="sigmoid")
    else:
        lowered = fcnn_layer_wgrad.lower(
            f32(batch, n_in), f32(batch, n_out), f32(batch, n_out),
            activation="sigmoid")
    _compiled_text(lowered)


@pytest.mark.parametrize("batch", [64, 128, 256, 1000])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_softmax_xent_compiles(one_chip, kernel, batch):
    n_classes = NN_BENCHMARKS["NN1"][-1]
    logits = _sds((batch, n_classes), one_chip)
    labels = _sds((batch,), one_chip, jnp.int32)
    if kernel == "fwd":
        lowered = softmax_xent_fwd.lower(logits, labels)
    else:
        row = _sds((batch,), one_chip)
        lowered = softmax_xent_dlogits.lower(logits, labels, row, row)
    _compiled_text(lowered)


# ----------------------------------------------- DeepSeek-V2 at its widths

# one chip's share, as the benchmark's dsv2lite configuration cuts it
DSV2 = get_config("deepseek-v2-lite").replace(
    n_layers=5, vocab_size=12800, expert_range=(0, 8))
DSV2_TOKENS = 2 * 4096


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_lm_loss_kernels_compile(one_chip, kernel):
    """The softmax cross-entropy kernels over the vocabulary slice."""
    logits = _sds((DSV2_TOKENS, DSV2.vocab_size), one_chip)
    labels = _sds((DSV2_TOKENS,), one_chip, jnp.int32)
    if kernel == "fwd":
        lowered = softmax_xent_fwd.lower(logits, labels)
    else:
        row = _sds((DSV2_TOKENS,), one_chip)
        lowered = softmax_xent_dlogits.lower(logits, labels, row, row)
    _compiled_text(lowered)


def _layer_shapes(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape[1:], sharding, s.dtype), tree)


def test_held_expert_layer_grad_compiles(one_chip):
    """8 held of 64 experts, top-6, 8,192 tokens: the routed pairs run as
    XLA's grouped-matmul kernel."""
    params = jax.eval_shape(lambda k: deepseek_v2.init(k, DSV2),
                            jax.random.PRNGKey(0))
    p = _layer_shapes(params["layers"]["moe"], one_chip)
    x = _sds((2, 4096, DSV2.d_model), one_chip, jnp.bfloat16)

    def f(p, x):
        y, aux, _ = moe.moe_mlp(p, x, DSV2)
        return jnp.sum(y.astype(jnp.float32)) + aux

    text = _compiled_text(jax.jit(jax.grad(f, argnums=(0, 1))).lower(p, x))
    assert "ragged-dot" in text


_FLASH_CALL = re.compile(r"%(flash_attention_[a-z]+)[.\d]* = .*tpu_custom_call")


def test_latent_attention_grad_compiles(one_chip, monkeypatch):
    """MLA at 4,096 positions (q/k head dim 192, v head dim 128) with the
    kernels dispatched to Pallas, as on the chip: Mosaic takes the flash
    kernels at the blocks ``select_blocks`` chooses, and they run under
    the ``mla`` scope, forward and backward, so ``mla_ms`` holds the
    attention core."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    params = jax.eval_shape(lambda k: deepseek_v2.init(k, DSV2),
                            jax.random.PRNGKey(0))
    p = jax.tree.map(lambda s: _sds(s.shape, one_chip, jnp.bfloat16),
                     _layer_shapes(params["dense"]["attn"], one_chip))
    x = _sds((2, 4096, DSV2.d_model), one_chip, jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(4096), (2, 4096))

    def f(p, x):
        with jax.named_scope(obs.MLA):
            return jnp.sum(deepseek_v2.mla(p, x, positions, DSV2).astype(
                jnp.float32))

    def step(p, x):
        return jax.grad(f, argnums=(0, 1))(p, x)

    assert flash_select_blocks(4096, 192, 128, 2) == (1024, 1024)
    before = obs.counters()
    text = _compiled_text(jax.jit(step).lower(p, x))
    after = obs.counters()
    assert after.get(obs.ATTENTION_FLASH, 0) == before.get(
        obs.ATTENTION_FLASH, 0) + 1
    assert after.get(obs.ATTENTION_CHUNKED) == before.get(
        obs.ATTENTION_CHUNKED)
    scopes = {}
    for line in text.splitlines():
        if (m := _FLASH_CALL.search(line)):
            scopes.setdefault(m.group(1), set()).update(present(line))
    assert "mla" in scopes["flash_attention_fwd"]
    assert "bwd:mla" in scopes["flash_attention_dkv"]
    assert "bwd:mla" in scopes["flash_attention_dq"]


_RAGGED = re.compile(r"%ragged-dot\S* = \w+\[([\d,]+)\]")


def test_expert_parallel_moe_step_compiles_on_four_chips(topo):
    """qwen2-moe's training step (smoke widths, bf16) on a 2 x 2 data x
    model mesh: its 6 experts are split 3 to a "model" device, each
    device's grouped matmuls take only its batch rows' (token, slot)
    pairs and its own 3 experts, and a sum over "model" adds the parts."""
    cfg = smoke_config("qwen2-moe-a2.7b").replace(dtype="bfloat16")
    model = get_model(cfg)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shape = ShapeSpec("ep", 64, 4, "train")
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step, st_sh, b_sh, spec = steps.build_train_step(model, mesh, shape)
        place = lambda s, sh: _sds(s.shape, sh, s.dtype)  # noqa: E731
        text = step.lower(
            jax.tree.map(place, spec, st_sh),
            jax.tree.map(place, model.input_specs(shape), b_sh),
        ).compile().as_text()
    assert st_sh["params"]["layers"]["moe"]["w_gate"].spec[1] == "model"
    pairs = 4 * 64 // 2 * cfg.experts_per_token   # a data shard's pairs
    shapes = [tuple(map(int, m.split(","))) for m in _RAGGED.findall(text)]
    assert {len(s) for s in shapes} == {2, 3}     # forward/dgrad, wgrad
    assert all(s[0] == pairs for s in shapes if len(s) == 2)
    assert all(s[0] == cfg.n_experts // 2 for s in shapes if len(s) == 3)
    assert re.search(r"all-reduce\(.*op_name=\"[^\"]*shard_map/moe\.route/"
                     r"psum", text)


# ------------------------------------------------------------- train step


def _train_step_text(name: str, devices, batch: int = 128) -> str:
    """Compile the sharded-residency ``train_step`` of paper network
    ``name`` with Pallas kernels on a ring of the described ``devices``.
    The executable's own ``init_state`` places arrays, which described
    devices cannot hold, so the state enters as ``jax.eval_shape`` shapes
    with the same layout."""
    mesh = Mesh(np.asarray(devices), ("cores",))
    exe = rexec.compile(workload(name, batch), onoc_config(), mesh,
                        residency="sharded", kernel_mode="pallas")
    opt = adam(1e-3)

    def init(key):
        params = exe.shard_params(fcnn.init(key, exe.program.layer_sizes))
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    n = len(devices)

    def placed(s):
        stacked = s.ndim >= 1 and s.shape[0] == n
        return _sds(s.shape, NamedSharding(mesh, P("cores") if stacked
                                           else P()), s.dtype)

    state = jax.tree.map(placed,
                         jax.eval_shape(init, jax.random.PRNGKey(0)))
    replicated = NamedSharding(mesh, P())
    data = {"x": _sds((batch, exe.program.layer_sizes[0]), replicated),
            "y": _sds((batch,), replicated, jnp.int32)}
    return _compiled_text(exe.train_step(opt).lower(state, data))


@pytest.mark.parametrize("name", ["NN1", "NN6"])
def test_train_step_compiles_on_one_chip(topo, name):
    text = _train_step_text(name, topo.devices[:1])
    # every instruction names a program scope (repro.obs); on one chip a
    # transition moves nothing, so no fp<i>.xfer op is left
    assert unscoped(text) == []
    assert REQUIRED - {"fp1.xfer"} <= present(text)
    # the kernels stream their own operands and results from and to HBM
    assert _fcnn_values_in_vmem(text) == []


@pytest.mark.parametrize("name", ["NN1", "NN6"])
def test_sharded_train_step_compiles_on_four_chips(topo, name):
    assert len(topo.devices) == 4
    text = _train_step_text(name, topo.devices)
    assert "all-gather" in text   # the inter-period activation broadcast
    assert unscoped(text) == []
    assert REQUIRED <= present(text)
    assert _fcnn_values_in_vmem(text) == []
