"""Compiles of the FCNN training path for a described TPU v5e, no chip
attached: the TPU compiler installed with JAX compiles for a ``v5e:2x2``
topology description, so what Mosaic or XLA would refuse on the chip is
refused here.  Nothing runs; these tests say nothing about values or
times (tests/test_kernels.py checks values in interpret mode).

All chip compiles live in this one file: the topology is described once,
in a module fixture, by the worker that runs the file.  Only one process
at a time may load the TPU library, so it is never described at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

import repro.exec as rexec
from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config, workload
from repro.kernels.fcnn_layer import (
    fcnn_layer,
    fcnn_layer_dgrad,
    fcnn_layer_wgrad,
)
from repro.kernels.softmax_xent import softmax_xent_dlogits, softmax_xent_fwd
from repro.models import fcnn
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


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text   # the Pallas kernel is in the program
    return text


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("batch", [64, 128])
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
    _train_step_text(name, topo.devices[:1])


@pytest.mark.parametrize("name", ["NN1", "NN6"])
def test_sharded_train_step_compiles_on_four_chips(topo, name):
    assert len(topo.devices) == 4
    text = _train_step_text(name, topo.devices)
    assert "all-gather" in text   # the inter-period activation broadcast
