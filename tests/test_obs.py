"""The program's own names for its work (``repro.obs``): the ``Batcher``'s
host spans as a profile records them, the ``train_step.traces`` counter,
and the device scopes on every instruction of the compiled training step
(one device and a four-device host ring, NN1 and NN6; and the LM step of
``launch/steps.build_train_step`` for DeepSeek-V2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.exec as rexec
from hlo_scopes import LM_REQUIRED, REQUIRED, present, unscoped
from repro import obs
from repro.configs import smoke_config
from repro.configs.base import ShapeSpec
from repro.configs.nn_benchmarks import onoc_config, workload
from repro.core.onoc_model import FCNNWorkload
from repro.data import Batcher
from repro.launch import steps
from repro.models.api import get_model
from repro.optim import adam


def _profile_names(path) -> list[str]:
    """Names of every host event in the profile written under ``path``."""
    from jax.profiler import ProfileData
    pb = next(path.glob("**/*.xplane.pb"))
    pd = ProfileData.from_file(str(pb))
    return [e.name for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("mesh_devices", [0, 2])
def test_batcher_spans_in_a_profile(tmp_path, mesh_devices):
    data = {"x": np.arange(40 * 3, dtype=np.float32).reshape(40, 3),
            "y": np.arange(40, dtype=np.int32)}
    mesh = (Mesh(np.asarray(jax.devices()[:mesh_devices]), ("data",))
            if mesh_devices else None)
    batcher = Batcher(data, batch_size=8, mesh=mesh)
    with jax.profiler.trace(str(tmp_path)):
        batches = [next(batcher) for _ in range(3)]
        jax.block_until_ready(batches)
    names = _profile_names(tmp_path)
    assert names.count("repro.batch.index") == 3
    assert names.count("repro.batch.put") == 3
    # the spans change nothing the batcher returns
    np.testing.assert_array_equal(np.asarray(batches[2]["y"]),
                                  np.arange(16, 24))


def test_train_step_traces_counter():
    sizes = [16, 32, 10]
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cores",))
    exe = rexec.compile(FCNNWorkload(sizes, batch_size=8), onoc_config(),
                        mesh, kernel_mode="ref")
    opt = adam(1e-3)
    state = exe.init_state(jax.random.PRNGKey(0), opt)
    step = exe.train_step(opt)
    rng = np.random.default_rng(0)

    def batch(rows):
        return {"x": jnp.asarray(rng.normal(size=(rows, sizes[0])),
                                 jnp.float32),
                "y": jnp.asarray(rng.integers(0, 10, rows), jnp.int32)}

    before = obs.counters().get(obs.TRAIN_STEP_TRACES, 0)
    for _ in range(5):
        state, _ = step(state, batch(8))
    assert obs.counters()[obs.TRAIN_STEP_TRACES] == before + 1
    state, _ = step(state, batch(16))   # a new shape: traced again
    jax.block_until_ready(state)
    assert obs.counters()[obs.TRAIN_STEP_TRACES] == before + 2


def _step_text(name: str, n_devices: int) -> str:
    """Compiled HLO of NN ``name``'s sharded training step with the Pallas
    kernels interpreted (so their padding is in the program) on a ring of
    ``n_devices`` host devices."""
    mesh = Mesh(np.asarray(jax.devices()[:n_devices]), ("cores",))
    exe = rexec.compile(workload(name, 128), onoc_config(), mesh,
                        residency="sharded", kernel_mode="pallas_interpret")
    opt = adam(1e-3)
    state = jax.eval_shape(lambda k: exe.init_state(k, opt),
                           jax.random.PRNGKey(0))
    data = {"x": jax.ShapeDtypeStruct((128, exe.program.layer_sizes[0]),
                                      jnp.float32),
            "y": jax.ShapeDtypeStruct((128,), jnp.int32)}
    return exe.train_step(opt).lower(state, data).compile().as_text()


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("name", ["NN1", "NN6"])
def test_every_step_instruction_names_a_program_scope(name, n_devices):
    text = _step_text(name, n_devices)
    assert unscoped(text) == []
    assert REQUIRED <= present(text)
    last = len(workload(name, 128).layer_sizes) - 1
    assert {f"fp{last}.run", f"bwd:fp{last}.xfer", "bwd:loss"} <= present(
        text)


@pytest.mark.parametrize("remat", [False, True])
def test_every_lm_step_instruction_names_a_model_scope(remat):
    """Each instruction of DeepSeek-V2's compiled training step names
    ``embed``, ``layers``, ``mla``, ``moe.route``, ``moe.experts``,
    ``moe.shared``, ``dense_mlp``, ``loss``, ``grad_norm`` or
    ``optimizer``."""
    cfg = smoke_config("deepseek-v2-lite").replace(remat=remat)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, _, _, spec = steps.build_train_step(
        get_model(cfg), mesh, ShapeSpec("t", 32, 2, "train"),
        steps.TrainSettings())
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    text = step.lower(spec, batch).compile().as_text()
    assert unscoped(text) == []
    assert LM_REQUIRED <= present(text)
