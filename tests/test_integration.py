"""End-to-end integration: FCNN training with the paper's plan actually
learns; the LM train loop with supervisor+checkpoint converges; elastic
re-planning re-derives allocations."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.configs.base import ShapeSpec
from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.data import Batcher, fcnn_classification_dataset
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models import fcnn
from repro.models.api import get_model
from repro.optim import adam
from repro.runtime.elastic import ElasticPlanner


def test_fcnn_training_learns():
    """Train a small FCNN on the synthetic classification set; accuracy
    must beat chance by a wide margin (the paper's workload, miniature)."""
    key = jax.random.PRNGKey(0)
    sizes = [32, 64, 32, 10]
    params = fcnn.init(key, sizes)
    x, y = fcnn_classification_dataset(512, input_dim=32, seed=3)
    opt = adam(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch, i):
        loss, grads = jax.value_and_grad(fcnn.loss_fn)(params, batch)
        params, state = opt.update(grads, state, params, i)
        return params, state, loss

    batcher = Batcher({"x": x, "y": y}, batch_size=64)
    losses = []
    for i in range(400):
        batch = next(batcher)
        params, state, loss = step(params, state, batch, i)
        losses.append(float(loss))
    acc = float(fcnn.accuracy(params, jnp.asarray(x), jnp.asarray(y)))
    assert losses[-1] < losses[0] * 0.5
    assert acc > 0.6


def test_lm_train_step_decreases_loss():
    cfg = smoke_config("granite-3-2b")
    model = get_model(cfg)
    mesh = make_host_mesh()
    shape = ShapeSpec("t", 32, 4, "train")
    settings = steps_lib.TrainSettings(learning_rate=1e-3)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step, st_sh, _, _ = steps_lib.build_train_step(model, mesh, shape,
                                                       settings)
        state = jax.device_put(
            steps_lib.init_train_state(model, settings, jax.random.PRNGKey(0)),
            st_sh)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "labels": tok}
        first = None
        for _ in range(10):
            state, metrics = step(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first


def test_int8_compression_still_learns():
    cfg = smoke_config("granite-3-2b")
    model = get_model(cfg)
    mesh = make_host_mesh()
    shape = ShapeSpec("t", 32, 4, "train")
    settings = steps_lib.TrainSettings(learning_rate=1e-3,
                                       grad_compression="int8")
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step, st_sh, _, _ = steps_lib.build_train_step(model, mesh, shape,
                                                       settings)
        state = jax.device_put(
            steps_lib.init_train_state(model, settings, jax.random.PRNGKey(0)),
            st_sh)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "labels": tok}
        first = None
        for _ in range(10):
            state, metrics = step(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first


def test_microbatched_step_matches_shapes():
    cfg = smoke_config("qwen3-14b")
    model = get_model(cfg)
    mesh = make_host_mesh()
    shape = ShapeSpec("t", 16, 8, "train")
    settings = steps_lib.TrainSettings(microbatches=2)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        step, st_sh, _, _ = steps_lib.build_train_step(model, mesh, shape,
                                                       settings)
        state = jax.device_put(
            steps_lib.init_train_state(model, settings, jax.random.PRNGKey(0)),
            st_sh)
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                 cfg.vocab_size)
        state, metrics = step(state, {"tokens": tok, "labels": tok})
        assert jnp.isfinite(metrics["loss"])
        assert int(state["step"]) == 1


def test_elastic_replanning():
    """Membership change -> the ONoC model re-derives the allocation."""
    w = FCNNWorkload([784, 1000, 500, 10], batch_size=8)
    planner = ElasticPlanner(w, ONoCConfig(lambda_max=8))
    cfg_full, cores_full, _ = planner.plan_for(1000)
    cfg_degraded, cores_degraded, mapping = planner.plan_for(700)
    assert max(cores_degraded) <= 700
    assert cores_degraded != cores_full
    assert mapping.m == 700
    # shrink further: still valid
    _, cores_tiny, _ = planner.plan_for(16)
    assert max(cores_tiny) <= 16
