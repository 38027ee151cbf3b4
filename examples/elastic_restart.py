"""Fault-tolerance scenarios: crash-restart, elastic replanning, and the
full seeded device-loss -> replan -> checkpoint-resume loop.

  PYTHONPATH=src python examples/elastic_restart.py

Fault taxonomy (``repro.runtime.faults.FaultKind``):

  DEVICE_LOSS          a core leaves the ring permanently — fatal to the
                       current mesh, triggers replan + resume (below);
  TRANSIENT_RUN        one period's RUN fails but the device survives —
                       cleared by TrainingSupervisor's bounded retry with
                       exponential backoff;
  STRAGGLER            a period runs magnitude× slow — observed by
                       StragglerMonitor / the injector's timeout hook;
  WAVELENGTH_DEGRADE   part of the WDM comb is lost — more TDM slots per
                       transition in the pricing model;
  LINK_DEGRADE         link capacity loss — transition drain inflates by
                       1/(1-magnitude).

Injection API: build a deterministic ``FaultSchedule`` (hand-authored
events, ``FaultSchedule.sample`` for Bernoulli-per-step rates, or
``FaultSchedule.seeded_device_loss`` for one mid-run loss burst) and
either price it (``simulate_epoch(..., faults=EpochFaults.from_schedule)``
/ ``expected_epoch_time``) or execute it: ``DegradedModeRunner`` walks the
compiled period program's instruction list each step and lets the
``FaultInjector`` fire events at instruction boundaries.

Replan-resume flow (scenario 3 below, also the CI ``fault-smoke`` job):
on ``DeviceLossFault`` the runner asks ``ElasticPlanner.replan_program``
for the Lemma-1 allocation on the survivors, recompiles the period
program for the shrunken ring (checked by ``exec.check`` as it is
compiled), rebuilds the mesh + executor, and re-enters
``TrainingSupervisor`` — which restores the latest complete checkpoint
(params, optimizer state *and* Batcher position, so no sample is skipped
or repeated) and resumes.  Because executor numerics are device-count
invariant, the resumed loss trajectory matches a from-scratch run on the
surviving mesh — asserted below.
"""

import dataclasses
import os
import shutil
import sys
import tempfile

sys.path.insert(0, "src")

# scenario 3 executes on an 8-device CPU ring: force host devices before
# the first jax import (no-op for already-multi-device backends).
_HOST_FLAG = "--xla_force_host_platform_device_count"
if _HOST_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        f"{_HOST_FLAG}=8 " + os.environ.get("XLA_FLAGS", "")).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.data import Batcher, fcnn_classification_dataset
from repro.models import fcnn
from repro.optim import adam
from repro.runtime import TrainingSupervisor
from repro.runtime.degraded import DegradedModeRunner
from repro.runtime.elastic import ElasticPlanner
from repro.runtime.faults import FaultSchedule


def crash_restart() -> None:
    """Scenario 1: transient crash mid-run; restart from checkpoint."""
    tmp = tempfile.mkdtemp(prefix="repro_elastic_")
    sizes = [64, 128, 64, 10]
    key = jax.random.PRNGKey(0)
    opt = adam(3e-3)

    params = fcnn.init(key, sizes)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}

    x, y = fcnn_classification_dataset(1024, input_dim=64, seed=1)
    batches = Batcher({"x": x, "y": y}, batch_size=32)

    fail_at = {"n": 0}

    @jax.jit
    def _step(state, batch):
        loss, grads = jax.value_and_grad(fcnn.loss_fn)(state["params"], batch)
        p, o = opt.update(grads, state["opt"], state["params"], state["step"])
        return {"params": p, "opt": o, "step": state["step"] + 1}, loss

    def step_fn(state, batch):
        fail_at["n"] += 1
        if fail_at["n"] == 60:                      # injected crash
            raise RuntimeError("simulated node failure")
        state, loss = _step(state, batch)
        return state, {"loss": float(loss)}

    sup = TrainingSupervisor(Checkpointer(tmp), checkpoint_every=20,
                             max_retries=0, backoff_s=0.0)
    state, history = sup.run(state, step_fn, batches, 100)
    print(f"completed {len(history)} steps with 1 injected failure; "
          f"loss {history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")
    assert history[-1]["loss"] < history[0]["loss"]
    shutil.rmtree(tmp, ignore_errors=True)


def elastic_shrink() -> None:
    """Scenario 2: the paper's model as the re-planning oracle."""
    planner = ElasticPlanner(FCNNWorkload([64, 128, 64, 10], batch_size=32),
                             ONoCConfig(m=1000, lambda_max=64))
    for m in (1000, 500, 100):
        _, cores, mapping = planner.plan_for(m)
        print(f"cluster size {m:4d}: allocation {cores} "
              f"({mapping.strategy.value} placement, "
              f"{len(mapping.active_cores())} active)")


def device_loss_replan_resume() -> None:
    """Scenario 3 (the CI fault-smoke): seeded mid-run device loss on the
    8-device CPU ring -> Lemma-1 replan on survivors -> checkpoint-resume;
    the resumed trajectory must match a from-scratch run on the small
    mesh.  Both runners execute the *weight-sharded* residency path
    (ISSUE 8): params are sliced once at step start into per-device
    column chunks and each replan re-derives the survivor ring's chunk
    geometry — residency recovery is exercised, not just replanning."""
    sizes = [32, 16, 8, 10]
    n_dev, n_steps, batch = 8, 8, 8
    w = FCNNWorkload(sizes, batch_size=batch)
    cfg = ONoCConfig(m=n_dev, lambda_max=64)
    x, y = fcnn_classification_dataset(64, input_dim=sizes[0], seed=3)
    params0 = fcnn.init(jax.random.PRNGKey(0), sizes)
    opt = adam(1e-2)

    schedule = FaultSchedule.seeded_device_loss(
        0, n_steps=n_steps, n_devices=n_dev, n_periods=2 * w.l)
    survivors = n_dev - len(schedule.events)

    with tempfile.TemporaryDirectory() as tmp:
        runner = DegradedModeRunner(
            workload=w, base_cfg=cfg, schedule=schedule,
            checkpointer=Checkpointer(tmp), optimizer=opt, n_devices=n_dev,
            kernel_mode="ref", residency="sharded", checkpoint_every=2,
            backoff_s=0.0)
        state, _, report = runner.run(
            params0, opt.init(params0),
            Batcher({"x": x, "y": y}, batch_size=batch), n_steps)

    with tempfile.TemporaryDirectory() as tmp:
        scratch = DegradedModeRunner(
            workload=w, base_cfg=dataclasses.replace(cfg, m=survivors),
            schedule=FaultSchedule(), checkpointer=Checkpointer(tmp),
            optimizer=opt, n_devices=survivors, kernel_mode="ref",
            residency="sharded", checkpoint_every=2, backoff_s=0.0)
        scratch.run(params0, opt.init(params0),
                    Batcher({"x": x, "y": y}, batch_size=batch), n_steps)

    rp = report.replans[0]
    print(f"device loss at step {rp['step']} period {rp['period']}: "
          f"lost {rp['lost']}, replanned {rp['from_devices']} -> "
          f"{rp['to_devices']} devices, resumed from checkpoint "
          f"{rp['resume_checkpoint']}")
    assert len(report.replans) == 1 and int(state["step"]) == n_steps
    for s in range(n_steps):
        np.testing.assert_allclose(runner.losses[s], scratch.losses[s],
                                   rtol=1e-4, atol=1e-6)
    print(f"resumed trajectory matches from-scratch run on {survivors} "
          f"devices ({n_steps} steps)")


def main() -> None:
    crash_restart()
    elastic_shrink()
    device_loss_replan_resume()


if __name__ == "__main__":
    main()
