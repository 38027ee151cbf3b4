"""Degraded-mode recovery (ISSUE 7 acceptance): a seeded device loss
mid-epoch on the 8-device CPU ring triggers replanning + checkpoint-resume
and the resumed trajectory matches a from-scratch run on the surviving
mesh — no sample skipped or repeated."""

import dataclasses
import tempfile

import jax
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.configs.nn_benchmarks import onoc_config
from repro.core.onoc_model import FCNNWorkload
from repro.data import Batcher, fcnn_classification_dataset
from repro.models import fcnn
from repro.optim import adam
from repro.runtime.degraded import DegradedModeRunner
from repro.runtime.faults import (
    FaultError,
    FaultEvent,
    FaultKind,
    FaultSchedule,
)

SIZES = [32, 16, 8, 10]
BATCH = 8
N_STEPS = 8
N_DEV = 8

W = FCNNWorkload(SIZES, batch_size=BATCH)
CFG = dataclasses.replace(onoc_config(lambda_max=64), m=N_DEV)
X, Y = fcnn_classification_dataset(64, input_dim=SIZES[0], seed=3)


def _run(schedule, n_devices, cfg=None, n_steps=N_STEPS, kernel_mode="ref",
         **kw):
    params0 = fcnn.init(jax.random.PRNGKey(0), SIZES)
    opt = adam(1e-2)
    with tempfile.TemporaryDirectory() as tmp:
        runner = DegradedModeRunner(
            workload=W,
            base_cfg=cfg or dataclasses.replace(CFG, m=n_devices),
            schedule=schedule, checkpointer=Checkpointer(tmp),
            optimizer=opt, n_devices=n_devices, kernel_mode=kernel_mode,
            checkpoint_every=2, backoff_s=0.0, **kw)
        state, history, report = runner.run(
            params0, opt.init(params0),
            Batcher({"x": X, "y": Y}, batch_size=BATCH), n_steps)
    return runner, state, history, report


def test_device_loss_replan_resume_matches_from_scratch():
    """8 -> 6 devices at step 4: replan, resume from checkpoint, and the
    per-step losses + final params match a fault-free 6-device run."""
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.DEVICE_LOSS, step=4, period=2, device=6),
        FaultEvent(kind=FaultKind.DEVICE_LOSS, step=4, period=2, device=7),))
    runner, state, _, report = _run(sched, N_DEV)

    assert len(report.replans) == 1
    rp = report.replans[0]
    assert rp["from_devices"] == 8 and rp["to_devices"] == 6
    assert rp["lost"] == [6, 7]
    assert report.resumed_from == [3]      # checkpoint at steps 1, 3
    assert int(state["step"]) == N_STEPS
    assert sorted(runner.losses) == list(range(N_STEPS))

    scratch, state2, _, report2 = _run(FaultSchedule(), 6)
    assert report2.replans == []
    for s in range(N_STEPS):
        np.testing.assert_allclose(runner.losses[s], scratch.losses[s],
                                   rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(state2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=5e-4)


def test_seeded_device_loss_scenario_recovers():
    """The exact seeded scenario CI runs (fault-smoke)."""
    sched = FaultSchedule.seeded_device_loss(
        0, n_steps=N_STEPS, n_devices=N_DEV, n_periods=2 * W.l)
    runner, state, _, report = _run(sched, N_DEV)
    assert len(report.replans) == 1
    assert report.replans[0]["to_devices"] == N_DEV - len(sched.events)
    assert int(state["step"]) == N_STEPS


def test_loss_before_first_checkpoint_restarts_from_scratch():
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.DEVICE_LOSS, step=0, period=1, device=7),))
    runner, state, _, report = _run(sched, N_DEV)
    assert report.resumed_from == [-1]     # no checkpoint existed yet
    assert int(state["step"]) == N_STEPS
    scratch, _, _, _ = _run(FaultSchedule(), 7)
    for s in range(N_STEPS):
        np.testing.assert_allclose(runner.losses[s], scratch.losses[s],
                                   rtol=1e-4, atol=1e-6)


def test_transient_run_fault_is_retried_not_fatal():
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.TRANSIENT_RUN, step=2, period=1,
                   device=0, count=2),))
    runner, state, history, report = _run(sched, N_DEV)
    assert report.retries == 2
    assert report.replans == []
    assert int(state["step"]) == N_STEPS
    scratch, _, _, _ = _run(FaultSchedule(), N_DEV)
    for s in range(N_STEPS):
        np.testing.assert_allclose(runner.losses[s], scratch.losses[s],
                                   rtol=1e-6, atol=1e-7)


def test_kernel_failure_degrades_to_ref_path():
    """An injected KernelFault on the fused path: the runner falls back to
    the reference path once and finishes training."""
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.KERNEL_FAILURE, step=0, period=1),))
    runner, state, _, report = _run(sched, N_DEV,
                                    kernel_mode="pallas_interpret",
                                    n_steps=3)
    assert report.kernel_fallbacks == 1
    assert [f["kind"] for f in report.fired] == ["kernel_failure"]
    assert runner.executor.kernel_mode == "ref"
    assert int(state["step"]) == 3
    scratch, _, _, _ = _run(FaultSchedule(), N_DEV, n_steps=3)
    for s in range(3):
        np.testing.assert_allclose(runner.losses[s], scratch.losses[s],
                                   rtol=1e-6, atol=1e-7)


def test_kernel_compile_error_propagates():
    """A real error from the step — here kernel_mode="pallas", which cannot
    lower on CPU — is not a KernelFault: it propagates instead of training
    on the reference path."""
    with pytest.raises(Exception) as info:
        _run(FaultSchedule(), N_DEV, kernel_mode="pallas", n_steps=1)
    assert not isinstance(info.value, FaultError)


def test_sharded_residency_recovery_matches_replicated():
    """ISSUE 8: the runner's weight-sharded path survives device loss —
    replan re-derives the survivor ring's chunk geometry and the resumed
    trajectory is bit-identical to the replicated-residency run (canonical
    state stays in full layout, so checkpoints are layout-portable)."""
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.DEVICE_LOSS, step=4, period=2, device=6),
        FaultEvent(kind=FaultKind.DEVICE_LOSS, step=4, period=2, device=7),))
    sharded, state_s, _, rep_s = _run(sched, N_DEV, residency="sharded")
    repl, state_r, _, rep_r = _run(sched, N_DEV, residency="replicated")

    assert len(rep_s.replans) == 1
    assert rep_s.replans[0]["to_devices"] == 6
    assert sharded.executable.residency == "sharded"
    assert int(state_s["step"]) == N_STEPS
    for s in range(N_STEPS):
        assert sharded.losses[s] == repl.losses[s]
    for a, b in zip(jax.tree.leaves(state_s["params"]),
                    jax.tree.leaves(state_r["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_straggler_and_degrade_events_are_recorded_not_fatal():
    sched = FaultSchedule(events=(
        FaultEvent(kind=FaultKind.STRAGGLER, step=1, period=2,
                   magnitude=2.0),
        FaultEvent(kind=FaultKind.WAVELENGTH_DEGRADE, step=2, period=1,
                   magnitude=0.5),))
    runner, state, _, report = _run(sched, N_DEV)
    assert report.straggles == 1
    assert {f["kind"] for f in report.fired} == {
        "straggler", "wavelength_degrade"}
    assert int(state["step"]) == N_STEPS
