"""Degraded-mode training: fault injection -> replan -> checkpoint-resume.

``DegradedModeRunner`` closes the loop the rest of the fault layer only
prices or detects:

  1. every training step walks the compiled ``PeriodProgram``'s
     instruction list and lets the ``FaultInjector`` fire scheduled faults
     at instruction boundaries;
  2. transient RUN faults propagate to ``TrainingSupervisor``'s bounded
     retry-with-backoff loop (and, past ``max_retries``, its
     restart-from-checkpoint fallback);
  3. an injected ``KernelFault`` on the fused path degrades the executor
     to the jnp reference path (``ProgramExecutor.degrade``) and rebuilds
     the jitted step — recorded as a ``kernel_fallback`` in the
     ``FaultReport``.  Nothing else degrades: a real lowering or compile
     error propagates, so a kernel the chip's compiler refuses never
     trains on the reference path unnoticed;
  4. a ``DeviceLossFault`` is fatal to the current mesh: the runner asks
     ``ElasticPlanner.replan_program`` for the Lemma-1 plan on the
     survivors, recompiles the period program for the shrunken ring
     (``compile_program`` checks it before it is returned), rebuilds the mesh + executor, and re-enters the
     supervisor — which restores the latest complete checkpoint
     (including ``Batcher`` state, so no sample is skipped or repeated)
     and resumes training where it left off.

Because the executor's numerics are device-count invariant (each weight
chunk is computed by exactly one selected device; losses/grads match the
single-device path to fp tolerance), the post-replan loss trajectory
coincides with a from-scratch run on the small mesh — pinned by
tests/test_fault_recovery.py.

The runner is deliberately CPU-friendly: with ``make_test_mesh`` it
exercises the full loss->replan->resume path on forced host devices (the
CI ``fault-smoke`` job runs exactly that via examples/elastic_restart.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.core.allocation import MappingStrategy
from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.exec.runtime import ProgramExecutor
from repro.launch.mesh import make_test_mesh
from repro.optim.optimizers import Optimizer
from repro.parallel.sharding import replicate
from repro.runtime.elastic import ElasticPlanner
from repro.runtime.fault_tolerance import TrainingSupervisor
from repro.runtime.faults import (
    DeviceLossFault,
    FaultInjector,
    FaultReport,
    FaultSchedule,
    KernelFault,
)

__all__ = ["DegradedModeRunner"]


@dataclasses.dataclass
class DegradedModeRunner:
    """Drives training through TrainingSupervisor under a FaultSchedule,
    replanning + recompiling + resuming-from-checkpoint on device loss.

    ``workload.m``-independent: the paper config's ``m`` is re-derived from
    the live device count at every (re)plan, so Lemma 1 always answers for
    the ring that actually exists.

    ``residency`` selects the executor path per ISSUE 8: ``"sharded"``
    runs the weight-sharded executor (params sliced once at step start
    into per-device chunks, ~1/d resident bytes), with the *canonical*
    state kept in the full layout so checkpoints restore across replans
    whose survivor rings have different chunk geometry; ``"replicated"``
    is the PR-6 oracle.  Both paths produce bit-identical losses, so the
    post-replan-equals-from-scratch pin holds in either mode.
    """

    workload: FCNNWorkload
    base_cfg: ONoCConfig
    schedule: FaultSchedule
    checkpointer: Checkpointer
    optimizer: Optimizer
    n_devices: int
    strategy: MappingStrategy = MappingStrategy.ORRM
    kernel_mode: str | None = None
    residency: str = "replicated"
    backend: Any = None
    checkpoint_every: int = 2
    max_retries: int = 3
    backoff_s: float = 0.01
    mesh_factory: Callable[[int], Any] | None = None
    report: FaultReport = dataclasses.field(default_factory=FaultReport)

    def __post_init__(self) -> None:
        self.injector = FaultInjector(self.schedule, report=self.report)
        self.planner = ElasticPlanner(self.workload, self.base_cfg,
                                      strategy=self.strategy)
        self.losses: dict[int, float] = {}   # step -> last observed loss
        self.program = None
        self.executable = None
        self.executor: ProgramExecutor | None = None
        self._step_jit = None
        self._mesh = None

    # ---------------------------------------------------------------- build

    def _make_mesh(self, n_devices: int):
        if self.mesh_factory is not None:
            return self.mesh_factory(n_devices)
        return make_test_mesh(n_devices)

    def _build(self, n_devices: int) -> None:
        """(Re)plan, recompile (which checks the program) and rebuild mesh
        + executor + jitted step for ``n_devices`` survivors.  A replanned
        program for a shrunken ring is where a schedule bug would surface
        first: it raises ``ProgramValidationError`` here, before anything
        is bound or executed."""
        cfg, plan, program = self.planner.replan_program(
            n_devices, backend=self.backend)
        self.program = program
        self._mesh = self._make_mesh(n_devices)
        # The façade re-derives residency for the survivor ring: the
        # recompiled schema-v2 program carries the survivors' chunk
        # geometry + param FREEs, and the executor's tracker accounts it.
        from repro.exec.api import Executable
        exe = Executable.from_program(
            program, self._mesh, residency=self.residency,
            kernel_mode=self.kernel_mode, workload=self.workload, cfg=cfg,
            plan=plan, backend=self.backend)
        self.executable = exe
        self.executor = exe.executor
        self._step_jit = self._fresh_step()

    def _fresh_step(self):
        ex, opt = self.executor, self.optimizer

        if ex.residency == "sharded":
            # Canonical state stays in the full layout so checkpoints are
            # portable across replans (each survivor ring has different
            # chunk geometry).  Params are sliced once at step start into
            # the stacked residency layout and never re-gathered whole
            # inside the program; only the grads come back full for the
            # layout-independent optimizer update.
            @jax.jit
            def step(params, opt_state, batch, i):
                sp = ex.shard_params(params)
                loss, sgrads = jax.value_and_grad(ex.loss_fn)(sp, batch)
                grads = ex.gather_params(sgrads)
                params, opt_state = opt.update(grads, opt_state, params, i)
                return params, opt_state, loss

            return step

        @jax.jit
        def step(params, opt_state, batch, i):
            loss, grads = jax.value_and_grad(ex.loss_fn)(params, batch)
            params, opt_state = opt.update(grads, opt_state, params, i)
            return params, opt_state, loss

        return step

    # ----------------------------------------------------------------- step

    def _step_fn(self, state: dict, batch: dict) -> tuple[dict, dict]:
        step = int(state["step"])
        try:
            for instr in self.program.instructions:
                self.injector.instruction_boundary(step, instr)
        except KernelFault:
            # degrade to the reference path once and rebuild the jitted
            # step; an already-degraded executor re-raises (retryable).
            if self.executor.kernel_mode == "ref":
                raise
            self.executor.degrade("ref")
            self.report.kernel_fallbacks += 1
            self._step_jit = self._fresh_step()
        t0 = time.monotonic()
        params, opt_state, loss = self._step_jit(
            state["params"], state["opt_state"], batch, state["step"])
        self.injector.observe_step(step, time.monotonic() - t0)
        loss_f = float(loss)
        self.losses[step] = loss_f
        state = {"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1}
        return state, {"loss": loss_f}

    # ------------------------------------------------------------------ run

    def run(self, params: Any, opt_state: Any, batches: Any,
            n_steps: int) -> tuple[dict, list[dict], FaultReport]:
        """Train ``n_steps`` under the fault schedule.  Returns the final
        state dict ``{"params", "opt_state", "step"}``, the supervisor's
        metric history, and the structured FaultReport."""
        n = self.n_devices
        state0 = {"params": params, "opt_state": opt_state,
                  "step": jnp.asarray(0, jnp.int32)}
        data_state0 = batches.state() if hasattr(batches, "state") else None
        history: list[dict] = []
        state = state0
        while True:
            self._build(n)
            state = replicate(state, self._mesh)
            shardings = jax.tree.map(
                lambda _: jax.sharding.NamedSharding(
                    self._mesh, jax.sharding.PartitionSpec()), state)
            supervisor = TrainingSupervisor(
                checkpointer=self.checkpointer,
                checkpoint_every=self.checkpoint_every,
                max_retries=self.max_retries,
                backoff_s=self.backoff_s,
                fatal=(DeviceLossFault,),
            )
            try:
                state, hist = supervisor.run(
                    state, self._step_fn, batches, n_steps,
                    start_step=0, restore_shardings=shardings)
                history.extend(hist)
                return state, history, self.report
            except DeviceLossFault as e:
                self.checkpointer.wait()   # flush any in-flight async save
                lost = [d for d in e.devices if d < n]
                survivors = n - len(lost)
                if survivors < 1:
                    raise
                last = supervisor.latest()
                self.report.replans.append({
                    "step": e.step, "period": e.period, "lost": lost,
                    "from_devices": n, "to_devices": survivors,
                    "resume_checkpoint": last,
                })
                self.report.resumed_from.append(
                    last if last is not None else -1)
                if last is None:
                    # no checkpoint yet: genuine from-scratch restart on
                    # the survivors — rewind state and the data pipeline.
                    state = state0
                    if data_state0 is not None:
                        batches.restore(data_state0)
                n = survivors
