"""One-call plan -> compile -> check -> execute façade.

The PR-6/7 surface scattered the pipeline over four entry points
(``compile_fcnn_program`` -> the static checker -> ``ProgramExecutor``
-> ``build_fcnn_program_step`` / ``build_train_step``), each with its own
params-layout assumptions.  The weight-sharded residency path changes that
layout contract end to end, so this module collapses the chain into:

    exe = repro.exec.compile(workload, cfg, mesh, strategy="orrm",
                             residency="sharded")
    state = exe.init_state(key, optimizer)
    step = exe.train_step(optimizer)
    state, metrics = step(state, batch)

``residency`` selects the executor path (see exec/runtime.py):
``"sharded"`` (default) keeps each device's resident parameters to its
column chunks — state lives in the stacked layout produced by
``Executable.shard_params`` and FREE semantics are real; ``"replicated"``
is the PR-6 oracle (full model on every device), retained for
equivalence testing and as the layout of the generic model zoo step.
The old entry points remain importable as thin deprecation shims.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import obs
from repro.core.allocation import MappingStrategy
from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.core.planner import FCNNPlan, plan_fcnn, ring_mesh_axes
from repro.exec.program import PeriodProgram, compile_program
from repro.exec.runtime import ProgramExecutor
from repro.optim.optimizers import Optimizer, clip_by_global_norm
from repro.parallel.sharding import replicate, shard_stacked

Params = dict[str, Any]

__all__ = ["Executable", "compile"]


@dataclasses.dataclass
class Executable:
    """A compiled, checked, mesh-bound period program ready to train.

    Produced by ``repro.exec.compile`` (or ``from_program`` when the
    ``PeriodProgram`` already exists, e.g. deserialized or replanned).
    The executor's residency mode fixes the params-layout contract for
    every method: ``init_state``/``train_step``/``loss_fn`` speak the
    stacked chunk layout in sharded mode and the full layout in
    replicated mode; ``shard_params``/``gather_params`` convert.
    """

    program: PeriodProgram
    mesh: Mesh
    executor: ProgramExecutor
    residency: str
    workload: FCNNWorkload | None = None
    cfg: ONoCConfig | None = None
    plan: FCNNPlan | None = None
    backend: Any = None

    @classmethod
    def from_program(cls, program: PeriodProgram, mesh: Mesh,
                     residency: str = "sharded",
                     kernel_mode: str | None = None,
                     workload: FCNNWorkload | None = None,
                     cfg: ONoCConfig | None = None,
                     plan: FCNNPlan | None = None,
                     backend: Any = None) -> "Executable":
        """Bind an existing program to ``mesh``.  Nothing is checked here:
        ``compile_program`` checked every program it made; call
        ``exec.check.check_program`` first on a program from anywhere
        else (a deserialized file)."""
        ex = ProgramExecutor(program, mesh, kernel_mode=kernel_mode,
                             residency=residency)
        return cls(program=program, mesh=mesh, executor=ex,
                   residency=residency, workload=workload, cfg=cfg,
                   plan=plan, backend=backend)

    # -------------------------------------------------------------- layout

    @property
    def tracker(self):
        """ResidencyTracker of the executor's layout (exec.residency)."""
        return self.executor.tracker

    @property
    def kernel_mode(self) -> str:
        return self.executor.kernel_mode

    def shard_params(self, params: Params) -> Params:
        return self.executor.shard_params(params)

    def gather_params(self, sparams: Params) -> Params:
        return self.executor.gather_params(sparams)

    def _place(self, tree: Any) -> Any:
        """Put a state pytree on the mesh in the residency layout: stacked
        leaves split over the ring axis in sharded mode, everything
        replicated otherwise (scalars always replicated)."""
        if self.residency != "sharded":
            return replicate(tree, self.mesh)
        return shard_stacked(tree, self.mesh, axis=self.executor.axis)

    # ----------------------------------------------------------- training

    def loss_fn(self, params: Params, batch: Params) -> jax.Array:
        """Program loss in the executable's residency layout (traceable;
        compose with jit/grad as usual)."""
        return self.executor.loss_fn(params, batch)

    def init_state(self, key, optimizer: Optimizer) -> Params:
        """Fresh ``{"params", "opt", "step"}`` state in the residency
        layout, placed on the mesh.  Optimizer moments mirror the params
        pytree, so in sharded mode they are chunked too — off-window zero
        chunks have zero grads and stay exactly zero through training."""
        from repro.models import fcnn

        params = fcnn.init(key, self.program.layer_sizes)
        if self.residency == "sharded":
            params = self.shard_params(params)
        state = {"params": params, "opt": optimizer.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        return self._place(state)

    def train_step(self, optimizer: Optimizer,
                   grad_clip: float | None = None,
                   donate: bool = True) -> Callable:
        """A jitted ``step(state, batch) -> (state, {"loss", "grad_norm"})``
        over the executable's loss.  ``grad_clip`` adds global-norm
        clipping (note: the global norm reduces over chunked leaves in
        sharded mode, so clipped trajectories agree with the replicated
        oracle only to fp tolerance; unclipped elementwise optimizers
        agree bit-for-bit)."""
        ex = self.executor

        def step(state, batch):
            obs.bump(obs.TRAIN_STEP_TRACES)   # once per trace, not per call
            loss, grads = jax.value_and_grad(ex.loss_fn)(state["params"],
                                                         batch)
            with jax.named_scope(obs.GRAD_NORM):
                if grad_clip is not None:
                    grads, gnorm = clip_by_global_norm(grads, grad_clip)
                else:
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
            with jax.named_scope(obs.OPTIMIZER):
                params, opt = optimizer.update(
                    grads, state["opt"], state["params"], state["step"])
                count = state["step"] + 1
            new_state = {"params": params, "opt": opt, "step": count}
            return new_state, {"loss": loss, "grad_norm": gnorm}

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    # ----------------------------------------------------------- recovery

    def degrade(self, mode: str = "ref") -> str:
        """Swap the kernel dispatch (exec/runtime ``degrade``) and return
        the previous mode.  Jitted steps built before the call hold the
        old dispatch — rebuild them via ``train_step``."""
        return self.executor.degrade(mode)


def compile(  # noqa: A001 — deliberate façade name, repro.exec.compile
    workload: FCNNWorkload,
    cfg: ONoCConfig,
    mesh: Mesh,
    strategy: MappingStrategy | str = MappingStrategy.ORRM,
    residency: str = "sharded",
    backend: Any = None,
    kernel_mode: str | None = None,
) -> Executable:
    """Plan (Lemma 1 on the divisor-complete ring), compile the period
    program (``compile_program`` checks it, once), and bind it to ``mesh``
    as an ``Executable`` in the requested residency mode — the single
    entry point replacing the compile_fcnn_program / checker /
    ProgramExecutor / build_*_step chain.
    """
    n = mesh.devices.size
    plan = plan_fcnn(workload, cfg, ring_mesh_axes(n), strategy=strategy)
    program = compile_program(plan, workload, cfg, n, backend=backend)
    return Executable.from_program(
        program, mesh, residency=residency, kernel_mode=kernel_mode,
        workload=workload, cfg=cfg, plan=plan, backend=backend)
