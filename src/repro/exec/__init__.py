"""Period-schedule execution engine.

The paper's fine-grained model assigns every one of the 2l periods of an
FCNN training epoch its own optimal core count, with a mapping strategy
(FM/RRM/ORRM) deciding how the active window moves between periods.  Until
this package existed the repo only *priced* those schedules
(``core.simulator``); here they become executable:

  * ``exec.program``  — the schedule compiler: lowers a planner plan plus a
    ``core.allocation.Mapping`` into a static, serializable per-period
    instruction program (RUN / SEND / RECV / FREE, alpa-style) whose cost
    annotations are cross-checkable against ``core.simulator.simulate_epoch``.
  * ``exec.runtime``  — the executor: interprets the program under
    ``jax.shard_map`` on a device mesh, driving the fused Pallas kernels
    (``kernels.ops``) as the per-shard math, in one of two residency
    modes: ``"sharded"`` (each device holds only its column chunks; FREE
    releases them at the Eq.-11 mirror periods) or ``"replicated"`` (the
    PR-6 full-model oracle).
  * ``exec.residency`` — per-device live-bytes accounting over the
    program's schema-v2 residency annotations.
  * ``exec.check``     — the static checker, ``check_program``: schedule
    invariants, the per-device streams (deadlocks, endpoints,
    chunk-granular memory safety), shapes and the cost contract vs the
    simulator.  ``compile_program`` runs it on every program it emits,
    so each compile and each replan checks its program once.
  * ``exec.api``      — the façade: ``repro.exec.compile(workload, cfg,
    mesh, strategy=..., residency=...) -> Executable`` with
    ``.train_step()`` / ``.loss_fn()`` / ``.program`` / ``.degrade()``,
    replacing the scattered compile/validate/executor/step-builder chain
    (the old entry points below remain as deprecation shims).

See exec/README.md for the API and dispatch rules.
"""

from repro.exec.check import (  # noqa: F401
    CheckReport,
    ProgramValidationError,
    check_program,
    corruption_corpus,
    expand_program,
)
from repro.exec.api import (  # noqa: F401
    Executable,
    compile,
)
from repro.exec.program import (  # noqa: F401
    Instruction,
    Opcode,
    PeriodProgram,
    compile_fcnn_program,
    compile_program,
)
from repro.exec.residency import (  # noqa: F401
    ResidencyTracker,
)
from repro.exec.runtime import (  # noqa: F401
    ProgramExecutor,
    build_train_step,
)

__all__ = [
    "compile",
    "Executable",
    "CheckReport",
    "ProgramValidationError",
    "check_program",
    "corruption_corpus",
    "expand_program",
    "Opcode",
    "Instruction",
    "PeriodProgram",
    "ResidencyTracker",
    "compile_program",
    "compile_fcnn_program",
    "ProgramExecutor",
    "build_train_step",
]
