"""The static checker for compiled period programs.

A ``PeriodProgram`` is plain data that gets shipped to workers and
re-generated on every replan — a silently corrupted schedule (a RECV whose
SEND was dropped, a window pointing off the mesh, a FREE that releases a
chunk the next period still needs) would execute as wrong numerics or a
deadlocked collective, not as an error.  ``check_program`` turns every
such corruption into a hard, precisely-worded ``ProgramValidationError``.

``exec.program.compile_program`` calls it on every program it emits, so
``repro.exec.compile`` and every replan (``runtime.degraded``,
``serve.elastic``) check each program exactly once.  A program from
anywhere else (``PeriodProgram.from_json``, a hand edit) is checked by
calling ``check_program`` on it.

It always runs every rule, first failure wins:

  * ``spmd``    — structure, mesh, degrees, SEND/RECV endpoints,
    transitions, window FREEs and the schema-v2 residency annotations,
    read from the SPMD instruction list;
  * ``expand``  — lowers the program into one concrete instruction
    stream per device (window membership, chunk geometry, SEND/RECV
    peers);
  * ``hb``      — the happens-before graph over those streams
    (communication deadlocks) and the per-device memory walk
    (use-before-def, use-after-FREE, double FREE, residency leaks);
  * ``shapes``  — batch, widths, activations and element width against
    the workload and config where given;
  * ``spmd.check_costs`` — with ``workload`` and ``cfg``, the cost
    contract against the simulator.

``corpus`` keeps a seeded corruption corpus the tests and the benchmark
sweep feed through the checker.
"""

from __future__ import annotations

import dataclasses

from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.exec.check.corpus import (  # noqa: F401
    CorruptedProgram,
    corruption_corpus,
)
from repro.exec.check.expand import (  # noqa: F401
    DeviceOp,
    expand_program,
    n_device_ops,
)
from repro.exec.check.hb import check_happens_before, check_memory
from repro.exec.check.shapes import check_shapes
from repro.exec.check.spmd import (  # noqa: F401
    ProgramValidationError,
    check_costs,
    check_spmd,
)
from repro.exec.program import PeriodProgram

__all__ = [
    "CheckReport",
    "CorruptedProgram",
    "DeviceOp",
    "ProgramValidationError",
    "check_program",
    "corruption_corpus",
    "expand_program",
    "n_device_ops",
]


@dataclasses.dataclass(frozen=True)
class CheckReport:
    """What the checker looked at — sized for benchmark reporting."""

    n_devices: int
    n_instructions: int
    n_device_ops: int
    n_hb_edges: int
    checks: tuple[str, ...]


def check_program(
    program: PeriodProgram,
    workload: FCNNWorkload | None = None,
    cfg: ONoCConfig | None = None,
    backend=None,
) -> CheckReport:
    """Check ``program``; raise ``ProgramValidationError`` on the first
    defect found.

    The cost contract is checked whenever ``workload`` and ``cfg`` are
    both given; pass the ``backend`` the program was compiled against to
    price SENDs with a non-default configuration.
    """
    check_spmd(program)
    streams = expand_program(program)
    n_edges = check_happens_before(streams)
    fp_windows = {r.layer: r.devices for r in program.runs("fp")}
    check_memory(streams, l=program.l, fp_windows=fp_windows,
                 check_params=program.version >= 2)
    check_shapes(program, workload, cfg)
    checks = ("spmd", "happens-before", "memory", "shapes")
    if workload is not None and cfg is not None:
        check_costs(program, workload, cfg, backend=backend)
        checks += ("costs",)
    return CheckReport(
        n_devices=program.n_devices,
        n_instructions=len(program.instructions),
        n_device_ops=n_device_ops(streams),
        n_hb_edges=n_edges,
        checks=checks,
    )
