"""Rules over the SPMD program: one instruction for a whole device set.

These rules read ``program.instructions`` as the compiler emits them and
need no per-device view.  ``check_spmd`` runs them in this order:

  structure     exactly one RUN per period 1..2l, periods non-decreasing,
                each RUN on its paper period-layer (Section 3.1), RUN
                geometry consistent (chunk_width · degree = n_layer,
                window length = degree, BP windows mirror FP via Eq. 11);
  mesh          every device id of every instruction lies in
                [0, n_devices);
  degrees       every RUN degree divides both the device count (uniform
                all-gather chunk layout) and its layer width (the paper's
                even-mapping constraint, Eq. 4 exact);
  endpoints     every SEND has a same-period RECV and vice versa, and each
                RECV's chunk-ordered ``sources`` name exactly the SEND's
                senders in window order (``check_endpoints``);
  transitions   SENDs exactly at {1..2l-1} \\ {l}; senders are the current
                RUN window and receivers the next RUN window;
  window FREE   (``layer`` is None) only devices held at the period are
                freed, never a device the next period's window still
                needs (free-before-last-use);
  residency     (schema v2) every RUN carries positive ``param_bytes``
                agreeing between a layer's FP run and its BP mirror; each
                layer's chunks are released by exactly one param FREE
                (``layer`` set), at exactly the BP mirror period 2l-i+1
                (Eq. 11 — the chunk's last use), over exactly the layer's
                window, for exactly the RUN's bytes.

Leaks, double FREEs and RUNs on freed chunks are per-device orderings:
``hb.check_memory`` owns them.  ``check_costs`` holds the program to the
simulator's prices once a workload and a config are known.
"""

from __future__ import annotations

from repro.core.allocation import map_cores
from repro.core.onoc_model import FCNNWorkload, ONoCConfig, compute_time
from repro.core.simulator import ONoCBackend, ENoCBackend
from repro.exec.program import Opcode, PeriodProgram

__all__ = [
    "ProgramValidationError",
    "check_costs",
    "check_endpoints",
    "check_spmd",
]


class ProgramValidationError(ValueError):
    """A compiled program violates the schedule invariants."""


def fail(msg: str) -> None:
    raise ProgramValidationError(msg)


def _runs(program: PeriodProgram) -> dict:
    return {i.period: i for i in program.instructions
            if i.opcode is Opcode.RUN}


def check_spmd(program: PeriodProgram) -> None:
    """Raise ``ProgramValidationError`` on the first violated rule."""
    l = program.l
    n_dev = program.n_devices
    instrs = list(program.instructions)

    # ---------------------------------------------------------- structure
    runs = _runs(program)
    if sorted(runs) != list(range(1, 2 * l + 1)):
        missing = sorted(set(range(1, 2 * l + 1)) - set(runs))
        fail(f"program must have one RUN per period 1..{2 * l}; "
             f"missing periods {missing}" if missing else
             f"program has RUNs at unexpected periods {sorted(runs)}")
    n_runs = sum(1 for i in instrs if i.opcode is Opcode.RUN)
    if n_runs != 2 * l:
        fail(f"expected {2 * l} RUN instructions, found {n_runs}")
    periods = [i.period for i in instrs]
    if periods != sorted(periods):
        fail(f"instructions out of period order: {periods}")

    for p, run in runs.items():
        layer = run.layer
        want_layer = p if p <= l else 2 * l - p + 1   # paper Section 3.1
        if layer != want_layer:
            fail(f"RUN period {p}: layer {layer} != paper period-layer "
                 f"{want_layer}")
        n_layer = program.layer_sizes[layer]
        d = run.degree
        if d != len(run.devices):
            fail(f"RUN period {p}: degree {d} != window size "
                 f"{len(run.devices)}")
        if len(set(run.devices)) != len(run.devices):
            fail(f"RUN period {p}: window has duplicate devices "
                 f"{list(run.devices)}")
        if d < 1 or n_dev % d != 0:
            fail(f"RUN period {p}: degree {d} does not divide the device "
                 f"count {n_dev} (non-uniform all-gather chunk layout)")
        if n_layer % d != 0:
            fail(f"RUN period {p}: degree {d} does not divide layer width "
                 f"{n_layer} (even-mapping constraint, Eq. 4)")
        if run.chunk_width != n_layer // d:
            fail(f"RUN period {p}: chunk_width {run.chunk_width} != "
                 f"{n_layer} / {d}")
    # Eq. 11: BP windows mirror FP windows
    for i in range(1, l + 1):
        fp, bp = runs[i], runs[2 * l - i + 1]
        if fp.devices != bp.devices:
            fail(f"BP period {2 * l - i + 1} window {list(bp.devices)} != "
                 f"FP period {i} window {list(fp.devices)} "
                 f"(data-locality constraint, Eq. 11)")

    # --------------------------------------------------------------- mesh
    for ins in instrs:
        bad = [d for d in ins.devices if not 0 <= d < n_dev]
        if bad:
            fail(f"{ins.opcode.value.upper()} period {ins.period}: devices "
                 f"{bad} outside the {n_dev}-device mesh [0, {n_dev})")

    # ------------------------------------------------ endpoints, SEND/RECV
    check_endpoints(program)
    sends = {i.period: i for i in instrs if i.opcode is Opcode.SEND}
    recvs = {i.period: i for i in instrs if i.opcode is Opcode.RECV}
    want = set(range(1, 2 * l)) - {l}
    if set(sends) != want:
        fail(f"transition periods {sorted(sends)} != "
             f"{sorted(want)} (Eq. 6: 2l-2 transitions, none at the "
             f"period-l turnaround)")
    for p, s in sends.items():
        if tuple(s.devices) != tuple(runs[p].devices):
            fail(f"SEND period {p}: senders {list(s.devices)} != period-{p} "
                 f"RUN window {list(runs[p].devices)}")
        if tuple(recvs[p].devices) != tuple(runs[p + 1].devices):
            fail(f"RECV period {p}: receivers {list(recvs[p].devices)} != "
                 f"period-{p + 1} RUN window {list(runs[p + 1].devices)}")

    # ------------------------------------------------- FREE (window kind)
    released: dict[int, set[int]] = {}
    for ins in instrs:
        if ins.opcode is Opcode.FREE and ins.layer is None:
            released.setdefault(ins.period, set()).update(ins.devices)
    for p, devs in released.items():
        held = set(runs[p].devices)
        ghost = sorted(devs - held)
        if ghost:
            fail(f"FREE period {p}: devices {ghost} not in the period's "
                 f"window {sorted(held)} — cannot free what is not held")
        if p < 2 * l:
            early = sorted(devs & set(runs[p + 1].devices))
            if early:
                fail(f"FREE period {p}: devices {early} are freed before "
                     f"last use — period {p + 1}'s window still needs "
                     f"their chunks")

    # ---------------------------------------------- residency (schema v2)
    if program.version < 2:
        return
    param_frees = [i for i in instrs if i.opcode is Opcode.FREE
                   and i.layer is not None]
    for layer in range(1, l + 1):
        fp = runs[layer]
        mirror = 2 * l - layer + 1
        bp = runs[mirror]
        if fp.param_bytes <= 0.0:
            fail(f"RUN period {layer}: param_bytes {fp.param_bytes!r} must "
                 f"be positive (schema v2 residency annotation)")
        if bp.param_bytes != fp.param_bytes:
            fail(f"RUN period {mirror}: BP param_bytes {bp.param_bytes!r} "
                 f"!= FP mirror's {fp.param_bytes!r} (layer {layer} chunks "
                 f"are reused, not re-acquired)")
        lf = [f for f in param_frees if f.layer == layer]
        if len(lf) != 1:
            fail(f"layer {layer}: expected exactly one param FREE, "
                 f"found {len(lf)} (chunk residency ledger)")
        f = lf[0]
        if f.period != mirror:
            fail(f"param FREE for layer {layer} at period {f.period} "
                 f"!= BP mirror period {mirror} (Eq. 11: the chunk's "
                 f"last use)")
        if set(f.devices) != set(fp.devices):
            fail(f"param FREE for layer {layer}: devices "
                 f"{sorted(f.devices)} != layer window "
                 f"{sorted(fp.devices)}")
        if f.param_bytes != fp.param_bytes:
            fail(f"param FREE for layer {layer}: releases "
                 f"{f.param_bytes!r} bytes != resident chunk bytes "
                 f"{fp.param_bytes!r} (ledger would not drain)")
    bad_layers = sorted({f.layer for f in param_frees}
                        - set(range(1, l + 1)))
    if bad_layers:
        fail(f"param FREE for unknown layer(s) {bad_layers}")


def check_endpoints(program: PeriodProgram) -> None:
    """Every SEND has a same-period RECV and vice versa; RECV sources must
    be the senders of that SEND, in chunk order (chunk j is computed and
    sent by sender window[j])."""
    sends = {i.period: i for i in program.instructions
             if i.opcode is Opcode.SEND}
    recv_periods = {i.period for i in program.instructions
                    if i.opcode is Opcode.RECV}
    for ins in program.instructions:
        p = ins.period
        if ins.opcode is Opcode.SEND and p not in recv_periods:
            fail(f"SEND period {p} on devices {list(ins.devices)}: no "
                 f"matching RECV — the sent chunks reach no receiver "
                 f"(unmatched endpoint)")
        if ins.opcode is not Opcode.RECV:
            continue
        send = sends.get(p)
        if send is None:
            fail(f"RECV period {p} on devices {list(ins.devices)}: no "
                 f"matching SEND — the receivers would wait forever "
                 f"(unmatched endpoint)")
        senders = tuple(send.devices)
        sources = tuple(ins.sources) or senders
        if len(sources) != len(senders):
            fail(f"RECV period {p}: {len(sources)} sources "
                 f"{list(sources)} != {len(senders)} senders "
                 f"{list(senders)} of the period-{p} SEND (unmatched "
                 f"endpoint: chunk count disagrees)")
        if set(sources) != set(senders):
            fail(f"RECV period {p}: sources {list(sources)} are not the "
                 f"senders {list(senders)} of the period-{p} SEND "
                 f"(unmatched endpoint)")
        for j, src in enumerate(sources):
            if src != senders[j]:
                fail(f"RECV period {p} on devices {list(ins.devices)}: "
                     f"chunk {j} is declared to come from device {src}, "
                     f"but chunk {j} of the period-{p} activation is "
                     f"computed and sent by device {senders[j]} (swapped "
                     f"RECV source — the gather would read the wrong "
                     f"device's chunk)")


def check_costs(program: PeriodProgram, workload: FCNNWorkload,
                cfg: ONoCConfig, backend=None) -> None:
    """RUN costs equal the paper-level ``compute_time`` and SEND costs the
    backend transition time, priced as ``simulate_epoch`` prices them.

    The compiler annotates each instruction with the same functions on
    the same arguments, so the values are compared bit for bit.  Pass the
    ``backend`` the program was compiled against to price SENDs with a
    non-default configuration.
    """
    if backend is None:
        backend = ONoCBackend() if program.backend == "onoc" else ENoCBackend()
    if backend.name != program.backend:
        fail(f"backend {backend.name!r} != program backend "
             f"{program.backend!r}")
    paper_mapping = map_cores(workload, cfg, program.strategy,
                              list(program.onoc_cores))
    for p, run in _runs(program).items():
        m_star = len(paper_mapping.window(p))
        if run.onoc_cores != m_star:
            fail(f"RUN period {p}: onoc_cores {run.onoc_cores} != paper "
                 f"window size {m_star}")
        want_cost = compute_time(workload, cfg, p, m_star)
        if run.cost_s != want_cost:
            fail(f"RUN period {p}: cost {run.cost_s!r} != paper-level "
                 f"compute_time {want_cost!r} (simulator contract)")
    for s in program.sends():
        p = s.period
        want_cost = backend.transition_time(workload, cfg, p,
                                            paper_mapping).comm_s
        if backend.name == "onoc" and p == 1:
            want_cost = 0.0  # Eq. (6): g(m_1) = 0
        if s.cost_s != want_cost:
            fail(f"SEND period {p}: cost {s.cost_s!r} != backend "
                 f"transition_time {want_cost!r} (simulator contract)")
