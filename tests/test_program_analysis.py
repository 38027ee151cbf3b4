"""The per-device half of the static checker (exec/check): expansion,
happens-before deadlock detection, chunk-level memory walk, shape rules
— the seeded corruption corpus the checker must reject with a precise
error, and the one place every compile and replan runs the checker."""

import dataclasses
import json

import pytest

import repro.exec.program as program_mod
from repro.checkpoint import Checkpointer
from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config, workload
from repro.core.allocation import MappingStrategy
from repro.exec import check
from repro.exec.check import (
    DeviceOp,
    ProgramValidationError,
    check_program,
    corruption_corpus,
    expand_program,
    n_device_ops,
)
from repro.exec.check.hb import check_memory
from repro.exec.program import Opcode, PeriodProgram, compile_fcnn_program
from repro.launch.mesh import make_test_mesh
from repro.optim import adam
from repro.runtime.degraded import DegradedModeRunner
from repro.runtime.faults import FaultSchedule

import repro.exec as rexec

N_DEV = 8
W = workload("NN1", batch_size=8)
CFG = onoc_config(lambda_max=64)

PROG = compile_fcnn_program(W, CFG, N_DEV, "orrm")
CORPUS = corruption_corpus(PROG, seed=0)


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh(N_DEV)


# ------------------------------------------------------------- clean pass

@pytest.mark.parametrize("residency", ["sharded", "replicated"])
@pytest.mark.parametrize("strategy", list(MappingStrategy))
@pytest.mark.parametrize("name", sorted(NN_BENCHMARKS))
def test_compiled_programs_analyze_clean(name, strategy, residency, mesh):
    """Acceptance sweep: every program produced by ``repro.exec.compile``
    for NN1..NN6 x {fm,rrm,orrm} x {sharded,replicated} passes every
    rule of the checker, the cost contract included."""
    w = workload(name, batch_size=8)
    exe = rexec.compile(w, CFG, mesh, strategy, residency=residency)
    report = check_program(exe.program, w, CFG)
    assert report.n_devices == N_DEV
    assert report.n_instructions == len(exe.program.instructions)
    assert report.checks == ("spmd", "happens-before", "memory", "shapes",
                             "costs")
    assert report.n_hb_edges > report.n_device_ops > 0
    # without workload and cfg every rule but the cost contract runs
    assert check_program(exe.program).checks == report.checks[:-1]


def test_analysis_error_is_a_validation_error():
    """One error class: every rejection, the per-device ones included, is
    a ``ProgramValidationError`` (a ``ValueError``)."""
    assert issubclass(ProgramValidationError, ValueError)
    for entry in CORPUS:
        with pytest.raises(ProgramValidationError) as err:
            check_program(entry.program, W, CFG)
        assert type(err.value) is ProgramValidationError, entry.name


def _count_checks(monkeypatch):
    calls = []
    real = check.check_program

    def counting(*args, **kw):
        calls.append(args[0])
        return real(*args, **kw)

    monkeypatch.setattr(check, "check_program", counting)
    return calls


def test_compile_checks_the_program_exactly_once(mesh, monkeypatch):
    calls = _count_checks(monkeypatch)
    exe = rexec.compile(W, CFG, mesh, "orrm")
    assert calls == [exe.program]


def _degraded_runner(ckpt_dir):
    return DegradedModeRunner(
        workload=W, base_cfg=dataclasses.replace(CFG, m=N_DEV),
        schedule=FaultSchedule(), checkpointer=Checkpointer(ckpt_dir),
        optimizer=adam(1e-2), n_devices=N_DEV, kernel_mode="ref")


def test_degraded_rebuild_checks_the_program_exactly_once(monkeypatch,
                                                          tmp_path):
    """A replan onto a shrunken ring checks its program once, inside
    ``compile_program`` — the runner adds no second check."""
    runner = _degraded_runner(tmp_path)
    calls = _count_checks(monkeypatch)
    runner._build(6)
    assert calls == [runner.program]
    assert runner.program.n_devices == 6


@pytest.mark.parametrize("entry", CORPUS, ids=lambda e: e.name)
def test_replan_emitting_a_corpus_entry_is_rejected(entry, monkeypatch,
                                                    tmp_path):
    """If the replan's ``compile_program`` emitted a corrupted program,
    the rebuild raises before any mesh, executor or step exists."""
    real = program_mod.PeriodProgram

    def corrupting(**kw):
        emitted = corruption_corpus(real(**kw), seed=0)
        return next(e for e in emitted if e.name == entry.name).program

    monkeypatch.setattr(program_mod, "PeriodProgram", corrupting)
    runner = _degraded_runner(tmp_path)
    with pytest.raises(ProgramValidationError, match=entry.match):
        runner._build(N_DEV)
    assert runner.program is None
    assert runner.executable is None and runner._step_jit is None


# -------------------------------------------------------------- expansion

def test_expansion_covers_every_device_in_program_order():
    streams = expand_program(PROG)
    assert sorted(streams) == list(range(N_DEV))
    assert n_device_ops(streams) == sum(
        len(i.devices) for i in PROG.instructions)
    for d, ops in streams.items():
        assert all(op.device == d for op in ops)
        indices = [op.index for op in ops]
        assert indices == sorted(indices)  # program order preserved


def test_expansion_resolves_chunks_and_endpoints():
    streams = expand_program(PROG)
    recvs = {i.period: i for i in PROG.instructions
             if i.opcode is Opcode.RECV}
    for ins in PROG.instructions:
        if ins.opcode is Opcode.RUN:
            for j, d in enumerate(ins.devices):
                op = next(o for o in streams[d]
                          if o.op == "run" and o.period == ins.period)
                assert op.chunk == j  # chunk j computed by window[j]
                assert op.chunk_width == ins.chunk_width
        elif ins.opcode is Opcode.SEND:
            recv = recvs[ins.period]
            for d in ins.devices:
                op = next(o for o in streams[d]
                          if o.op == "send" and o.period == ins.period)
                assert op.peers == tuple(recv.devices)
        elif ins.opcode is Opcode.RECV:
            for d in ins.devices:
                op = next(o for o in streams[d]
                          if o.op == "recv" and o.period == ins.period)
                assert op.peers == tuple(ins.sources)


def test_device_stream_helpers():
    for d in range(N_DEV):
        stream = PROG.device_stream(d)
        assert all(d in i.devices for i in stream)
    assert sorted(PROG.device_streams()) == list(range(N_DEV))
    with pytest.raises(ValueError, match="device 8 out of range"):
        PROG.device_stream(N_DEV)
    with pytest.raises(ValueError, match="out of range"):
        PROG.device_stream(-1)


def test_recv_sources_survive_json_roundtrip():
    back = PeriodProgram.from_json(json.loads(json.dumps(PROG.to_json())))
    for a, b in zip(PROG.instructions, back.instructions):
        assert a.sources == b.sources
    check_program(back, W, CFG)


def test_recv_without_sources_derives_from_send():
    """Programs serialized before the ``sources`` annotation existed
    still check: endpoints fall back to the same-period SEND window."""
    stripped = dataclasses.replace(PROG, instructions=tuple(
        dataclasses.replace(i, sources=())
        if i.opcode is Opcode.RECV else i
        for i in PROG.instructions))
    report = check_program(stripped, W, CFG)
    assert report.n_hb_edges > 0


# ------------------------------------------------------ corruption corpus

def test_corpus_is_complete_and_deterministic():
    assert [e.name for e in CORPUS] == [
        "deadlocked-send-cycle",
        "swapped-recv-source",
        "free-before-last-use",
        "shape-mismatched-run-batch",
        "shape-mismatched-run-activation",
    ]
    again = corruption_corpus(PROG, seed=0)
    assert [(e.name, e.description) for e in again] == \
           [(e.name, e.description) for e in CORPUS]


@pytest.mark.parametrize("entry", CORPUS, ids=lambda e: e.name)
def test_checker_rejects_corpus_entry(entry):
    """Each corruption sits in a blind spot of the set-level rules; the
    checker still rejects it, with the entry's message."""
    with pytest.raises(ProgramValidationError, match=entry.match):
        check_program(entry.program, W, CFG)


@pytest.mark.parametrize(
    "entry",
    [e for e in CORPUS if not e.name.startswith("shape-")],
    ids=lambda e: e.name)
def test_structural_corruptions_rejected_without_workload(entry):
    """Deadlock/endpoint/memory corruptions need no workload or config:
    the checker rejects them from the program alone."""
    with pytest.raises(ProgramValidationError, match=entry.match):
        check_program(entry.program)


def test_deadlock_message_names_the_cycle():
    entry = next(e for e in CORPUS if e.name == "deadlocked-send-cycle")
    with pytest.raises(ProgramValidationError) as err:
        check_program(entry.program)
    msg = str(err.value)
    assert "deadlock" in msg
    assert "RECV period" in msg and "SEND period" in msg  # cycle chain
    assert "device" in msg


def test_corpus_errors_name_device_and_period():
    for entry in CORPUS:
        with pytest.raises(ProgramValidationError) as err:
            check_program(entry.program, W, CFG)
        assert "period" in str(err.value), entry.name


# ----------------------------------------------- memory walk (synthetic)

def _run(d, idx, period, layer, phase="fp", **kw):
    return DeviceOp(device=d, index=idx, op="run", period=period,
                    layer=layer, phase=phase, chunk=0, chunk_width=1, **kw)


def test_check_memory_rejects_double_window_free():
    ops = (
        _run(0, 0, 1, 1),
        DeviceOp(device=0, index=1, op="free", period=1,
                 free_kind="window"),
        DeviceOp(device=0, index=2, op="free", period=1,
                 free_kind="window"),
    )
    with pytest.raises(ProgramValidationError,
                       match="double FREE.*device 0.*freed at period 1"):
        check_memory({0: ops}, l=1, fp_windows={1: (0,)},
                     check_params=False)


def test_check_memory_rejects_param_double_free_and_leak():
    free = DeviceOp(device=0, index=2, op="free", period=2, layer=1,
                    free_kind="param")
    with pytest.raises(ProgramValidationError, match="double FREE: param"):
        check_memory({0: (_run(0, 0, 1, 1), free,
                          dataclasses.replace(free, index=3, period=3))},
                     l=1, fp_windows={1: (0,)})
    with pytest.raises(ProgramValidationError,
                       match="residency leak: device 0"):
        check_memory({0: (_run(0, 0, 1, 1),)}, l=1, fp_windows={1: (0,)})


def test_check_memory_rejects_run_after_param_free():
    ops = (
        _run(0, 0, 1, 1),
        DeviceOp(device=0, index=1, op="free", period=1, layer=1,
                 free_kind="param"),
        _run(0, 2, 2, 1, phase="bp"),
    )
    with pytest.raises(ProgramValidationError,
                       match="use-after-FREE: RUN period 2"):
        check_memory({0: ops}, l=1, fp_windows={1: (0,)})
