"""Static program checking (exec/check), the rules a hand-corrupted
program breaks — every such program must be rejected with a precise
error, every compiled program must pass."""

import dataclasses

import pytest

from repro.configs.nn_benchmarks import onoc_config, workload
from repro.core.allocation import MappingStrategy
from repro.core.simulator import ENoCBackend
from repro.exec.program import (
    Instruction,
    Opcode,
    compile_fcnn_program,
)
from repro.exec.check import ProgramValidationError, check_program

N_DEV = 8
W = workload("NN1", batch_size=8)
CFG = onoc_config(lambda_max=64)


@pytest.fixture(scope="module")
def prog():
    return compile_fcnn_program(W, CFG, N_DEV, "orrm")


def _with_instrs(prog, instrs):
    return dataclasses.replace(prog, instructions=tuple(instrs))


@pytest.mark.parametrize("strategy", list(MappingStrategy))
@pytest.mark.parametrize("backend", [None, ENoCBackend()])
def test_compiled_programs_validate(strategy, backend):
    """compile_* checks internally; checking again externally (with the
    full cost contract) must also pass for every strategy and backend."""
    p = compile_fcnn_program(W, CFG, N_DEV, strategy, backend=backend)
    check_program(p, W, CFG, backend=backend)


def test_rejects_dangling_recv(prog):
    instrs = [i for i in prog.instructions
              if not (i.opcode is Opcode.SEND and i.period == 2)]
    with pytest.raises(ProgramValidationError,
                       match="RECV period 2 on devices .*: no matching SEND"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_dangling_send(prog):
    instrs = [i for i in prog.instructions
              if not (i.opcode is Opcode.RECV and i.period == 2)]
    with pytest.raises(ProgramValidationError,
                       match="SEND period 2 on devices .*: no matching RECV"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_out_of_mesh_window(prog):
    instrs = list(prog.instructions)
    idx = next(k for k, i in enumerate(instrs) if i.opcode is Opcode.FREE)
    bad = dataclasses.replace(
        instrs[idx], devices=(N_DEV + 91,) + instrs[idx].devices[1:])
    instrs[idx] = bad
    with pytest.raises(ProgramValidationError,
                       match=r"outside the 8-device mesh"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_free_before_last_use(prog):
    runs = {i.period: i for i in prog.instructions if i.opcode is Opcode.RUN}
    p = next(p for p in sorted(runs) if p < 2 * W.l
             and set(runs[p].devices) & set(runs[p + 1].devices))
    dev = min(set(runs[p].devices) & set(runs[p + 1].devices))
    instrs = []
    for i in prog.instructions:
        instrs.append(i)
        if i.opcode is Opcode.RUN and i.period == p:
            instrs.append(Instruction.FREE(period=p, released=(dev,)))
    with pytest.raises(ProgramValidationError,
                       match="freed before last use"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_non_divisor_degree(prog):
    instrs = list(prog.instructions)
    idx = next(k for k, i in enumerate(instrs)
               if i.opcode is Opcode.RUN and i.degree > 1)
    r = instrs[idx]
    instrs[idx] = dataclasses.replace(r, degree=3, devices=(0, 1, 2))
    with pytest.raises(ProgramValidationError,
                       match="degree 3 does not divide the device count 8"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_residency_leak(prog):
    """A device leaving the window with its FREE dropped is a leak."""
    drop = next(i for i in prog.instructions
                if i.opcode is Opcode.FREE and i.period < 2 * W.l)
    instrs = [i for i in prog.instructions if i is not drop]
    with pytest.raises(ProgramValidationError, match="residency leak"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_cost_contract_violation(prog):
    instrs = list(prog.instructions)
    idx = next(k for k, i in enumerate(instrs) if i.opcode is Opcode.RUN)
    instrs[idx] = dataclasses.replace(instrs[idx],
                                      cost_s=instrs[idx].cost_s * 2 + 1)
    bad = _with_instrs(prog, instrs)
    check_program(bad)           # no workload/cfg: costs not checked
    with pytest.raises(ProgramValidationError, match="simulator contract"):
        check_program(bad, W, CFG)


def test_rejects_missing_run(prog):
    instrs = [i for i in prog.instructions
              if not (i.opcode is Opcode.RUN and i.period == 2)]
    with pytest.raises(ProgramValidationError, match="missing periods \\[2\\]"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_broken_bp_mirror(prog):
    """Eq. 11: BP windows must mirror FP windows."""
    instrs = list(prog.instructions)
    idx = next(k for k, i in enumerate(instrs)
               if i.opcode is Opcode.RUN and i.phase == "bp"
               and len(i.devices) > 1)
    r = instrs[idx]
    rotated = r.devices[1:] + r.devices[:1]
    instrs[idx] = dataclasses.replace(r, devices=rotated)
    with pytest.raises(ProgramValidationError, match="Eq. 11"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_run_on_non_resident_chunks(prog):
    """A RUN scheduled after its layer's param FREE touches freed chunks
    (the checker's per-device memory walk rejects it)."""
    l = W.l
    instrs = list(prog.instructions)
    pf = next(i for i in instrs if i.opcode is Opcode.FREE and i.layer == 1)
    instrs.remove(pf)
    idx = next(k for k, i in enumerate(instrs)
               if i.opcode is Opcode.RUN and i.period == 2 * l)
    instrs.insert(idx, pf)
    with pytest.raises(ProgramValidationError,
                       match="use-after-FREE: RUN period 6 on device"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_param_free_byte_mismatch(prog):
    """FREE-after-last-use now verifies *bytes*: releasing fewer bytes
    than resident leaves the ledger undrained."""
    instrs = list(prog.instructions)
    idx = next(k for k, i in enumerate(instrs)
               if i.opcode is Opcode.FREE and i.layer is not None)
    instrs[idx] = dataclasses.replace(
        instrs[idx], param_bytes=instrs[idx].param_bytes / 2)
    with pytest.raises(ProgramValidationError,
                       match="ledger would not drain"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_param_free_off_mirror_period(prog):
    """Param FREEs must sit at the layer's Eq.-11 BP mirror period (the
    chunk's last use), nowhere else."""
    l = W.l
    instrs = list(prog.instructions)
    pf = next(i for i in instrs if i.opcode is Opcode.FREE and i.layer == 2)
    instrs.remove(pf)
    instrs.append(dataclasses.replace(pf, period=2 * l))
    with pytest.raises(ProgramValidationError, match="BP mirror period"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_missing_param_free(prog):
    instrs = [i for i in prog.instructions
              if not (i.opcode is Opcode.FREE and i.layer == 1)]
    with pytest.raises(ProgramValidationError,
                       match="exactly one param FREE"):
        check_program(_with_instrs(prog, instrs))


def test_rejects_param_bytes_geometry_mismatch(prog):
    """A self-consistent but wrong byte ledger (every FREE drains what its
    RUN holds) is caught by the chunk's element width: against the other
    layers' without a config, against ``cfg.bytes_per_value`` with one."""
    instrs = []
    for i in prog.instructions:
        if i.layer == 1:          # FP RUN, BP RUN and param FREE of layer 1
            i = dataclasses.replace(i, param_bytes=i.param_bytes * 2)
        instrs.append(i)
    bad = _with_instrs(prog, instrs)
    with pytest.raises(ProgramValidationError,
                       match="dtype mismatch: RUN period 2 .* period 1's"):
        check_program(bad)
    with pytest.raises(ProgramValidationError,
                       match="dtype mismatch: RUN period 1 .* "
                             "cfg.bytes_per_value"):
        check_program(bad, W, CFG)


def test_v1_programs_skip_residency_ledger(prog):
    """Schema-v1 programs (PR 6) have no residency annotations; the
    residency rules only apply from v2 on."""
    instrs = [i for i in prog.instructions
              if not (i.opcode is Opcode.FREE and i.layer is not None)]
    with pytest.raises(ProgramValidationError,
                       match="exactly one param FREE"):
        check_program(_with_instrs(prog, instrs))
    v1 = dataclasses.replace(_with_instrs(prog, instrs), version=1)
    check_program(v1)             # same instructions, v1: accepted
