"""Static checker sweep: every paper benchmark's compiled program on the
8-device ring checks clean (with its size: instructions, per-device ops,
happens-before edges), and the corruption-corpus regression row — every
seeded corruption must be rejected with the expected message."""

from __future__ import annotations

import re

from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config, workload
from repro.core.allocation import MappingStrategy
from repro.exec.check import (
    ProgramValidationError,
    check_program,
    corruption_corpus,
)
from repro.exec.program import compile_fcnn_program

N_DEV = 8


def run() -> list[dict]:
    rows = []
    cfg = onoc_config(lambda_max=64)
    for nn in sorted(NN_BENCHMARKS):
        w = workload(nn, batch_size=64)
        prog = compile_fcnn_program(w, cfg, N_DEV, MappingStrategy.ORRM)
        try:
            report = check_program(prog, w, cfg)
        except ProgramValidationError:
            report = None
        rows.append({
            "case": f"{nn.lower()}_orrm",
            "nn": nn,
            "strategy": "orrm",
            "n_devices": N_DEV,
            "instructions": 0 if report is None else report.n_instructions,
            "device_ops": 0 if report is None else report.n_device_ops,
            "hb_edges": 0 if report is None else report.n_hb_edges,
            "clean": report is not None,
        })

    # the corpus regression: derived from the NN1 program, each entry
    # rejected with the expected message
    w = workload("NN1", batch_size=64)
    prog = compile_fcnn_program(w, cfg, N_DEV, MappingStrategy.ORRM)
    entries = corruption_corpus(prog, seed=0)
    rejected = 0
    for e in entries:
        try:
            check_program(e.program, w, cfg)
        except ProgramValidationError as err:
            if re.search(e.match, str(err)):
                rejected += 1
    rows.append({
        "case": "corruption_corpus",
        "n_entries": len(entries),
        "rejected": rejected,
        "corpus_ok": rejected == len(entries),
    })
    return rows
