#!/usr/bin/env python3
"""Readings that the cells' limits are set from (run on the chip by hand;
the benchmark's own runs never run it).

For each seed, in one process that holds the chips:

* ``program`` — the timed step's first three steps against the reference
  (the lower reading of each compared number);
* ``control`` — the reference one precision below the configuration's
  (bfloat16 for float32), in the program's place;
* ``half_batch``, ``altered_answer`` — faults planted in the reference put
  in the program's place;
* ``no_exchange`` — the program with its all-gather transitions replaced
  by each chip's own chunk (cells on more than one chip).

The program and the reference are the kind's (``kinds/<kind>.py``).  A
step that returns its state unchanged reads 1 on ``update_norm_gap`` by
construction and needs no run.

    python3 chipbench/calibrate.py --workload nn6.b128 --seeds 1-12 \
        --what program,control,half_batch,altered_answer --out FILE
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import checks, harness, spec  # noqa: E402

WHATS = ("program", "control", "half_batch", "altered_answer", "no_exchange")


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def _no_exchange(chips):
    """An all-gather with the exchange left out: every chip sees only its
    own chunk, in every chunk's place."""
    def gather(x, axis_name, **_):
        return jnp.broadcast_to(x[None], (chips,) + x.shape)
    return gather


def readings(cell, devices, seeds, what, kernel_mode="pallas",
             detail=False):
    """One row of compared numbers per seed, for each reading in
    ``what`` (with ``detail``, also each side's per-leaf norms)."""
    kind = spec.load_kind(cell)
    programs = {"program": kind.build(cell, devices, kernel_mode)}
    if "no_exchange" in what:
        # its own program, so that the planted fault is traced afresh
        programs["no_exchange"] = kind.build(cell, devices, kernel_mode)

    def program(name, seed, data):
        prog, step = programs[name]
        state = kind.init_state(cell, prog, seed)
        _, got = kind.first_steps(cell, step, state,
                                  kind.feed(cell, prog, data))
        return got

    rows = []
    for seed in seeds:
        t = time.perf_counter()
        data = kind.dataset(cell, seed)
        ref = kind.reference(cell, seed, data, devices[0])
        row = {"seed": seed, "ref_losses": list(ref.losses)}
        if detail:
            row["ref_grad_norms"] = ref.grad_norms
            row["ref_update_norms"] = ref.update_norms
        for w in what:
            if w == "program":
                got = program(w, seed, data)
            elif w == "no_exchange":
                gather = jax.lax.all_gather
                jax.lax.all_gather = _no_exchange(len(devices))
                try:
                    got = program(w, seed, data)
                finally:
                    jax.lax.all_gather = gather
            else:
                got = kind.reference(cell, seed, data, devices[0], planted=w)
            row[w] = checks.compare(got, ref)
            row[f"{w}_losses"] = list(got.losses)
            if detail:
                row[f"{w}_grad_norms"] = got.grad_norms
                row[f"{w}_update_norms"] = got.update_norms
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {}
    for w in what:
        pick = max if w == "program" else min
        summary[w] = {k: pick(r[w][k] for r in rows) for k in checks.NAMES}
    return rows, summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--out", required=True)
    ap.add_argument("--detail", action="store_true",
                    help="also record each side's per-leaf norms")
    args = ap.parse_args()
    what = args.what.split(",")
    unknown = set(what) - set(WHATS)
    if unknown:
        ap.error(f"unknown readings {sorted(unknown)}")
    cell = spec.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache(spec.ROOT)
    rows, summary = readings(cell, devices, _seeds(args.seeds), what,
                             detail=args.detail)
    print(json.dumps({"workload": cell.name, "device": devices[0].device_kind,
                      "summary": summary}), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "device": devices[0].device_kind,
                   "rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
