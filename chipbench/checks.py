"""The comparison that decides ``correct`` for a training cell.

Set-up drives the timed step through its first ``CHECK_STEPS`` steps; the
plain reference follows the same steps from the same seed, and both name
the parameter leaves alike.  The numbers below are computed in every run;
each cell's file gives limits to those it compares:

* ``loss_gap`` — the largest relative gap of the three steps' losses;
  ``first_loss_gap`` — the first step's alone.
* ``grad_norm_gap`` — the norm of the first gradient as the optimizer got
  it (worked out from the optimizer's state after one step), by the worst
  leaf: |‖g_prog‖ − ‖g_ref‖| over the larger of the reference leaf's norm
  and the median leaf's.
* ``update_norm_gap`` — the same for the parameters' change over the
  three steps, by the worst leaf; ``update_median_gap`` — by the median
  leaf.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone under Adam and are left out.

A number that neither the control nor any fault reads far above the
program's own readings has no limit that could hold, and is printed but
not compared; where one small leaf or the later steps swing on a few
seeds, the first step's loss and the median leaf's change stand in.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Sequence

CHECK_STEPS = 3
NAMES = ("loss_gap", "first_loss_gap", "grad_norm_gap", "update_norm_gap",
         "update_median_gap")
STILL = 1e-3


@dataclasses.dataclass(frozen=True)
class Readings:
    """What one side produced over the first steps."""

    losses: tuple[float, ...]
    grad_norms: dict[str, float]
    update_norms: dict[str, float]


def _worst(values: Sequence[float]) -> float:
    """The largest value, or NaN if any is NaN."""
    return math.nan if any(math.isnan(v) for v in values) else max(values)


def _leaf_gaps(prog: dict[str, float], ref: dict[str, float],
               leaves: Sequence[str]) -> list[float]:
    median = statistics.median(ref[k] for k in leaves)
    return [abs(prog[k] - ref[k]) / max(ref[k], median) for k in leaves]


def _median(values: Sequence[float]) -> float:
    return (math.nan if any(math.isnan(v) for v in values)
            else statistics.median(values))


def compare(prog: Readings, ref: Readings) -> dict[str, float]:
    """Every number of ``NAMES``; NaN stays NaN."""
    if len(prog.losses) != len(ref.losses):
        raise ValueError(f"{len(prog.losses)} program losses against "
                         f"{len(ref.losses)} reference losses")
    leaves = list(ref.grad_norms)
    median_g = statistics.median(ref.grad_norms.values())
    moving = [k for k in leaves if ref.grad_norms[k] >= STILL * median_g]
    losses = [abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses)]
    updates = _leaf_gaps(prog.update_norms, ref.update_norms, moving)
    return {
        "loss_gap": _worst(losses),
        "first_loss_gap": losses[0],
        "grad_norm_gap": _worst(_leaf_gaps(prog.grad_norms, ref.grad_norms,
                                           leaves)),
        "update_norm_gap": _worst(updates),
        "update_median_gap": _median(updates),
    }


def judge(numbers: dict[str, float],
          limits: dict[str, float]) -> tuple[bool, dict[str, dict]]:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell
    gives a limit; a number that is not finite fails."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NAMES
             if k in limits}
    ok = bool(table) and all(
        math.isfinite(v["value"]) and v["value"] <= v["limit"]
        for v in table.values())
    return ok, table
