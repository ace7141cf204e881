"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same three things about the first optimizer steps
from the same weights and batches: the loss of each step, the norm of each
leaf of the first gradient (as the optimizer got it), and the norm of each
leaf's change over those steps.  The program's come from the object the
window then times; the reference's from ``benchmark/reference``.

Each number compared is a gap, worst case first:

  loss_gap    max over the steps of |program - reference| / |reference|
  grad_gap    worst leaf of |‖g‖_program - ‖g‖_reference| over the larger of
              the reference's norm of that leaf and of its median leaf
  delta_gap   the same for the change of the parameters

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both: a key's bias under softmax, a convolution's bias under
batch normalisation.  The loss does not depend on them, the program's
gradient for them is round-off alone, and Adam turns round-off into a move
of full size.  (The median that a gradient is measured against is still the
median of all leaves.)

``grad_gap_median`` and ``delta_gap_median`` are the median leaf's gap where
those two are the worst leaf's: for a network whose worst leaf is noise at
the configuration's own precision (PERF.md says which, and why).  A cell's
file holds a limit for each number it compares; the others are not shown.
"""
from __future__ import annotations

import math
import statistics

ZERO_GRAD_SHARE = 1e-3
# the control's precision: the nearest below the one a configuration states
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def _leaf_gaps(program: dict, reference: dict, leave_out=(),
               median_of_all=False):
    """(worst gap, its leaf, median gap) over the leaves kept."""
    names = [n for n in reference if n not in leave_out]
    missing = [n for n in names if n not in program]
    if missing:
        raise KeyError(f"the program gave no norm for {missing[:4]} ...")
    median = statistics.median(
        reference[n] for n in (reference if median_of_all else names))
    gaps = {}
    for n in names:
        gap = abs(program[n] - reference[n]) / max(reference[n], median,
                                                    1e-30)
        gaps[n] = gap if math.isfinite(gap) else math.inf
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def readings(program: dict, reference: dict) -> dict:
    """The numbers compared, and the leaf at which each is worst."""
    steps = len(reference["losses"])
    if len(program["losses"]) != steps:
        raise ValueError("the two sides followed different numbers of steps")
    loss_gap = max(
        (abs(p - r) / abs(r)) if math.isfinite(p) else math.inf
        for p, r in zip(program["losses"], reference["losses"]))
    ref_g = reference["grad_norms"]
    floor = ZERO_GRAD_SHARE * statistics.median(ref_g.values())
    unmoved = tuple(n for n, g in ref_g.items() if g < floor)
    grad_gap, grad_leaf, grad_median = _leaf_gaps(
        program["grad_norms"], ref_g, unmoved, median_of_all=True)
    delta_gap, delta_leaf, delta_median = _leaf_gaps(
        program["delta_norms"], reference["delta_norms"], unmoved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap, "grad_gap_median": grad_median,
            "delta_gap_median": delta_median,
            "_where": {"grad_gap": grad_leaf, "delta_gap": delta_leaf,
                       "left_out": len(unmoved)}}


def verdict(read: dict, limits: dict, extra: dict = None):
    """(correct, compared): every number that the cell's file gives a limit
    for, beside that limit.  A cell with no limit at all is never correct,
    nor is one whose file names a number that is not read."""
    compared = {}
    ok = bool(limits)
    for name, limit in limits.items():
        value = read.get(name, math.nan)
        compared[name] = {"value": value, "limit": limit}
        if not value <= limit:
            ok = False
    for name, (value, limit, passed) in (extra or {}).items():
        compared[name] = {"value": value, "limit": limit}
        ok = ok and bool(passed)
    return ok, compared
