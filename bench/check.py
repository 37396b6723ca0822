"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings (``reference.train_readings`` for the
reference; ``harness.program_readings`` for the program): the loss of
steps 1-3, every leaf's gradient norm at step 1 before the clip, and every
leaf's change after three steps.  The numbers compared:

  * ``loss_gap``: the largest relative gap of a step's loss;
  * ``grad_gap``: the worst leaf's gap of gradient norms, over the larger
    of that leaf's reference norm and the median leaf's;
  * ``update_gap``: the same for the change after three steps, leaving
    out leaves whose reference gradient is under a thousandth of the
    median leaf's (they move under Adam by round-off alone).

Cells may add exact numbers of their own (``backup_mismatch``), with the
limit 0.  A number is within its limit when it is finite and not above it.
"""

from __future__ import annotations

import math
import statistics

STILL = 1e-3       # a leaf whose reference gradient is under this share of
                   # the median leaf's is left out of ``update_gap``


def leaf_gap(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """Worst leaf's | |got| - |want| | / max(|want|, median |want|)."""
    names = [n for n in want if keep is None or n in keep]
    med = statistics.median(want[n] for n in names)
    worst, at = 0.0, ""
    for n in names:
        g = abs(got[n] - want[n]) / max(want[n], med, 1e-30)
        if not g <= worst:          # NaN counts as the worst
            worst, at = g, n
    return worst, at


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers of one run, with the leaf that set each."""
    loss = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
               for a, b in zip(prog["loss"], ref["loss"]))
    grad, grad_at = leaf_gap(prog["grad"], ref["grad"])
    med = statistics.median(ref["grad"].values())
    moving = {n for n, g in ref["grad"].items() if g >= STILL * med}
    upd, upd_at = leaf_gap(prog["delta"], ref["delta"], keep=moving)
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": upd,
            "grad_gap_leaf": grad_at, "update_gap_leaf": upd_at}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for every limited number."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, math.nan)
        out[name] = {"value": value, "limit": limit}
        ok &= isinstance(value, (int, float)) and math.isfinite(value) \
            and value <= limit
    return ok, out
