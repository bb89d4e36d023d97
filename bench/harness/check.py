"""The comparison that decides ``correct`` for a training cell.

Four numbers, each read off the first steps of the run and set against the
plain reference (``reference/dp_step.py``) of the same steps from the same
seed, each held to a limit of its own (``limits/<cell>.json``):

  loss     the largest relative gap of the first steps' losses;
  grad     the first averaged gradient, as AdamW's first moment holds it:
           per leaf the gap between the program's norm and the reference's,
           over the larger of the reference's norm of that leaf and of the
           median leaf; the worst leaf;
  update   the same for the parameters' change over the first steps, over
           the leaves whose reference clipped sum is at least 1e-3 of the
           median leaf's (leaves whose gradient is nought to rounding move
           under Adam by round-off alone);
  signal   the first gradient times the batch, the reference's noise taken
           out, projected on the reference's clipped (or plain) gradient
           sum: per leaf the gap between that projection and the sum's
           norm, over the larger of that norm and the median leaf's; the
           worst leaf. With noise of std sigma on every element, the
           norms above are the noise's; this is the number that sees the
           clipped sum underneath.
"""
from __future__ import annotations

import statistics

NAMES = ("loss", "grad", "update", "signal")
MIN_LEAF_SHARE = 1e-3


def _worst(prog: dict, ref: dict, leaves) -> float:
    med = statistics.median(ref[p] for p in leaves)
    return max(abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30)
               for p in leaves)


def numbers(prog: dict, ref: dict, name: str = "program") -> dict:
    """prog: {"losses", "g0_norm", "upd_norm"}; ref: the reference's
    readings, with ``proj[name]`` taken against prog's first gradient."""
    leaves = sorted(ref["g0_norm"])
    med_s = statistics.median(ref["s_norm"][p] for p in leaves)
    moved = [p for p in leaves if ref["s_norm"][p] >= MIN_LEAF_SHARE * med_s]
    return {
        "loss": max(abs(a - b) / abs(b)
                    for a, b in zip(prog["losses"], ref["losses"])),
        "grad": _worst(prog["g0_norm"], ref["g0_norm"], leaves),
        "update": _worst(prog["upd_norm"], ref["upd_norm"], moved),
        "signal": _worst(ref["proj"][name], ref["s_norm"], leaves),
    }


def verdict(values: dict, limits: dict) -> tuple:
    """-> (correct, [(name, value, limit)]). A number that is not finite, or
    missing, fails."""
    rows, ok = [], True
    for name in NAMES:
        v, lim = values.get(name), limits[name]
        good = v is not None and v == v and v <= lim
        ok = ok and good
        rows.append((name, v, lim))
    return ok, rows
