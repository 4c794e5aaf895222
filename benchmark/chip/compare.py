"""The comparison that decides ``correct``: numbers, each beside a limit.

A training cell's readings are ``{"losses", "grad_norms", "delta_norms"}``
(``reference.train_readings`` gives them for the reference; a driver reads
the same three from the program's own first steps).  The numbers:

- ``loss_gap``: the widest ``|loss - reference| / reference`` over the steps
  followed;
- ``grad_norm_gap``: the worst leaf's gap between the norm of the first
  gradient as the optimizer got it and the reference's, measured against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- ``delta_norm_gap``: the same for the norm of each leaf's change after the
  last step followed.  A leaf whose reference gradient is under a
  thousandth of the median leaf's moves by round-off alone under Adam and
  is left out, by that rule and not by name.
"""
from __future__ import annotations

import math
import statistics

NEVER = 1e30      # what a missing or non-finite reading counts as


def _worst_gap(ours, ref, keep=None):
    floor = statistics.median(ref)
    gaps = [abs(a - b) / max(b, floor)
            for i, (a, b) in enumerate(zip(ours, ref))
            if keep is None or keep[i]]
    return max(gaps)


def train_numbers(ours, ref):
    """``{name: value}`` for a training cell; a missing or non-finite
    reading gives ``NEVER``, which no limit admits."""
    out = {}
    try:
        if len(ours["losses"]) != len(ref["losses"]):
            raise ValueError("steps followed differ")
        out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in
                              zip(ours["losses"], ref["losses"]))
        out["grad_norm_gap"] = _worst_gap(ours["grad_norms"],
                                          ref["grad_norms"])
        med = statistics.median(ref["grad_norms"])
        keep = [g >= 1e-3 * med for g in ref["grad_norms"]]
        out["delta_norm_gap"] = _worst_gap(ours["delta_norms"],
                                           ref["delta_norms"], keep)
    except (KeyError, TypeError, ValueError, ZeroDivisionError):
        out = dict.fromkeys(("loss_gap", "grad_norm_gap", "delta_norm_gap"),
                            NEVER)
    return {k: (v if math.isfinite(v) else NEVER) for k, v in out.items()}


def judge(numbers, limits):
    """``(correct, {name: [value, limit]})``: every number named in
    ``limits`` has to be there and at or under its limit."""
    table = {k: [numbers.get(k, NEVER), lim] for k, lim in limits.items()}
    return all(v <= lim for v, lim in table.values()), table
