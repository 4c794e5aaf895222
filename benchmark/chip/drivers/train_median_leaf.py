"""Driver ``train_median_leaf``: ``train_batches`` in every respect, set-up
and window alike, whose comparison also reads the median leaf.

``grad_norm_gap`` is the worst leaf's gap between the program's first
gradient norm and the reference's.  In a model whose gradient at its random
start has a leaf or two that any rounding moves far (Qwen3-Next: the routers
and shared-expert gates behind the Gated DeltaNet layers, whose output norm
works near its ``eps`` there), that worst leaf is set by the conditioning and
not by the precision: bfloat16 and fp8 read alike on it.  Over the other
leaves the gap still follows the precision.  So ``grad_norm_gap_median`` is
the median over the leaves of the same per-leaf gap (``compare.py``'s rule:
against the reference's norm of that leaf or of the median leaf, whichever
is larger), and ``delta_norm_gap_median`` the same for each leaf's change
after the last step followed, over the leaves ``delta_norm_gap`` keeps; a
missing or non-finite reading counts as ``compare.NEVER``.
"""
from __future__ import annotations

import importlib.util
import math
import os
import statistics

import compare

_spec = importlib.util.spec_from_file_location(
    "chipbench_drivers_train_batches",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "train_batches.py"))
train_batches = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train_batches)
reference_shardings = train_batches.reference_shardings


def _median(ours, ref, keep=None):
    floor = statistics.median(ref)
    value = statistics.median(
        abs(a - b) / max(b, floor) for i, (a, b) in enumerate(zip(ours, ref))
        if keep is None or keep[i])
    return value if math.isfinite(value) else compare.NEVER


def median_gaps(ours, ref):
    """``{"grad_norm_gap_median", "delta_norm_gap_median"}`` of two
    readings."""
    try:
        grads = ref["grad_norms"]
        if len(ours["grad_norms"]) != len(grads) \
                or len(ours["delta_norms"]) != len(ref["delta_norms"]):
            raise ValueError("leaves differ")
        keep = [g >= 1e-3 * statistics.median(grads) for g in grads]
        return {"grad_norm_gap_median": _median(ours["grad_norms"], grads),
                "delta_norm_gap_median": _median(
                    ours["delta_norms"], ref["delta_norms"], keep)}
    except (KeyError, TypeError, ValueError, ZeroDivisionError):
        return dict.fromkeys(("grad_norm_gap_median",
                              "delta_norm_gap_median"), compare.NEVER)


def numbers(ours, ref):
    """``compare.train_numbers`` and the median leaf's gaps."""
    return dict(compare.train_numbers(ours, ref), **median_gaps(ours, ref))


class Driver(train_batches.Driver):
    def check(self):
        super().check()
        return numbers(self.run["ours"], self.run["reference"])
