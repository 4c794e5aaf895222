"""What the per-layer metrics of a model with several kinds of layer read:
device time by the scope a block opened (``jax.named_scope``; the trace
keeps it as each operation's ``tf_op``, ``trace_reduce.scope_seconds``), the
grouped products of the expert layers, and the routing counters of
``mxnet_tpu.telemetry`` (fed from what the step program accumulated on the
device, fetched when read).  Every function returns ``None`` where it finds
nothing to read: a run without a trace, a program that opens no such scope
or has no such counter.
"""
from __future__ import annotations

import re

import trace_reduce

# the TPU compiler writes ``lax.ragged_dot`` as Mosaic kernels named
# ``ragged-dot-*``, under the scope of the operator that called it (``moe``)
# but outside the operator's own ``moe_experts``: they are found by name
GROUPED = re.compile(r"^ragged-dot")


def _scope(kind):
    """An operation traced under the scope ``kind``, whatever wraps the
    name (``jvp(moe)``, ``transpose(jvp(moe))``) or follows it."""
    return r"(^|[/(])%s([/)]|$)" % re.escape(kind)


def steps(run):
    """Steps that ran inside the traced window: every step the window
    dispatched ends inside it (the last loss is fetched there)."""
    return run.get("steps") or None


def scope_seconds(run, kind):
    """Device seconds in the traced window under the scope ``kind``."""
    trace = run.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, _scope(kind), *run["trace_window"])
    return seconds if events else None


def grouped_seconds(run):
    """Device seconds of the grouped products (by name)."""
    trace = run.get("trace")
    if trace is None:
        return None
    seconds, events = trace_reduce.matching_seconds(
        trace, GROUPED.pattern, *run["trace_window"])
    return seconds if events else None


def ms_per_step(run, kind):
    """Milliseconds of a device step under the scope ``kind``."""
    seconds, n = scope_seconds(run, kind), steps(run)
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n


def moe_counts(run):
    """``{"total", "dropped", "held": {(layer, expert): n}}`` from the
    program's counters, or ``None`` where it has none."""
    try:
        from mxnet_tpu.telemetry import metrics
    except ImportError:
        return None
    snap = metrics.snapshot()

    def series(name):
        return snap.get(name, {}).get("series", [])
    total = series("mxnet_moe_assignments_total")
    if not total or not total[0]["value"]:
        return None
    return {"total": total[0]["value"],
            "dropped": sum(s["value"] for s in series(
                "mxnet_moe_dropped_total")),
            "held": {(s["labels"]["layer"], s["labels"]["expert"]):
                     s["value"] for s in series(
                         "mxnet_moe_assignments_held_total")}}


def layer_steps(run, counts):
    """How many times an expert layer ran, over all the steps counted:
    assignments in all over the assignments a layer makes a step."""
    wl, cfg = run["workload"], run["cfg"]
    return counts["total"] / (wl["batch"] * wl["seq"]
                              * cfg["num_experts_per_tok"])
