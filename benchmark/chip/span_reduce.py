"""The program's own spans in a trace: from ``mx:`` annotations to numbers.

``mxnet_tpu.profiler.span(name)`` writes ``mx:<name>`` onto the host plane
of the profiler's trace, on the clock of the device's operations.
``JitTrainStep.step`` is ``mx:train_step``, partitioned into four phases::

    mx:train_step.place_batch   nd.array, device_put (data-sharded on a mesh)
    mx:train_step.scalars       RNG key split, lr and t as device scalars
    mx:train_step.call          the jitted step program, dispatched
    mx:train_step.tag           memdump's tags on the new weights

(``step_n`` is ``mx:train_step_n`` around the same four names.)  Works on
``trace_reduce.load``'s plain form, so a test hands it a trace it wrote.
Every reduction gives ``None`` or nothing where the trace holds no ``mx:``
span (a program from before the spans) or no device operation (a CPU
rehearsal): the readers under ``metrics/`` then leave their metric out.
"""
from __future__ import annotations

import statistics

import trace_reduce

MARK = "mx:"
STEP = MARK + "train_step"
PHASES = ("place_batch", "scalars", "call", "tag")


def steps(trace, t0=None, t1=None, name=STEP):
    """The ``name`` spans that begin in ``[t0, t1)``, in order of time, each
    with the phase spans nested in it:
    ``[{"start": ns, "duration": ns, "phases": [(phase, start, duration)]}]``.
    Nesting in time is the parent link."""
    spans = trace_reduce.marks(trace, MARK)
    phases = [(n[len(STEP) + 1:], s, d) for n, s, d in spans
              if n.startswith(STEP + ".")]
    out = []
    for n, s, d in spans:
        if n != name or (t0 is not None and not t0 <= s < t1):
            continue
        out.append({"start": s, "duration": d,
                    "phases": [p for p in phases
                               if s <= p[1] and p[1] + p[2] <= s + d]})
    return out


def coverage(step):
    """Share of a step span that its phase spans cover."""
    return trace_reduce.union_ns([(s, d) for _, s, d in step["phases"]]) \
        / max(1, step["duration"])


def _device_steps(trace, window):
    """The window's steps, or ``[]`` where no device ran anything."""
    if trace is None or not trace_reduce.device_ops(trace):
        return []
    return steps(trace, *window)


def phase_ms_p50(trace, window, phase):
    """Median over the window's steps of the host's time in ``phase``, ms.
    Once the device's queue is full, the phase in which the host waits for
    the device reads about one device step."""
    durs = [d for st in _device_steps(trace, window)
            for p, _, d in st["phases"] if p == phase]
    return statistics.median(durs) / 1e6 if durs else None


def idle_by_span(trace, t0, t1):
    """``{span name without mx: -> seconds}`` of device idle time, by
    ``trace_reduce.idle_gaps``' own rule (first device, the gap's start,
    the innermost covering span) applied to the ``mx:`` spans: that
    function is handed the ``mx:`` spans under its own prefix."""
    mine = [(trace_reduce.MARK + n[len(MARK):], s, d)
            for n, s, d in trace_reduce.marks(trace, MARK)]
    view = {"planes": trace_reduce.device_planes(trace) + [
        {"name": trace_reduce.HOST_PLANE,
         "lines": [{"name": "mx", "events": mine}]}]}
    return dict(trace_reduce.idle_gaps(view, len(mine) + 1, t0, t1))


def phase_idle_ms(trace, window, phase, idle=None):
    """Device idle time whose gap begins under ``phase``, per step of the
    window, ms; ``idle`` is ``idle_by_span``'s answer where the caller has
    it already."""
    n = len(_device_steps(trace, window))
    if not n:
        return None
    if idle is None:
        idle = idle_by_span(trace, *window)
    return 1e3 * idle.get(STEP[len(MARK):] + "." + phase, 0.0) / n


def programs_per_step(trace, window):
    """Programs (``XLA Modules`` events) the first device began in the
    window over the window's steps: the step's own program and whatever
    small ones the host dispatched beside it."""
    n = len(_device_steps(trace, window))
    if not n:
        return None
    first = min(trace_reduce.device_ops(trace))       # as idle_gaps does
    t0, t1 = window
    mods = [e for p in trace_reduce.device_planes(trace) if p["name"] == first
            for e in trace_reduce._line(p, trace_reduce.MODULES_LINE)
            if t0 <= e[1] < t1]
    return len(mods) / n


def read(run, fn, *args):
    """What a reader under ``metrics/`` returns: ``fn`` on the run's trace
    and window, ``None`` where the run was not traced."""
    if run.get("trace") is None:
        return None
    return fn(run["trace"], run["trace_window"], *args)


def read_idle(run, phase):
    """``step_idle_ms.<phase>``'s reader.  The walk over the device's gaps
    is the same for the four phases, so the first reader keeps its answer
    on the run."""
    if run.get("trace") is None:
        return None
    trace, window = run["trace"], run["trace_window"]
    if not _device_steps(trace, window):
        return None
    if "mx_idle_s" not in run:
        run["mx_idle_s"] = idle_by_span(trace, *window)
    return phase_idle_ms(trace, window, phase, run["mx_idle_s"])
