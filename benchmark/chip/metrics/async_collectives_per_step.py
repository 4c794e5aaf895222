"""Collectives begun asynchronously a step: ``async-collective-start*``
operations on the first device's ``XLA Ops`` line in the traced window,
over the ``mx:train_step`` spans in the window.  Such a collective runs
under the operations between its start and its ``async-collective-done``.
A program compiled without them (any before PR 28, any on one device) has
nothing to read here."""
import re

import span_reduce
import trace_reduce

START = re.compile(r"^async-collective-start(\.|\s|$)")


def per_step(trace, window):
    ops = trace_reduce.device_ops(trace)
    if not ops:
        return None
    t0, t1 = window
    starts = sum(1 for name, s, _ in ops[min(ops)]        # as idle_gaps does
                 if t0 <= s < t1 and START.match(name))
    steps = len(span_reduce.steps(trace, t0, t1))
    return starts / steps if starts and steps else None


def read(run):
    return span_reduce.read(run, per_step)
