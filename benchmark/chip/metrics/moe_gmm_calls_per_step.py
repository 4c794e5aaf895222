"""Grouped-matmul kernel launches a step: the operations on the first
device's ``XLA Ops`` line in the traced window that were traced under
``mx_gmm*`` (``mx_gmm``, ``mx_gmm_dw``), over the ``mx:train_step`` spans
in the window.  Eight a layer when every grouped product of the step is
the kernel (two forward, the same two again in the backward pass, and for
each projection the gradient of its rows and of its weights): 24.0 with
three expert layers.  A program without the kernels has nothing to read
here."""
import re

import span_reduce
import trace_reduce

KERNELS = re.compile(r"(^|/)mx_gmm[^/]*/")


def per_step(trace, window):
    ops = trace_reduce.device_ops(trace)
    if not ops:
        return None
    t0, t1 = window
    scopes = trace.get("scope", {})
    calls = sum(1 for name, s, _ in ops[min(ops)]        # as idle_gaps does
                if t0 <= s < t1 and KERNELS.search(scopes.get(name, "")))
    steps = len(span_reduce.steps(trace, t0, t1))
    return calls / steps if calls and steps else None


def read(run):
    return span_reduce.read(run, per_step)
