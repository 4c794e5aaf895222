"""Median device time of the train step's program (its events on the
devices' ``XLA Modules`` line): the step without the host."""
import statistics

import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    mods = trace_reduce.module_durations(trace, r"jit_step")
    durs = [d for v in mods.values() for d in v]
    if len(durs) < 3:
        return None
    return 1e3 * statistics.median(durs)
