"""Median over the traced window's steps of the host's time in
``mx:train_step.scalars``: making the step's scalars (the RNG key split, lr
and t as device arrays).  These are the step's first programs, so with the
runtime's queue of programs full the host waits here for a program to end,
and this reads about one device step (PERF.md, PR 27)."""
import span_reduce


def read(run):
    return span_reduce.read(run, span_reduce.phase_ms_p50, "scalars")
