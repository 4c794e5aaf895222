"""Median over the traced window's steps of the host's time in
``mx:train_step.tag``: tagging the new weights for memdump."""
import span_reduce


def read(run):
    return span_reduce.read(run, span_reduce.phase_ms_p50, "tag")
