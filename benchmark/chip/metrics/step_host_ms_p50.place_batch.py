"""Median over the traced window's steps of the host's time in
``mx:train_step.place_batch``: wrapping the batch (``nd.array``) and
putting it on the device or, data-sharded, on the mesh."""
import span_reduce


def read(run):
    return span_reduce.read(run, span_reduce.phase_ms_p50, "place_batch")
