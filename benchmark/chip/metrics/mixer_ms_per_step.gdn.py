"""Milliseconds of a device step spent under the scope ``gdn`` (the Gated
DeltaNet mixer: its two projections, the delta-rule kernels that take the
rest and the output projection; forward and backward of every such layer),
from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "gdn")
