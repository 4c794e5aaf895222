"""Milliseconds of a device step spent under the scope ``kda`` (the Kimi Delta Attention mixer: projections, convolutions, gate
and the delta-rule scan;
forward and backward of every such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "kda")
