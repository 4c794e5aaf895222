"""Milliseconds of a device step spent under the scope ``moe`` (router,
grouped products, the rest of the experts' operator and the shared expert,
forward and backward of every such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "moe")
