"""Milliseconds of a device step spent under the scope ``mlp`` (the dense SwiGLU feed-forward;
forward and backward of every such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "mlp")
