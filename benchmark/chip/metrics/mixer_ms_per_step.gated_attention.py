"""Milliseconds of a device step spent under the scope ``gated_attention``
(the gated softmax attention mixer: projections, q and k norms, the partial
rotation, the flash kernels, the output gate and ``W_o``; forward and
backward of every such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "gated_attention")
