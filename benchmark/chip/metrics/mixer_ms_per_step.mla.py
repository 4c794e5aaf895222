"""Milliseconds of a device step spent under the scope ``mla`` (the latent attention mixer: projections and the flash kernels;
forward and backward of every such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "mla")
