"""Milliseconds of a device step spent under the scope ``bd_attention``
(the block-diffusion attention sub-block: projections, qk-norm, rotation,
the repeat of K and V and the flash kernels; forward and backward of every
such layer), from the traced window."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "bd_attention")
