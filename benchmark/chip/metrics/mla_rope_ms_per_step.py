"""Milliseconds of a device step spent under the scope ``mla_rope`` (inside
``mla``: the rotary embedding of the queries' and the key's rope channels,
the repeat of the rope key over the heads and the concatenation that
builds the keys; forward and gradient of every such layer), from the traced
window.  A program that opens no such scope has nothing to read."""
import mixer_reduce


def read(run):
    return mixer_reduce.ms_per_step(run, "mla_rope")
