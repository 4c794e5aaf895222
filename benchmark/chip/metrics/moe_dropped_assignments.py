"""Assignments to a held expert that the program did not compute
(``mxnet_moe_dropped_total``).  Has to be 0: there is no capacity."""
import mixer_reduce


def read(run):
    counts = mixer_reduce.moe_counts(run)
    return None if counts is None else counts["dropped"]
