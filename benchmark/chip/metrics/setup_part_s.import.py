"""Seconds of the program's own import
(``mxnet_setup_seconds_total{stage="setup.import"}``: ``mxnet_tpu/__init__.py``
first statement to last, and jax's where the process had not imported it)."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.import")
