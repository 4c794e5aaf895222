"""Trace, lowering and backend seconds of every set-up program built under
neither of the step's stages: the benchmark's eager programs (weights from
the seed, the readings ``correct`` compares) and the step's small companions."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.other_programs")
