"""``setup_s`` less the six parts above: the interpreter's and jax's import,
the TPU runtime's start, the benchmark's weights and readings, the first
steps' run time.  The parts sum to ``setup_s`` by construction."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.outside_program")
