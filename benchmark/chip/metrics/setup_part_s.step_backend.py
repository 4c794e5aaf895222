"""``backend_s`` of the step program's entry in the ledger: retrieval,
deserialisation and load onto the cell's chips on a cache hit, XLA's compile
on a miss (``cache_misses_setup`` says which)."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.step_backend")
