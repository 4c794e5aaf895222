"""``lower_s`` of the step program's entry in the ledger: jaxpr to StableHLO."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.step_lower")
