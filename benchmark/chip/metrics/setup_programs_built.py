"""Programs the process built by the start of the window (the ledger's
entries up to ``cache_at_window["requests"]``): hits, misses and those that
made no request of the cache."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_programs_built")
