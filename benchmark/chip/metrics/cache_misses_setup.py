"""Cache requests by the start of the window that found no entry
(``cache_at_window["misses"]``): 0 says the run was warm, so the set-up
parts are loads, not compiles."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "cache_misses_setup")
