"""Process start to the start of the window: imports, model build, weights,
compile or cache load, the first steps and the warm-up."""


def read(run):
    return run["setup_s"]
