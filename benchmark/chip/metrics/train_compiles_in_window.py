"""Compiles inside the window: growth of ``mxnet_compiles_total`` plus
growth of the step function's jit cache.  Has to be 0."""


def read(run):
    if "compiles" not in run:
        return None
    (a0, b0), (a1, b1) = run["compiles"]
    return (a1 - a0) + (b1 - b0)
