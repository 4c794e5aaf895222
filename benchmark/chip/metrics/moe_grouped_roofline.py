"""The experts' grouped products' share of their roofline: the least time
the chip could take for the six products of every expert layer and traced
step (``archs/<model_type>.py:grouped_calls``) **at the rows the program's
counters say landed** on the held experts (their mean a layer and step, not
the expectation), over the device time of the ``ragged-dot`` kernels in the
traced window (the recomputed forward products are in that time and not in
the count).  A dense product over the bound ``tokens x k`` would read about
``held / experts``."""
import archs
import flops
import mixer_reduce


def read(run):
    arch = archs.of(run["cfg"])
    seconds, steps = mixer_reduce.grouped_seconds(run), \
        mixer_reduce.steps(run)
    counts = mixer_reduce.moe_counts(run)
    if seconds is None or not steps or counts is None \
            or not hasattr(arch, "grouped_calls"):
        return None
    cfg = run["cfg"]
    rows = sum(counts["held"].values()) / mixer_reduce.layer_steps(
        run, counts)
    least, _ = flops.least_seconds(arch.grouped_calls(cfg, rows), run["peak"])
    layers = cfg["hybrid_override_pattern"].count("E")
    return 100.0 * least * layers * steps / seconds
