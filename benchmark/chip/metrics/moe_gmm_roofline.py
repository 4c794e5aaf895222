"""The grouped expert kernels' share of their roofline: the least time the
chip could take for the six products of every expert layer and traced step
(``archs/<model_type>.py:grouped_calls``) **at the rows the program's
counters say landed** on the held experts, exactly as
``moe_grouped_roofline`` counts it, over the device time of the operations
traced under ``mx_gmm`` (the kernels' ``name=``: ``mx_gmm`` forward and
rows' gradient, ``mx_gmm_dw`` weights' gradient) in the traced window.  The
two forward products that the backward pass makes again are in that time
and not in the count, so the share cannot pass 75.  A program whose grouped
products are not these kernels (any before PR 31) has nothing to read."""
import archs
import flops
import mixer_reduce
import trace_reduce

KERNELS = r"(^|/)mx_gmm[^/]*/"


def read(run):
    trace, steps = run.get("trace"), mixer_reduce.steps(run)
    arch = archs.of(run["cfg"])
    if trace is None or not steps or not hasattr(arch, "grouped_calls"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, KERNELS, *run["trace_window"])
    counts = mixer_reduce.moe_counts(run)
    if not events or counts is None:
        return None
    cfg = run["cfg"]
    rows = sum(counts["held"].values()) / mixer_reduce.layer_steps(
        run, counts)
    least, _ = flops.least_seconds(arch.grouped_calls(cfg, rows), run["peak"])
    layers = cfg["hybrid_override_pattern"].count("E")
    return 100.0 * least * layers * steps / seconds
