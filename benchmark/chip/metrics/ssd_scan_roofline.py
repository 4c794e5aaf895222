"""The Mamba-2 scan's share of its roofline: the least time the chip could
take for the scan of every Mamba-2 layer and traced step, forward and
backward (``archs/<model_type>.py:ssd_calls`` at the cell's shapes; the
recomputed forward does not count), over the device time under the scope
``ssd_scan`` in the traced window."""
import archs
import flops
import mixer_reduce


def read(run):
    arch = archs.of(run["cfg"])
    seconds, steps = mixer_reduce.scope_seconds(run, "ssd_scan"), \
        mixer_reduce.steps(run)
    if seconds is None or not steps or not hasattr(arch, "ssd_calls"):
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.ssd_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    layers = cfg["hybrid_override_pattern"].count("M")
    return 100.0 * least * layers * steps / seconds
