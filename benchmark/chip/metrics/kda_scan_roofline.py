"""The delta-rule scan's share of its roofline: the least time the chip could
take for the scan of every KDA layer and traced step, forward and backward
(``archs/<model_type>.py:kda_calls`` at the cell's shapes, the recurrence's
own count; the recomputed forward and what the chunked form adds do not
count), over the device time under the scope ``kda_scan`` in the traced
window."""
import archs
import flops
import mixer_reduce


def read(run):
    arch = archs.of(run["cfg"])
    seconds, steps = mixer_reduce.scope_seconds(run, "kda_scan"), \
        mixer_reduce.steps(run)
    if seconds is None or not steps or not hasattr(arch, "kda_calls"):
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.kda_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    layers = len(cfg["linear_attn_config"]["kda_layers"])
    return 100.0 * least * layers * steps / seconds
