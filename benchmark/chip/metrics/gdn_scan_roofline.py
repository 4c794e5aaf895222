"""The Gated DeltaNet mixers' kernels' share of their roofline: the least time
the chip could take for the mixer kernels of every GDN layer and traced step,
forward and backward (``archs/<model_type>.py:gdn_calls`` at the cell's
shapes, the recurrence's own count; the forward made again and what the
chunked form adds do not count), over the device time under the scope
``gdn_scan`` in the traced window.  A program without that scope, or an
architecture module without ``gdn_calls``, has nothing to read here."""
import archs
import flops
import mixer_reduce


def read(run):
    arch = archs.of(run["cfg"])
    seconds, steps = mixer_reduce.scope_seconds(run, "gdn_scan"), \
        mixer_reduce.steps(run)
    if seconds is None or not steps or not hasattr(arch, "gdn_calls"):
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.gdn_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    return 100.0 * least * arch.gdn_layers(cfg) * steps / seconds
