"""The block-diffusion flash kernels' share of their roofline: the least
time the chip could take for the forward, dq and dk/dv kernels under the
block-diffusion mask (``archs/<model_type>.py:bd_flash_calls``: the
operations over the mask's live (query, key) pairs, whatever computes them,
and the bytes of ``q, k, v, o`` over both halves) of ``attention_layers(cfg)``
layers and every traced step, over the device time of the operations traced
under ``mx_flash_*_bd`` (the kernels' ``name=``) in the traced window.  The
partly live tiles are computed whole and count in the time alone, so the
share stays below the kernels' own efficiency.  A program without those
kernels, or an architecture module without ``bd_flash_calls``, has nothing
to read here."""
import archs
import flops
import mixer_reduce
import trace_reduce

KERNELS = r"mx_flash_\w+_bd"


def read(run):
    trace, steps = run.get("trace"), mixer_reduce.steps(run)
    arch = archs.of(run["cfg"])
    if trace is None or not steps or not hasattr(arch, "bd_flash_calls"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, KERNELS, *run["trace_window"])
    if not events or seconds <= 0:
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.bd_flash_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    return 100.0 * least * arch.attention_layers(cfg) * steps / seconds
