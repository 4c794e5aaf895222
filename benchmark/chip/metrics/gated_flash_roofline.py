"""The flash kernels' share of their roofline in the gated attention layers:
the least time the chip could take for the forward, dq and dk/dv kernels
(``archs/<model_type>.py:attention_calls``: causal, keys and values of
``head_dim`` channels) of ``attention_layers(cfg)`` layers and every traced
step, over the device time of the generic flash kernels (``mx_flash_fwd``,
``mx_flash_bwd_dq``, ``mx_flash_bwd_dkv``; not the latent-attention or the
block-diffusion ones) traced inside the scope ``gated_attention`` in the
traced window.  A program without that scope, or an architecture module
without ``attention_calls``, has nothing to read here."""
import archs
import flops
import mixer_reduce
import trace_reduce

KERNELS = r"(^|[/(])gated_attention([/)]).*mx_flash_(fwd|bwd_dq|bwd_dkv)\b"


def read(run):
    trace, steps = run.get("trace"), mixer_reduce.steps(run)
    arch = archs.of(run["cfg"])
    if trace is None or not steps or not hasattr(arch, "attention_calls"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, KERNELS, *run["trace_window"])
    if not events or seconds <= 0:
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.attention_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    return 100.0 * least * arch.attention_layers(cfg) * steps / seconds
