"""The flash kernels' share of their roofline in a model whose attention
layers are latent attention: the least time the chip could take for the
forward, dq and dk/dv kernels of every layer that attends and traced step
(``archs/<model_type>.py:mla_flash_calls``: keys of ``nope + rope``
channels, values of ``v_head_dim``) over the device time of the operations
traced under ``mx_flash_*`` (the kernels' ``name=``) in the traced
window."""
import archs
import flops
import mixer_reduce
import trace_reduce


def read(run):
    trace, steps = run.get("trace"), mixer_reduce.steps(run)
    arch = archs.of(run["cfg"])
    if trace is None or not steps or not hasattr(arch, "mla_flash_calls"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, r"mx_flash_", *run["trace_window"])
    if not events:
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.mla_flash_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    layers = len(cfg["linear_attn_config"]["full_attn_layers"])
    return 100.0 * least * layers * steps / seconds
