"""The flash kernels' share of their roofline in a model whose
architecture module says how many of its layers attend through them: the
least time the chip could take for the forward, dq and dk/dv kernels
(``archs/<model_type>.py:mla_flash_calls``: keys of ``nope + rope``
channels, values of ``v_head_dim``) of ``attention_layers(cfg)`` layers and
every traced step, over the device time of the operations traced under
``mx_flash_*`` (the kernels' ``name=``) in the traced window.  A kernel
call that the program makes again in its backward pass counts in the time
and not in the least time: the share falls by what is made again.  An
architecture module without ``attention_layers`` has nothing to read here
(``mla_flash_roofline`` reads the one that names its layers in
``linear_attn_config``)."""
import archs
import flops
import mixer_reduce
import trace_reduce


def read(run):
    trace, steps = run.get("trace"), mixer_reduce.steps(run)
    arch = archs.of(run["cfg"])
    if trace is None or not steps or not hasattr(arch, "attention_layers") \
            or not hasattr(arch, "mla_flash_calls"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, r"mx_flash_", *run["trace_window"])
    if not events:
        return None
    cfg, wl = run["cfg"], run["workload"]
    least, _ = flops.least_seconds(
        arch.mla_flash_calls(cfg, wl["batch"], wl["seq"]), run["peak"])
    return 100.0 * least * arch.attention_layers(cfg) * steps / seconds
