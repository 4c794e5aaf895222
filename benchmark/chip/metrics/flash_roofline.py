"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the forward, dq and dk/dv kernels of every layer and
traced step (``flops.flash_calls`` at the cell's shapes, per chip) over the
device time of the Mosaic custom calls in the traced window."""
import flops
import trace_reduce

# what marks a Pallas (Mosaic) kernel in a device operation's HLO text
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    cfg, wl = run["cfg"], run["workload"]
    seconds, events = trace_reduce.matching_seconds(
        trace, KERNEL, *run["trace_window"], detail=True)
    per_step = 3 * cfg["num_hidden_layers"]
    if seconds <= 0 or events < per_step:
        return None
    steps = events / per_step      # kernel calls seen, in steps
    mesh = cfg.get("mesh") or {}
    calls = flops.flash_calls(
        wl["batch"] // mesh.get("data", 1),
        cfg["num_attention_heads"] // mesh.get("model", 1),
        wl["seq"], flops.head_dim(cfg))
    least, _ = flops.least_seconds(calls, run["peak"])
    return 100.0 * least * cfg["num_hidden_layers"] * steps / seconds
