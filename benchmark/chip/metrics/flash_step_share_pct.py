"""The flash-attention kernels' share of the device's busy time in the
traced window: the operations traced under ``mx_flash_*`` (the kernels'
``name=``: forward, dq, dk/dv) over everything that ran."""
import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not run.get("busy_s"):
        return None
    seconds, events = trace_reduce.scope_seconds(
        trace, r"mx_flash_", *run["trace_window"])
    return 100.0 * seconds / run["busy_s"] if events else None
