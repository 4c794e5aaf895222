"""Share of the traced window, per device, spent inside a collective
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute)
while nothing else ran on that device."""
import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or run["chips"] < 2:
        return None
    exposed = trace_reduce.collective_exposed_seconds(
        trace, *run["trace_window"])
    return 100.0 * exposed / run["trace_window_s"]
