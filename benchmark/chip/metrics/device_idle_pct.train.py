"""1 - the union of the device's operation intervals over the traced
window, averaged over the chips used."""


def read(run):
    if run.get("trace") is None:
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["trace_window_s"])
