"""``peak_bytes_in_use / bytes_limit`` of the fullest device, read after
the window and before the reference runs."""


def read(run):
    if not run.get("memory_limit_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / run["memory_limit_bytes"]
