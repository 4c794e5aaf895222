"""All tokens of all steps completed in the window over the whole window
(host clock; the last step ended by fetching its loss)."""


def read(run):
    if not run.get("tokens"):
        return None
    return run["tokens"] / run["window_s"]
