"""The busiest held expert's assignments over the mean held expert's,
over every layer and step counted: 1 is even."""
import mixer_reduce


def read(run):
    counts = mixer_reduce.moe_counts(run)
    if counts is None or not counts["held"]:
        return None
    layers = run["cfg"]["hybrid_override_pattern"].count("E")
    slots = layers * run["cfg"]["n_routed_experts"]
    loads = list(counts["held"].values())
    return max(loads) / (sum(loads) / slots)
