"""Assignments that landed on a held expert, mean a layer and step
(``mxnet_moe_assignments_held_total`` over the layer-steps counted and the
experts held)."""
import mixer_reduce


def read(run):
    counts = mixer_reduce.moe_counts(run)
    if counts is None or not counts["held"]:
        return None
    held = run["cfg"]["n_routed_experts"]
    return sum(counts["held"].values()) / (
        mixer_reduce.layer_steps(run, counts) * held)
