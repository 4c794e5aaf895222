"""Rows of the sorted layout that the kernels around the grouped products
walked, over the rows it has (as many as there are assignments), in percent:
``mxnet_moe_sorted_rows_walked_total`` over ``mxnet_moe_assignments_total``,
both summed over every expert layer and step counted.  The kernels walk the
128-row tiles that hold a landed row: 100 when every assignment lands on a
held expert, and what lands (rounded up to tiles) otherwise.  A program
without the counter (any before PR 33, whose operations around the products
ran over every row of the bound) has nothing to read."""
import mixer_reduce


def read(run):
    counts = mixer_reduce.moe_counts(run)
    if counts is None:
        return None
    from mxnet_tpu.telemetry import metrics

    walked = metrics.snapshot().get(
        "mxnet_moe_sorted_rows_walked_total", {}).get("series", [])
    if not walked:
        return None
    return 100.0 * sum(s["value"] for s in walked) / counts["total"]
