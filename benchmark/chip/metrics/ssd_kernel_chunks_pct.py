"""Chunks of the Mamba-2 scans that ran in the Pallas kernels (``mx_ssd_fwd``,
``mx_ssd_bwd``), over the chunks the scans ran, in percent:
``mxnet_ssd_kernel_chunks_total`` over ``mxnet_ssd_chunks_total``, both summed
over every Mamba-2 layer and step counted.  The program takes the kernels
where the shapes tile (a group's heads and the states of whole 128-lane rows,
chunks of 128): 100 then, 0 otherwise.  A program without the counter (any
before PR 37, whose scan was chunked einsums) has nothing to read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    kernel = _total(families, "mxnet_ssd_kernel_chunks_total")
    chunks = _total(families, "mxnet_ssd_chunks_total")
    if kernel is None or not chunks:
        return None
    return 100.0 * kernel / chunks
