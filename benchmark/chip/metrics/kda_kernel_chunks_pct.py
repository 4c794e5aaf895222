"""Chunks of the delta-rule scans that ran in the Pallas kernels
(``mx_kda_fwd``, ``mx_kda_bwd``), over the chunks the scans ran, in percent:
``mxnet_kda_kernel_chunks_total`` over ``mxnet_kda_chunks_total``, both summed
over every KDA layer and step counted.  The program takes the kernels where
the shapes tile (keys and values of whole 128-lane rows): 100 then, 0
otherwise.  A program without the counter (any before PR 35, whose scan was
chunked einsums) has nothing to read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    kernel = _total(families, "mxnet_kda_kernel_chunks_total")
    chunks = _total(families, "mxnet_kda_chunks_total")
    if kernel is None or not chunks:
        return None
    return 100.0 * kernel / chunks
