"""Tiles of the flash kernels' grid that they computed under the
block-diffusion mask, over the tiles of the grid, in percent:
``mxnet_flash_tiles_computed_total`` over ``mxnet_flash_tiles_total``, both
summed over every such layer and step counted.  At ``n`` tiles a half the
kernels compute ``n (n + 2)`` of ``4 n^2`` (31.25 at 8192 positions in
512-token tiles); 100 where the shape takes the dense fallback.  A program
without the counters (no flash kernels under that mask) has nothing to
read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    computed = _total(families, "mxnet_flash_tiles_computed_total")
    tiles = _total(families, "mxnet_flash_tiles_total")
    if computed is None or not tiles:
        return None
    return 100.0 * computed / tiles
