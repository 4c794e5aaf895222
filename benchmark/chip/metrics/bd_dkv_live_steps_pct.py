"""Grid steps of the block-diffusion dk/dv kernel (``mx_flash_bwd_dkv_bd``)
that compute a live tile, in percent: ``mxnet_flash_tiles_computed_total``
over ``mxnet_flash_dkv_steps_total``, both summed over every such layer and
step counted.  Where the layer reads in place (``ops/bd_kernels.py``) the
kernel's grid walks the mask's live (key tile, query head, query tile)
visits alone: 100.  Where it takes the composition the grid walks every
tile and skips the dead ones: ``n (n + 2)`` of ``4 n^2`` compute (31.25 at
8192 positions in 512-token tiles).  A program without the counter (no such
layer, or a program that does not count the kernel's steps) has nothing to
read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    computed = _total(families, "mxnet_flash_tiles_computed_total")
    steps = _total(families, "mxnet_flash_dkv_steps_total")
    if computed is None or not steps:
        return None
    return 100.0 * computed / steps
