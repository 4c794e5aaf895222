"""Block-diffusion attention layers whose flash kernels read their operands
where the projections wrote them (``mx_flash_fwd_bd``, ``mx_flash_bwd_dq_bd``,
``mx_flash_bwd_dkv_bd`` of ``ops/bd_kernels.py``), over the block-diffusion
attention layers that ran, in percent: ``mxnet_bd_kernel_layers_total`` over
``mxnet_bd_layers_total``, both summed over every such layer and step
counted.  The layer reads in place where the shapes tile (a head whole
128-lane tiles, the query heads a whole number of key heads' groups, a tile
that takes the mask, no mesh): 100 then; 0 where it fell back to the
composition (heads transposed, K and V repeated, ``mx_flash_*_bd`` on ``(B,
H, 2T, D)`` operands).  A program without the counter has nothing to
read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    kernel = _total(families, "mxnet_bd_kernel_layers_total")
    layers = _total(families, "mxnet_bd_layers_total")
    if kernel is None or not layers:
        return None
    return 100.0 * kernel / layers
