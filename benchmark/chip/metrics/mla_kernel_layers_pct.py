"""Latent attention layers whose flash kernels read their operands where
the projections wrote them (``mx_flash_fwd_mla``, ``mx_flash_bwd_dq_mla``,
``mx_flash_bwd_dkv_mla``), over the latent attention layers that ran, in
percent: ``mxnet_mla_kernel_layers_total`` over ``mxnet_mla_layers_total``,
both summed over every such layer and step counted.  The mixer reads in
place where the shapes tile (``nope`` and ``v_head_dim`` whole 128-lane
tiles, ``rope`` 64 or a multiple, 512 tokens or more, no mesh): 100 then; 0
where it fell back to the composition (heads transposed, the rope key
repeated and concatenated, ``mx_flash_*`` on ``(B, H, T, D)`` operands).  A
program without the counter (any before PR 39) has nothing to read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    kernel = _total(families, "mxnet_mla_kernel_layers_total")
    layers = _total(families, "mxnet_mla_layers_total")
    if kernel is None or not layers:
        return None
    return 100.0 * kernel / layers
