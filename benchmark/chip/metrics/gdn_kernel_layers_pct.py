"""Gated DeltaNet mixers that the delta-rule kernels (``mx_kda_fwd``,
``mx_kda_bwd`` in the GDN form) took whole, reading the two projections'
results where they lie, over the GDN mixers that ran, in percent:
``mxnet_gdn_kernel_layers_total`` over ``mxnet_gdn_layers_total``, both summed
over every GDN layer and step counted.  The mixer is taken whole where the
shapes tile (heads of whole 128-lane rows, value heads a whole number of key
heads', chunks of whole sub-chunks, convolutions of at most 17 taps): 100
then, 0 where it kept the composition.  A program without the counters has
nothing to read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    kernel = _total(families, "mxnet_gdn_kernel_layers_total")
    layers = _total(families, "mxnet_gdn_layers_total")
    if kernel is None or not layers:
        return None
    return 100.0 * kernel / layers
