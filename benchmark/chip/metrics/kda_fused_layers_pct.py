"""KDA mixers that the kernels (``mx_kda_fwd``, ``mx_kda_bwd``) took whole,
reading the projections' results where they lie and making the
convolutions, the norms, the decay gate, ``beta`` and the output gate in
VMEM, over the KDA mixers that ran, in percent:
``mxnet_kda_fused_layers_total`` over ``mxnet_kda_layers_total``, both summed
over every KDA layer and step counted.  The mixer is taken whole where the
shapes tile (heads of whole 128-lane rows, chunks of whole sub-chunks,
convolutions of at most 17 taps): 100 then, 0 where it kept the composition.
A program without the counters (any before PR 41) has nothing to read."""


def _total(families, name):
    series = families.get(name, {}).get("series", [])
    return sum(s["value"] for s in series) if series else None


def read(run):
    from mxnet_tpu.telemetry import metrics

    families = metrics.snapshot()
    fused = _total(families, "mxnet_kda_fused_layers_total")
    layers = _total(families, "mxnet_kda_layers_total")
    if fused is None or not layers:
        return None
    return 100.0 * fused / layers
