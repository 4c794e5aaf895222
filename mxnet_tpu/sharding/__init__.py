"""``mx.sharding`` — first-class named sharding (the GSPMD substrate).

One mesh object, one spec vocabulary, one ambient scope.  Every
multi-device feature in the framework — data/tensor/pipeline/expert
parallel training, multihost arrays, multi-chip serving, elastic
checkpoint resharding — expresses placement through this package:

    import mxnet_tpu as mx
    from mxnet_tpu.sharding import Mesh, P

    mesh = Mesh({"data": 4, "model": 2})
    with mx.tpu(mesh=mesh):              # a context names a device SET
        w = mx.nd.ones((1024, 1024))
        w = mx.nd.shard(w, P(None, "model"))   # lives on 2 chips
        y = mx.nd.dot(x, w)              # GSPMD propagates the sharding

See docs/sharding.md for the full contract and the migration table from
the legacy per-module mesh plumbing.
"""
from .spec import (  # noqa: F401
    Mesh, NamedSharding, PartitionSpec, P,
    as_jax_mesh, canonicalize_spec, named_sharding, spec_axes_label,
    current_mesh, current_jax_mesh, push_mesh, pop_mesh, batch_axis,
)
from .verify import enabled as verify_enabled  # noqa: F401
from .verify import maybe_verify, verify_spec  # noqa: F401
from .reshard import record_reshard  # noqa: F401
from .checkpoint import (  # noqa: F401
    is_global_checkpoint, load_global, save_global,
    spec_from_wire, spec_to_wire,
)
