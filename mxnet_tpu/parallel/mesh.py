"""Device-mesh helpers (legacy spellings over the sharding substrate).

The mesh is the TPU-native replacement for the reference's device lists
(``ctx=[mx.gpu(0), mx.gpu(1), ...]`` in ``Module.bind`` /
``Trainer``): axes are named (``data``, ``model``, ``pipe``, ``seq``,
``expert``) and shardings are expressed as ``PartitionSpec`` over those
names; XLA lowers them to ICI/DCN collectives (scaling-book recipe).

Since the GSPMD substrate landed (``mxnet_tpu/sharding/``), that
package owns mesh construction and the ambient-mesh scope; this module
keeps the historical entry points (``make_mesh``, ``current_mesh``,
``MeshScope``, ``shard_params``) as thin delegates so existing callers
and checkpoints of API usage keep working.  New code should prefer
``mx.sharding.Mesh`` — it is the same object underneath.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import sharding as _sharding


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` over a framework ``sharding.Mesh`` or a raw jax
    mesh."""
    return jax.shard_map(f, mesh=_sharding.as_jax_mesh(mesh),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def make_mesh(axes=None, devices=None):
    """Create a named jax Mesh.

    ``axes``: dict name->size (-1 once for 'remaining devices'), or None
    for a 1-axis data mesh over all devices.  Returns the raw
    ``jax.sharding.Mesh`` (legacy contract); ``sharding.Mesh`` wraps the
    same constructor.
    """
    return _sharding.Mesh(axes, devices=devices).jax_mesh


def current_mesh():
    """The ambient mesh — one stack shared with ``sharding.current_mesh``
    (so ``with mx.tpu(mesh=...)`` and ``MeshScope`` see each other)."""
    return _sharding.current_mesh()


class MeshScope:
    """``with MeshScope(mesh):`` — sets the ambient mesh for Trainer/KVStore.

    Same stack as ``with sharding.Mesh(...):``; kept for back-compat."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _sharding.push_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _sharding.pop_mesh()


def replicated(mesh):
    return NamedSharding(_sharding.as_jax_mesh(mesh), P())


def data_sharding(mesh, axis="data", ndim=1):
    """Shard dim 0 (batch) over ``axis``, replicate the rest."""
    return NamedSharding(_sharding.as_jax_mesh(mesh),
                         P(axis, *([None] * (ndim - 1))))


def shard_params(mesh, params, rule=None):
    """Device_put parameter arrays with shardings from ``rule``.

    ``rule(name, shape) -> PartitionSpec`` (None → replicate).  This is the
    entry point for tensor parallelism: e.g. megatron-style rules return
    ``P(None, 'model')`` for up-projections.
    """
    jm = _sharding.as_jax_mesh(mesh)
    out = {}
    for name, arr in params.items():
        spec = rule(name, arr.shape) if rule is not None else None
        sh = NamedSharding(jm, spec if spec is not None else P())
        _sharding.maybe_verify(jm, sh.spec, shape=arr.shape,
                               what="shard_params[%s]" % name)
        out[name] = jax.device_put(arr, sh)
    return out
