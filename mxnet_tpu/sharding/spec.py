"""Named meshes and partition specs — the one GSPMD substrate.

The reference framework spreads multi-device placement across device
lists (``ctx=[mx.gpu(0), mx.gpu(1)]``), KVStore types and per-module
mesh plumbing.  TPU-native, placement is a *sharding*: a ``Mesh`` names
a device set with named axes (``data``, ``model``, ``pipe``, ``seq``,
``expert``) and a ``PartitionSpec`` maps array dimensions onto those
axes; XLA's GSPMD pass lowers the spec to ICI/DCN collectives.

This module is the substrate everything else builds on:

- ``Mesh`` — the framework's mesh object.  Wraps ``jax.sharding.Mesh``
  (construct from a dict of axis sizes, a raw jax mesh, or another
  wrapper) and doubles as a context manager that sets the *ambient*
  mesh, which ``mx.tpu(mesh=...)`` contexts, ``JitTrainStep`` and
  ``nd.shard`` pick up implicitly.
- ``PartitionSpec`` / ``P`` — re-exported verbatim from jax: specs are
  shared vocabulary with the compiler, not a wrapper.
- ``as_jax_mesh`` / ``named_sharding`` / ``canonicalize_spec`` — the
  adapters every consumer (parallel strategies, engine, serve) uses so
  raw jax meshes and framework meshes stay interchangeable.

The legacy helpers in ``parallel/mesh.py`` (``make_mesh``,
``current_mesh``, ``MeshScope``) delegate here; they remain as the
back-compat spelling.
"""
from __future__ import annotations

import threading

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

P = PartitionSpec

_state = threading.local()


def _build_jax_mesh(axes=None, devices=None):
    """dict name->size (one -1 allowed for 'remaining devices') → jax Mesh."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    names = list(axes)
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError("mesh %s needs %d devices, have %d"
                         % (axes, total, n))
    arr = np.asarray(devices[:total]).reshape(sizes)
    return jax.sharding.Mesh(arr, tuple(names))


class Mesh:
    """A named device set: ``Mesh({"data": 4, "model": 2})``.

    Equality and hashing delegate to the underlying jax mesh, so two
    framework meshes over the same devices/axes are one mesh — the
    bitwise-parity guarantee of the substrate rests on this (identical
    ``NamedSharding`` objects → identical compiled executables).

    ``with mesh:`` sets the ambient mesh for the enclosed code; scopes
    nest.  A ``Context`` built with ``mx.tpu(mesh=...)`` enters the
    same ambient stack.
    """

    __slots__ = ("_jax",)

    def __init__(self, axes=None, devices=None):
        if isinstance(axes, Mesh):
            self._jax = axes._jax
        elif isinstance(axes, jax.sharding.Mesh):
            self._jax = axes
        else:
            self._jax = _build_jax_mesh(axes, devices)

    # -- structure --------------------------------------------------------
    @property
    def jax_mesh(self):
        """The wrapped ``jax.sharding.Mesh`` (for shard_map et al.)."""
        return self._jax

    @property
    def axis_names(self):
        return self._jax.axis_names

    @property
    def shape(self):
        """OrderedDict axis name -> size (same contract as jax's Mesh)."""
        return self._jax.shape

    @property
    def devices(self):
        return self._jax.devices

    @property
    def size(self):
        return self._jax.size

    def axis_size(self, axis):
        """Total devices along ``axis`` (a name or tuple of names)."""
        names = (axis,) if isinstance(axis, str) else tuple(axis)
        size = 1
        for a in names:
            size *= dict(self._jax.shape)[a]
        return size

    # -- sharding construction -------------------------------------------
    def sharding(self, *spec):
        """``mesh.sharding("data", None)`` → a NamedSharding on this mesh.

        Also accepts one prebuilt spec: ``mesh.sharding(P("data"))`` or
        ``mesh.sharding(None)`` (replicated)."""
        if len(spec) == 1 and (spec[0] is None or
                               isinstance(spec[0], (PartitionSpec, list))):
            return NamedSharding(self._jax, canonicalize_spec(spec[0]))
        return NamedSharding(self._jax, PartitionSpec(*spec))

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        if isinstance(other, Mesh):
            return self._jax == other._jax
        if isinstance(other, jax.sharding.Mesh):
            return self._jax == other
        return NotImplemented

    def __hash__(self):
        return hash(self._jax)

    def __repr__(self):
        return "Mesh(%s)" % (dict(self._jax.shape),)

    # -- ambient scope ----------------------------------------------------
    def __enter__(self):
        push_mesh(self)
        return self

    def __exit__(self, *args):
        pop_mesh()


# ---------------------------------------------------------------------------
# ambient mesh state (one stack; parallel.mesh.MeshScope delegates here)
# ---------------------------------------------------------------------------


def push_mesh(mesh):
    """Push ``mesh`` (framework Mesh, raw jax Mesh, or None) onto the
    ambient stack.  ``None`` is a real entry: ``with MeshScope(None):``
    masks an outer mesh, matching the legacy thread-local semantics."""
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(mesh)
    return mesh


def pop_mesh():
    stack = getattr(_state, "stack", None)
    if stack:
        return stack.pop()
    return None


def current_mesh():
    """The innermost ambient mesh, exactly as it was pushed (framework
    ``Mesh`` or raw jax mesh), or None outside any scope."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def current_jax_mesh():
    return as_jax_mesh(current_mesh())


# The axis of jax's context mesh (``jax.set_mesh``) that carries the
# batch, for ops that choose shard_map specs while a step program is
# traced.  A jax user context, so it is part of every jit cache key like
# the context mesh itself: ``with batch_axis("dp"): ...``,
# ``batch_axis.value``.
batch_axis = jax.make_user_context("data")


# ---------------------------------------------------------------------------
# adapters
# ---------------------------------------------------------------------------


def as_jax_mesh(mesh):
    """Coerce a framework Mesh / raw jax Mesh / axes dict to a jax Mesh.

    ``None`` passes through — callers treat it as 'no mesh'.
    """
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        return mesh.jax_mesh
    if isinstance(mesh, jax.sharding.Mesh):
        return mesh
    if isinstance(mesh, dict):
        return _build_jax_mesh(mesh)
    raise TypeError("cannot interpret %r as a device mesh" % (mesh,))


def canonicalize_spec(spec):
    """Coerce a user spec to a PartitionSpec.

    Accepts a PartitionSpec, an axis name, a tuple/list of entries
    (``None`` = replicate that dim), or None (fully replicated).
    """
    if spec is None:
        return PartitionSpec()
    if isinstance(spec, PartitionSpec):
        return spec
    if isinstance(spec, str):
        return PartitionSpec(spec)
    if isinstance(spec, (tuple, list)):
        return PartitionSpec(*spec)
    raise TypeError("cannot interpret %r as a PartitionSpec" % (spec,))


def named_sharding(mesh, spec=None):
    """(mesh, spec) → jax NamedSharding; mesh defaults to the ambient one."""
    jm = as_jax_mesh(mesh) if mesh is not None else current_jax_mesh()
    if jm is None:
        raise ValueError(
            "no mesh: pass mesh= or enter one (`with mx.sharding.Mesh(...)"
            ":` or `with mx.tpu(mesh=...):`)")
    return NamedSharding(jm, canonicalize_spec(spec))


def spec_axes_label(spec):
    """Bounded-cardinality telemetry label for a spec's mesh axes:
    ``"data"``, ``"data,model"``, or ``"replicated"``."""
    spec = canonicalize_spec(spec)
    names = []
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            names.append(str(a))
    return ",".join(names) if names else "replicated"
