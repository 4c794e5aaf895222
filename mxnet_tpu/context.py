"""Device contexts, with a first-class ``mx.tpu()``.

Reference: ``python/mxnet/context.py`` (``mx.cpu()/mx.gpu()``).  The rebuild's
north star is a framework where TPU is the default accelerator: a ``Context``
names a logical device and resolves to a concrete ``jax.Device``.  All array
placement goes through ``Context.jax_device`` + ``jax.device_put``; compiled
executables are placed by XLA.

Unlike the reference (device_typeid enum routed through the C ABI), a context
here is a thin value object; there is no per-device stream state to manage —
PJRT owns streams.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


def _accelerator_platforms():
    return ("tpu", "cuda", "rocm", "gpu")


def _told_cpu():
    """True where the process was told to run on CPU: ``JAX_PLATFORMS``
    (or the ``jax_platforms`` config it feeds) names ``cpu`` first — the
    test suite and the virtual-mesh rehearsals."""
    plats = str(jax.config.jax_platforms or "")
    return plats.split(",")[0].strip().lower() == "cpu"


def _require_told_cpu(what):
    """An accelerator was asked for and there is none: carry on on CPU
    only where the process was told to, never in silence."""
    if not _told_cpu():
        raise MXNetError(
            "%s: this process has no accelerator (jax.devices() = %s). "
            "Set JAX_PLATFORMS=cpu to run on CPU on purpose."
            % (what, jax.devices()))


class Context:
    """A logical device. ``Context('tpu', 0)`` resolves to the first TPU chip.

    With ``mesh=`` a context names a device *set*: ``mx.tpu(mesh=...)``
    entered as a scope also sets the ambient mesh, so ``nd.shard`` /
    ``JitTrainStep`` inside the scope pick it up implicitly (the GSPMD
    substrate, ``mxnet_tpu/sharding/``).  Placement of plain arrays
    still resolves to one device (``jax_device``); the mesh governs
    sharded placement.
    """

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0, mesh=None):
        if isinstance(device_type, Context):
            if mesh is None:
                mesh = device_type.mesh
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in _DEVTYPE2ID:
            raise ValueError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = device_id
        if mesh is not None:
            from .sharding import Mesh as _Mesh

            mesh = mesh if isinstance(mesh, _Mesh) else _Mesh(mesh)
        self.mesh = mesh
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device (cached per process device list)."""
        return _resolve_jax_device(self.device_type, self.device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
            and self.mesh == other.mesh
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id, self.mesh))

    def __repr__(self):
        if self.mesh is not None:
            return "%s(%d, mesh=%s)" % (self.device_type, self.device_id,
                                        dict(self.mesh.shape))
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        if self.mesh is not None:
            from . import sharding as _sharding

            _sharding.push_mesh(self.mesh)
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx
        if self.mesh is not None:
            from . import sharding as _sharding

            _sharding.pop_mesh()

    def empty_cache(self):
        """Parity with mx.Context.empty_cache; PJRT pools its own memory."""
        try:
            for buf in self.jax_device.live_buffers():  # pragma: no cover
                del buf
        except Exception:
            pass


def _resolve_jax_device(device_type, device_id):
    # local_devices, not devices: in a multi-process (multi-host) runtime
    # jax.devices() is the GLOBAL list and entry 0 may belong to another
    # process — a Context always names a device THIS process can address
    devices = jax.local_devices()
    if device_type in ("cpu", "cpu_pinned", "cpu_shared"):
        try:
            cpus = jax.local_devices(backend="cpu")
        except RuntimeError:
            cpus = [d for d in devices if d.platform == "cpu"]
        if cpus:
            return cpus[device_id % len(cpus)]
        return devices[0]
    accels = [d for d in devices if d.platform in _accelerator_platforms()]
    if not accels:
        # mx.tpu() code paths stay testable on the 8-device virtual CPU
        # mesh, where the process asked for CPU; anywhere else a lost
        # device is an error
        _require_told_cpu("mx.%s(%d)" % (device_type, device_id))
        accels = devices
    return accels[device_id % len(accels)]


def cpu(device_id=0, mesh=None):
    return Context("cpu", device_id, mesh=mesh)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0, mesh=None):
    """Kept for API parity; resolves to an accelerator (TPU on TPU hosts)."""
    return Context("gpu", device_id, mesh=mesh)


def tpu(device_id=0, mesh=None):
    """First-class TPU context (north-star feature; no reference counterpart).

    ``mx.tpu(mesh={"data": 8})`` names a device set: entering it as a
    scope makes the mesh ambient for sharded placement."""
    return Context("tpu", device_id, mesh=mesh)


def num_gpus():
    return len([d for d in jax.devices() if d.platform in ("cuda", "rocm", "gpu")])


def num_tpus():
    """TPU chips this process sees; 0 only where it was told to run on
    CPU (``_told_cpu``), an error where the TPU went missing."""
    n = len([d for d in jax.devices() if d.platform == "tpu"])
    if n == 0:
        _require_told_cpu("mx.num_tpus()")
    return n


def default_context():
    """The ambient context: what a ``with ctx:`` scope set, else cpu(0) as
    in the reference — on a TPU host that is the host's CPU backend, so
    TPU programs pass ``ctx=mx.tpu()`` or enter ``with mx.tpu():``."""
    if getattr(Context._default_ctx, "value", None) is not None:
        return Context._default_ctx.value
    return Context("cpu", 0)


def current_context():
    return default_context()


def _best_context():
    """tpu(0); cpu(0) only where the process was told to run on CPU."""
    return tpu(0) if num_tpus() > 0 else cpu(0)
