"""NDArray: the tensor type, on PJRT buffers.

Reference: ``include/mxnet/ndarray.h:82`` + ``python/mxnet/ndarray/ndarray.py``
— a ref-counted Chunk holding a Storage handle plus an engine Var, with lazy
allocation and view semantics.

TPU-native: the chunk is a ``jax.Array`` (PJRT buffer) — already asynchronous
(dispatch returns futures), already pooled (PJRT allocator, reference
``src/storage/pooled_storage_manager.h`` has no work left to do).  MXNet-style
*mutation* (``a += b``, ``a[1:3] = x``, optimizer in-place updates) is
implemented as functional update + buffer swap, with the engine ``Var`` version
bumped so caches can observe writes.  Slicing returns copies, not aliasing
views: XLA buffers are immutable, so write-through views cannot exist — writes
must go through the base array (documented deviation; the test suites of the
reference never rely on write-through slices).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..context import Context, current_context
from ..engine import Engine, Var, _BulkRef
from ..telemetry import memdump as _memdump
from .. import autograd
from ..ops import registry as _reg

_DTYPE_ALIASES = {
    "float16": jnp.float16, "float32": jnp.float32, "float64": jnp.float64,
    "bfloat16": jnp.bfloat16, "uint8": jnp.uint8, "int8": jnp.int8,
    "int32": jnp.int32, "int64": jnp.int64, "bool": jnp.bool_,
}


def _to_jax_dtype(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        return _DTYPE_ALIASES.get(dtype, jnp.dtype(dtype))
    return jnp.dtype(dtype)


class NDArray:
    """A mutable-by-convention tensor over an immutable XLA buffer."""

    _op_result_cls = None  # resolved to NDArray below; mx.np overrides

    __slots__ = (
        "_data", "_pending", "_ctx", "_var",
        "_marked", "_grad", "_grad_req", "_grad_gen", "_fresh_grad",
        "_grad_owner", "_dlpack_mirror",
        "_tape_node", "_tape_index",
        "__weakref__",
    )

    def __init__(self, data, ctx=None, dtype=None):
        # a deferred bulk-segment output (engine._BulkRef) makes a LAZY
        # array: ``_data`` holds only the aval until the segment flushes
        pending = None
        if isinstance(data, _BulkRef):
            pending = data
        elif isinstance(data, NDArray):
            p = data._pending
            if p is not None:
                jdt0 = _to_jax_dtype(dtype)
                if jdt0 is None or jdt0 == p.aval.dtype:
                    pending = p  # share the promise; no forced flush
                else:
                    data = data.data()  # dtype change needs the value
            if pending is None:
                data = data._data
        if pending is not None:
            self._data = pending.aval  # ShapeDtypeStruct placeholder
            self._pending = pending
            self._init_rest(ctx)
            return
        jdt = _to_jax_dtype(dtype)
        if not isinstance(data, jax.Array):
            data = _np.asarray(data, dtype=jdt or None)
            if data.dtype == _np.float64 and jdt is None:
                data = data.astype(_np.float32)
            ctx = ctx if ctx is not None else current_context()
            data = jax.device_put(data, ctx.jax_device)
            # a host->device upload is a real allocation (params, data
            # batches) — attribute it; op results (already jax.Array)
            # churn too fast to tag and count as "temp" in the sweep
            _memdump.tag(data)
        elif jdt is not None and data.dtype != jdt:
            data = data.astype(jdt)
        self._data = data
        self._pending = None
        self._init_rest(ctx)

    def _init_rest(self, ctx):
        self._ctx = ctx if ctx is not None else current_context()
        self._var = Var()
        self._marked = False
        self._grad = None
        self._grad_owner = None
        self._dlpack_mirror = None
        self._grad_req = "write"
        self._grad_gen = -1
        self._fresh_grad = False
        self._tape_node = None
        self._tape_index = 0

    # ------------------------------------------------------------------
    # core accessors
    # ------------------------------------------------------------------
    def data(self):
        """The raw jax.Array (framework-internal)."""
        if self._pending is not None:
            self._materialize()
        if self._dlpack_mirror is not None:
            self._sync_dlpack_write()
        return self._data

    def _materialize(self):
        """Resolve a deferred bulk-segment output into a concrete buffer.

        Reading a lazy array is a sync point: the open segment flushes
        (one fused push) and the promised value lands here.  If the flush
        failed, the first reader gets the original exception (propagated
        from flush / rethrown off this var) and the value is gone for good.
        """
        p = self._pending
        if p.value is None and not p.failed:
            p.segment.flush("data")
        if p.value is None:
            self._var.rethrow()
            raise MXNetError(
                "deferred NDArray lost: the bulk segment computing it "
                "failed (the original error was raised at the first read)")
        self._data = p.value
        self._pending = None

    def _set_data(self, new_data):
        """In-place write: swap buffer + bump the engine var version."""
        old = self._data
        self._data = new_data
        self._pending = None  # an overwrite supersedes any deferred value
        self._var.on_write()
        # grad-view write-through: reference .grad is the ACTUAL shared
        # NDArray, so mutating it mutates the stored gradient.  Our wrapper
        # is fresh per access (immutable buffers), so propagate writes back
        # to the owning array's gradient slot — but only while the view is
        # current (a later backward() orphans old views instead of letting
        # their read-modify-writes clobber the newer gradient).
        owner = self._grad_owner
        if owner is not None and owner._grad is old:
            owner._grad = new_data

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def sharding(self):
        """The jax sharding of the backing buffer (SingleDeviceSharding
        for plain arrays, NamedSharding after ``nd.shard``/``reshard``).

        Reading a lazy (bulk-deferred) array's sharding is a sync point:
        the open segment flushes so the concrete buffer can answer.
        """
        self._var.rethrow()
        return self.data().sharding

    @property
    def _in_graph(self):
        return self._marked or self._tape_node is not None

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        self._var.rethrow()
        Engine.get().notify_sync("wait_to_read")
        self.data().block_until_ready()
        return self

    def asnumpy(self):
        self._var.rethrow()
        Engine.get().notify_sync("asnumpy")
        return _np.asarray(self.data())

    def __array__(self, dtype=None, copy=None):
        # numpy protocol: without this np.asarray() would fall back to
        # element-wise __getitem__ iteration (one device gather per scalar)
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def asscalar(self):
        if self.size != 1:
            raise ValueError("the array is not scalar-sized")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            _np.asarray(self.data()), "x".join(map(str, self.shape)),
            self._ctx)

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Mark for gradient collection (parity: ndarray.py attach_grad)."""
        self._marked = True
        self._grad_req = grad_req
        self._grad = jnp.zeros(self.shape, self.dtype) if grad_req != "null" else None
        if self._grad is not None:
            _memdump.tag(self._grad, origin="grad")

    @property
    def grad(self):
        if self._grad is None:
            return None
        from .sparse import BaseSparseNDArray

        if isinstance(self._grad, BaseSparseNDArray):
            return self._grad
        out = NDArray(self._grad, ctx=self._ctx)
        out._grad_owner = self
        return out

    def _accumulate_grad(self, ct):
        # MXNet 'write' semantics: a new backward pass overwrites .grad, but
        # multiple contributions WITHIN one pass sum.  The pass generation
        # counter (autograd._backward_gen) distinguishes the two cases.
        if self._grad_req == "null":
            return
        from .sparse import BaseSparseNDArray, RowSparseNDArray

        gen = autograd.current_backward_gen()
        fresh = self._grad_gen != gen
        self._grad_gen = gen
        self._fresh_grad = True
        if isinstance(ct, BaseSparseNDArray):
            # row_sparse gradient (sparse Embedding path): keep it sparse
            prev = self._grad
            if prev is None or (fresh and self._grad_req == "write"):
                self._grad = ct
            elif isinstance(prev, RowSparseNDArray):
                self._grad = prev + ct
            else:
                self._grad = ct.scatter_add_into(prev)
            return
        ct = ct.astype(self.dtype)
        if isinstance(self._grad, BaseSparseNDArray):
            prev = self._grad.tostype("default").data() \
                if not (fresh and self._grad_req == "write") else None
            self._grad = ct if prev is None else prev + ct
            return
        if self._grad is None or (fresh and self._grad_req == "write"):
            self._grad = ct
        else:
            self._grad = self._grad + ct
        # re-attribute: accumulation replaced the buffer attach_grad
        # tagged (no-op for deferred/sparse values — tag() only takes
        # concrete jax.Arrays, and backward flushes before returning)
        _memdump.tag(self._grad, origin="grad")

    def zero_grad(self):
        if self._grad is not None:
            self._grad = jnp.zeros(self.shape, self.dtype)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def detach(self):
        # passing the NDArray (not its buffer) keeps a deferred value lazy
        out = NDArray(self, ctx=self._ctx)
        return out

    # ------------------------------------------------------------------
    # conversion / copies
    # ------------------------------------------------------------------
    def astype(self, dtype, copy=True):
        jdt = _to_jax_dtype(dtype)
        if not copy and self.dtype == jdt:
            return self
        return _reg.invoke("cast", [self], {"dtype": _np.dtype(jdt).name})

    def copy(self):
        return NDArray(self, ctx=self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError("copyto shape mismatch")
            other._set_data(
                jax.device_put(self.data(), other._ctx.jax_device).astype(other.dtype))
            return other
        if isinstance(other, Context):
            return self.as_in_context(other)
        raise TypeError("copyto target must be NDArray or Context")

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        out = NDArray(jax.device_put(self.data(), context.jax_device), ctx=context)
        out._tape_node = self._tape_node
        out._tape_index = self._tape_index
        return out

    def as_in_ctx(self, context):
        return self.as_in_context(context)

    def reshard(self, spec=None, mesh=None):
        """In-place redistribute onto ``mesh`` per ``spec`` (async push).

        The data movement is ``jax.device_put`` pushed through the
        engine like any op — dispatch returns immediately and the swap
        publishes a future-backed buffer.  ``mesh`` defaults to the
        ambient mesh (``with Mesh(...):`` / ``mx.tpu(mesh=...)``).
        Counted by ``mxnet_reshard_total{axis}`` — resharding in a hot
        loop is an mxlint finding (SH902).
        """
        if autograd.is_recording() and self._in_graph:
            # in-place placement swap on a taped array would invalidate
            # the recorded primals; use nd.shard() for a taped copy
            raise MXNetError("reshard on a taped array; use nd.shard()")
        from .. import sharding as _sharding

        sh = _sharding.named_sharding(mesh, spec)
        _sharding.maybe_verify(sh.mesh, sh.spec, shape=self.shape,
                               what="reshard")
        data = self.data()
        eng = Engine.get()
        new = eng.push(lambda: jax.device_put(data, sh),
                       read_vars=(self._var,), op_name="reshard")
        eng.track(new)
        _sharding.record_reshard(sh.spec, data.nbytes, origin="reshard")
        self._set_data(new)
        return self

    def with_sharding_constraint(self, spec=None, mesh=None):
        """Pin this array's partitioning through a recorded op — the
        traceable form of :func:`shard` (usable under autograd,
        ``hybridize`` and inside bulk segments; under jit it lowers to
        the GSPMD annotation rather than a data movement)."""
        from .. import sharding as _sharding

        sh = _sharding.named_sharding(mesh, spec)
        _sharding.maybe_verify(sh.mesh, sh.spec, shape=self.shape,
                               what="with_sharding_constraint")
        return _reg.invoke("_sharding_constraint", [self], {"sharding": sh})

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # DLPack interchange (reference ndarray.py:2825-2893 to_dlpack_for_read/
    # to_dlpack_for_write/from_dlpack).  Zero-copy when the PJRT backend
    # exports external references (CPU, TPU); a backend that does not
    # gets a host copy.
    # ------------------------------------------------------------------
    def _dlpack_source(self):
        """The object whose ``__dlpack__`` we export: the device buffer when
        the backend supports external references, else a host copy."""
        self._var.rethrow()
        if self._pending is not None:
            self._materialize()
        if self._dlpack_mirror is not None:
            return self._dlpack_mirror
        try:
            self._data.__dlpack_device__()
            return self._data
        except Exception:  # PJRT_Buffer_*ExternalReference unimplemented
            # np.array (not asarray): device_get hands back a READONLY host
            # view, which numpy refuses to export over DLPack
            return _np.array(self._data)

    def __dlpack__(self, **kwargs):
        return self._dlpack_source().__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._dlpack_source().__dlpack_device__()

    def to_dlpack_for_read(self):
        """Legacy-capsule export; no writes allowed through the capsule."""
        return self._dlpack_source().__dlpack__()

    def to_dlpack_for_write(self):
        """Writable export: a host mirror that this array re-adopts at its
        next read sync point (``data()``/``asnumpy()``/``wait_to_read()``).

        XLA buffers are immutable, so the reference's write-through alias
        (engine WaitForWrite ordering) cannot exist; the documented
        TPU-native contract is: external writes through the capsule are
        visible after the next read-side sync, and the capsule must not be
        written after that.
        """
        self._var.rethrow()
        if self._pending is not None:
            self._materialize()
        if self._dlpack_mirror is None:
            self._dlpack_mirror = _np.array(self._data)  # writable host copy
        self._var.on_write()
        return self._dlpack_mirror.__dlpack__()

    def _sync_dlpack_write(self):
        m, self._dlpack_mirror = self._dlpack_mirror, None
        self._set_data(jax.device_put(m, self._ctx.jax_device))

    def tostype(self, stype):
        if stype != "default":
            from ..ndarray import sparse as _sp

            return _sp.dense_to(self, stype)
        return self

    # ------------------------------------------------------------------
    # shape ops (method forms)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if not shape and "shape" in kwargs:
            shape = tuple(kwargs["shape"])
        return _reg.invoke("reshape", [self], {"shape": tuple(shape)})

    def reshape_like(self, other):
        return _reg.invoke("reshape_like", [self, other])

    def flatten(self):
        return _reg.invoke("flatten", [self])

    def expand_dims(self, axis):
        return _reg.invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return _reg.invoke("squeeze", [self], {"axis": axis})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _reg.invoke("transpose", [self], {"axes": axes or None})

    @property
    def T(self):
        return self.transpose()

    def swapaxes(self, dim1, dim2):
        return _reg.invoke("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def broadcast_to(self, shape):
        return _reg.invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return _reg.invoke("broadcast_like", [self, other])

    def tile(self, reps):
        return _reg.invoke("tile", [self], {"reps": tuple(reps)})

    def slice(self, begin, end, step=None):
        return _reg.invoke("slice", [self],
                           {"begin": tuple(begin), "end": tuple(end),
                            "step": tuple(step) if step else ()})

    def slice_axis(self, axis, begin, end):
        return _reg.invoke("slice_axis", [self],
                           {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return _reg.invoke("take", [self, _as_nd(indices, self._ctx)],
                           {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kwargs):
        kwargs["depth"] = depth
        return _reg.invoke("one_hot", [self], kwargs)

    # reductions as methods
    def sum(self, axis=None, keepdims=False):
        return _reg.invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _reg.invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return _reg.invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return _reg.invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return _reg.invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return _reg.invoke("norm", [self],
                           {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return _reg.invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return _reg.invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def clip(self, a_min, a_max):
        return _reg.invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return _reg.invoke("abs", [self])

    def sign(self):
        return _reg.invoke("sign", [self])

    def sqrt(self):
        return _reg.invoke("sqrt", [self])

    def square(self):
        return _reg.invoke("square", [self])

    def exp(self):
        return _reg.invoke("exp", [self])

    def log(self):
        return _reg.invoke("log", [self])

    def relu(self):
        return _reg.invoke("relu", [self])

    def sigmoid(self):
        return _reg.invoke("sigmoid", [self])

    def tanh(self):
        return _reg.invoke("tanh", [self])

    def softmax(self, axis=-1):
        return _reg.invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return _reg.invoke("log_softmax", [self], {"axis": axis})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return _reg.invoke("dot", [self, other],
                           {"transpose_a": transpose_a, "transpose_b": transpose_b})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return _reg.invoke("topk", [self],
                           {"axis": axis, "k": k, "ret_typ": ret_typ,
                            "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return _reg.invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def argsort(self, axis=-1, is_ascend=True):
        return _reg.invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def flip(self, axis):
        return _reg.invoke("reverse", [self], {"axis": axis})

    def pad(self, mode, pad_width, constant_value=0.0):
        return _reg.invoke("pad", [self],
                           {"mode": mode, "pad_width": tuple(pad_width),
                            "constant_value": constant_value})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return _reg.invoke("split", [self],
                           {"num_outputs": num_outputs, "axis": axis,
                            "squeeze_axis": squeeze_axis})

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binary(self, other, op, scalar_op, rscalar_op=None, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return _reg.invoke(op, [a, b])
        if isinstance(other, (int, float, bool, _np.number)):
            name = (rscalar_op or scalar_op) if reverse else scalar_op
            return _reg.invoke(name, [self], {"scalar": float(other)})
        if isinstance(other, (_np.ndarray, list, tuple)):
            return self._binary(NDArray(other, ctx=self._ctx), op, scalar_op,
                                rscalar_op, reverse)
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    def __radd__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar", reverse=True)

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar",
                            reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar", reverse=True)

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar", "_rdiv_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar", "_rdiv_scalar",
                            reverse=True)

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar", "_rmod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar", "_rmod_scalar",
                            reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar", "_rpower_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar", "_rpower_scalar",
                            reverse=True)

    def __neg__(self):
        return _reg.invoke("negative", [self])

    def __abs__(self):
        return _reg.invoke("abs", [self])

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = None  # mutable; matches reference NDArray unhashability

    # in-place (functional under the hood; tape-aware like reference += )
    def __iadd__(self, o):
        res = self.__add__(o)
        self._adopt(res)
        return self

    def __isub__(self, o):
        res = self.__sub__(o)
        self._adopt(res)
        return self

    def __imul__(self, o):
        res = self.__mul__(o)
        self._adopt(res)
        return self

    def __itruediv__(self, o):
        res = self.__truediv__(o)
        self._adopt(res)
        return self

    def _adopt(self, res):
        p = res._pending
        if p is not None and self._grad_owner is None \
                and self._dlpack_mirror is None:
            # adopt the promise itself: the in-place write stays deferred
            # but its version bump happens NOW, exactly when eager would
            self._data = p.aval  # ShapeDtypeStruct placeholder
            self._pending = p
            self._var.on_write()
        else:
            self._set_data(res.data())
        self._tape_node = res._tape_node
        self._tape_index = res._tape_index

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _conv_index(self, key):
        if isinstance(key, NDArray):
            return self._data_index(key)
        if isinstance(key, tuple):
            return tuple(self._data_index(k) if isinstance(k, NDArray) else k
                         for k in key)
        return key

    @staticmethod
    def _data_index(k):
        d = k.data()
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = d.astype(jnp.int32)
        return d

    def __getitem__(self, key):
        key = self._conv_index(key)
        if autograd.is_recording() and self._in_graph:
            # route through a recorded op so indexing stays differentiable
            # (reference supports basic-index reads under autograd;
            # index arrays are gather constants — no grad w.r.t. them)
            from ..ops.registry import invoke_fn

            (out,) = invoke_fn(lambda d: (d[key],), [self],
                               op_name="_index")
            return out
        return NDArray(self.data()[key], ctx=self._ctx)

    def __setitem__(self, key, value):
        if autograd.is_recording() and self._in_graph:
            raise MXNetError("in-place assignment on a taped array")
        key = self._conv_index(key)
        if isinstance(value, NDArray):
            value = value.data()
        elif not isinstance(value, jax.Array):
            value = _np.asarray(value)
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            new = jnp.broadcast_to(jnp.asarray(value, dtype=self.dtype),
                                   self.shape)
        else:
            new = self.data().at[key].set(jnp.asarray(value, dtype=self.dtype))
        self._set_data(jnp.asarray(new, dtype=self.dtype))

    # ------------------------------------------------------------------
    # serialization handled in ndarray.utils (save/load)
    # ------------------------------------------------------------------


NDArray._op_result_cls = NDArray


class _CapsuleHolder:
    """Adapts a legacy DLPack capsule to the array-protocol consumers
    (np.from_dlpack / jax.dlpack.from_dlpack want ``__dlpack__`` objects)."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, **kwargs):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # kDLCPU; legacy capsules carry no device metadata


def from_dlpack(obj):
    """Build an NDArray from a DLPack-capable object or legacy capsule.

    Parity: reference ``ndarray.py:2878-2893`` (``from_dlpack``).  Zero-copy
    where the producing/consuming backends share memory space (CPU);
    otherwise the backend copies on import.
    """
    if isinstance(obj, NDArray):
        return obj
    if not hasattr(obj, "__dlpack__"):
        obj = _CapsuleHolder(obj)  # legacy PyCapsule form
    try:
        data = jax.dlpack.from_dlpack(obj)
    except Exception:
        data = jnp.asarray(_np.from_dlpack(obj))
    return NDArray(data)


def to_dlpack_for_read(data):
    """Module-level form of ``NDArray.to_dlpack_for_read`` (reference
    ``ndarray.py:2825``)."""
    return data.to_dlpack_for_read()


def to_dlpack_for_write(data):
    """Module-level form of ``NDArray.to_dlpack_for_write`` (reference
    ``ndarray.py:2851``)."""
    return data.to_dlpack_for_write()


def _as_nd(x, ctx=None):
    if isinstance(x, NDArray):
        return x
    return NDArray(x, ctx=ctx)


def shard(arr, spec=None, mesh=None):
    """A copy of ``arr`` distributed onto ``mesh`` per ``spec``.

    ``mesh`` defaults to the ambient mesh (``with Mesh(...):`` or
    ``mx.tpu(mesh=...)``); ``spec=None`` replicates.  The movement is a
    ``jax.device_put`` pushed through the engine — async like any op.
    Under autograd recording the put is routed through a recorded op
    (``device_put`` is differentiable: gradients reshard back), so a
    sharded forward stays on the tape.
    """
    from .. import sharding as _sharding

    arr = _as_nd(arr)
    sh = _sharding.named_sharding(mesh, spec)
    _sharding.maybe_verify(sh.mesh, sh.spec, shape=arr.shape, what="shard")
    _sharding.record_reshard(sh.spec, arr.dtype.itemsize * arr.size,
                             origin="shard")
    if autograd.is_recording() and arr._in_graph:
        from ..ops.registry import invoke_fn

        (out,) = invoke_fn(lambda d: (jax.device_put(d, sh),), [arr],
                           op_name="_shard")
        return out
    data = arr.data()
    eng = Engine.get()
    new = eng.push(lambda: jax.device_put(data, sh),
                   read_vars=(arr._var,), op_name="shard")
    eng.track(new)
    out = NDArray(new, ctx=arr._ctx)
    return out


# ----------------------------------------------------------------------------
# creation helpers (parity: python/mxnet/ndarray/utils.py + ndarray.py)
# ----------------------------------------------------------------------------


def array(source_array, ctx=None, dtype=None):
    return NDArray(source_array, ctx=ctx, dtype=dtype)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    dtype = dtype or "float32"
    ctx = ctx or current_context()
    return NDArray(jnp.zeros(shape, _to_jax_dtype(dtype)), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    dtype = dtype or "float32"
    ctx = ctx or current_context()
    return NDArray(jnp.ones(shape, _to_jax_dtype(dtype)), ctx=ctx)


def full(shape, val, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    dtype = dtype or "float32"
    ctx = ctx or current_context()
    return NDArray(jnp.full(shape, val, _to_jax_dtype(dtype)), ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    dtype = dtype or "float32"
    out = jnp.arange(start, stop, step, _to_jax_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(out, ctx=ctx or current_context())


def zeros_like(a):
    return _reg.invoke("zeros_like", [a])


def ones_like(a):
    return _reg.invoke("ones_like", [a])


def concatenate(arrays, axis=0, always_copy=True):
    return _reg.invoke("concat", list(arrays), {"dim": axis})


def moveaxis(tensor, source, destination):
    axes = list(range(tensor.ndim))
    axes.remove(source % tensor.ndim)
    axes.insert(destination % tensor.ndim, source % tensor.ndim)
    return tensor.transpose(axes)


def save(fname, data):
    """Save NDArray / list / dict of NDArrays (parity: MXNDArraySave).

    Format: the reference's binary ``.params`` container (versioned
    magic numbers, ``src/ndarray/ndarray.cc:1586-1860``) — files are
    interchangeable with reference MXNet in both directions.

    Atomic: bytes land in a same-directory temp file that is renamed
    over ``fname`` only once complete, so a preemption mid-write never
    corrupts an existing checkpoint (docs/fault_tolerance.md).
    """
    from ..base import atomic_path
    from . import legacy_io

    if isinstance(data, NDArray):
        arrays, names = [data.asnumpy()], []
    elif isinstance(data, (list, tuple)):
        arrays, names = [a.asnumpy() for a in data], []
    elif isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k].asnumpy() for k in names]
    else:
        raise TypeError("unsupported save payload")
    with atomic_path(fname) as tmp:
        legacy_io.save_params(tmp, arrays, names)


def load(fname, ctx=None):
    """Load what :func:`save` (or reference MXNet) wrote.

    Accepts the reference binary container in all its versions (pre-V1
    through V3) and, for back-compat with earlier snapshots of this
    framework, the .npz container it used to write.
    """
    from . import legacy_io

    if legacy_io.is_legacy_file(fname):
        arrays, names = legacy_io.load_params(fname)
        nds = [NDArray(a, ctx=ctx) if a is not None else None
               for a in arrays]
        if names:
            return dict(zip(names, nds))
        return nds
    with _np.load(fname, allow_pickle=False) as z:
        kind = str(z["__kind__"])
        if kind == "single":
            return NDArray(z["arr_0"], ctx=ctx)
        if kind == "list":
            n = len([k for k in z.files if k.startswith("arr_")])
            return [NDArray(z["arr_%d" % i], ctx=ctx) for i in range(n)]
        out = {}
        for k in z.files:
            if k.startswith("key:"):
                out[k[4:]] = NDArray(z[k], ctx=ctx)
        return out


def waitall():
    Engine.get().wait_for_all()
