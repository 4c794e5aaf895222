"""Gluon Block / HybridBlock.

Reference: ``python/mxnet/gluon/block.py`` — ``Block:229``, ``HybridBlock:839``
whose ``hybridize():1043`` traces ``hybrid_forward`` with Symbol proxies into
an nnvm graph executed by ``CachedOp`` (``_build_cache:933``).

TPU-native rebuild: there is no separate symbolic tracer — the jaxpr IS the
captured graph.  ``hybridize()`` arms a cache; on a cache miss the whole
imperative forward is traced by ``jax.jit`` with (rng_key, *params, *inputs)
as arguments, producing ONE XLA executable per (input shapes/dtypes, mode)
— the direct analogue of ``CachedOp::SetForwardGraph``'s shape-keyed
executable (``src/imperative/cached_op.cc:417``), with XLA doing memory
planning (= ``MXPlanMemory``) and fusion (= pointwise fusion pass) for free.
Autograd records the executable as ONE tape node via ``jax.vjp``.  Aux state
(BatchNorm moving stats) written during the trace is routed out as extra
outputs through a trace-time side channel and assigned back after each run.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax

from ..base import MXNetError
from ..context import current_context
from ..ndarray.ndarray import NDArray
from .. import ndarray as _nd_module
from .. import autograd
from .. import random as _random
from ..engine import Engine
from .parameter import (
    Parameter, ParameterDict, DeferredInitializationError,
)

# ---------------------------------------------------------------------------
# trace plumbing (hybridize)
# ---------------------------------------------------------------------------
_trace_state = threading.local()


class _functional_params:
    """Context manager: run imperative forwards with parameters
    substituted by the given arrays (the functional-trace choke point
    used by hybridize, JitTrainStep, deploy, and the pipeline stages).

    ``with _functional_params(params, arrays): net._forward_imperative(x)``
    maps ``id(param) -> NDArray(array)`` for the duration and restores
    the previous trace state on exit.
    """

    def __init__(self, params, arrays):
        from ..ndarray.ndarray import NDArray

        self._map = {id(p): NDArray(a) for p, a in zip(params, arrays)}
        self._prev = None

    def __enter__(self):
        st = _trace_st()
        self._prev = (st.param_map, st.aux_updates, st.active)
        st.param_map = self._map
        st.aux_updates = []
        st.active = True
        return st

    def __exit__(self, *exc):
        st = _trace_st()
        st.param_map, st.aux_updates, st.active = self._prev
        return False


def _trace_st():
    if not hasattr(_trace_state, "param_map"):
        _trace_state.param_map = None   # id(Parameter) -> NDArray(tracer)
        _trace_state.aux_updates = None  # list of (Parameter, jax array)
        _trace_state.active = False
        _trace_state.step_stats = None   # name -> jax array, in a train step
    return _trace_state


def _trace_param_lookup(param):
    st = _trace_st()
    if st.param_map is None:
        return None
    return st.param_map.get(id(param))


def is_tracing():
    return _trace_st().active


def record_aux_update(param, value):
    """Write an aux parameter; inside a hybridize trace the write is deferred
    and returned from the compiled executable instead (side-channel)."""
    st = _trace_st()
    data = value.data() if isinstance(value, NDArray) else value
    if st.aux_updates is not None:
        st.aux_updates.append((param, data))
    else:
        param.set_data(data)


def record_step_stat(name, value):
    """Add ``value`` to the statistic ``name`` of the train step being
    traced.  A block that counts something a step (a routed layer's
    assignments) declares it in ``step_stat_specs() -> {name: (shape,
    dtype)}``; ``parallel.JitTrainStep`` then carries one accumulator a name
    through its step program and sums what each step records into it on the
    device (``JitTrainStep.step_stats()`` fetches them).  Outside such a
    trace (imperative, ``hybridize()``) the value is dropped."""
    stats = _trace_st().step_stats
    if stats is not None:
        data = value.data() if isinstance(value, NDArray) else value
        stats[name] = stats[name] + data if name in stats else data


class _BlockScope:
    """Name manager for nested blocks (parity: block.py _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_manager_next(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = "%s%d_" % (hint, count)
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


_name_counter = {}
_name_lock = threading.Lock()


def _name_manager_next(hint):
    with _name_lock:
        c = _name_counter.get(hint, 0)
        _name_counter[hint] = c + 1
    return "%s%d" % (hint, c)


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------
class Block:
    """Base building block (parity: gluon.Block, block.py:229)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def __repr__(self):
        lines = []
        for name, child in self._children.items():
            block_repr = repr(child).replace("\n", "\n  ")
            lines.append("  (%s): %s" % (name, block_repr))
        return "%s(\n%s\n)" % (self.__class__.__name__, "\n".join(lines)) \
            if lines else "%s()" % self.__class__.__name__

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        from .utils import HookHandle

        handle = HookHandle()
        handle.attach(self._forward_hooks, hook)
        return handle

    def register_forward_pre_hook(self, hook):
        from .utils import HookHandle

        handle = HookHandle()
        handle.attach(self._forward_pre_hooks, hook)
        return handle

    def collect_params(self, select=None):
        """All params of self + descendants, optionally regex-filtered.

        Parity: Block.collect_params (block.py:378).
        """
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({
                name: value for name, value in self.params.items()
                if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for param in self.params.values():
            param.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- checkpointing ---------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """Parity: Block.save_parameters (block.py:417); block-local names.

        The write is atomic (``nd.save`` goes through ``base.atomic_path``):
        an interrupted save leaves any previous file loadable.
        """
        params = self._collect_params_with_prefix()
        from ..ndarray import ndarray as _ndm

        _ndm.save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        from ..ndarray import ndarray as _ndm

        loaded = _ndm.load(filename, ctx=ctx)
        if not isinstance(loaded, dict):
            raise MXNetError("%s is not a parameter dict file" % filename)
        if any(k.startswith(("arg:", "aux:")) for k in loaded):
            # exported-model format (HybridBlock.export / save_checkpoint)
            loaded = {k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                      else k: v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        if loaded and not set(loaded) & set(params):
            # exported files use FLAT ParameterDict names (p.name), not
            # the structural dotted names save_parameters writes; fall
            # back to name-based matching (reference load_parameters does
            # the same when keys don't look structural)
            by_flat = {p.name: p for p in self.collect_params().values()}
            if set(loaded) & set(by_flat):
                params = by_flat
            else:
                # a FRESH net instance carries a different auto-prefix
                # (resnetv10_ vs resnetv11_); retry with the instance
                # prefix (first '_' token) stripped from both sides, but
                # only when the mapping stays unambiguous
                def strip(k):
                    return k.split("_", 1)[1] if "_" in k else k

                flat2 = {}
                for p in self.collect_params().values():
                    flat2.setdefault(strip(p.name), p)
                loaded2 = {}
                for k, v in loaded.items():
                    loaded2.setdefault(strip(k), v)
                if len(flat2) == len(by_flat) and \
                        len(loaded2) == len(loaded) and \
                        set(loaded2) & set(flat2):
                    params, loaded = flat2, loaded2
        for name, p in params.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError(
                        "parameter %s missing in %s" % (name, filename))
                continue
            arr = loaded[name]
            if p._data is None:
                p.shape = tuple(arr.shape)
                if p._deferred_init is not None:
                    p._finish_deferred_init()
                else:
                    p.initialize(ctx=ctx)
            p.set_data(arr)
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(
                    "%s has extra parameters %s" % (filename, sorted(extra)))

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + name: p for name, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- forward ---------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):  # pragma: no cover - abstract
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary by running a forward with hooks."""
        summary_rows = []

        def make_hook(name):
            def hook(block, ins, outs):
                out = outs[0] if isinstance(outs, (list, tuple)) else outs
                n_params = sum(
                    int(p.data().size) for p in block._reg_params.values()
                    if p._data is not None)
                summary_rows.append((name or "(root)",
                                     block.__class__.__name__,
                                     tuple(out.shape), n_params))
            return hook

        handles = []
        for name, child in self._iter_blocks():
            child._forward_hooks[("__summary__", name)] = make_hook(name)
            handles.append(child)
        try:
            self(*inputs)
        finally:
            for child in handles:
                child._forward_hooks = OrderedDict(
                    (k, v) for k, v in child._forward_hooks.items()
                    if not (isinstance(k, tuple) and k[0] == "__summary__"))
        header = "%-30s %-20s %-20s %10s" % ("Layer", "Type", "Output Shape",
                                             "Params")
        lines = [header, "-" * len(header)]
        total = 0
        for name, typ, shape, n in summary_rows:
            lines.append("%-30s %-20s %-20s %10d" % (name, typ, shape, n))
            total += n
        lines.append("-" * len(header))
        lines.append("Total params: %d" % total)
        print("\n".join(lines))

    def _iter_blocks(self, prefix=""):
        yield prefix, self
        for name, child in self._children.items():
            yield from child._iter_blocks(prefix + ("." if prefix else "")
                                          + name)


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------
class HybridBlock(Block):
    """Block that can be compiled to one XLA executable (see module doc).

    Subclasses implement ``hybrid_forward(F, x, *args, **params)`` exactly as
    in the reference; ``F`` is always the ``mxnet_tpu.ndarray`` module here
    because tracing happens at the XLA level, not the symbol level.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_ops = {}      # (shapes,dtypes,mode) -> compiled record
        self._warmed_up = False
        self._flags = {}
        self._aot_path = None      # hybridize(aot=...) bundle file
        self._aot_ops = {}         # (shapes,dtypes,mode) -> AOT record
        self._aot_entries = None   # raw bundle entries (lazy load)

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  lint=False, aot=None, **kwargs):
        """Arm/disarm compilation (parity: HybridBlock.hybridize:1043).

        ``static_alloc``/``static_shape`` accepted for API parity; XLA's
        buffer assignment always behaves like static_alloc=True.

        ``lint=True`` runs the mxlint tracing-safety pass (TS1xx,
        ``mxnet_tpu.analysis``) over this block's ``hybrid_forward`` source
        — and every child's — before arming, and raises ``MXNetError`` on
        findings: the static analogue of tracing the block and hitting a
        ConcretizationError three epochs in.

        ``aot=path`` arms warm-start serialization (compile_cache.py): each
        input signature this block compiles for is AOT-exported to ``path``
        (PJRT executable serialization), and a fresh process that
        hybridizes with the same ``aot=path`` loads the executable instead
        of tracing+compiling — bitwise-identical outputs, zero compiles.
        AOT entries serve inference; calls under ``autograd.record()`` fall
        back to the live jit path (a deserialized executable cannot be
        re-linearized for vjp).  Parameters must be initialized (e.g. via
        ``load_parameters``) before an AOT entry can serve.
        """
        if active and lint:
            findings = self.lint()
            if findings:
                raise MXNetError(
                    "hybridize(lint=True): tracing-safety findings in "
                    "hybrid_forward:\n  "
                    + "\n  ".join(str(f) for f in findings))
        self._active = active
        self._aot_path = aot if active else None
        if not active or aot is None:
            self._aot_ops = {}
            self._aot_entries = None
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        if not active:
            self._cached_ops = {}
            self._warmed_up = False
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def lint(self):
        """Run the mxlint tracing-safety pass over this block tree's
        ``hybrid_forward`` sources; returns a list of findings (empty when
        trace-safe).  See ``docs/static_analysis.md``."""
        from ..analysis import lint_block
        return lint_block(self)

    def clear_cache(self):
        self._cached_ops = {}
        self._aot_ops = {}
        self._aot_entries = None
        self._warmed_up = False

    def cast(self, dtype):
        self.clear_cache()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes given example inputs.

        Built-in layers override ``_shape_hint``; composite blocks recurse by
        simply running a forward (each layer resolves itself en route).
        """
        self._shape_hint(*args)

    def _shape_hint(self, *args):
        return None

    # -- forward dispatch -------------------------------------------------
    def forward(self, x, *args):
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            return self._forward_symbolic(x, *args)
        if not isinstance(x, NDArray):
            raise MXNetError(
                "HybridBlock.forward expects NDArray inputs, got %s"
                % type(x).__name__)
        self._export_input_sig = [
            (tuple(a.shape), str(a.dtype))
            for a in (x,) + args if isinstance(a, NDArray)]
        if self._active and not is_tracing():
            return self._call_cached(x, *args)
        return self._forward_imperative(x, *args)

    def _forward_symbolic(self, x, *args):
        """Trace hybrid_forward into a Symbol graph (reference parity:
        HybridBlock's symbolic path, block.py:1090 __call__ with Symbol).

        Parameters surface as symbol variables named by their full
        ``collect_params`` key, carrying ``shape=``/``dtype=`` so
        downstream ``.shape`` reads and shape inference work.  Used by
        ONNX export and ``HybridBlock.export``.
        """
        from .. import symbol as _sym_module
        from ..symbol.symbol import var as _sym_var

        params = {}
        for name, p in self._reg_params.items():
            shape = tuple(p.shape) if p.shape else None
            if shape is not None and any(d == 0 for d in shape):
                shape = None  # deferred — the op shape-hints resolve it
            params[name] = _sym_var(
                p.name, shape=shape,
                dtype=str(p.dtype) if getattr(p, "dtype", None) else None)
        return self.hybrid_forward(_sym_module, x, *args, **params)

    def _forward_imperative(self, x, *args):
        self._shape_hint(x, *args)
        try:
            params = {name: p.data() for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._shape_hint(x, *args)
            for p in self._reg_params.values():
                p._finish_deferred_init()
            params = {name: p.data() for name, p in self._reg_params.items()}
        return self.hybrid_forward(_nd_module, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **params):  # pragma: no cover
        raise NotImplementedError

    # -- cached (compiled) path ------------------------------------------
    def _call_cached(self, *inputs):
        key = (tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
               autograd.is_training())
        if self._aot_path is not None and not autograd.is_recording():
            # warm start: serve a deserialized executable — no warmup
            # forward, no trace, no compile.  Recording falls through to
            # the live jit path (a loaded executable has no vjp).
            rec = self._aot_ops.get(key)
            if rec is None:
                rec = self._try_aot_load(key)
                if rec is not None:
                    self._aot_ops[key] = rec
            if rec is not None:
                return self._run_cached(rec, inputs)
        if not self._warmed_up:
            # First call after hybridize(): run imperatively — this resolves
            # deferred parameter shapes (CachedOp's _deferred_infer_shape) and
            # gives the answer for free; compile on the next call.
            self._warmed_up = True
            return self._forward_imperative(*inputs)
        rec = self._cached_ops.get(key)
        if rec is None:
            rec = self._build_cache(inputs)
            self._cached_ops[key] = rec
            if self._aot_path is not None:
                self._aot_export(key, rec, inputs)
                aot_rec = self._aot_ops.get(key)
                if aot_rec is not None and not autograd.is_recording():
                    # run the executable we just compiled for export rather
                    # than paying jit's own compile of the same program
                    rec = aot_rec
        return self._run_cached(rec, inputs)

    def _build_cache(self, inputs):
        """Trace the full imperative forward into one jitted executable."""
        params = list(self.collect_params().values())
        for p in params:
            p._check_initialized()
        n_params = len(params)
        outer = self
        meta = {}  # filled at trace time: n_outputs, aux param order

        def fn(rng_key, *arrays):
            st = _trace_st()
            prev = (st.param_map, st.aux_updates, st.active)
            st.param_map = {
                id(p): NDArray(a) for p, a in zip(params, arrays[:n_params])
            }
            st.aux_updates = []
            st.active = True
            try:
                with _random.trace_key_scope(rng_key):
                    nd_in = [NDArray(a) for a in arrays[n_params:]]
                    out = outer._forward_imperative(*nd_in)
                outs = [out] if isinstance(out, NDArray) else list(out)
                meta["n_outputs"] = len(outs)
                meta["aux_params"] = [p for p, _ in st.aux_updates]
                flat = [o.data() for o in outs] + [v for _, v in
                                                   st.aux_updates]
                return tuple(flat)
            finally:
                st.param_map, st.aux_updates, st.active = prev

        jitted = jax.jit(fn)
        try:
            jitted._mx_stable = True  # cacheable backward (lazy tape)
        except Exception:
            pass
        return {"fn": jitted, "params": params, "meta": meta}

    # -- AOT warm start (hybridize(aot=path), see compile_cache.py) -------
    def _bundle_entries(self):
        import os
        import warnings

        from .. import compile_cache as _ccache

        if self._aot_entries is None:
            self._aot_entries = {}
            if self._aot_path and os.path.exists(self._aot_path):
                try:
                    doc = _ccache.load_bundle(self._aot_path)
                    self._aot_entries = dict(doc["entries"])
                except MXNetError as e:
                    warnings.warn(
                        "hybridize(aot=%r): ignoring unusable bundle (%s); "
                        "falling back to live compilation"
                        % (self._aot_path, e))
        return self._aot_entries

    def _try_aot_load(self, key):
        import warnings

        from .. import compile_cache as _ccache

        entry = self._bundle_entries().get(repr(key))
        if entry is None:
            return None
        params = list(self.collect_params().values())
        try:
            for p in params:
                p._check_initialized()
        except Exception:
            return None  # deferred params: warm up imperatively first
        names = [p.name for p in params]
        if names != entry["param_names"]:
            raise MXNetError(
                "hybridize(aot=%r): bundle entry was exported with "
                "parameters %s but this block has %s — the architecture "
                "changed since export" % (self._aot_path,
                                          entry["param_names"], names))
        try:
            compiled = _ccache.deserialize_compiled(entry["blob"])
        except MXNetError as e:
            warnings.warn("hybridize(aot=%r): %s; falling back to live "
                          "compilation" % (self._aot_path, e))
            return None
        pmap = {p.name: p for p in params}
        aux = [pmap[n] for n in entry["aux_names"]]
        return {"fn": compiled, "params": params,
                "meta": {"n_outputs": entry["n_outputs"],
                         "aux_params": aux},
                "aot": True}

    def _aot_export(self, key, rec, inputs):
        import warnings

        from .. import compile_cache as _ccache

        params = rec["params"]
        datas = (
            (_random.next_key(),)
            + tuple(p.data().data() for p in params)
            + tuple(x.data() for x in inputs)
        )
        try:
            # lower() traces fn, filling rec["meta"] exactly as a call would
            compiled = rec["fn"].lower(*datas).compile()
            blob = _ccache.serialize_compiled(compiled)
        except Exception as e:
            warnings.warn(
                "hybridize(aot=%r): executable export failed (%s: %s); the "
                "block still runs, but a fresh process will recompile"
                % (self._aot_path, type(e).__name__, e))
            return
        meta = rec["meta"]
        entries = self._bundle_entries()
        entries[repr(key)] = {
            "blob": blob,
            "n_outputs": meta["n_outputs"],
            "aux_names": [p.name for p in meta["aux_params"]],
            "param_names": [p.name for p in params],
        }
        try:
            _ccache.save_bundle(self._aot_path, entries,
                                meta={"block": self.name})
        except Exception as e:
            warnings.warn("hybridize(aot=%r): bundle write failed (%s: %s)"
                          % (self._aot_path, type(e).__name__, e))
            return
        # serve subsequent non-recording calls straight from the compiled
        # executable — the exporting process pays exactly one compile
        self._aot_ops[key] = {"fn": compiled, "params": params,
                              "meta": {"n_outputs": meta["n_outputs"],
                                       "aux_params": list(
                                           meta["aux_params"])},
                              "aot": True}

    def _run_cached(self, rec, inputs):
        params = rec["params"]
        datas = (
            (_random.next_key(),)
            + tuple(p.data().data() for p in params)
            + tuple(x.data() for x in inputs)
        )
        eng = Engine.get()
        fn = rec["fn"]
        recording = autograd.is_recording()
        node = None
        flat = eng.push(lambda: fn(*datas), op_name=self.name + "_cached")
        if recording:
            # lazy tape: forward runs its cached executable; backward
            # re-linearizes through ONE cached jitted vjp per cache entry
            # (autograd._node_backward) instead of tracing jax.vjp on
            # every recorded call
            tape_inputs = [p.data() for p in params] + list(inputs)
            node = autograd.TapeNode(
                None, tape_inputs,
                [(o.shape, o.dtype) for o in flat],
                skip_grad_inputs=1,
                op_name=self.name + "_cached",
                prim=(fn, datas, 1))
        meta = rec["meta"]
        n_out = meta["n_outputs"]
        ctx = inputs[0].context if inputs else current_context()
        outs = []
        for i in range(n_out):
            arr = NDArray(flat[i], ctx=ctx)
            if node is not None:
                arr._tape_node = node
                arr._tape_index = i
            outs.append(arr)
        # write back aux updates (moving stats); not taped
        for p, new in zip(meta["aux_params"], flat[n_out:]):
            p.set_data(new)
        return outs[0] if n_out == 1 else tuple(outs)

    # -- export -----------------------------------------------------------
    def export(self, path, epoch=0):
        """Serialize to ``path-symbol.json`` + ``path-%04d.params``
        (parity: HybridBlock.export:1081).

        Like the reference, the block must have run at least one forward
        (that recorded the input signature).  The symbol file is a REAL
        Symbol graph traced via the symbolic path — loadable with
        ``SymbolBlock.imports`` / ``mx.mod.Module`` — with a structural
        JSON fallback when the graph cannot be expressed symbolically
        (e.g. data-dependent ops).
        """
        import json as _json

        from .. import symbol as _sym_mod
        from ..ndarray import ndarray as _ndm
        from ..symbol.symbol import Symbol

        params = self.collect_params()
        sym = None
        sig = getattr(self, "_export_input_sig", None)
        if sig:
            try:
                data_vars = [
                    _sym_mod.var("data" if i == 0 else "data%d" % i,
                                 shape=shp, dtype=dt)
                    for i, (shp, dt) in enumerate(sig)]
                with autograd.predict_mode():
                    out = self.forward(*data_vars)
                if isinstance(out, (list, tuple)) and all(
                        isinstance(o, Symbol) for o in out):
                    out = Symbol.Group(out)
                sym = out if isinstance(out, Symbol) else None
            except Exception:
                import logging

                # genuinely untraceable graphs (data-dependent ops) fall
                # back to the structural stub; log so tracer REGRESSIONS
                # stay visible rather than silently degrading exports
                logging.getLogger(__name__).warning(
                    "HybridBlock.export: symbolic trace failed, writing "
                    "structural stub", exc_info=True)
                sym = None
        aux_names = set(sym.list_auxiliary_states()) if sym else set()
        arg = {}
        for name, p in params.items():
            if p._data is not None:
                tag = "aux:" if name in aux_names else "arg:"
                arg[tag + name] = p.data()
        _ndm.save("%s-%04d.params" % (path, epoch), arg)
        if sym is not None:
            with open(path + "-symbol.json", "w") as f:
                f.write(sym.tojson())
            return
        desc = {"framework": "mxnet_tpu", "block": self.__class__.__name__,
                "name": self.name,
                "params": {k: list(p.shape or ()) for k, p in params.items()}}
        with open(path + "-symbol.json", "w") as f:
            _json.dump(desc, f, indent=2)


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol graph (parity: block.py:1194)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol.symbol import Symbol

        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if not isinstance(outputs, Symbol):
            raise MXNetError("SymbolBlock outputs must be a Symbol")
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._sym_outputs = outputs
        self._sym_inputs = [i.name for i in inputs]
        input_set = set(self._sym_inputs)
        for name in outputs.list_arguments():
            if name not in input_set:
                self._reg_params[name] = self.params.get(
                    name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self._reg_params[name] = self.params.get(
                name, grad_req="null", allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load

        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        from ..symbol import var

        inputs = [var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.load_parameters(param_file, ctx=ctx,
                                allow_missing=False, ignore_extra=True)
        return ret

    def forward(self, *args):
        bindings = dict(zip(self._sym_inputs, args))
        for name, p in self._reg_params.items():
            if p._data is None and p.shape is not None and \
                    all(s != 0 for s in p.shape):
                p.initialize()
            if p._data is not None:
                bindings[name] = p.data()
        out = self._sym_outputs.eval_imperative(bindings)
        return out[0] if len(out) == 1 else out

    def hybrid_forward(self, F, *args, **kwargs):  # pragma: no cover
        raise NotImplementedError
