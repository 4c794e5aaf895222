"""Deployment: compile a trained model to a portable StableHLO artifact.

Parity role: the reference's C predict API
(``src/c_api/c_predict_api.cc``, ``include/mxnet/c_predict_api.h`` — a
deployment-only ABI that loads ``model-symbol.json`` + ``.params`` and
runs inference without the Python frontend) and the ``amalgamation/``
single-file build of the same.

TPU-native mechanism: instead of replaying a symbol graph through an
interpreter, the whole trained forward is staged to **StableHLO** via
``jax.export`` and serialized.  The artifact is:

- self-contained — weights are baked in as constants (or kept as
  arguments with ``embed_params=False`` for A/B-able weights),
- ahead-of-time shape/dtype checked (calling with the wrong signature
  fails at load, like the predict API's provided-shape checks),
- loadable by ANY PJRT runtime that understands StableHLO — a C++
  server links PJRT and runs the module without this package (the C++
  story the reference's predict ABI served), and ``Predictor`` here is
  the in-process loader.

Versioning: jax.export guarantees forward/backward compatibility windows
for serialized modules, which replaces the reference's ``.params`` magic
-number versioning for deployment artifacts.
"""
from __future__ import annotations

import json
import os

import numpy as np

from .base import MXNetError


_MAGIC = b"MXTPU1\n"
_AOT_MAGIC = b"MXAOT1\n"  # compile_cache bundles (serving tier)


def export_serving_bundle(net, path, **kwargs):
    """Export a Llama-family ``net`` as an AOT serving bundle: the
    paged prefill/decode executable pair plus the KV-page geometry in
    the bundle meta.  Thin re-export of
    :func:`mxnet_tpu.serve.export_serving_bundle` so deployment code
    has one module to import for both artifact kinds.  See
    docs/serving.md."""
    from .serve.model import export_serving_bundle as _export

    return _export(net, path, **kwargs)


def load_serving_bundle(path, expect_geometry=None):
    """Load + validate a serving bundle: ``(KVGeometry, executables,
    weights)``, the weight tree on the device, passed to every
    executable after the cache state.

    All checks run at load time — bundle kind, complete KV-page
    geometry (page size, num pages, dtype, …), presence of every
    executable the geometry names and of the weights, and agreement with
    ``expect_geometry`` when given — so a mismatched bundle fails here
    with a field-by-field error instead of inside XLA on the first
    decode."""
    from .serve.model import load_serving_executables

    return load_serving_executables(path, expect=expect_geometry)


def export_model(net, example_inputs, path, embed_params=True,
                 platforms=None):
    """Compile ``net``'s forward on ``example_inputs`` and write a
    deployable artifact to ``path`` (conventionally ``*.mxtpu``).

    ``example_inputs``: NDArray/ndarray tuple fixing input shapes+dtypes.
    ``embed_params=True`` bakes the weights into the module as
    constants; ``False`` keeps them as trailing arguments and stores
    them beside the module (loadable/updatable separately).
    ``platforms``: e.g. ``("tpu", "cpu")`` for a multi-platform module;
    defaults to the current backend.
    """
    import jax
    from jax import export as jexport

    from . import autograd
    from . import random as _random
    from .gluon import block as block_mod
    from .ndarray.ndarray import NDArray

    if not isinstance(example_inputs, (tuple, list)):
        example_inputs = (example_inputs,)
    xs = tuple(np.asarray(x.asnumpy() if isinstance(x, NDArray) else x)
               for x in example_inputs)
    # resolve deferred shapes with one forward — only when needed
    params = list(net.collect_params().values())
    if any(p._data is None for p in params):
        net(*[NDArray(np.asarray(x)) for x in xs])
        params = list(net.collect_params().values())
    weights = tuple(p.data().data() for p in params)

    def fwd(inputs, ws):
        st = block_mod._trace_st()
        prev = (st.param_map, st.aux_updates, st.active)
        st.param_map = {id(p): NDArray(w) for p, w in zip(params, ws)}
        st.aux_updates = []
        st.active = True
        try:
            with autograd.predict_mode(), \
                    _random.trace_key_scope(jax.random.PRNGKey(0)):
                out = net._forward_imperative(
                    *[NDArray(x) for x in inputs])
            if isinstance(out, (list, tuple)):
                return tuple(o.data() for o in out)
            return (out.data(),)
        finally:
            st.param_map, st.aux_updates, st.active = prev

    kwargs = {}
    if platforms is not None:
        kwargs["platforms"] = tuple(platforms)

    if embed_params:
        fn = jax.jit(lambda *inputs: fwd(inputs, weights))
        exp = jexport.export(fn, **kwargs)(*xs)
        blobs = {}
    else:
        fn = jax.jit(lambda inputs, ws: fwd(inputs, ws))
        exp = jexport.export(fn, **kwargs)(xs, weights)
        blobs = {"param_%05d" % i: np.asarray(w)
                 for i, w in enumerate(weights)}

    module = exp.serialize()
    meta = {
        "embed_params": bool(embed_params),
        "n_inputs": len(xs),
        "n_params": len(params),
        "param_names": [p.name for p in params],
        "param_shapes": [list(np.asarray(w).shape) for w in weights],
        "param_dtypes": [str(np.asarray(w).dtype) for w in weights],
        "input_shapes": [list(x.shape) for x in xs],
        "input_dtypes": [str(x.dtype) for x in xs],
        "platforms": list(exp.platforms),
    }
    with open(path, "wb") as f:
        f.write(_MAGIC)
        head = json.dumps(meta).encode()
        f.write(len(head).to_bytes(8, "little"))
        f.write(head)
        f.write(len(module).to_bytes(8, "little"))
        f.write(module)
        if blobs:
            import io as _io

            buf = _io.BytesIO()
            np.savez(buf, **blobs)
            f.write(buf.getvalue())
    return meta


class Predictor:
    """In-process loader for exported artifacts (parity:
    ``MXPredCreate``/``MXPredForward``/``MXPredGetOutput``)."""

    def __init__(self, path):
        with open(path, "rb") as f:
            magic = f.read(len(_MAGIC))
            if magic == _AOT_MAGIC:
                # an AOT serving bundle, not a StableHLO artifact: say so
                # (and validate its KV geometry) instead of failing as a
                # generic bad-magic or, worse, later inside XLA
                from .serve.model import read_bundle_geometry

                geometry, _ = read_bundle_geometry(path)
                raise MXNetError(
                    "%s is an AOT serving bundle [%s], not a StableHLO "
                    "artifact — load it with serve.LlamaServer(path) or "
                    "deploy.load_serving_bundle(path)"
                    % (path, geometry.describe()))
            if magic != _MAGIC:
                raise MXNetError("%s is not an exported model" % path)
            hlen = int.from_bytes(f.read(8), "little")
            self.meta = json.loads(f.read(hlen).decode())
            mlen = int.from_bytes(f.read(8), "little")
            module = f.read(mlen)
            rest = f.read()
        from jax import export as jexport

        self._exp = jexport.deserialize(module)
        self._weights = ()
        if not self.meta["embed_params"] and self.meta["n_params"]:
            import io as _io

            # validate the weight blobs AT LOAD (parity: the predict API's
            # provided-shape checks) — a truncated artifact or one whose
            # stored weights no longer match the module signature must fail
            # here, not as an opaque XLA error on the first request
            try:
                blobs = np.load(_io.BytesIO(rest))
                ws = tuple(blobs["param_%05d" % i]
                           for i in range(self.meta["n_params"]))
            except MXNetError:
                raise
            except Exception as e:
                raise MXNetError(
                    "%s: embed_params=False artifact is missing/corrupt "
                    "weight blobs (%s: %s)" % (path, type(e).__name__, e))
            self._check_param_sig(ws, path)
            self._weights = ws

    def _check_param_sig(self, arrays, origin="set_params"):
        shapes = self.meta.get("param_shapes")
        dtypes = self.meta.get("param_dtypes")
        if shapes is None:
            return  # pre-param-sig artifact: best effort
        for i, (a, shape, dt) in enumerate(zip(arrays, shapes, dtypes)):
            if list(a.shape) != shape or str(a.dtype) != dt:
                raise MXNetError(
                    "%s: param %d (%s) mismatch: got %s %s, module wants "
                    "%s %s" % (origin, i,
                               self.meta["param_names"][i],
                               tuple(a.shape), a.dtype, tuple(shape), dt))

    def set_params(self, arrays):
        """Swap the weights of a ``embed_params=False`` artifact.

        Shape/dtype-checked against the module signature immediately — a
        wrong weight set raises HERE, not on the next ``predict``.
        """
        if self.meta["embed_params"]:
            raise MXNetError("artifact has embedded params")
        if len(arrays) != self.meta["n_params"]:
            raise MXNetError("expected %d params" % self.meta["n_params"])
        ws = tuple(np.asarray(a) for a in arrays)
        self._check_param_sig(ws)
        self._weights = ws

    def warm(self):
        """Pre-compile the module before the first request.

        Runs the exported forward once on zeros shaped from the artifact's
        input signature, so the PJRT compile (disk-cached via
        compile_cache.py when MXNET_COMPILE_CACHE is on) happens at server
        startup instead of on the first live request.  Returns ``self``
        for ``Predictor(path).warm()`` chaining.
        """
        zeros = tuple(
            np.zeros(shape, dtype=dt)
            for shape, dt in zip(self.meta["input_shapes"],
                                 self.meta["input_dtypes"]))
        if self.meta["embed_params"]:
            self._exp.call(*zeros)
        else:
            if len(self._weights) != self.meta["n_params"]:
                raise MXNetError(
                    "warm() before set_params on an embed_params=False "
                    "artifact with no stored weights")
            self._exp.call(zeros, self._weights)
        return self

    def predict(self, *inputs):
        """Run the compiled forward; returns NDArray or list of them."""
        from .ndarray.ndarray import NDArray

        xs = tuple(np.asarray(x.asnumpy() if isinstance(x, NDArray) else x)
                   for x in inputs)
        if len(xs) != self.meta["n_inputs"]:
            raise MXNetError("expected %d inputs" % self.meta["n_inputs"])
        for x, shape, dt in zip(xs, self.meta["input_shapes"],
                                self.meta["input_dtypes"]):
            if list(x.shape) != shape or str(x.dtype) != dt:
                raise MXNetError(
                    "input mismatch: got %s %s, artifact wants %s %s"
                    % (x.shape, x.dtype, tuple(shape), dt))
        if self.meta["embed_params"]:
            outs = self._exp.call(*xs)
        else:
            outs = self._exp.call(xs, self._weights)
        if isinstance(outs, (list, tuple)):
            res = [NDArray(o) for o in outs]
            return res[0] if len(res) == 1 else res
        return NDArray(outs)

    @property
    def mlir(self):
        """StableHLO text of the deployed module (debugging/audit)."""
        return self._exp.mlir_module()
