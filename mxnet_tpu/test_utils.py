"""Testing harness: numeric-gradient and cross-context consistency checks.

Capability parity with the reference harness
(``python/mxnet/test_utils.py``): ``assert_almost_equal`` with max-violation
reporting (ref ``:534``), finite-difference ``check_numeric_gradient``
(ref ``:981``), golden-forward/backward checks ``check_symbolic_forward`` /
``check_symbolic_backward`` (ref ``:1124``, ``:1205``), and the
cross-device oracle ``check_consistency`` (ref ``:1422``) — the designated
TPU test pattern: bind the same symbol on a reference context (CPU,
float64) and the device under test and compare outputs and gradients.

TPU-native mechanism: instead of perturbing executor buffers in place
(the reference mutates ``executor.arg_arrays``), both sides are pure
functions built from the Symbol; the finite-difference loop re-runs ONE
jitted scalar projection ``f(args) = Σ out·proj`` under
``jax.enable_x64`` so the FD arithmetic happens in float64
even though the framework default is float32, and the analytic side is
the very same ``jax.vjp`` path the real executors use.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, cpu, current_context
from . import ndarray as nd
from .ndarray.ndarray import NDArray
from . import random as _random

_DEFAULT_CTX = None

_DTYPE_RTOL = {np.dtype(np.float16): 1e-2,
               np.dtype("bfloat16") if hasattr(np, "bfloat16") else
               np.dtype(np.float16): 1e-2,
               np.dtype(np.float32): 1e-4,
               np.dtype(np.float64): 1e-7}
_DTYPE_ATOL = {np.dtype(np.float16): 1e-3,
               np.dtype(np.float32): 1e-5,
               np.dtype(np.float64): 1e-9}


def default_context():
    """The context tests run on (ref test_utils.py:58)."""
    return _DEFAULT_CTX or current_context()


def set_default_context(ctx):
    global _DEFAULT_CTX
    _DEFAULT_CTX = ctx


def default_dtype():
    return np.float32


def _np(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    if isinstance(a, jax.Array):
        return np.asarray(a)
    return np.asarray(a)


def get_rtol(rtol=None, dtype=None):
    if rtol is not None:
        return rtol
    if dtype is not None:
        return _DTYPE_RTOL.get(np.dtype(dtype), 1e-5)
    return 1e-5


def get_atol(atol=None, dtype=None):
    if atol is not None:
        return atol
    if dtype is not None:
        return _DTYPE_ATOL.get(np.dtype(dtype), 1e-20)
    return 1e-20


def random_arrays(*shapes):
    """Random float32 numpy arrays; scalar for () shapes (ref :95)."""
    arrays = [np.array(np.random.randn(), dtype=np.float32) if len(s) == 0
              else np.random.randn(*s).astype(np.float32) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def rand_shape_nd(num_dim, dim=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return tuple(np.random.randint(low, dim + 1, size=num_dim))


def rand_shape_2d(dim0=10, dim1=10, allow_zero_size=False):
    return rand_shape_nd(2, max(dim0, dim1), allow_zero_size)


def rand_shape_3d(dim0=10, dim1=10, dim2=10, allow_zero_size=False):
    return rand_shape_nd(3, max(dim0, dim1, dim2), allow_zero_size)


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None, scale=1.0):
    """Random NDArray (dense; row_sparse/csr via ndarray.sparse)."""
    dtype = dtype or default_dtype()
    data = (np.random.uniform(-scale, scale, size=shape)).astype(dtype)
    if stype == "default":
        return nd.array(data, ctx=ctx)
    from .ndarray import sparse as _sp
    density = 0.1 if density is None else density
    mask = np.random.uniform(size=shape) < density
    data = data * mask
    if stype == "row_sparse":
        return _sp.RowSparseNDArray.from_dense(nd.array(data, ctx=ctx))
    if stype == "csr":
        return _sp.CSRNDArray.from_dense(nd.array(data, ctx=ctx))
    raise MXNetError("unknown storage type %r" % stype)


def same(a, b):
    return np.array_equal(_np(a), _np(b))


def find_max_violation(a, b, rtol=None, atol=None):
    """Index/value of the worst |a-b| - (atol + rtol|b|) violation (ref :492)."""
    a, b = _np(a), _np(b)
    rtol, atol = get_rtol(rtol), get_atol(atol)
    diff = np.abs(a - b)
    tol = atol + rtol * np.abs(b)
    violation = diff / (tol + 1e-300)
    idx = np.unravel_index(np.argmax(violation), violation.shape)
    return idx, np.max(violation)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    return np.allclose(_np(a), _np(b), rtol=get_rtol(rtol),
                       atol=get_atol(atol), equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Allclose with a max-violation error message (ref :534)."""
    a_np, b_np = _np(a), _np(b)
    if a_np.shape != b_np.shape:
        raise AssertionError(
            "shape mismatch: %s %s vs %s %s"
            % (names[0], a_np.shape, names[1], b_np.shape))
    if np.allclose(a_np, b_np, rtol=get_rtol(rtol), atol=get_atol(atol),
                   equal_nan=equal_nan):
        return
    idx, rel = find_max_violation(a_np, b_np, rtol, atol)
    raise AssertionError(
        "%s and %s differ: max violation %.3g x tolerance at index %s "
        "(%s=%r, %s=%r); rtol=%g atol=%g"
        % (names[0], names[1], rel, idx,
           names[0], a_np[idx], names[1], b_np[idx],
           get_rtol(rtol), get_atol(atol)))


def assert_exception(f, exception_type, *args, **kwargs):
    try:
        f(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError("did not raise %s" % exception_type)


def with_seed(seed=None):
    """Decorator seeding mx+numpy per test, logging the seed on failure
    (reference tests/python/unittest/common.py:155)."""
    import functools as _ft

    def deco(f):
        @_ft.wraps(f)
        def wrapper(*args, **kwargs):
            import random as _pyrandom

            actual = (np.random.randint(0, np.iinfo(np.int32).max)
                      if seed is None else seed)
            from . import random as _mxrandom

            _mxrandom.seed(actual)
            np.random.seed(actual)
            _pyrandom.seed(actual)
            try:
                return f(*args, **kwargs)
            except Exception:
                print("*** test failed with seed %d: rerun with "
                      "with_seed(%d) to reproduce ***" % (actual, actual))
                raise
        return wrapper
    return deco


def retry(n):
    """Retry a flaky (randomized) test up to n times (ref common.py)."""
    def deco(f):
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            for i in range(n):
                try:
                    return f(*args, **kwargs)
                except AssertionError:
                    if i == n - 1:
                        raise
        return wrapper
    return deco


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    """Feed inputs by name, return outputs as numpy (ref :754)."""
    outs = sym.eval(ctx=ctx, **{k: nd.array(v) for k, v in inputs.items()})
    outs = [o.asnumpy() for o in outs]
    return outs[0] if len(outs) == 1 else outs


# ---------------------------------------------------------------------------
# location parsing
# ---------------------------------------------------------------------------

def _parse_location(sym, location, dtype=np.float64):
    """list-or-dict of arrays → dict name→np array (ref :782)."""
    arg_names = sym.list_arguments()
    if isinstance(location, dict):
        unknown = set(location) - set(arg_names)
        if unknown:
            raise MXNetError("unknown arguments %s" % sorted(unknown))
        loc = dict(location)
    else:
        if len(location) != len(arg_names):
            raise MXNetError(
                "expected %d args (%s), got %d"
                % (len(arg_names), arg_names, len(location)))
        loc = dict(zip(arg_names, location))
    out = {}
    for k, v in loc.items():
        v = _np(v)
        out[k] = v.astype(dtype) if np.issubdtype(v.dtype, np.floating) \
            else v
    return out


def _parse_aux_states(sym, aux_states, dtype=np.float64):
    aux_names = sym.list_auxiliary_states()
    if aux_states is None:
        return {}
    if isinstance(aux_states, dict):
        aux = dict(aux_states)
    else:
        aux = dict(zip(aux_names, aux_states))
    out = {}
    for k, v in aux.items():
        v = _np(v)
        out[k] = v.astype(dtype) if np.issubdtype(v.dtype, np.floating) \
            else v
    return out


def _project_fn(sym, bindings_names, projs, mode="train"):
    """Scalar f(grad_args, other_args) = Σ_i sum(out_i · proj_i)."""
    raw = sym._make_fn(bindings_names, mode=mode)

    def scalar(grad_args, other_args, key):
        with _random.trace_key_scope(key):
            b = dict(other_args)
            b.update(grad_args)
            outs = raw(b)
        total = 0.0
        for o, p in zip(outs, projs):
            total = total + jnp.sum(o.astype(jnp.float64) * p)
        return total

    return scalar


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-4,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None,
                           dtype=np.float64):
    """Finite-difference check of the backward pass (ref :981).

    Projects the outputs to a scalar with a fixed random cotangent, then
    compares ``jax.grad`` of that scalar (the same vjp machinery the
    executors use) against central finite differences computed in float64.
    """
    location = _parse_location(sym, location, dtype)
    aux = _parse_aux_states(sym, aux_states, dtype)
    arg_names = sym.list_arguments()
    if grad_nodes is None:
        grad_nodes = [n for n in arg_names
                      if np.issubdtype(location[n].dtype, np.floating)]
    elif isinstance(grad_nodes, dict):
        grad_nodes = [n for n, req in grad_nodes.items() if req != "null"]
    grad_nodes = list(grad_nodes)
    mode = "train" if use_forward_train else "predict"

    with jax.enable_x64(True):
        key = jax.random.PRNGKey(0)
        # fixed random projection per output
        probe = sym._make_fn(sym.list_inputs(), mode=mode)
        all_bind = dict(location)
        all_bind.update(aux)
        with _random.trace_key_scope(key):
            outs = probe({k: jnp.asarray(v) for k, v in all_bind.items()})
        rng = np.random.RandomState(42)
        projs = [jnp.asarray(rng.normal(size=np.shape(o)) + 0.1)
                 for o in outs]

        grad_args = {n: jnp.asarray(location[n]) for n in grad_nodes}
        other = {k: jnp.asarray(v) for k, v in all_bind.items()
                 if k not in set(grad_nodes)}
        scalar = _project_fn(sym, sym.list_inputs(), projs, mode)
        analytic = jax.jit(jax.grad(scalar))(grad_args, other, key)
        fwd = jax.jit(scalar)

        for name in grad_nodes:
            base = np.asarray(location[name], dtype=np.float64)
            num = np.zeros_like(base, dtype=np.float64)
            flat = base.ravel()
            for i in range(flat.size):
                for sgn in (1.0, -1.0):
                    pert = flat.copy()
                    pert[i] += sgn * numeric_eps
                    ga = dict(grad_args)
                    ga[name] = jnp.asarray(pert.reshape(base.shape))
                    num.ravel()[i] += sgn * float(fwd(ga, other, key))
            num /= 2 * numeric_eps
            assert_almost_equal(
                _np(analytic[name]), num, rtol=rtol, atol=atol,
                names=("analytic_grad_of_%s" % name,
                       "numeric_grad_of_%s" % name))


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=None,
                           aux_states=None, ctx=None, equal_nan=False,
                           dtype=np.float32):
    """Compare forward outputs against expected numpy arrays (ref :1124)."""
    location = _parse_location(sym, location, dtype)
    aux = _parse_aux_states(sym, aux_states, dtype)
    args = {k: nd.array(v) for k, v in location.items()}
    args.update({k: nd.array(v) for k, v in aux.items()})
    outs = sym.eval(ctx=ctx, **args)
    if isinstance(expected, dict):
        expected = [expected[n] for n in sym.list_outputs()]
    for o, e in zip(outs, expected):
        assert_almost_equal(o.asnumpy(), _np(e), rtol=rtol, atol=atol,
                            names=("output", "expected"),
                            equal_nan=equal_nan)
    return [o.asnumpy() for o in outs]


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None, equal_nan=False, dtype=np.float32):
    """Compare backward gradients against expected numpy arrays (ref :1205)."""
    location = _parse_location(sym, location, dtype)
    aux = _parse_aux_states(sym, aux_states, dtype)
    ctx = ctx or default_context()
    args = {k: nd.array(v) for k, v in location.items()}
    auxs = {k: nd.array(v) for k, v in aux.items()}
    if isinstance(grad_req, str):
        reqs = {n: grad_req for n in sym.list_arguments()}
    elif isinstance(grad_req, (list, tuple)):
        reqs = dict(zip(sym.list_arguments(), grad_req))
    else:
        reqs = dict(grad_req)
    exe = sym.bind(ctx=ctx, args=args, grad_req=reqs)
    for n, arr in auxs.items():
        exe.aux_dict[n]._set_data(arr.data())
    exe.forward(is_train=True)
    if isinstance(out_grads, (nd.NDArray, np.ndarray)):
        out_grads = [out_grads]
    if isinstance(out_grads, dict):
        out_grads = [out_grads[n] for n in sym.list_outputs()]
    exe.backward([g if isinstance(g, NDArray) else nd.array(g)
                  for g in out_grads])
    if isinstance(expected, dict):
        items = expected.items()
    else:
        items = zip(sym.list_arguments(), expected)
    grads = {}
    for name, e in items:
        if e is None or reqs.get(name, "null") == "null":
            continue
        g = exe.grad_dict[name].asnumpy()
        grads[name] = g
        assert_almost_equal(g, _np(e), rtol=rtol, atol=atol,
                            names=("grad_of_%s" % name, "expected"),
                            equal_nan=equal_nan)
    return grads


def get_tolerance(rtol, ctx=None, dtype=np.float32):
    return max(rtol or 0, get_rtol(None, dtype))


def check_consistency(sym, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, aux_params=None, tol=None,
                      raise_on_err=True, ground_truth=None,
                      equal_nan=False, use_uniform=False,
                      rand_type=np.float64):
    """Bind the same symbol on several contexts/dtypes, compare (ref :1422).

    ``ctx_list`` entries: ``{'ctx': Context, 'type_dict': {name: dtype},
    <name>: shape, ...}``.  The most precise entry is the oracle — the
    designated CPU-reference-vs-TPU test pattern (SURVEY §4.2).
    """
    if tol is None:
        tol = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
               np.dtype(np.float64): 1e-5, np.dtype(np.uint8): 0,
               np.dtype(np.int32): 0, np.dtype(np.int64): 0}
    elif isinstance(tol, (int, float)):
        tol = {np.dtype(t): tol for t in
               (np.float16, np.float32, np.float64, np.uint8, np.int32,
                np.int64)}
    syms = sym if isinstance(sym, list) else [sym] * len(ctx_list)
    arg_names = syms[0].list_arguments()

    # generate shared f64 input data from the first spec
    spec0 = ctx_list[0]
    shapes = {k: v for k, v in spec0.items()
              if k not in ("ctx", "type_dict")}
    if use_uniform:
        data = {n: np.random.uniform(-scale, scale, size=s)
                .astype(rand_type) for n, s in shapes.items()}
    else:
        data = {n: (np.random.normal(size=s) * scale).astype(rand_type)
                for n, s in shapes.items()}
    if arg_params:
        data.update({k: _np(v).astype(rand_type)
                     for k, v in arg_params.items()})
    for n in arg_names:
        if n not in data:
            raise MXNetError("check_consistency: no shape for arg %r" % n)

    results = []
    for s, spec in zip(syms, ctx_list):
        ctx = spec.get("ctx", default_context())
        type_dict = spec.get("type_dict", {})
        args = {n: nd.array(data[n].astype(type_dict.get(n, np.float32)),
                            ctx=ctx) for n in arg_names}
        exe = s.bind(ctx=ctx, args=args, grad_req=grad_req)
        if aux_params:
            for n, v in aux_params.items():
                exe.aux_dict[n]._set_data(nd.array(v).data())
        exe.forward(is_train=(grad_req != "null"))
        outs = [o.asnumpy().astype(np.float64) for o in exe.outputs]
        grads = {}
        if grad_req != "null":
            exe.backward([nd.array(np.ones(o.shape, np.float32))
                          for o in exe.outputs])
            grads = {n: g.asnumpy().astype(np.float64)
                     for n, g in exe.grad_dict.items() if g is not None}
        dtypes = [np.dtype(type_dict.get(n, np.float32))
                  for n in arg_names] or [np.dtype(np.float32)]
        max_dt = max(dtypes, key=lambda d: d.itemsize)
        results.append((outs, grads, max_dt))

    if ground_truth is None:
        gt_idx = max(range(len(results)),
                     key=lambda i: results[i][2].itemsize)
        gt_outs, gt_grads, _ = results[gt_idx]
    else:
        gt_outs, gt_grads = ground_truth, {}

    errors = []
    for i, (outs, grads, dt) in enumerate(results):
        t = tol.get(dt, 1e-3)
        for j, (o, g) in enumerate(zip(outs, gt_outs)):
            try:
                assert_almost_equal(o, g, rtol=t, atol=t,
                                    names=("ctx%d_out%d" % (i, j), "gt"),
                                    equal_nan=equal_nan)
            except AssertionError as e:
                errors.append(str(e))
        for n, g in grads.items():
            if n in gt_grads:
                try:
                    assert_almost_equal(
                        g, gt_grads[n], rtol=t, atol=t,
                        names=("ctx%d_grad_%s" % (i, n), "gt"),
                        equal_nan=equal_nan)
                except AssertionError as e:
                    errors.append(str(e))
    if errors and raise_on_err:
        raise AssertionError("check_consistency failed:\n"
                             + "\n".join(errors))
    return [r[0] for r in results]
