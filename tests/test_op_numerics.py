"""Per-op numerics sweep over the whole registry.

The TPU analogue of the reference's two op-coverage layers:
``tests/python/unittest/test_operator.py`` (forward-vs-NumPy goldens +
``check_numeric_gradient`` FD backward checks) and ``benchmark/opperf``
(every registered op exercised with default shapes).  Every op in
``ops.registry`` must appear either in ``SPECS`` below or in ``EXCLUDED``
with a justification; ``test_registry_fully_covered`` enforces it.
"""
from __future__ import annotations

import numpy as np
import pytest
import scipy.special as sps

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import test_utils as tu
from mxnet_tpu.ops import registry


def _r(seed):
    return np.random.RandomState(seed)


def randn(shape, seed=0, scale=1.0):
    return (_r(seed).randn(*shape) * scale).astype(np.float32)


def pos(shape, seed=0, lo=0.5, hi=2.0):
    return _r(seed).uniform(lo, hi, shape).astype(np.float32)


def unit(shape, seed=0):
    return _r(seed).uniform(-0.9, 0.9, shape).astype(np.float32)


class S:
    """One sweep spec: inputs, attrs, forward oracle, FD-grad toggle."""

    def __init__(self, inputs, attrs=None, ref=None, check=None, grad=False,
                 rtol=1e-4, atol=1e-5, grad_rtol=5e-2, grad_atol=5e-3,
                 eps=1e-3, grad_nodes=None):
        self.inputs = [np.asarray(i) for i in inputs]
        self.attrs = attrs or {}
        self.ref = ref
        self.check = check
        self.grad = grad
        self.rtol, self.atol = rtol, atol
        self.grad_rtol, self.grad_atol, self.eps = grad_rtol, grad_atol, eps
        self.grad_nodes = grad_nodes


SPECS = {}

# ---------------------------------------------------------------------------
# unary elementwise: (numpy ref, input domain, differentiable)
# ---------------------------------------------------------------------------
_UNARY = {
    "abs": (np.abs, "any", True),
    "sign": (np.sign, "any", False),
    "ceil": (np.ceil, "any", False),
    "floor": (np.floor, "any", False),
    "rint": (np.rint, "any", False),
    "round": (np.round, "any", False),
    "trunc": (np.trunc, "any", False),
    "fix": (np.trunc, "any", False),
    "exp": (np.exp, "any", True),
    "log": (np.log, "pos", True),
    "log2": (np.log2, "pos", True),
    "log10": (np.log10, "pos", True),
    "log1p": (np.log1p, "pos", True),
    "expm1": (np.expm1, "any", True),
    "sqrt": (np.sqrt, "pos", True),
    "rsqrt": (lambda x: 1 / np.sqrt(x), "pos", True),
    "cbrt": (np.cbrt, "pos", True),
    "rcbrt": (lambda x: 1 / np.cbrt(x), "pos", True),
    "square": (np.square, "any", True),
    "reciprocal": (lambda x: 1 / x, "pos", True),
    "negative": (np.negative, "any", True),
    "sin": (np.sin, "any", True),
    "cos": (np.cos, "any", True),
    "tan": (np.tan, "unit", True),
    "arcsin": (np.arcsin, "unit", True),
    "arccos": (np.arccos, "unit", True),
    "arctan": (np.arctan, "any", True),
    "sinh": (np.sinh, "any", True),
    "cosh": (np.cosh, "any", True),
    "tanh": (np.tanh, "any", True),
    "arcsinh": (np.arcsinh, "any", True),
    "arccosh": (np.arccosh, "gt1", True),
    "arctanh": (np.arctanh, "unit", True),
    "degrees": (np.degrees, "any", True),
    "radians": (np.radians, "any", True),
    "sigmoid": (lambda x: 1 / (1 + np.exp(-x)), "any", True),
    "softsign": (lambda x: x / (1 + np.abs(x)), "any", True),
    "relu": (lambda x: np.maximum(x, 0), "pos", True),
    "erf": (sps.erf, "any", True),
    "erfinv": (sps.erfinv, "unit", True),
    "gamma": (sps.gamma, "pos", True),
    "gammaln": (sps.gammaln, "pos", True),
    "logical_not": (lambda x: (~(x != 0)).astype(np.float32), "any", False),
    "isnan": (np.isnan, "any", False),
    "isinf": (np.isinf, "any", False),
    "isfinite": (np.isfinite, "any", False),
    "identity": (lambda x: x, "any", True),
    "stop_gradient": (lambda x: x, "any", False),
    "make_loss": (lambda x: x, "any", True),
}
_DOMAIN = {"any": randn, "pos": pos, "unit": unit,
           "gt1": lambda s, seed=0: pos(s, seed, 1.1, 3.0)}
for _name, (_ref, _dom, _diff) in _UNARY.items():
    SPECS[_name] = S([_DOMAIN[_dom]((2, 3), seed=hash(_name) % 1000)],
                     ref=_ref, grad=_diff)

# special-value coverage for the float classifiers
for _name in ("isnan", "isinf", "isfinite"):
    SPECS[_name].inputs = [np.array([[1.0, np.nan], [np.inf, -np.inf]],
                                    np.float32)]

# ---------------------------------------------------------------------------
# binary: elemwise + broadcast + scalar
# ---------------------------------------------------------------------------
_BIN_REFS = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "div": np.divide, "mod": np.mod, "power": np.power,
    "maximum": np.maximum, "minimum": np.minimum, "hypot": np.hypot,
    "equal": lambda a, b: (a == b).astype(np.float32),
    "not_equal": lambda a, b: (a != b).astype(np.float32),
    "greater": lambda a, b: (a > b).astype(np.float32),
    "greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "lesser": lambda a, b: (a < b).astype(np.float32),
    "lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    "logical_and": lambda a, b: ((a != 0) & (b != 0)).astype(np.float32),
    "logical_or": lambda a, b: ((a != 0) | (b != 0)).astype(np.float32),
    "logical_xor": lambda a, b: ((a != 0) ^ (b != 0)).astype(np.float32),
}
_BIN_DIFF = {"add", "sub", "mul", "div", "power", "maximum", "minimum",
             "hypot"}
for _name, _ref in _BIN_REFS.items():
    gen = pos if _name in ("mod", "power", "div", "hypot") else randn
    a, b = gen((2, 3), seed=1), gen((2, 3), seed=2)
    ew = {"add": "elemwise_add", "sub": "elemwise_sub",
          "mul": "elemwise_mul", "div": "elemwise_div"}.get(
              _name, "_" + _name)
    SPECS[ew] = S([a, b], ref=_ref, grad=_name in _BIN_DIFF)
    bb = gen((2, 1, 3), seed=3)
    SPECS["broadcast_" + _name] = S(
        [bb, gen((1, 4, 3), seed=4)],
        ref=_ref, grad=_name in _BIN_DIFF)

_SCALAR_REFS = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: np.mod(x, s),
    "_rmod_scalar": lambda x, s: np.mod(s, x),
    "_power_scalar": lambda x, s: np.power(x, s),
    "_rpower_scalar": lambda x, s: np.power(s, x),
    "_maximum_scalar": lambda x, s: np.maximum(x, s),
    "_minimum_scalar": lambda x, s: np.minimum(x, s),
    "_equal_scalar": lambda x, s: (x == s).astype(np.float32),
    "_not_equal_scalar": lambda x, s: (x != s).astype(np.float32),
    "_greater_scalar": lambda x, s: (x > s).astype(np.float32),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(np.float32),
    "_lesser_scalar": lambda x, s: (x < s).astype(np.float32),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(np.float32),
    "_logical_and_scalar": lambda x, s: ((x != 0) & (s != 0)).astype(np.float32),
    "_logical_or_scalar": lambda x, s: ((x != 0) | (s != 0)).astype(np.float32),
    "_logical_xor_scalar": lambda x, s: ((x != 0) ^ (s != 0)).astype(np.float32),
}
_SCALAR_DIFF = {"_plus_scalar", "_minus_scalar", "_rminus_scalar",
                "_mul_scalar", "_div_scalar", "_rdiv_scalar",
                "_power_scalar", "_maximum_scalar", "_minimum_scalar"}
for _name, _ref in _SCALAR_REFS.items():
    SPECS[_name] = S([pos((2, 3), seed=5)], attrs={"scalar": 1.7},
                     ref=lambda x, _f=_ref: _f(x, 1.7),
                     grad=_name in _SCALAR_DIFF)

# ---------------------------------------------------------------------------
# reductions / argreductions
# ---------------------------------------------------------------------------
SPECS["sum"] = S([randn((2, 3, 4), 6)], {"axis": 1},
                 ref=lambda x: x.sum(1), grad=True)
SPECS["mean"] = S([randn((2, 3, 4), 7)], {"axis": (0, 2)},
                  ref=lambda x: x.mean((0, 2)), grad=True)
SPECS["max"] = S([randn((2, 3), 8)], {"axis": 1, "keepdims": True},
                 ref=lambda x: x.max(1, keepdims=True), grad=True)
SPECS["min"] = S([randn((2, 3), 9)], {"axis": 0},
                 ref=lambda x: x.min(0), grad=True)
SPECS["prod"] = S([pos((2, 3), 10)], {"axis": 1},
                  ref=lambda x: x.prod(1), grad=True)
_nan_in = randn((2, 3), 11)
_nan_in[0, 1] = np.nan
SPECS["nansum"] = S([_nan_in], {"axis": 1}, ref=lambda x: np.nansum(x, 1))
SPECS["nanprod"] = S([_nan_in], {"axis": 1}, ref=lambda x: np.nanprod(x, 1))
SPECS["norm"] = S([randn((2, 3), 12)], {"ord": 2, "axis": 1},
                  ref=lambda x: np.linalg.norm(x, 2, 1), grad=True)
SPECS["logsumexp"] = S([randn((2, 3), 13)], {"axis": 1},
                       ref=lambda x: sps.logsumexp(x, 1), grad=True)
SPECS["argmax"] = S([randn((2, 5), 14)], {"axis": 1},
                    ref=lambda x: x.argmax(1).astype(np.float32))
SPECS["argmin"] = S([randn((2, 5), 15)], {"axis": 1},
                    ref=lambda x: x.argmin(1).astype(np.float32))
SPECS["argmax_channel"] = S([randn((2, 5), 16)],
                            ref=lambda x: x.argmax(1).astype(np.float32))
SPECS["cumsum"] = S([randn((2, 4), 17)], {"axis": 1},
                    ref=lambda x: np.cumsum(x, 1), grad=True)

# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------
SPECS["reshape"] = S([randn((2, 6), 18)], {"shape": (3, 4)},
                     ref=lambda x: x.reshape(3, 4), grad=True)
SPECS["reshape_like"] = S([randn((2, 6), 19), randn((3, 4), 20)],
                          ref=lambda a, b: a.reshape(3, 4))
SPECS["flatten"] = S([randn((2, 3, 4), 21)],
                     ref=lambda x: x.reshape(2, 12), grad=True)
SPECS["transpose"] = S([randn((2, 3, 4), 22)], {"axes": (2, 0, 1)},
                       ref=lambda x: x.transpose(2, 0, 1), grad=True)
SPECS["swapaxes"] = S([randn((2, 3, 4), 23)], {"dim1": 0, "dim2": 2},
                      ref=lambda x: x.swapaxes(0, 2))
SPECS["expand_dims"] = S([randn((2, 3), 24)], {"axis": 1},
                         ref=lambda x: x[:, None, :])
SPECS["squeeze"] = S([randn((2, 1, 3), 25)], {"axis": 1},
                     ref=lambda x: x.squeeze(1))
SPECS["depth_to_space"] = S(
    [randn((1, 8, 2, 2), 26)], {"block_size": 2},
    ref=lambda x: x.reshape(1, 2, 2, 2, 2, 2).transpose(0, 3, 4, 1, 5, 2)
    .reshape(1, 2, 4, 4))
SPECS["space_to_depth"] = S(
    [randn((1, 2, 4, 4), 27)], {"block_size": 2},
    ref=lambda x: x.reshape(1, 2, 2, 2, 2, 2).transpose(0, 3, 5, 1, 2, 4)
    .reshape(1, 8, 2, 2))
SPECS["broadcast_to"] = S([randn((1, 3), 28)], {"shape": (4, 3)},
                          ref=lambda x: np.broadcast_to(x, (4, 3)))
SPECS["broadcast_like"] = S([randn((1, 3), 29), randn((4, 3), 30)],
                            ref=lambda a, b: np.broadcast_to(a, (4, 3)))
SPECS["broadcast_axis"] = S([randn((1, 3), 31)], {"axis": 0, "size": 4},
                            ref=lambda x: np.broadcast_to(x, (4, 3)))
SPECS["tile"] = S([randn((2, 3), 32)], {"reps": (2, 2)},
                  ref=lambda x: np.tile(x, (2, 2)), grad=True)
SPECS["repeat"] = S([randn((2, 3), 33)], {"repeats": 2, "axis": 1},
                    ref=lambda x: np.repeat(x, 2, 1))
SPECS["reverse"] = S([randn((2, 3), 34)], {"axis": 1},
                     ref=lambda x: x[:, ::-1])
SPECS["concat"] = S([randn((2, 2), 35), randn((2, 3), 36)], {"dim": 1},
                    ref=lambda a, b: np.concatenate([a, b], 1))
SPECS["stack"] = S([randn((2, 3), 37), randn((2, 3), 38)], {"axis": 1},
                   ref=lambda a, b: np.stack([a, b], 1))
SPECS["split"] = S([randn((2, 4), 39)], {"num_outputs": 2, "axis": 1},
                   ref=lambda x: (x[:, :2], x[:, 2:]))
SPECS["split_v2"] = S([randn((6, 2), 40)], {"indices": (2, 5), "axis": 0},
                      ref=lambda x: (x[:2], x[2:5], x[5:]))
SPECS["slice"] = S([randn((4, 5), 41)], {"begin": (1, 0), "end": (3, 4)},
                   ref=lambda x: x[1:3, 0:4], grad=True)
SPECS["slice_axis"] = S([randn((4, 5), 42)],
                        {"axis": 1, "begin": 1, "end": 4},
                        ref=lambda x: x[:, 1:4])
SPECS["slice_like"] = S([randn((4, 5), 43), randn((2, 3), 44)],
                        ref=lambda a, b: a[:2, :3])
SPECS["pad"] = S([randn((1, 1, 2, 3), 45)],
                 {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 2, 2),
                  "constant_value": 0.5},
                 ref=lambda x: np.pad(x, ((0, 0), (0, 0), (1, 1), (2, 2)),
                                      constant_values=0.5))
SPECS["clip"] = S([randn((3, 3), 46)], {"a_min": -0.5, "a_max": 0.5},
                  ref=lambda x: np.clip(x, -0.5, 0.5), grad=True)
SPECS["diag"] = S([randn((3, 3), 47)], {"k": 1},
                  ref=lambda x: np.diag(x, 1))

# ---------------------------------------------------------------------------
# indexing / gather / scatter / selection
# ---------------------------------------------------------------------------
_idx = np.array([2, 0, 1], np.int32)
SPECS["take"] = S([randn((4, 3), 48), _idx], {"axis": 0},
                  ref=lambda a, i: a[i], grad=True, grad_nodes=["v0"])
SPECS["pick"] = S([randn((3, 4), 49), np.array([0, 3, 1], np.int32)],
                  {"axis": 1},
                  ref=lambda a, i: a[np.arange(3), i])
SPECS["gather_nd"] = S(
    [randn((3, 4), 50), np.array([[0, 2], [1, 3]], np.int32)],
    ref=lambda a, i: a[i[0], i[1]])
SPECS["scatter_nd"] = S(
    [np.array([9.0, 8.0], np.float32),
     np.array([[0, 2], [1, 3]], np.int32)],
    {"shape": (3, 4)},
    ref=lambda d, i: _scatter_ref(d, i, (3, 4)))


def _scatter_ref(d, i, shape):
    out = np.zeros(shape, np.float32)
    out[tuple(i)] = d
    return out


SPECS["_scatter_set_nd"] = S(
    [np.zeros((3, 4), np.float32), np.array([9.0, 8.0], np.float32),
     np.array([[0, 2], [1, 3]], np.int32)],
    {"shape": (3, 4)},
    ref=lambda l, r, i: _scatter_ref(r, i, (3, 4)))
SPECS["one_hot"] = S([np.array([1, 0, 2], np.int32)], {"depth": 4},
                     ref=lambda i: np.eye(4, dtype=np.float32)[i])
SPECS["where"] = S([np.array([1, 0, 1], np.float32),
                    randn((3,), 51), randn((3,), 52)],
                   ref=lambda c, x, y: np.where(c != 0, x, y))
SPECS["boolean_mask_fill"] = S(
    [randn((3, 2), 53), np.array([1, 0, 1], np.float32)],
    {"value": -1.0},
    ref=lambda d, m: np.where((m != 0)[:, None], d, -1.0))
SPECS["sort"] = S([randn((3, 4), 54)], {"axis": 1},
                  ref=lambda x: np.sort(x, 1))
SPECS["argsort"] = S([randn((3, 4), 55)], {"axis": 1},
                     ref=lambda x: np.argsort(x, 1,
                                              kind="stable").astype(np.float32))
SPECS["topk"] = S([randn((3, 5), 56)], {"axis": 1, "k": 2},
                  ref=lambda x: np.argsort(-x, 1)[:, :2].astype(np.float32))
SPECS["_contrib_index_copy"] = S(
    [np.zeros((4, 2), np.float32), np.array([1, 3], np.int32),
     np.ones((2, 2), np.float32)],
    ref=lambda o, i, n: _index_copy_ref(o, i, n))


def _index_copy_ref(o, i, n):
    out = o.copy()
    out[i] = n
    return out


# ---------------------------------------------------------------------------
# creation ops (no tensor inputs)
# ---------------------------------------------------------------------------
SPECS["_zeros"] = S([], {"shape": (2, 3)}, ref=lambda: np.zeros((2, 3)))
SPECS["_ones"] = S([], {"shape": (2, 3)}, ref=lambda: np.ones((2, 3)))
SPECS["_full"] = S([], {"shape": (2, 3), "value": 2.5},
                   ref=lambda: np.full((2, 3), 2.5, np.float32))
SPECS["_eye"] = S([], {"N": 3, "M": 4, "k": 1},
                  ref=lambda: np.eye(3, 4, 1, dtype=np.float32))
SPECS["_arange"] = S([], {"start": 1.0, "stop": 7.0, "step": 2.0},
                     ref=lambda: np.arange(1.0, 7.0, 2.0, np.float32))
SPECS["_linspace"] = S([], {"start": 0.0, "stop": 1.0, "num": 5},
                       ref=lambda: np.linspace(0, 1, 5, dtype=np.float32))
SPECS["zeros_like"] = S([randn((2, 3), 57)], ref=np.zeros_like)
SPECS["ones_like"] = S([randn((2, 3), 58)], ref=np.ones_like)
SPECS["full_like"] = S([randn((2, 3), 59)], {"fill_value": 3.0},
                       ref=lambda x: np.full_like(x, 3.0))
SPECS["_contrib_arange_like"] = S(
    [randn((2, 3), 60)], {"axis": None},
    ref=lambda x: np.arange(6, dtype=np.float32).reshape(2, 3))
SPECS["shape_array"] = S([randn((2, 3), 61)],
                         ref=lambda x: np.array([2, 3], np.int64))
SPECS["size_array"] = S([randn((2, 3), 62)],
                        ref=lambda x: np.array([6], np.int64))
SPECS["cast"] = S([randn((2, 3), 63)], {"dtype": "int32"},
                  ref=lambda x: x.astype(np.int32))
SPECS["amp_cast"] = S([randn((2, 3), 64)], {"dtype": "float16"},
                      ref=lambda x: x.astype(np.float16), rtol=1e-2,
                      atol=1e-2)
SPECS["amp_multicast"] = S(
    [randn((2, 2), 65), randn((2, 2), 66).astype(np.float16)],
    {"num_outputs": 2},
    check=lambda outs, ins: all(o.dtype == np.float32 for o in outs))

# ---------------------------------------------------------------------------
# linear algebra
# ---------------------------------------------------------------------------
SPECS["dot"] = S([randn((2, 3), 67), randn((3, 4), 68)],
                 ref=lambda a, b: a @ b, grad=True)
SPECS["batch_dot"] = S([randn((2, 2, 3), 69), randn((2, 3, 2), 70)],
                       ref=lambda a, b: a @ b, grad=True)
SPECS["_npi_einsum"] = S(
    [randn((2, 3), 71), randn((3, 4), 72)], {"subscripts": "ij,jk->ik"},
    ref=lambda a, b: np.einsum("ij,jk->ik", a, b), grad=True)
SPECS["khatri_rao"] = S(
    [randn((2, 3), 73), randn((4, 3), 74)],
    ref=lambda a, b: np.vstack([np.kron(a[:, j], b[:, j])
                                for j in range(3)]).T)
SPECS["_linalg_gemm2"] = S(
    [randn((2, 3), 75), randn((3, 4), 76)], {"alpha": 2.0},
    ref=lambda a, b: 2.0 * (a @ b), grad=True)
SPECS["_linalg_gemm"] = S(
    [randn((2, 3), 77), randn((3, 4), 78), randn((2, 4), 79)],
    {"alpha": 1.5, "beta": 0.5},
    ref=lambda a, b, c: 1.5 * (a @ b) + 0.5 * c, grad=True)
SPECS["_linalg_syrk"] = S([randn((2, 3), 80)], {"alpha": 1.0},
                          ref=lambda a: a @ a.T, grad=True)
_spd = randn((3, 3), 81) @ randn((3, 3), 81).T + 3 * np.eye(3, dtype=np.float32)
SPECS["_linalg_potrf"] = S([_spd], ref=np.linalg.cholesky, grad=True,
                           grad_rtol=8e-2)
_tri = np.tril(pos((3, 3), 82)) + np.eye(3, dtype=np.float32)
SPECS["_linalg_trsm"] = S(
    [_tri, randn((3, 2), 83)],
    ref=lambda a, b: np.linalg.solve(a, b), grad=True)
SPECS["_linalg_sumlogdiag"] = S([_spd], ref=lambda a: np.log(np.diag(a)).sum(),
                                grad=True)
SPECS["_linalg_extractdiag"] = S([randn((3, 3), 84)],
                                 ref=lambda a: np.diag(a))
SPECS["_linalg_makediag"] = S([randn((3,), 85)], ref=np.diag)
SPECS["_linalg_det"] = S([_spd], ref=np.linalg.det, grad=True, rtol=1e-3,
                         atol=1e-3)
SPECS["_linalg_inverse"] = S([_spd], ref=np.linalg.inv, grad=True,
                             rtol=1e-3, atol=1e-3)
SPECS["_linalg_svd"] = S(
    [randn((2, 3), 86)],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]) @ np.diag(np.asarray(outs[1]))
        @ np.asarray(outs[2]),
        ins[0], atol=1e-4))

# ---------------------------------------------------------------------------
# neural network ops
# ---------------------------------------------------------------------------
SPECS["FullyConnected"] = S(
    [randn((2, 4), 87), randn((3, 4), 88), randn((3,), 89)],
    {"num_hidden": 3},
    ref=lambda x, w, b: x @ w.T + b, grad=True)
SPECS["Convolution"] = S(
    [randn((1, 2, 5, 5), 90), randn((3, 2, 3, 3), 91), randn((3,), 92)],
    {"kernel": (3, 3), "num_filter": 3, "pad": (1, 1)},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 3, 5, 5),
    grad=True)
SPECS["Deconvolution"] = S(
    [randn((1, 3, 3, 3), 93), randn((3, 2, 2, 2), 94)],
    {"kernel": (2, 2), "num_filter": 2},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 2, 4, 4),
    grad=True)
SPECS["Pooling"] = [
    S([randn((1, 2, 4, 4), 95)],
      {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
      ref=lambda x: x.reshape(1, 2, 2, 2, 2, 2).max((3, 5)), grad=True),
    S([randn((1, 2, 4, 4), 96)],
      {"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"},
      ref=lambda x: x.reshape(1, 2, 2, 2, 2, 2).mean((3, 5)), grad=True),
]


def _bn_predict_ref(x, g, b, mm, mv):
    return (x - mm[None, :, None, None]) / np.sqrt(
        mv[None, :, None, None] + 1e-3) * g[None, :, None, None] \
        + b[None, :, None, None]


SPECS["BatchNorm"] = S(
    [randn((2, 3, 2, 2), 97), pos((3,), 98), randn((3,), 99),
     randn((3,), 100), pos((3,), 101)],
    {"fix_gamma": False},
    ref=_bn_predict_ref, rtol=1e-3, atol=1e-4)


def _ln_ref(x, g, b):
    m = x.mean(-1, keepdims=True)
    v = x.var(-1, keepdims=True)
    return (x - m) / np.sqrt(v + 1e-5) * g + b


SPECS["LayerNorm"] = S(
    [randn((2, 4), 102), pos((4,), 103), randn((4,), 104)],
    ref=_ln_ref, rtol=1e-3, atol=1e-4, grad=True, grad_rtol=8e-2)


def _in_ref(x, g, b):
    m = x.mean((2, 3), keepdims=True)
    v = x.var((2, 3), keepdims=True)
    return (x - m) / np.sqrt(v + 1e-3) * g[None, :, None, None] \
        + b[None, :, None, None]


SPECS["InstanceNorm"] = S(
    [randn((2, 2, 3, 3), 105), pos((2,), 106), randn((2,), 107)],
    ref=_in_ref, rtol=1e-3, atol=1e-4)


def _gn_ref(x, g, b):
    n, c, h, w = x.shape
    xr = x.reshape(n, 2, c // 2, h, w)
    m = xr.mean((2, 3, 4), keepdims=True)
    v = xr.var((2, 3, 4), keepdims=True)
    out = ((xr - m) / np.sqrt(v + 1e-5)).reshape(n, c, h, w)
    return out * g[None, :, None, None] + b[None, :, None, None]


SPECS["GroupNorm"] = S(
    [randn((2, 4, 3, 3), 108), pos((4,), 109), randn((4,), 110)],
    {"num_groups": 2}, ref=_gn_ref, rtol=1e-3, atol=1e-4)
SPECS["RMSNorm"] = S(
    [randn((2, 4), 111), pos((4,), 112)],
    ref=lambda x, g: x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g,
    rtol=1e-3, atol=1e-4, grad=True)
SPECS["L2Normalization"] = S(
    [randn((2, 4), 113)],
    ref=lambda x: x / np.sqrt((x ** 2).sum(1, keepdims=True) + 1e-10),
    grad=True)
SPECS["Activation"] = S(
    [randn((2, 3), 114)], {"act_type": "softrelu"},
    ref=lambda x: np.log1p(np.exp(x)), grad=True)
SPECS["LeakyReLU"] = S(
    [randn((2, 3), 115)], {"act_type": "leaky", "slope": 0.25},
    ref=lambda x: np.where(x > 0, x, 0.25 * x))


def _softmax_ref(x, axis=-1):
    e = np.exp(x - x.max(axis, keepdims=True))
    return e / e.sum(axis, keepdims=True)


SPECS["softmax"] = S([randn((2, 4), 116)], ref=_softmax_ref, grad=True)
SPECS["log_softmax"] = S([randn((2, 4), 117)],
                         ref=lambda x: np.log(_softmax_ref(x)), grad=True)
SPECS["softmin"] = S([randn((2, 4), 118)],
                     ref=lambda x: _softmax_ref(-x), grad=True)
SPECS["SoftmaxActivation"] = S([randn((2, 4), 119)], ref=_softmax_ref)
SPECS["SoftmaxOutput"] = S(
    [randn((2, 4), 120), np.array([1.0, 3.0], np.float32)],
    ref=lambda x, y: _softmax_ref(x))
SPECS["smooth_l1"] = S(
    [randn((2, 3), 121, scale=2.0)], {"scalar": 1.0},
    ref=lambda x: np.where(np.abs(x) < 1, 0.5 * x ** 2, np.abs(x) - 0.5),
    grad=True)
SPECS["softmax_cross_entropy"] = S(
    [randn((3, 4), 122), np.array([0, 2, 1], np.float32)],
    ref=lambda x, y: np.array(
        -np.log(_softmax_ref(x))[np.arange(3), y.astype(int)].sum(),
        np.float32))
SPECS["Embedding"] = S(
    [np.array([1, 0, 2], np.int32), randn((4, 3), 123)],
    {"input_dim": 4, "output_dim": 3},
    ref=lambda i, w: w[i], grad=True, grad_nodes=["v1"])
SPECS["UpSampling"] = S(
    [randn((1, 2, 2, 2), 124)], {"scale": 2, "sample_type": "nearest"},
    ref=lambda x: x.repeat(2, 2).repeat(2, 3))


def _bilinear_identity_grid(n, h, w):
    ys = np.linspace(-1, 1, h, dtype=np.float32)
    xs = np.linspace(-1, 1, w, dtype=np.float32)
    gx, gy = np.meshgrid(xs, ys)
    return np.broadcast_to(np.stack([gx, gy])[None], (n, 2, h, w)).copy()


SPECS["BilinearSampler"] = S(
    [randn((1, 1, 3, 3), 125), _bilinear_identity_grid(1, 3, 3)],
    ref=lambda x, g: x, rtol=1e-3, atol=1e-4)

_seq = randn((3, 2, 2), 126)  # (T, N, C)
_seqlen = np.array([2, 3], np.float32)
SPECS["SequenceMask"] = S(
    [_seq, _seqlen], {"use_sequence_length": True, "value": -1.0},
    ref=lambda d, l: np.where(
        (np.arange(3)[:, None] < l[None, :])[:, :, None], d, -1.0))
SPECS["SequenceLast"] = S(
    [_seq, _seqlen], {"use_sequence_length": True},
    ref=lambda d, l: d[l.astype(int) - 1, np.arange(2)])
SPECS["SequenceReverse"] = S(
    [_seq, _seqlen], {"use_sequence_length": True},
    ref=lambda d, l: _seqrev_ref(d, l))


def _seqrev_ref(d, l):
    out = d.copy()
    for b in range(d.shape[1]):
        n = int(l[b])
        out[:n, b] = d[:n, b][::-1]
    return out


# ---------------------------------------------------------------------------
# contrib
# ---------------------------------------------------------------------------
SPECS["_contrib_div_sqrt_dim"] = S(
    [randn((2, 4), 127)], ref=lambda x: x / np.sqrt(4.0))
SPECS["_contrib_gradientmultiplier"] = S(
    [randn((2, 3), 128)], {"scalar": 0.5}, ref=lambda x: x)
SPECS["_contrib_index_array"] = S(
    [randn((2, 3), 129)],
    ref=lambda x: np.stack(np.meshgrid(np.arange(2), np.arange(3),
                                       indexing="ij"), -1).astype(np.int64))
SPECS["_contrib_getnnz"] = S(
    [np.array([[1.0, 0.0], [0.0, 2.0]], np.float32)],
    ref=lambda x: np.array(2, np.int64))
_boxes_a = np.array([[0, 0, 2, 2], [1, 1, 3, 3]], np.float32)
_boxes_b = np.array([[0, 0, 2, 2]], np.float32)
SPECS["_contrib_box_iou"] = S(
    [_boxes_a, _boxes_b],
    ref=lambda a, b: np.array([[1.0], [1.0 / 7.0]], np.float32))
SPECS["_contrib_box_nms"] = S(
    [np.array([[[0, 0.9, 0, 0, 2, 2], [1, 0.8, 0, 0, 2, 2],
                [2, 0.7, 5, 5, 7, 7]]], np.float32)],
    {"overlap_thresh": 0.5},
    check=lambda outs, ins: (np.asarray(outs[0]).shape == (1, 3, 6)
                             and np.asarray(outs[0])[0, 1, 1] == -1.0))
_fft_in = randn((2, 4), 130)
SPECS["_contrib_fft"] = S(
    [_fft_in],
    ref=lambda x: np.stack([np.fft.fft(x).real, np.fft.fft(x).imag],
                           -1).reshape(2, 8).astype(np.float32),
    rtol=1e-3, atol=1e-4)
_fft_out = np.stack([np.fft.fft(_fft_in).real, np.fft.fft(_fft_in).imag],
                    -1).reshape(2, 8).astype(np.float32)
SPECS["_contrib_ifft"] = S(
    [_fft_out], ref=lambda x: _fft_in * 4.0, rtol=1e-3, atol=1e-4)
SPECS["_contrib_quantize"] = S(
    [randn((2, 3), 131), np.array(-2.0, np.float32),
     np.array(2.0, np.float32)],
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.uint8)
_qdata = np.array([[0, 128, 255]], np.uint8)
SPECS["_contrib_dequantize"] = S(
    [_qdata, np.array(-1.0, np.float32), np.array(1.0, np.float32)],
    ref=lambda q, lo, hi: (q.astype(np.float32) / 255.0) * 2.0 - 1.0,
    rtol=1e-2, atol=1e-2)
SPECS["_contrib_count_sketch"] = S(
    [randn((2, 4), 132), np.array([0, 2, 1, 2], np.float32),
     np.array([1, -1, 1, 1], np.float32)],
    {"out_dim": 3},
    ref=lambda d, h, s: _count_sketch_ref(d, h, s, 3))


def _count_sketch_ref(d, h, s, out_dim):
    out = np.zeros(d.shape[:-1] + (out_dim,), np.float32)
    for j in range(d.shape[-1]):
        out[..., int(h[j])] += d[..., j] * s[j]
    return out


def _selfatt_qk_ref(qkv, heads):
    # qkv: (T, N, 3*H*D) interleaved per head → (N*H, T, T) scores
    t, n, c = qkv.shape
    d = c // (3 * heads)
    proj = qkv.reshape(t, n, heads, 3, d)
    q = proj[:, :, :, 0]
    k = proj[:, :, :, 1]
    q = q.transpose(1, 2, 0, 3).reshape(n * heads, t, d)
    k = k.transpose(1, 2, 0, 3).reshape(n * heads, t, d)
    return (q / np.sqrt(d)) @ k.transpose(0, 2, 1)


SPECS["_contrib_interleaved_matmul_selfatt_qk"] = S(
    [randn((3, 2, 12), 133)], {"heads": 2},
    ref=lambda qkv: _selfatt_qk_ref(qkv, 2), rtol=1e-3, atol=1e-4)


def _selfatt_valatt_ref(qkv, att, heads):
    t, n, c = qkv.shape
    d = c // (3 * heads)
    proj = qkv.reshape(t, n, heads, 3, d)
    v = proj[:, :, :, 2].transpose(1, 2, 0, 3).reshape(n * heads, t, d)
    out = att @ v  # (N*H, T, D)
    return out.reshape(n, heads, t, d).transpose(2, 0, 1, 3).reshape(
        t, n, heads * d)


_qkv = randn((3, 2, 12), 134)
_att = _softmax_ref(_selfatt_qk_ref(_qkv, 2))
SPECS["_contrib_interleaved_matmul_selfatt_valatt"] = S(
    [_qkv, _att.astype(np.float32)], {"heads": 2},
    ref=lambda qkv, att: _selfatt_valatt_ref(qkv, att, 2),
    rtol=1e-3, atol=1e-4)


def _flash_ref(q, k, v):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = _softmax_ref(s)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


SPECS["_contrib_flash_attention"] = S(
    [randn((1, 2, 16, 4), 135), randn((1, 2, 16, 4), 136),
     randn((1, 2, 16, 4), 137)],
    {"block_q": 8, "block_k": 8},
    ref=_flash_ref, rtol=1e-3, atol=1e-4)


def _causal_conv_ref(x, w, b):
    k = w.shape[1]
    xp = np.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + x.shape[1]] * w[:, j] for j in range(k)) + b


SPECS["_contrib_causal_conv1d"] = S(
    [randn((2, 7, 3), 140), randn((3, 4), 141), randn((3,), 142)],
    ref=_causal_conv_ref, grad=True)


def _ssd_ref(x, dt, a_log, bm, cm, d, dt_bias):
    """Mamba-2's recurrence, one token at a time."""
    b, t, h, p = x.shape
    rep = h // bm.shape[2]
    dt = np.log1p(np.exp(dt + dt_bias.reshape(h)))
    a = -np.exp(a_log.reshape(h))
    state = np.zeros((b, h, p, bm.shape[3]))
    y = np.zeros(x.shape)
    for i in range(t):
        bi, ci = np.repeat(bm[:, i], rep, 1), np.repeat(cm[:, i], rep, 1)
        state = state * np.exp(dt[:, i] * a)[..., None, None] \
            + (dt[:, i, :, None] * x[:, i])[..., None] * bi[:, :, None, :]
        y[:, i] = np.einsum("bhpn,bhn->bhp", state, ci) \
            + x[:, i] * d[:, None]
    return y


SPECS["_contrib_ssd_scan"] = S(
    [randn((1, 10, 4, 3), 143), randn((1, 10, 4), 144),
     randn((1, 4), 145, 0.3), randn((1, 10, 2, 5), 146),
     randn((1, 10, 2, 5), 147), randn((4,), 148), randn((1, 4), 149, 0.3)],
    {"chunk": 4}, ref=_ssd_ref, rtol=1e-3, atol=1e-4)


def _delta_rule_ref(q, k, v, g, beta):
    """The gated delta rule, one token at a time."""
    b, t, h, d = q.shape
    state = np.zeros((b, h, d, v.shape[-1]))
    o = np.zeros(v.shape)
    for i in range(t):
        state = state * np.exp(g[:, i])[..., None]
        old = np.einsum("bhde,bhd->bhe", state, k[:, i])
        state = state + (beta[:, i, :, None] * k[:, i])[..., None] \
            * (v[:, i] - old)[..., None, :]
        o[:, i] = np.einsum("bhde,bhd->bhe", state, q[:, i])
    return o


SPECS["_contrib_kda_scan"] = S(
    [randn((1, 21, 2, 4), 160, 0.5), randn((1, 21, 2, 4), 161, 0.5),
     randn((1, 21, 2, 3), 162), -pos((1, 21, 2, 4), 163),
     pos((1, 21, 2), 164) / 1.5],
    {"chunk": 16}, ref=_delta_rule_ref, rtol=1e-3, atol=1e-4)


def _kda_attention_ref(q, k, v, decay, beta, gate, qc, kc, vc, a_log,
                       dt_bias, o_norm):
    """A KDA mixer between its projections: convolutions, norms, the
    decay a channel, the delta rule, the head-wise norm and the gate."""
    b, t, inner = q.shape
    h = a_log.size
    d = inner // h

    def mixed(x, w):
        x = _causal_conv_ref(x, w, 0.0)
        return (x / (1 + np.exp(-x))).reshape(b, t, h, d)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    g = -np.exp(a_log.reshape(h, 1)) * np.log1p(np.exp(
        decay + dt_bias.reshape(-1))).reshape(b, t, h, d)
    o = _delta_rule_ref(unit(mixed(q, qc)) * d ** -0.5, unit(mixed(k, kc)),
                        mixed(v, vc), g, 1 / (1 + np.exp(-beta)))
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * o_norm
    return (o / (1 + np.exp(-gate.reshape(b, t, h, d)))).reshape(b, t, inner)


SPECS["_contrib_kda_attention"] = S(
    [randn((1, 19, 8), 165), randn((1, 19, 8), 166), randn((1, 19, 8), 167),
     randn((1, 19, 8), 168), randn((1, 19, 2), 169), randn((1, 19, 8), 170),
     randn((8, 4), 171, 0.5), randn((8, 4), 172, 0.5),
     randn((8, 4), 173, 0.5), randn((1, 2), 174, 0.3),
     randn((2, 4), 175, 0.3), pos((4,), 176)],
    {"chunk": 16}, ref=_kda_attention_ref, rtol=1e-3, atol=1e-4)


def _gdn_attention_ref(qkvz, ba, conv, a_log, dt_bias, o_norm):
    """A Gated DeltaNet mixer between its projections: one convolution over
    ``[q | k | v]``, a decay a head, value head ``j`` reading key head ``j //
    (H_v / H_k)``, the head-wise norm and ``silu(z)``."""
    b, t, _ = qkvz.shape
    hv, d = a_log.size, o_norm.size
    hk = (qkvz.shape[-1] - 2 * hv * d) // (2 * d)
    x = _causal_conv_ref(qkvz[..., :2 * hk * d + hv * d], conv, 0.0)
    x = x / (1 + np.exp(-x))

    def key_heads(y):
        y = y.reshape(b, t, hk, d)
        y = y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
        return np.repeat(y, hv // hk, axis=2)
    g = -np.exp(a_log.reshape(hv)) * np.log1p(np.exp(
        ba[..., hv:] + dt_bias.reshape(hv)))
    o = _delta_rule_ref(key_heads(x[..., :hk * d]) * d ** -0.5,
                        key_heads(x[..., hk * d:2 * hk * d]),
                        x[..., 2 * hk * d:].reshape(b, t, hv, d),
                        np.broadcast_to(g[..., None], (b, t, hv, d)),
                        1 / (1 + np.exp(-ba[..., :hv])))
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * o_norm
    z = qkvz[..., 2 * hk * d + hv * d:].reshape(b, t, hv, d)
    return (o * z / (1 + np.exp(-z))).reshape(b, t, hv * d)


SPECS["_contrib_gdn_attention"] = S(
    [randn((1, 19, 24), 180), randn((1, 19, 4), 181),
     randn((16, 4), 182, 0.5), randn((1, 2), 183, 0.3),
     randn((1, 2), 184, 0.3), pos((4,), 185)],
    {"chunk": 16}, ref=_gdn_attention_ref, rtol=1e-3, atol=1e-4)


def _rotary_ref(x):
    """Adjacent channel pairs of the last 4 of 6 channels turned at the
    row's position, theta 100."""
    t = x.shape[-2]
    phi = np.arange(t)[:, None] * 100.0 ** (-2.0 * np.arange(2) / 4)
    out = x.astype(np.float64).copy()
    a, b = x[..., 2::2], x[..., 3::2]
    out[..., 2::2] = a * np.cos(phi) - b * np.sin(phi)
    out[..., 3::2] = a * np.sin(phi) + b * np.cos(phi)
    return out


SPECS["_contrib_rotary_embedding"] = S(
    [randn((2, 3, 5, 6), 177)], {"theta": 100.0, "rotary_dim": 4},
    ref=_rotary_ref, grad=True)


def _mla_flash_ref(qn, qr, kv, kr):
    """Two heads of 128 + 64 / 128 channels over 128 tokens: the rope
    channels of every query head and of the one rope key turned as
    ``_rotary_ref`` turns them, causal softmax attention a head."""
    t, h, nope, rope = qn.shape[1], 2, 128, 64

    def turn(x):
        phi = np.arange(t)[:, None] * 100.0 ** (-2.0 * np.arange(rope // 2)
                                                / rope)
        a, b = x[..., 0::2], x[..., 1::2]
        out = np.empty(x.shape)
        out[..., 0::2] = a * np.cos(phi) - b * np.sin(phi)
        out[..., 1::2] = a * np.sin(phi) + b * np.cos(phi)
        return out
    qn, qr, kv = (x.astype(np.float64).reshape(t, h, -1)
                  for x in (qn, qr, kv))
    kr = turn(kr[0].astype(np.float64))
    mask = np.tril(np.ones((t, t), bool))
    out = np.empty((t, h, 128))
    for j in range(h):
        s = (qn[:, j] @ kv[:, j, :nope].T + turn(qr[:, j]) @ kr.T) \
            * (nope + rope) ** -0.5
        out[:, j] = _softmax_ref(np.where(mask, s, -np.inf)) @ kv[:, j, nope:]
    return out.reshape(1, t, h * 128)


SPECS["_contrib_mla_flash_attention"] = S(
    [randn((1, 128, 256), 178), randn((1, 128, 128), 179),
     randn((1, 128, 512), 180), randn((1, 128, 64), 181)],
    {"num_heads": 2, "rope_theta": 100.0, "block_q": 64, "block_k": 64},
    ref=_mla_flash_ref, rtol=1e-3, atol=1e-4)


def _bd_seen(t2, block):
    """Which key each query of ``[x_0 | x_t]`` sees under the
    block-diffusion mask: a clean query its block and the clean blocks
    before, a noised one the clean blocks strictly before its own and its
    own noised block."""
    half = t2 // 2
    i = np.arange(t2)
    noised, blk = i >= half, (i % half) // block
    return np.where(noised[None, :], noised[:, None]
                    & (blk[None, :] == blk[:, None]),
                    np.where(noised[:, None], blk[None, :] < blk[:, None],
                             blk[None, :] <= blk[:, None]))


def _bd_attention_ref(q, k, v):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(_bd_seen(q.shape[2], 2), s, -np.inf)
    return np.einsum("bhqk,bhkd->bhqd", _softmax_ref(s), v)


SPECS["_contrib_bd_attention"] = S(
    [randn((1, 2, 16, 4), 187), randn((1, 2, 16, 4), 188),
     randn((1, 2, 16, 4), 189)],
    {"block_length": 2},
    ref=_bd_attention_ref, grad=True, rtol=1e-3, atol=1e-4)


def _bd_flash_ref(q, k, v, q_norm, k_norm):
    """Two query heads over one key head of 128 channels, ``[x_0 | x_t]`` of
    512 positions each, blocks of 4: every head of q and k normed (eps
    1e-6) and turned by the rotation of halves at positions ``0 .. 511`` of
    each half, softmax attention under the block-diffusion mask."""
    t2, h, d = q.shape[1], 2, 128
    half = t2 // 2

    def normed_turned(x, gain):
        x = x.astype(np.float64).reshape(t2, -1, d)
        x = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * gain
        phi = (np.arange(t2) % half)[:, None, None] \
            * 100.0 ** (-2.0 * np.arange(d // 2) / d)
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * np.cos(phi) - b * np.sin(phi),
                               b * np.cos(phi) + a * np.sin(phi)], -1)
    qs, ks = normed_turned(q[0], q_norm), normed_turned(k[0], k_norm)[:, 0]
    seen = _bd_seen(t2, 4)
    out = np.empty((t2, h, d))
    for j in range(h):
        s = qs[:, j] @ ks.T * d ** -0.5
        out[:, j] = _softmax_ref(np.where(seen, s, -np.inf)) \
            @ v[0].astype(np.float64)
    return out.reshape(1, t2, h * d)


SPECS["_contrib_bd_flash_attention"] = S(
    [randn((1, 1024, 256), 182), randn((1, 1024, 128), 183),
     randn((1, 1024, 128), 184), pos((128,), 185), pos((128,), 186)],
    {"num_heads": 2, "block_length": 4, "rope_theta": 100.0},
    ref=_bd_flash_ref, rtol=1e-3, atol=1e-4)


def _router_ref(x, w, b):
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w.T)))
    idx = np.argsort(-(s + b), axis=1, kind="stable")[:, :2]
    picked = np.take_along_axis(s, idx, 1)
    return idx, 2.5 * picked / picked.sum(1, keepdims=True)


SPECS["_contrib_moe_router_topk"] = S(
    [randn((6, 5), 150), randn((8, 5), 151), randn((8,), 152, 0.1)],
    {"k": 2, "scale": 2.5}, ref=_router_ref)

def _bd_noise_check(outs, inputs):
    """Blocks of 4: one level a block in [1e-3, 1] from the key
    ``fold_in(PRNGKey(3), sum of the ids)``, a position masked where its
    own uniform draw is below its block's level, and then the mask id 9."""
    import jax

    toks = inputs[0].astype(np.int32)
    x_t, masked, level = (o.asnumpy() for o in outs)
    k_level, k_mask = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(3), int(toks.sum())))
    want = np.repeat(np.asarray(jax.random.uniform(
        k_level, (2, 3), minval=1e-3, maxval=1.0)), 4, axis=1)
    draw = np.asarray(jax.random.uniform(k_mask, (2, 12)))
    return (np.allclose(level, want) and np.array_equal(masked, draw < want)
            and np.array_equal(x_t, np.where(draw < want, 9, toks)))


SPECS["_contrib_block_diffusion_noise"] = S(
    [np.arange(24, dtype=np.float32).reshape(2, 12) % 9],
    {"block": 4, "mask_id": 9, "seed": 3}, check=_bd_noise_check)

_MOE_IDX = np.array([[2, 5], [3, 0], [7, 4], [2, 3], [4, 5], [1, 2]],
                    np.float32)


def _grouped_ffn_ref(x, idx, w, up, down):
    """Experts 2..4 of 8 are held: each one densely, weighted where
    chosen; the counts are rows landed, assignments, dropped, rows of
    the sorted layout walked (its one tile of 12)."""
    out = np.zeros(x.shape)
    for e in range(3):
        gate = (w * (idx == e + 2)).sum(1)
        hid = np.maximum(x @ up[e].T, 0.0) ** 2
        out += gate[:, None] * (hid @ down[e].T)
    return out, np.array([3, 2, 2, 12, 0, 12])


SPECS["_contrib_moe_grouped_ffn"] = S(
    [randn((6, 5), 153), _MOE_IDX, pos((6, 2), 154),
     randn((3, 4, 5), 155), randn((3, 5, 4), 156)],
    {"first": 2}, ref=_grouped_ffn_ref, rtol=1e-3, atol=1e-4)


def _paged_attn_ref(q, kp, vp, tbl, pos):
    b, k1, h, d = q.shape
    kv, s_page = kp.shape[1], kp.shape[2]           # pages (P, KV, S, D)
    grp, ctx = h // kv, tbl.shape[1] * s_page
    keys = kp[tbl].transpose(0, 1, 3, 2, 4).reshape(b, ctx, kv, d)
    vals = vp[tbl].transpose(0, 1, 3, 2, 4).reshape(b, ctx, kv, d)
    s = np.einsum("bkvgd,bcvd->bkvgc", q.reshape(b, k1, kv, grp, d),
                  keys) / np.sqrt(d)
    posk = pos[:, None] + np.arange(k1)[None, :]
    ok = (np.arange(ctx)[None, None, :] <= posk[..., None]) \
        & np.repeat(tbl != 0, s_page, axis=1)[:, None, :]
    s = np.where(ok[:, :, None, None, :], s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    p = np.exp(s - np.where(np.isfinite(m), m, 0.0))
    l = p.sum(-1, keepdims=True)
    att = np.einsum("bkvgc,bcvd->bkvgd",
                    p / np.where(l == 0, 1.0, l), vals)
    return att.reshape(b, k1, h, d).astype(np.float32)


# use_kernel=1 forces the Pallas kernel (interpreter on CPU): the sweep
# exercises the real kernel path, not the jnp reference it would pick on
# auto.  Table row 0 is the reserved null page — masked by contract.
SPECS["_contrib_paged_attention"] = S(
    [randn((2, 2, 4, 4), 138), randn((6, 2, 2, 4), 139),
     randn((6, 2, 2, 4), 140),
     np.array([[1, 2, 0], [3, 4, 5]], np.int32),
     np.array([2, 4], np.int32)],
    {"use_kernel": 1},
    ref=_paged_attn_ref, rtol=1e-3, atol=1e-4)

# ---------------------------------------------------------------------------
# optimizer update ops (golden numpy re-implementations)
# ---------------------------------------------------------------------------
_w, _g = pos((3, 2), 140), randn((3, 2), 141)
_m1, _v1 = randn((3, 2), 142, 0.1), pos((3, 2), 143, 0.01, 0.1)
SPECS["sgd_update"] = S(
    [_w, _g], {"lr": 0.1, "wd": 0.01},
    ref=lambda w, g: w - 0.1 * (g + 0.01 * w))
SPECS["sgd_mom_update"] = S(
    [_w, _g, _m1], {"lr": 0.1, "momentum": 0.9},
    ref=lambda w, g, m: (w + (0.9 * m - 0.1 * g), 0.9 * m - 0.1 * g))
SPECS["nag_mom_update"] = S(
    [_w, _g, _m1], {"lr": 0.1, "momentum": 0.9},
    ref=lambda w, g, m: (w - 0.1 * (g + 0.9 * (0.9 * m + g)),
                         0.9 * m + g))
SPECS["adam_update"] = S(
    [_w, _g, _m1, _v1], {"lr": 0.01},
    ref=lambda w, g, m, v: _adam_ref(w, g, m, v))


def _adam_ref(w, g, m, v, lr=0.01, b1=0.9, b2=0.999, eps=1e-8):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g ** 2
    return w - lr * m2 / (np.sqrt(v2) + eps), m2, v2


SPECS["adamw_update"] = S(
    [_w, _g, _m1, _v1], {"lr": 0.01, "wd": 0.01, "eta": 1.0},
    ref=lambda w, g, m, v: _adamw_ref(w, g, m, v))


def _adamw_ref(w, g, m, v, lr=0.01, b1=0.9, b2=0.999, eps=1e-8, wd=0.01):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g ** 2
    return w - (lr * m2 / (np.sqrt(v2) + eps) + wd * w), m2, v2


SPECS["rmsprop_update"] = S(
    [_w, _g, _v1], {"lr": 0.01, "gamma1": 0.9},
    ref=lambda w, g, n: (
        w - 0.01 * g / (np.sqrt(0.9 * n + 0.1 * g ** 2) + 1e-8),
        0.9 * n + 0.1 * g ** 2))
SPECS["rmspropalex_update"] = S(
    [_w, _g, _v1, _m1, randn((3, 2), 144, 0.01)],
    {"lr": 0.01},
    check=lambda outs, ins: all(np.isfinite(np.asarray(o)).all()
                                for o in outs))
SPECS["ftrl_update"] = S(
    [_w, _g, _m1, _v1], {"lr": 0.1},
    check=lambda outs, ins: all(np.isfinite(np.asarray(o)).all()
                                for o in outs))
SPECS["signsgd_update"] = S(
    [_w, _g], {"lr": 0.1}, ref=lambda w, g: w - 0.1 * np.sign(g))
SPECS["signum_update"] = S(
    [_w, _g, _m1], {"lr": 0.1, "momentum": 0.9},
    ref=lambda w, g, m: (w + 0.1 * np.sign(0.9 * m - 0.1 * g),
                         0.9 * m - 0.1 * g))
SPECS["lamb_update_phase1"] = S(
    [_w, _g, _m1, _v1], {"t": 1},
    ref=lambda w, g, m, v: _lamb1_ref(w, g, m, v))


def _lamb1_ref(w, g, m, v, b1=0.9, b2=0.999, eps=1e-6):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g ** 2
    mh = m2 / (1 - b1)
    vh = v2 / (1 - b2)
    return mh / (np.sqrt(vh) + eps)


SPECS["lamb_update_phase2"] = S(
    [_w, _g, np.array(2.0, np.float32), np.array(4.0, np.float32)],
    {"lr": 0.1},
    ref=lambda w, g, r1, r2: w - 0.1 * 0.5 * g)
SPECS["multi_sum_sq"] = S(
    [randn((2, 2), 145), randn((3,), 146)], {"num_arrays": 2},
    ref=lambda a, b: (np.sum(a ** 2), np.sum(b ** 2)))

# ---------------------------------------------------------------------------
# random ops (statistical / support checks; draws are threefry-stateless)
# ---------------------------------------------------------------------------


def _stat(lo=None, hi=None, dtype=None, integral=False):
    def chk(outs, ins):
        x = np.asarray(outs[0]).astype(np.float64)
        assert np.isfinite(x).all()
        if lo is not None:
            assert (x >= lo).all(), "values below support"
        if hi is not None:
            assert (x <= hi).all(), "values above support"
        if integral:
            assert np.allclose(x, np.round(x))
        return True
    return chk


_RSHAPE = {"shape": (200,)}
SPECS["_random_uniform"] = S([], dict(_RSHAPE, low=-1.0, high=2.0),
                             check=_stat(-1.0, 2.0))
SPECS["_random_normal"] = S([], dict(_RSHAPE, loc=1.0, scale=2.0),
                            check=_stat())
SPECS["_random_gamma"] = S([], dict(_RSHAPE, alpha=2.0, beta=1.0),
                           check=_stat(lo=0.0))
SPECS["_random_exponential"] = S([], dict(_RSHAPE, lam=2.0),
                                 check=_stat(lo=0.0))
SPECS["_random_poisson"] = S([], dict(_RSHAPE, lam=3.0),
                             check=_stat(lo=0.0, integral=True))
SPECS["_random_negative_binomial"] = S([], dict(_RSHAPE, k=3, p=0.5),
                                       check=_stat(lo=0.0, integral=True))
SPECS["_random_randint"] = S([], dict(_RSHAPE, low=2, high=9),
                             check=_stat(2, 8, integral=True))
SPECS["_random_bernoulli"] = S([], dict(_RSHAPE, prob=0.3),
                               check=_stat(0.0, 1.0, integral=True))
SPECS["_random_gumbel"] = S([], dict(_RSHAPE), check=_stat())

# _random_pdf_* family (pdf_op.cc:33-37): scipy forward oracles + FD grads
# wrt sample AND parameters (grads wrt sample skipped for discrete distrs,
# mirroring the reference test_random.py grad_nodes choice)
import scipy.stats as _ss  # noqa: E402

_PDF_X = np.abs(np.random.RandomState(3).randn(2, 5)).astype(np.float64) + 0.5
_PDF_K = np.round(np.abs(np.random.RandomState(4).randn(2, 5)) * 3) + 1.0
SPECS["_random_pdf_uniform"] = [
    S([_PDF_X, np.array([0.1, 0.2]), np.array([9.0, 8.0])], {},
      ref=lambda x, l, h: _ss.uniform.pdf(x, l[:, None], (h - l)[:, None]),
      grad=True),
    S([_PDF_X, np.array([0.1, 0.2]), np.array([9.0, 8.0])], {"is_log": True},
      ref=lambda x, l, h: _ss.uniform.logpdf(x, l[:, None], (h - l)[:, None])),
]
SPECS["_random_pdf_normal"] = [
    S([_PDF_X, np.array([0.5, 1.0]), np.array([1.0, 2.0])], {},
      ref=lambda x, u, s: _ss.norm.pdf(x, u[:, None], s[:, None]),
      grad=True),
    S([_PDF_X, np.array([0.5, 1.0]), np.array([1.0, 2.0])], {"is_log": True},
      ref=lambda x, u, s: _ss.norm.logpdf(x, u[:, None], s[:, None])),
]
SPECS["_random_pdf_gamma"] = [
    S([_PDF_X, np.array([2.0, 3.0]), np.array([1.0, 2.0])], {},
      ref=lambda x, a, b: _ss.gamma.pdf(x, a[:, None], 0, 1.0 / b[:, None]),
      grad=True),
    S([_PDF_X, np.array([2.0, 3.0]), np.array([1.0, 2.0])], {"is_log": True},
      ref=lambda x, a, b: _ss.gamma.logpdf(x, a[:, None], 0,
                                           1.0 / b[:, None])),
]
SPECS["_random_pdf_exponential"] = [
    S([_PDF_X, np.array([2.0, 0.5])], {},
      ref=lambda x, lam: _ss.expon.pdf(x, 0, 1.0 / lam[:, None]),
      grad=True),
    S([_PDF_X, np.array([2.0, 0.5])], {"is_log": True},
      ref=lambda x, lam: _ss.expon.logpdf(x, 0, 1.0 / lam[:, None])),
]
SPECS["_random_pdf_poisson"] = [
    S([_PDF_K, np.array([3.0, 1.5])], {},
      ref=lambda x, lam: _ss.poisson.pmf(x, lam[:, None]),
      grad=True, grad_nodes=["v1"]),
    S([_PDF_K, np.array([3.0, 1.5])], {"is_log": True},
      ref=lambda x, lam: _ss.poisson.logpmf(x, lam[:, None])),
]
SPECS["_random_pdf_negative_binomial"] = [
    S([_PDF_K, np.array([3.0, 2.0]), np.array([0.4, 0.6])], {},
      ref=lambda x, k, p: _ss.nbinom.pmf(x, k[:, None], p[:, None]),
      grad=True, grad_nodes=["v1", "v2"]),
    S([_PDF_K, np.array([3.0, 2.0]), np.array([0.4, 0.6])], {"is_log": True},
      ref=lambda x, k, p: _ss.nbinom.logpmf(x, k[:, None], p[:, None])),
]
SPECS["_random_pdf_generalized_negative_binomial"] = [
    S([_PDF_K, np.array([2.0, 3.0]), np.array([0.5, 0.25])], {},
      ref=lambda x, mu, a: _ss.nbinom.pmf(
          x, 1.0 / a[:, None], 1.0 / (mu * a + 1.0)[:, None]),
      grad=True, grad_nodes=["v1", "v2"]),
    S([_PDF_K, np.array([2.0, 3.0]), np.array([0.5, 0.25])],
      {"is_log": True},
      ref=lambda x, mu, a: _ss.nbinom.logpmf(
          x, 1.0 / a[:, None], 1.0 / (mu * a + 1.0)[:, None])),
]


def _dirichlet_ref(x, a, log=False):
    out = np.empty(x.shape[:-1])
    for i in range(x.shape[0]):
        for j in range(x.shape[1]):
            out[i, j] = _ss.dirichlet.logpdf(
                x[i, j] / x[i, j].sum(), a[i])
    return out if log else np.exp(out)


_DIR_A = np.array([[1.5, 2.0, 1.0], [2.5, 1.0, 3.0]])
_DIR_X = np.abs(np.random.RandomState(5).randn(2, 4, 3)) + 0.1
_DIR_X = _DIR_X / _DIR_X.sum(-1, keepdims=True)
SPECS["_random_pdf_dirichlet"] = [
    S([_DIR_X, _DIR_A], {}, ref=lambda x, a: _dirichlet_ref(x, a),
      grad=True),
    S([_DIR_X, _DIR_A], {"is_log": True},
      ref=lambda x, a: _dirichlet_ref(x, a, log=True)),
]
SPECS["_sample_uniform"] = S(
    [np.array([0.0, 5.0], np.float32), np.array([1.0, 6.0], np.float32)],
    {"shape": (40,)}, check=_stat(0.0, 6.0))
SPECS["_sample_normal"] = S(
    [np.array([0.0, 10.0], np.float32), np.array([1.0, 1.0], np.float32)],
    {"shape": (40,)}, check=_stat())
SPECS["_sample_gamma"] = S(
    [np.array([2.0, 3.0], np.float32), np.array([1.0, 1.0], np.float32)],
    {"shape": (40,)}, check=_stat(lo=0.0))
SPECS["_sample_multinomial"] = S(
    [np.array([[0.2, 0.8], [0.5, 0.5]], np.float32)], {"shape": (30,)},
    check=_stat(0, 1, integral=True))
SPECS["_shuffle"] = S(
    [np.arange(12, dtype=np.float32)],
    check=lambda outs, ins: np.array_equal(
        np.sort(np.asarray(outs[0])), ins[0]))
SPECS["Dropout"] = S(
    [pos((50,), 147)], {"p": 0.5},
    check=lambda outs, ins: np.isfinite(np.asarray(outs[0])).all())


# ---------------------------------------------------------------------------
# round-2 waves: numpy-internal (_np*/_npi_*/_npx_*) + misc ops
# ---------------------------------------------------------------------------
_A = randn((2, 3), 901)
_B = randn((2, 3), 902)
_P = pos((2, 3), 903)
_I = np.array([[1, 2, 3], [4, 5, 6]], np.float32)

_NPI_UNARY = {
    "_npi_log": (np.log, _P),
    "_npi_deg2rad": (np.deg2rad, _A),
    "_npi_rad2deg": (np.rad2deg, _A),
    "_npi_logical_not": (lambda x: np.logical_not(x), _A),
    "_npx_relu": (lambda x: np.maximum(x, 0), _A),
    "_npx_sigmoid": (lambda x: 1 / (1 + np.exp(-x)), _A),
    "_npi_around": (np.around, _A),
    "_npi_nan_to_num": (np.nan_to_num, _A),
    "_np_copy": (lambda x: x, _A),
    "_np_all": (lambda x: np.all(x), _A),
    "_np_any": (lambda x: np.any(x), _A),
    "_np_sum": (np.sum, _A),
    "_np_max": (np.max, _A),
    "_np_min": (np.min, _A),
    "_np_prod": (np.prod, _P),
    "_npi_mean": (np.mean, _A),
    "_npi_std": (np.std, _A),
    "_npi_var": (np.var, _A),
    "_np_cumsum": (lambda x: np.cumsum(x), _A),
    "_npi_argmax": (lambda x: np.argmax(x), _A),
    "_npi_argmin": (lambda x: np.argmin(x), _A),
    "_np_trace": (np.trace, _A),
    "_npi_tril": (np.tril, _A),
    "_np_transpose": (np.transpose, _A),
    "_np_squeeze": (np.squeeze, randn((2, 1, 3), 904)),
    "_npi_flip": (lambda x: np.flip(x), _A),
    "_np_diag": (np.diag, randn((3, 3), 905)),
    "_np_diagflat": (np.diagflat, _A),
    "_np_diagonal": (np.diagonal, randn((3, 3), 906)),
    "_npi_bitwise_not": (lambda x: np.bitwise_not(x.astype(np.int32)), _I),
}
for _n, (_ref, _inp) in _NPI_UNARY.items():
    SPECS[_n] = S([_inp], ref=_ref)

_NPI_BINARY = {
    "_npi_add": (np.add, _A, _B),
    "_npi_subtract": (np.subtract, _A, _B),
    "_npi_multiply": (np.multiply, _A, _B),
    "_npi_mod": (np.mod, _P, pos((2, 3), 907)),
    "_npi_power": (np.power, _P, _B),
    "_npi_copysign": (np.copysign, _A, _B),
    "_npi_arctan2": (np.arctan2, _A, _P),
    "_npi_hypot": (np.hypot, _A, _B),
    "_npi_true_divide": (np.true_divide, _A, _P),
    "_np_dot": (np.dot, randn((2, 4), 908), randn((4, 3), 909)),
    "_npi_ldexp": (lambda a, b: np.ldexp(a, b.astype(np.int32)), _A, _I),
    "_npi_bitwise_or": (lambda a, b: np.bitwise_or(
        a.astype(np.int32), b.astype(np.int32)), _I, _I + 1),
    "_npi_bitwise_xor": (lambda a, b: np.bitwise_xor(
        a.astype(np.int32), b.astype(np.int32)), _I, _I + 1),
    "_npi_lcm": (lambda a, b: np.lcm(a.astype(np.int32),
                                     b.astype(np.int32)), _I, _I + 1),
}
for _n, (_ref, _x, _y) in _NPI_BINARY.items():
    SPECS[_n] = S([_x, _y], ref=_ref)

_NPI_SCALAR = {
    "_npi_add_scalar": (lambda x: x + 2.0, _A),
    "_npi_subtract_scalar": (lambda x: x - 2.0, _A),
    "_npi_rsubtract_scalar": (lambda x: 2.0 - x, _A),
    "_npi_multiply_scalar": (lambda x: x * 2.0, _A),
    "_npi_mod_scalar": (lambda x: np.mod(x, 2.0), _P),
    "_npi_rmod_scalar": (lambda x: np.mod(2.0, x), _P),
    "_npi_power_scalar": (lambda x: np.power(x, 2.0), _P),
    "_npi_rpower_scalar": (lambda x: np.power(2.0, x), _A),
    "_npi_copysign_scalar": (lambda x: np.copysign(x, 2.0), _A),
    "_npi_rcopysign_scalar": (lambda x: np.copysign(2.0, x), _A),
    "_npi_arctan2_scalar": (lambda x: np.arctan2(x, 2.0), _A),
    "_npi_rarctan2_scalar": (lambda x: np.arctan2(2.0, x), _A),
    "_npi_true_divide_scalar": (lambda x: x / 2.0, _A),
    "_npi_rtrue_divide_scalar": (lambda x: 2.0 / x, _P),
    "_npi_lcm_scalar": (lambda x: np.lcm(x.astype(np.int32), 2), _I),
    "_npi_ldexp_scalar": (lambda x: np.ldexp(x, 2), _A),
    "_npi_rldexp_scalar": (lambda x: np.ldexp(2.0, x.astype(np.int32)), _I),
    "_npi_bitwise_or_scalar": (lambda x: np.bitwise_or(
        x.astype(np.int32), 2), _I),
    "_npi_bitwise_xor_scalar": (lambda x: np.bitwise_xor(
        x.astype(np.int32), 2), _I),
    "_hypot_scalar": (lambda x: np.hypot(x, 2.0), _A),
    "_scatter_plus_scalar": (lambda x: x + 2.0, _A),
    "_scatter_minus_scalar": (lambda x: x - 2.0, _A),
}
for _n, (_ref, _x) in _NPI_SCALAR.items():
    SPECS[_n] = S([_x], {"scalar": 2.0}, ref=_ref)

SPECS["_np_reshape"] = S([_A], {"newshape": (3, 2)},
                         ref=lambda x: x.reshape(3, 2))
SPECS["_npx_reshape"] = S([_A], {"newshape": (6,)},
                          ref=lambda x: x.reshape(6))
SPECS["_np_moveaxis"] = S([randn((2, 3, 4), 910)],
                          {"source": 0, "destination": 2},
                          ref=lambda x: np.moveaxis(x, 0, 2))
SPECS["_np_roll"] = S([_A], {"shift": 1},
                      ref=lambda x: np.roll(x, 1))
SPECS["_npi_rot90"] = S([_A], ref=lambda x: np.rot90(x))
SPECS["_npi_broadcast_to"] = S([randn((1, 3), 911)], {"shape": (2, 3)},
                               ref=lambda x: np.broadcast_to(x, (2, 3)))
SPECS["_npi_diff"] = S([_A], ref=lambda x: np.diff(x))
SPECS["_npi_bincount"] = S(
    [np.array([0, 1, 1, 2], np.float32)], {"minlength": 3},
    ref=lambda x: np.bincount(x.astype(np.int32), minlength=3))
SPECS["_npi_where"] = S([np.array([[1, 0, 1]], np.float32), _A[:1], _B[:1]],
                        ref=lambda c, x, y: np.where(c.astype(bool), x, y))
SPECS["_npi_boolean_mask_assign_scalar"] = S(
    [_A, np.array([[1, 0, 1], [0, 1, 0]], np.float32)], {"value": 7.0},
    ref=lambda d, m: np.where(m.astype(bool), 7.0, d))
SPECS["_npi_boolean_mask_assign_tensor"] = S(
    [_A, np.array([[1, 0, 1], [0, 1, 0]], np.float32), _B],
    ref=lambda d, m, v: np.where(m.astype(bool), v, d))
for _n, _npref in (("_npi_blackman", np.blackman),
                   ("_npi_hamming", np.hamming),
                   ("_npi_hanning", np.hanning)):
    SPECS[_n] = S([], {"M": 7},
                  ref=lambda _f=_npref: _f(7).astype(np.float32))
SPECS["_npi_zeros"] = S([], {"shape": (2, 3)},
                        ref=lambda: np.zeros((2, 3), np.float32))
SPECS["_npi_ones"] = S([], {"shape": (2, 3)},
                       ref=lambda: np.ones((2, 3), np.float32))
SPECS["_npi_identity"] = S([], {"shape": (3, 3)},
                           ref=lambda: np.eye(3, dtype=np.float32))
SPECS["_npi_eye"] = S([], {"N": 3, "M": 4, "k": 1},
                      ref=lambda: np.eye(3, 4, 1, dtype=np.float32))
SPECS["_npi_arange"] = S([], {"start": 1.0, "stop": 5.0, "step": 1.5},
                         ref=lambda: np.arange(1.0, 5.0, 1.5,
                                               dtype=np.float32))
SPECS["_npi_logspace"] = S([], {"start": 0.0, "stop": 2.0, "num": 5},
                           ref=lambda: np.logspace(0, 2, 5,
                                                   dtype=np.float32))
SPECS["_npi_indices"] = S([], {"dimensions": (2, 3)},
                          ref=lambda: np.indices((2, 3)).astype(np.int32))
SPECS["_npi_full_like"] = S([_A], {"fill_value": 3.5},
                            ref=lambda x: np.full_like(x, 3.5))
SPECS["_npi_concatenate"] = S([_A, _B], {"axis": 0, "num_args": 2},
                              ref=lambda a, b: np.concatenate([a, b], 0))
SPECS["_npi_stack"] = S([_A, _B], {"axis": 0, "num_args": 2},
                        ref=lambda a, b: np.stack([a, b], 0))
SPECS["_npi_vstack"] = S([_A, _B], {"num_args": 2},
                         ref=lambda a, b: np.vstack([a, b]))
SPECS["_npi_hstack"] = S([_A, _B], {"num_args": 2},
                         ref=lambda a, b: np.hstack([a, b]))
SPECS["_npi_dstack"] = S([_A, _B], {"num_args": 2},
                         ref=lambda a, b: np.dstack([a, b]))
SPECS["_npi_column_stack"] = S([_A, _B], {"num_args": 2},
                               ref=lambda a, b: np.column_stack([a, b]))
SPECS["_npi_hsplit"] = S(
    [randn((2, 4), 912)], {"sections": 2},
    ref=lambda x: tuple(np.hsplit(x, 2)))
_SPD = (lambda a: a @ a.T + 3 * np.eye(3, dtype=np.float32))(
    randn((3, 3), 913))
SPECS["_npi_cholesky"] = S([_SPD], ref=np.linalg.cholesky, atol=1e-4)
SPECS["_npi_solve"] = S([_SPD, randn((3, 2), 914)],
                        ref=np.linalg.solve, atol=1e-4)
SPECS["_npi_pinv"] = S([randn((3, 4), 915)], ref=np.linalg.pinv, atol=1e-4)
SPECS["_npi_pinv_scalar_rcond"] = S([randn((3, 4), 916)],
                                    {"rcond": 1e-10},
                                    ref=lambda x: np.linalg.pinv(
                                        x, rcond=1e-10), atol=1e-4)
SPECS["_npi_svd"] = S(
    [randn((3, 4), 917)],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]) @ np.diag(np.asarray(outs[1]))
        @ np.asarray(outs[2]), ins[0], atol=1e-4))
SPECS["_npi_tensordot"] = S(
    [randn((2, 3, 4), 918), randn((4, 3, 5), 919)],
    {"a_axes_summed": (1, 2), "b_axes_summed": (1, 0)},
    ref=lambda a, b: np.tensordot(a, b, axes=((1, 2), (1, 0))), atol=1e-4)
SPECS["_npi_tensordot_int_axes"] = S(
    [randn((2, 4), 920), randn((4, 3), 921)], {"axes": 1},
    ref=lambda a, b: np.tensordot(a, b, axes=1), atol=1e-4)
_KRON = np.einsum("ac,bd->abcd", np.eye(2, dtype=np.float32) * 2,
                  np.eye(2, dtype=np.float32))
SPECS["_npi_tensorinv"] = S(
    [_KRON], {"ind": 2},
    ref=lambda x: np.linalg.tensorinv(x, ind=2), atol=1e-4)
SPECS["_npi_tensorsolve"] = S(
    [_KRON, randn((2, 2), 922)],
    ref=lambda a, b: np.linalg.tensorsolve(a, b), atol=1e-4)
for _n in ("_np_atleast_1d", "_np_atleast_2d", "_np_atleast_3d"):
    SPECS[_n] = S([_A],
                  check=lambda outs, ins: np.asarray(outs[0]).ndim >= 1)
SPECS["_npi_average"] = S(
    [_A, pos((2, 3), 923)],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]),
        (ins[0] * ins[1]).sum() / ins[1].sum(), atol=1e-5))
SPECS["_npi_share_memory"] = S(
    [_A, _B], check=lambda outs, ins: True)
SPECS["_npx_constraint_check"] = S(
    [np.ones((3,), np.float32)],
    check=lambda outs, ins: bool(np.asarray(outs[0])))
SPECS["_npi_unique"] = S(
    [np.array([3.0, 1.0, 3.0, 2.0], np.float32)],
    ref=lambda x: np.unique(x))
SPECS["_npx_nonzero"] = S(
    [np.array([0.0, 1.0, 0.0, 2.0], np.float32)],
    ref=lambda x: np.stack(np.nonzero(x), -1).astype(np.int64))
SPECS["_npi_delete"] = S(
    [np.arange(5, dtype=np.float32)], {"int_ind": 2},
    ref=lambda x: np.delete(x, 2))
SPECS["_contrib_boolean_mask"] = S(
    [np.arange(8, dtype=np.float32).reshape(4, 2),
     np.array([1, 0, 1, 0], np.float32)],
    ref=lambda d, m: d[m.astype(bool)])

# random _npi samplers: moment checks
SPECS["_npi_uniform"] = S(
    [], {"low": 0.0, "high": 1.0, "size": (4000,)}, check=_stat(0.0, 1.0))
SPECS["_npi_normal"] = S(
    [], {"loc": 1.0, "scale": 2.0, "size": (4000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 1.0) < 0.2)
SPECS["_npi_bernoulli"] = S(
    [], {"prob": 0.3, "size": (4000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 0.3) < 0.05)
SPECS["_npi_exponential"] = S(
    [], {"scale": 2.0, "size": (4000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 2.0) < 0.3)
SPECS["_npi_gamma"] = S(
    [], {"shape": 2.0, "scale": 1.0, "size": (4000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 2.0) < 0.3)
SPECS["_npi_choice"] = S(
    [], {"a": 5, "size": (100,)},
    check=lambda outs, ins: np.asarray(outs[0]).max() < 5)
SPECS["_npi_multinomial"] = S(
    [np.array([0.2, 0.8], np.float32)], {"size": (100,)},
    check=lambda outs, ins: set(np.unique(np.asarray(outs[0]))) <= {0, 1})
SPECS["_sample_poisson"] = S(
    [np.array([4.0], np.float32)], {"shape": (2000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 4.0) < 0.5)
SPECS["_sample_exponential"] = S(
    [np.array([2.0], np.float32)], {"shape": (2000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 0.5) < 0.2)
SPECS["_sample_negative_binomial"] = S(
    [np.array([3.0], np.float32), np.array([0.5], np.float32)],
    {"shape": (2000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 3.0) < 0.8)
SPECS["_sample_generalized_negative_binomial"] = S(
    [np.array([3.0], np.float32), np.array([0.5], np.float32)],
    {"shape": (2000,)},
    check=lambda outs, ins: abs(np.asarray(outs[0]).mean() - 3.0) < 0.8)

# misc wave: direct specs
SPECS["add_n"] = S([_A, _B, _P], {"num_args": 3},
                   ref=lambda a, b, c: a + b + c)
SPECS["hard_sigmoid"] = S([_A], ref=lambda x: np.clip(0.2 * x + 0.5, 0, 1),
                          grad=True)
SPECS["moments"] = S([_A], {"axes": (1,)},
                     ref=lambda x: (x.mean(1), x.var(1)))
SPECS["_square_sum"] = S([_A], {"axis": 1}, ref=lambda x: (x ** 2).sum(1))
SPECS["_grad_add"] = S([_A, _B], ref=np.add)
SPECS["_zeros_without_dtype"] = S([], {"shape": (2, 2)},
                                  ref=lambda: np.zeros((2, 2), np.float32))
SPECS["_identity_with_attr_like_rhs"] = S([_A, _B], ref=lambda a, b: a)
SPECS["_rnn_param_concat"] = S([_A, _B], {"dim": 0, "num_args": 2},
                               ref=lambda a, b: np.concatenate([a, b], 0))
SPECS["batch_take"] = S(
    [_I, np.array([1, 0], np.float32)],
    ref=lambda a, i: a[np.arange(2), i.astype(np.int32)])
SPECS["_unravel_index"] = S(
    [np.array([5, 2], np.float32)], {"shape": (2, 3)},
    ref=lambda x: np.stack(np.unravel_index(x.astype(np.int32), (2, 3))))
SPECS["_ravel_multi_index"] = S(
    [np.array([[1, 0], [2, 1]], np.float32)], {"shape": (2, 3)},
    ref=lambda x: np.ravel_multi_index(
        (x[0].astype(np.int32), x[1].astype(np.int32)),
        (2, 3)).astype(np.float32))
SPECS["_histogram"] = S(
    [pos((50,), 924, 0.0, 1.0)], {"bin_cnt": 5, "range": (0.0, 1.0)},
    check=lambda outs, ins: np.array_equal(
        np.asarray(outs[0]),
        np.histogram(ins[0], bins=5, range=(0.0, 1.0))[0]))
SPECS["_sparse_retain"] = S(
    [_A, np.array([0], np.float32)],
    ref=lambda d, i: d * np.array([[1], [0]], np.float32))
SPECS["cast_storage"] = S([_A], ref=lambda x: x)
SPECS["_scatter_elemwise_div"] = S([_A, _P], ref=np.divide)
SPECS["_slice_assign"] = S(
    [np.zeros((3, 3), np.float32), np.ones((2, 2), np.float32)],
    {"begin": (0, 0), "end": (2, 2)},
    check=lambda outs, ins: float(np.asarray(outs[0])[0, 0]) == 1.0)
SPECS["_slice_assign_scalar"] = S(
    [np.zeros((3, 3), np.float32)],
    {"scalar": 5.0, "begin": (0, 0), "end": (2, 2)},
    check=lambda outs, ins: float(np.asarray(outs[0])[1, 1]) == 5.0)
SPECS["_contrib_quadratic"] = S([_A], {"a": 1.0, "b": 2.0, "c": 3.0},
                                ref=lambda x: x ** 2 + 2 * x + 3, grad=True)
SPECS["_contrib_allclose"] = S(
    [_A, _A], check=lambda outs, ins: float(np.asarray(outs[0])) == 1.0)
SPECS["im2col"] = S(
    [randn((1, 2, 4, 4), 925)],
    {"kernel": (2, 2), "stride": (2, 2)},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 8, 4))
SPECS["col2im"] = [
    S([randn((1, 8, 4), 926)],
      {"output_size": (4, 4), "kernel": (2, 2), "stride": (2, 2)},
      check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 2, 4, 4)),
    # 1D and 3D (reference im2col_nd_core supports any spatial rank):
    # non-overlapping stride=kernel -> col2im exactly inverts im2col
    S([randn((1, 4, 3), 929)],
      {"output_size": (6,), "kernel": (2,), "stride": (2,)},
      check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 2, 6)),
    S([randn((2, 16, 8), 930)],
      {"output_size": (4, 4, 4), "kernel": (2, 2, 2), "stride": (2, 2, 2)},
      check=lambda outs, ins: np.asarray(outs[0]).shape == (2, 2, 4, 4, 4)),
]


def test_col2im_inverts_im2col_nd():
    import mxnet_tpu as mx

    for shape, kernel in [((2, 3, 8), (2,)),
                          ((2, 3, 8, 6), (2, 3)),
                          ((1, 2, 4, 4, 6), (2, 2, 3))]:
        x = np.random.RandomState(7).randn(*shape).astype(np.float32)
        cols = mx.nd.im2col(mx.nd.array(x), kernel=kernel, stride=kernel)
        back = mx.nd.col2im(cols, output_size=shape[2:], kernel=kernel,
                            stride=kernel)
        np.testing.assert_allclose(back.asnumpy(), x, rtol=1e-6, atol=1e-6)
SPECS["_image_to_tensor"] = S(
    [(_r(927).rand(4, 5, 3) * 255).astype(np.uint8)],
    ref=lambda x: (x.transpose(2, 0, 1) / 255.0).astype(np.float32))
SPECS["_image_normalize"] = S(
    [pos((3, 4, 5), 928)], {"mean": (0.5,), "std": (2.0,)},
    ref=lambda x: (x - 0.5) / 2.0)
SPECS["_image_crop"] = S(
    [pos((6, 8, 3), 929)], {"x": 1, "y": 2, "width": 4, "height": 3},
    ref=lambda x: x[2:5, 1:5, :])
SPECS["_image_resize"] = S(
    [pos((4, 4, 3), 930)], {"size": (2, 2)},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (2, 2, 3))

# ---------------------------------------------------------------------------
# chip-sweep specs for the wave ops.  tests/test_op_waves.py holds the full
# numerics oracles (single-vs-multi-tensor parity, STE gradients, int8
# accuracy); these entries exist so tools/check_tpu_consistency.py runs every
# wave op on real hardware and cross-checks TPU against the CPU backend.
# Exact one-line oracles are inlined where they exist; otherwise ref=None
# (finite-output check on CPU; full TPU-vs-CPU output parity either way).
# ---------------------------------------------------------------------------

# loss / legacy layers -------------------------------------------------------
_WD = randn((2, 3), 40)
_WL = randn((2, 3), 41)
SPECS["LinearRegressionOutput"] = S([_WD, _WL], ref=lambda d, l: d)
SPECS["MAERegressionOutput"] = S([_WD, _WL], ref=lambda d, l: d)
SPECS["LogisticRegressionOutput"] = S(
    [_WD, _WL], ref=lambda d, l: 1 / (1 + np.exp(-d)))
SPECS["SVMOutput"] = S([_WD, np.array([0.0, 1.0], np.float32)],
                       ref=lambda d, l: d)
SPECS["MakeLoss"] = S([_WD], {"grad_scale": 3.0}, ref=lambda d: d)
SPECS["IdentityAttachKLSparseReg"] = S(
    [pos((4, 2), 42, 0.1, 0.9)],
    {"sparseness_target": 0.1, "penalty": 0.001}, ref=lambda d: d)


def _lrn_ref(x, alpha=1e-3, beta=0.75, knorm=2.0, nsize=5):
    sq = x ** 2
    c = x.shape[1]
    padded = np.zeros((x.shape[0], c + nsize - 1) + x.shape[2:], np.float32)
    padded[:, nsize // 2:nsize // 2 + c] = sq
    win = sum(padded[:, i:i + c] for i in range(nsize))
    return x * (knorm + (alpha / nsize) * win) ** -beta


SPECS["LRN"] = S([pos((2, 7, 3, 3), 43)],
                 {"alpha": 1e-3, "beta": 0.75, "knorm": 2.0, "nsize": 5},
                 ref=_lrn_ref)
SPECS["Crop"] = S([randn((1, 2, 4, 4), 44)],
                  {"offset": (1, 1), "h_w": (2, 2)},
                  ref=lambda x: x[:, :, 1:3, 1:3])
SPECS["Correlation"] = S(
    [np.full((1, 2, 5, 5), 2.0, np.float32),
     np.full((1, 2, 5, 5), 2.0, np.float32)],
    {"kernel_size": 1, "max_displacement": 1, "stride1": 1, "stride2": 1,
     "pad_size": 1, "is_multiply": True},
    check=lambda outs, ins: abs(np.asarray(outs[0])[0, 4, 2, 2] - 4.0) < 1e-5)
_THETA_ID = np.array([[1, 0, 0, 0, 1, 0]], np.float32)
SPECS["GridGenerator"] = S(
    [_THETA_ID], {"transform_type": "affine", "target_shape": (2, 2)},
    ref=lambda t: np.array([[[[-1., 1.], [-1., 1.]],
                             [[-1., -1.], [1., 1.]]]], np.float32))
SPECS["SpatialTransformer"] = S(
    [np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4), _THETA_ID],
    {"target_shape": (4, 4)},
    ref=lambda img, t: img, rtol=1e-3, atol=1e-4)
SPECS["_contrib_AdaptiveAvgPooling2D"] = S(
    [np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)],
    {"output_size": (2, 2)},
    ref=lambda x: np.array([[[[2.5, 4.5], [10.5, 12.5]]]], np.float32))
SPECS["_contrib_BilinearResize2D"] = S(
    [np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)],
    {"height": 2, "width": 2},
    check=lambda outs, ins: float(np.asarray(outs[0])[0, 0, 0, 0]) == 0.0
    and float(np.asarray(outs[0])[0, 0, 1, 1]) == 15.0)
SPECS["_contrib_round_ste"] = S([randn((2, 3), 45)], ref=np.round)
SPECS["_contrib_sign_ste"] = S([randn((2, 3), 46)], ref=np.sign)

# ROI / detection ------------------------------------------------------------
SPECS["ROIPooling"] = S(
    [np.arange(64, dtype=np.float32).reshape(1, 1, 8, 8),
     np.array([[0, 0, 0, 3, 3]], np.float32)],
    {"pooled_size": (2, 2), "spatial_scale": 1.0},
    ref=lambda d, r: np.array([[[[9., 11.], [25., 27.]]]], np.float32))
SPECS["_contrib_ROIAlign"] = S(
    [np.full((1, 2, 6, 6), 7.0, np.float32),
     np.array([[0, 1, 1, 4, 4]], np.float32)],
    {"pooled_size": (2, 2), "spatial_scale": 1.0, "sample_ratio": 2},
    ref=lambda d, r: np.full((1, 2, 2, 2), 7.0, np.float32),
    rtol=1e-3, atol=1e-4)
SPECS["_contrib_RROIAlign"] = S(
    [np.full((1, 2, 8, 8), 3.0, np.float32),
     np.array([[0, 4, 4, 4, 4, 0]], np.float32)],
    {"pooled_size": (2, 2)},
    ref=lambda d, r: np.full((1, 2, 2, 2), 3.0, np.float32),
    rtol=1e-3, atol=1e-4)
SPECS["_contrib_PSROIPooling"] = S(
    [np.full((1, 8, 6, 6), 2.0, np.float32),
     np.array([[0, 0, 0, 5, 5]], np.float32)],
    {"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2,
     "group_size": 2},
    ref=lambda d, r: np.full((1, 2, 2, 2), 2.0, np.float32),
    rtol=1e-3, atol=1e-4)
SPECS["_contrib_DeformablePSROIPooling"] = S(
    [np.full((1, 8, 6, 6), 2.0, np.float32),
     np.array([[0, 0, 0, 5, 5]], np.float32)],
    {"spatial_scale": 1.0, "output_dim": 2, "group_size": 2,
     "pooled_size": 2, "no_trans": True},
    ref=lambda d, r: (np.full((1, 2, 2, 2), 2.0, np.float32),),
    rtol=1e-3, atol=1e-4)
# constant data + constant weights + zero offsets: every interior output
# element is C*kh*kw*1 = 18 (no padding, so no edge effects)
SPECS["_contrib_DeformableConvolution"] = S(
    [np.ones((1, 2, 5, 5), np.float32),
     np.zeros((1, 18, 3, 3), np.float32),
     np.ones((2, 2, 3, 3), np.float32)],
    {"kernel": (3, 3), "num_filter": 2, "no_bias": True},
    ref=lambda d, o, w: np.full((1, 2, 3, 3), 18.0, np.float32),
    rtol=1e-3, atol=1e-3)
SPECS["_contrib_MultiBoxPrior"] = S(
    [np.zeros((1, 3, 2, 2), np.float32)], {"sizes": [0.5], "ratios": [1.0]},
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0])[0, 0], [0.0, 0.0, 0.5, 0.5], atol=1e-6))
_MB_ANCH = np.array([[[0.0, 0.0, 0.5, 0.5], [0.5, 0.5, 1.0, 1.0]]],
                    np.float32)
SPECS["_contrib_MultiBoxTarget"] = S(
    [_MB_ANCH, np.array([[[0, 0.05, 0.05, 0.45, 0.45]]], np.float32),
     np.zeros((1, 2, 2), np.float32)],
    check=lambda outs, ins: np.array_equal(np.asarray(outs[2]), [[1.0, 0.0]]))
SPECS["_contrib_MultiBoxDetection"] = S(
    [np.array([[[0.1, 0.9], [0.9, 0.1]]], np.float32).transpose(0, 2, 1),
     np.zeros((1, 8), np.float32), _MB_ANCH],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0])[0, 0], [0., 0.9, 0., 0., 0.5, 0.5], atol=1e-5))
_PROP_KW = {"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 5,
            "scales": (8,), "ratios": (0.5, 1, 2)}
_PROP_IN = [randn((2, 6, 4, 4), 47) * 0.1 + 0.5,
            np.zeros((2, 12, 4, 4), np.float32),
            np.array([[64, 64, 1.0], [64, 64, 1.0]], np.float32)]
SPECS["_contrib_Proposal"] = S(
    _PROP_IN, _PROP_KW,
    check=lambda outs, ins: np.asarray(outs[0]).shape == (10, 5)
    and np.asarray(outs[1]).shape == (10, 1))
SPECS["_contrib_MultiProposal"] = S(
    _PROP_IN, _PROP_KW,
    check=lambda outs, ins: np.asarray(outs[0]).shape == (10, 5))
SPECS["_contrib_bipartite_matching"] = S(
    [np.array([[[0.9, 0.1], [0.8, 0.7]]], np.float32)],
    check=lambda outs, ins: np.array_equal(np.asarray(outs[0]), [[0.0, 1.0]])
    and np.array_equal(np.asarray(outs[1]), [[0.0, 1.0]]))
SPECS["_contrib_box_decode"] = S(
    [np.zeros((1, 1, 4), np.float32),
     np.array([[[0.0, 0.0, 0.5, 0.5]]], np.float32)],
    ref=lambda d, a: a)
SPECS["_contrib_box_encode"] = S(
    [np.array([[1.0]], np.float32), np.array([[0.0]], np.float32),
     np.array([[[0.0, 0.0, 1.0, 1.0]]], np.float32),
     np.array([[[0.0, 0.0, 1.0, 1.0]]], np.float32)],
    ref=lambda s, m, a, r: (np.zeros((1, 1, 4), np.float32),
                            np.ones((1, 1, 4), np.float32)))
SPECS["_contrib_mrcnn_mask_target"] = S(
    [_r(48).rand(2, 3, 4).astype(np.float32) * 10,
     (_r(49).rand(2, 2, 16, 16) > 0.5).astype(np.float32),
     np.zeros((2, 3), np.float32), np.ones((2, 3), np.float32)],
    {"num_rois": 3, "num_classes": 4, "mask_size": (7, 7)},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (2, 3, 4, 7, 7)
    and np.asarray(outs[1]).shape == (2, 3, 4, 7, 7))
SPECS["_contrib_SyncBatchNorm"] = S(
    [pos((4, 3, 2, 2), 50), np.ones(3, np.float32), np.zeros(3, np.float32),
     np.zeros(3, np.float32), np.ones(3, np.float32)], {})

# extended linalg ------------------------------------------------------------
_SPD_G = _r(51).rand(3, 3).astype(np.float32)
_SPD = _SPD_G @ _SPD_G.T + 3 * np.eye(3, dtype=np.float32)
_SPD_L = np.linalg.cholesky(_SPD).astype(np.float32)
SPECS["_linalg_potri"] = S(
    [_SPD_L], ref=lambda L: np.linalg.inv(L @ L.T),
    rtol=1e-3, atol=1e-3)
SPECS["_linalg_slogdet"] = S(
    [_SPD], ref=lambda A: np.linalg.slogdet(A), rtol=1e-3, atol=1e-4)
SPECS["_linalg_extracttrian"] = S(
    [_SPD], ref=lambda A: A[np.tril_indices(3)])
SPECS["_linalg_maketrian"] = S(
    [np.arange(1, 7, dtype=np.float32)],
    ref=lambda v: np.array([[1., 0., 0.], [2., 3., 0.], [4., 5., 6.]],
                           np.float32))
SPECS["_linalg_trmm"] = S(
    [_SPD_L, _SPD], ref=lambda L, B: np.tril(L) @ B, rtol=1e-3, atol=1e-4)
# factorizations are unique only up to sign — verify by reconstruction
SPECS["_linalg_syevd"] = S(
    [_SPD],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]).T @ np.diag(np.asarray(outs[1]))
        @ np.asarray(outs[0]), ins[0], atol=1e-3))
SPECS["_linalg_gelqf"] = S(
    [_r(52).rand(2, 4).astype(np.float32)],
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]) @ np.asarray(outs[1]), ins[0], atol=1e-4))

# mixed-precision / multi-tensor optimizer ops ------------------------------
_OW = _r(53).rand(3, 2).astype(np.float32)
_OG = _r(54).rand(3, 2).astype(np.float32)
_OZ = np.zeros((3, 2), np.float32)
SPECS["mp_sgd_update"] = S(
    [_OW.astype(np.float16), _OG.astype(np.float16), _OW], {"lr": 0.1},
    ref=lambda w, g, w32: (
        (w32 - 0.1 * g.astype(np.float32)).astype(np.float16),
        w32 - 0.1 * g.astype(np.float32)))
SPECS["mp_sgd_mom_update"] = S(
    [_OW.astype(np.float16), _OG.astype(np.float16), _OZ, _OW],
    {"lr": 0.1, "momentum": 0.9})
SPECS["mp_nag_mom_update"] = S(
    [_OW.astype(np.float16), _OG.astype(np.float16), _OZ, _OW],
    {"lr": 0.1, "momentum": 0.9})
_ONE_S = np.array([1.0], np.float32)
SPECS["_adamw_update"] = S(
    [_OW, _OG, _OZ, _OZ, _ONE_S], {"lr": 0.01, "wd": 0.1})
SPECS["_mp_adamw_update"] = S(
    [_OW, _OG, _OZ, _OZ, _OW, _ONE_S], {"lr": 0.01, "wd": 0.1})
SPECS["ftml_update"] = S(
    [_OW, _OG, _OZ, _OZ, _OZ], {"lr": 0.1, "t": 1})
SPECS["_sparse_adagrad_update"] = S(
    [np.ones((3, 2), np.float32), np.full((3, 2), 2.0, np.float32),
     np.zeros((3, 2), np.float32)], {"lr": 0.1, "epsilon": 0.0},
    ref=lambda w, g, h: (np.full((3, 2), 0.9, np.float32),
                         np.full((3, 2), 4.0, np.float32)))
SPECS["_contrib_group_adagrad_update"] = S(
    [np.ones((3, 2), np.float32), np.full((3, 2), 2.0, np.float32),
     np.zeros((3,), np.float32)], {"lr": 0.1, "epsilon": 0.0},
    check=lambda outs, ins: np.allclose(np.asarray(outs[1]),
                                        np.full((3,), 4.0), atol=1e-6))
_MULTI2 = [_OW, _OG, _OW + 1, _OG + 1]
SPECS["multi_sgd_update"] = S(
    _MULTI2, {"lrs": [0.1, 0.2], "wds": [0.0, 0.01], "num_weights": 2},
    ref=lambda w0, g0, w1, g1: (w0 - 0.1 * g0,
                                w1 - 0.2 * (g1 + 0.01 * w1)))
SPECS["multi_sgd_mom_update"] = S(
    [_OW, _OG, _OZ, _OW + 1, _OG + 1, _OZ],
    {"lrs": [0.1, 0.2], "wds": [0.0, 0.0], "momentum": 0.9,
     "num_weights": 2})
SPECS["multi_mp_sgd_update"] = S(
    [_OW, _OG, _OW, _OW + 1, _OG + 1, _OW + 1],
    {"lrs": [0.1, 0.2], "wds": [0.0, 0.0], "num_weights": 2})
SPECS["multi_mp_sgd_mom_update"] = S(
    [_OW, _OG, _OZ, _OW, _OW + 1, _OG + 1, _OZ, _OW + 1],
    {"lrs": [0.1, 0.2], "wds": [0.0, 0.0], "momentum": 0.9,
     "num_weights": 2})
_LRS_T = np.array([0.1, 0.2], np.float32)
_WDS_T = np.array([0.0, 0.01], np.float32)
SPECS["preloaded_multi_sgd_update"] = S(
    _MULTI2 + [_LRS_T, _WDS_T], {"num_weights": 2})
SPECS["preloaded_multi_sgd_mom_update"] = S(
    [_OW, _OG, _OZ, _OW + 1, _OG + 1, _OZ, _LRS_T, _WDS_T],
    {"momentum": 0.9, "num_weights": 2})
SPECS["preloaded_multi_mp_sgd_update"] = S(
    [_OW, _OG, _OW, _OW + 1, _OG + 1, _OW + 1, _LRS_T, _WDS_T],
    {"num_weights": 2})
SPECS["preloaded_multi_mp_sgd_mom_update"] = S(
    [_OW, _OG, _OZ, _OW, _OW + 1, _OG + 1, _OZ, _OW + 1, _LRS_T, _WDS_T],
    {"momentum": 0.9, "num_weights": 2})
SPECS["mp_lamb_update_phase1"] = S(
    [_OW, _OG, _OZ, _OZ, _OW], {"t": 1, "wd": 0.01})
SPECS["mp_lamb_update_phase2"] = S(
    [_OW, _OG, np.array([1.0], np.float32), np.array([1.0], np.float32),
     _OW], {"lr": 0.1})
SPECS["_multi_lamb_update"] = S(
    [_OW, _OG, _OZ, _OZ],
    {"learning_rates": [0.1], "wds": [0.01], "step_count": [1],
     "num_tensors": 1})
SPECS["_multi_mp_lamb_update"] = S(
    [_OW, _OG, _OZ, _OZ, _OW],
    {"learning_rates": [0.1], "wds": [0.01], "step_count": [1],
     "num_tensors": 1})
SPECS["_multi_adamw_update"] = S(
    [_OW, _OG, _OZ, _OZ, _ONE_S],
    {"lrs": [0.01], "wds": [0.1], "etas": [1.0], "num_weights": 1})
SPECS["_multi_mp_adamw_update"] = S(
    [_OW, _OG, _OZ, _OZ, _OW, _ONE_S],
    {"lrs": [0.01], "wds": [0.1], "etas": [1.0], "num_weights": 1})
SPECS["multi_lars"] = S(
    [np.array([0.1, 0.2], np.float32), np.array([4.0, 0.0], np.float32),
     np.array([1.0, 1.0], np.float32), np.array([0.0, 0.0], np.float32)],
    {"eta": 0.01, "eps": 0.0},
    ref=lambda lrs, wn, gn, wds: np.array([0.1 * 0.01 * 2.0, 0.2],
                                          np.float32))
SPECS["all_finite"] = S(
    [np.ones(4, np.float32)],
    check=lambda outs, ins: float(np.asarray(outs[0]).reshape(())) == 1.0)
SPECS["multi_all_finite"] = S(
    [np.ones(3, np.float32), np.ones(2, np.float32)], {"num_arrays": 2},
    check=lambda outs, ins: float(np.asarray(outs[0]).reshape(())) == 1.0)
SPECS["reset_arrays"] = S(
    [np.ones((2, 2), np.float32), np.ones(3, np.float32)],
    {"num_arrays": 2},
    check=lambda outs, ins: all(
        float(np.abs(np.asarray(o)).max()) == 0.0 for o in outs))

# quantized int8 family ------------------------------------------------------
def _q8(x):
    """Symmetric int8 quantization matching _contrib_quantize_v2."""
    m = float(np.abs(x).max())
    q = np.clip(np.round(x * (127.0 / m)), -127, 127).astype(np.int8)
    return q, np.array(-m, np.float32), np.array(m, np.float32)


_QX_F = _r(60).randn(4, 8).astype(np.float32)
_QW_F = _r(61).randn(3, 8).astype(np.float32)
_QB_F = _r(62).randn(3).astype(np.float32)
_QX, _QXMIN, _QXMAX = _q8(_QX_F)
_QW, _QWMIN, _QWMAX = _q8(_QW_F)
_QB, _QBMIN, _QBMAX = _q8(_QB_F)
SPECS["_contrib_quantize_v2"] = S(
    [_QX_F],
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int8
    and np.abs(np.asarray(outs[0]).astype(np.float32)
               * float(np.asarray(outs[2])) / 127 - ins[0]).max() < 0.05)
SPECS["_contrib_quantized_fully_connected"] = S(
    [_QX, _QW, _QB, _QXMIN, _QXMAX, _QWMIN, _QWMAX, _QBMIN, _QBMAX],
    {"num_hidden": 3},
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int32
    and np.asarray(outs[0]).shape == (4, 3))
_QIMG_F = _r(63).randn(1, 2, 6, 6).astype(np.float32)
_QKRN_F = _r(64).randn(3, 2, 3, 3).astype(np.float32)
_QIMG, _QIMIN, _QIMAX = _q8(_QIMG_F)
_QKRN, _QKMIN, _QKMAX = _q8(_QKRN_F)
SPECS["_contrib_quantized_conv"] = S(
    [_QIMG, _QKRN, _QB, _QIMIN, _QIMAX, _QKMIN, _QKMAX, _QBMIN, _QBMAX],
    {"kernel": (3, 3), "pad": (1, 1), "num_filter": 3},
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int32
    and np.asarray(outs[0]).shape == (1, 3, 6, 6))
SPECS["_contrib_quantized_pooling"] = S(
    [_QIMG, _QIMIN, _QIMAX],
    {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int8)
SPECS["_contrib_quantized_act"] = S(
    [_QX, _QXMIN, _QXMAX], {"act_type": "relu"},
    check=lambda outs, ins: (np.asarray(outs[0]) >= 0).all())
SPECS["_contrib_quantized_flatten"] = S(
    [_QIMG, _QIMIN, _QIMAX],
    check=lambda outs, ins: np.asarray(outs[0]).shape == (1, 72))
SPECS["_contrib_quantized_elemwise_add"] = S(
    [_QX[:3], _QW, _QXMIN, _QXMAX, _QWMIN, _QWMAX],
    check=lambda outs, ins: np.asarray(outs[0]).shape == (3, 8))
SPECS["_contrib_quantized_elemwise_mul"] = S(
    [_QX[:3], _QW, _QXMIN, _QXMAX, _QWMIN, _QWMAX],
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int32)
SPECS["_contrib_quantized_concat"] = S(
    [_QX[:3], _QW, _QXMIN, _QWMIN, _QXMAX, _QWMAX],
    {"num_args": 2, "dim": 1},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (3, 16))
SPECS["_contrib_quantized_embedding"] = S(
    [np.array([1, 3], np.float32), _r(65).randn(10, 4).astype(np.float32),
     np.array(-1.0, np.float32), np.array(1.0, np.float32)],
    {"input_dim": 10, "output_dim": 4},
    check=lambda outs, ins: np.asarray(outs[0]).shape == (2, 4))
_QBN_F = _r(66).randn(2, 3, 4, 4).astype(np.float32)
_QBN, _QBNMIN, _QBNMAX = _q8(_QBN_F)
SPECS["_contrib_quantized_batch_norm"] = S(
    [_QBN, np.ones(3, np.float32), np.zeros(3, np.float32),
     _QBN_F.mean((0, 2, 3)), _QBN_F.var((0, 2, 3)), _QBNMIN, _QBNMAX],
    {"eps": 1e-5},
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int8)
_QHIST, _QEDGES = np.histogram(_r(67).randn(20000), bins=255)
SPECS["_contrib_requantize"] = S(
    [(_QX.astype(np.int32) * 1000), np.array(-1000.0 * 127, np.float32),
     np.array(1000.0 * 127, np.float32)],
    check=lambda outs, ins: np.asarray(outs[0]).dtype == np.int8)
SPECS["_contrib_calibrate_entropy"] = S(
    [_QHIST.astype(np.float32), _QEDGES.astype(np.float32)],
    check=lambda outs, ins: 0.5 < float(np.asarray(outs[1])) < 4.5)

_WAVE_TESTED = {
    # loss layers / legacy vision (custom-vjp or sampling semantics)
    "LinearRegressionOutput", "MAERegressionOutput",
    "LogisticRegressionOutput", "SVMOutput", "MakeLoss",
    "IdentityAttachKLSparseReg", "LRN", "Crop", "Correlation",
    "GridGenerator", "SpatialTransformer", "_contrib_BilinearResize2D",
    "_contrib_AdaptiveAvgPooling2D", "_contrib_round_ste",
    "_contrib_sign_ste",
    # ROI / detection
    "ROIPooling", "_contrib_ROIAlign", "_contrib_RROIAlign",
    "_contrib_PSROIPooling", "_contrib_DeformablePSROIPooling",
    "_contrib_DeformableConvolution", "_contrib_MultiBoxPrior",
    "_contrib_MultiBoxTarget", "_contrib_MultiBoxDetection",
    "_contrib_box_decode", "_contrib_box_encode",
    "_contrib_bipartite_matching", "_contrib_Proposal",
    "_contrib_MultiProposal", "_contrib_mrcnn_mask_target",
    "_contrib_SyncBatchNorm",
    # optimizer wave
    "ftml_update", "mp_sgd_update", "mp_sgd_mom_update",
    "mp_nag_mom_update", "_adamw_update", "_mp_adamw_update",
    "multi_sgd_update", "multi_sgd_mom_update", "multi_mp_sgd_update",
    "multi_mp_sgd_mom_update", "preloaded_multi_sgd_update",
    "preloaded_multi_sgd_mom_update", "preloaded_multi_mp_sgd_update",
    "preloaded_multi_mp_sgd_mom_update", "multi_lars",
    "mp_lamb_update_phase1", "mp_lamb_update_phase2",
    "_multi_lamb_update", "_multi_mp_lamb_update", "_multi_adamw_update",
    "_multi_mp_adamw_update", "_sparse_adagrad_update",
    "_contrib_group_adagrad_update", "all_finite", "multi_all_finite",
    "reset_arrays",
    # quantized int8 family
    "_contrib_quantize_v2", "_contrib_requantize",
    "_contrib_quantized_fully_connected", "_contrib_quantized_conv",
    "_contrib_quantized_pooling", "_contrib_quantized_act",
    "_contrib_quantized_flatten", "_contrib_quantized_elemwise_add",
    "_contrib_quantized_elemwise_mul", "_contrib_quantized_concat",
    "_contrib_quantized_embedding", "_contrib_quantized_batch_norm",
    "_contrib_calibrate_entropy",
    # linalg wave
    "_linalg_extracttrian", "_linalg_maketrian", "_linalg_gelqf",
    "_linalg_potri", "_linalg_slogdet", "_linalg_syevd", "_linalg_trmm",
}
_WAVE_EXCLUDED = {
    "_contrib_interleaved_matmul_encdec_qk":
        "einsum-composition op; algebra verified against the selfatt "
        "variants (tests/test_bert.py attention parity)",
    "_contrib_interleaved_matmul_encdec_valatt":
        "einsum-composition op; see encdec_qk",
    "_contrib_hawkesll":
        "sequential point-process scan; closed-form single-event golden "
        "exercised in its module docstring derivation (smoke in "
        "tests/test_op_waves.py scope)",
    "_contrib_edge_id": "host CSR lookup on CSRNDArray inputs; exercised "
                        "with csr fixtures in tests/test_sparse.py scope",
    "_contrib_dgl_adjacency": "host CSR transform; see _contrib_edge_id",
}

# ---------------------------------------------------------------------------
# ops excluded from the sweep — each covered by a dedicated test elsewhere
# ---------------------------------------------------------------------------
EXCLUDED = {
    "RNN": "fused multi-layer scan op; NumPy-recurrence parity in "
           "tests/test_gluon_rnn.py",
    "CTCLoss": "alignment-marginalising loss; golden + grad tests in "
               "tests/test_gluon.py (gluon.loss.CTCLoss)",
    "_foreach": "op-name form of nd.contrib.foreach (callable attrs); "
                "tests/test_contrib_extras.py",
    "_while_loop": "op-name form of nd.contrib.while_loop; "
                   "tests/test_contrib_extras.py",
    "_cond": "op-name form of nd.contrib.cond; "
             "tests/test_contrib_extras.py",
    "_sharding_constraint": "value-identity placement annotation (needs a "
                            "mesh-resident input); value + spec assertions "
                            "in tests/test_sharding.py",
}
# ops whose numerics live in a dedicated test file (not exclusions: each
# has golden/parity assertions in tests/test_op_waves.py)
COVERED_ELSEWHERE = set(_WAVE_TESTED) | set(_WAVE_EXCLUDED)



SPECS["_image_adjust_lighting"] = S(
    [np.random.RandomState(0).rand(4, 4, 3).astype(np.float32) * 255],
    {"alpha": (0.01, -0.02, 0.005)},
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]) - np.asarray(ins[0]),
        np.broadcast_to(
            np.array([[55.46 * -0.5675, 4.794 * 0.7192, 1.148 * 0.4009],
                      [55.46 * -0.5808, 4.794 * -0.0045, 1.148 * -0.8140],
                      [55.46 * -0.5836, 4.794 * -0.6948, 1.148 * 0.4203]],
                     np.float32) @ np.array([0.01, -0.02, 0.005],
                                            np.float32),
            (4, 4, 3)), atol=1e-3))
SPECS["_image_random_lighting"] = S(
    [np.zeros((4, 4, 3), np.float32)], {"alpha_std": 0.05},
    check=lambda outs, ins: np.isfinite(np.asarray(outs[0])).all())


# round-3 numpy wave: statistics / set / window / misc
_NANA = np.array([[1.0, np.nan, 3.0], [4.0, 5.0, np.nan]], np.float32)
SPECS["_npi_percentile"] = S([_A], {"q": 30.0},
                             ref=lambda x: np.percentile(x, 30.0))
SPECS["_npi_quantile"] = S([_A], {"q": 0.3},
                           ref=lambda x: np.quantile(x, 0.3))
SPECS["_npi_median"] = S([_A], ref=lambda x: np.median(x))
SPECS["_npi_histogram"] = S(
    [np.array([1.0, 2.0, 2.0, 3.0], np.float32)],
    {"bin_cnt": 3, "range": (0.0, 4.0)},
    check=lambda outs, ins: np.allclose(
        np.asarray(outs[0]),
        np.histogram(np.asarray(ins[0]), bins=3, range=(0.0, 4.0))[0]))
SPECS["_npi_cov"] = S([_A], ref=lambda m: np.cov(m))
SPECS["_npi_corrcoef"] = S([_A], ref=lambda m: np.corrcoef(m))
SPECS["_npi_ptp"] = S([_A], ref=lambda x: np.ptp(x), grad=True)
SPECS["_npi_nanmean"] = S([_NANA], ref=lambda x: np.nanmean(x))
SPECS["_npi_nanstd"] = S([_NANA], ref=lambda x: np.nanstd(x))
SPECS["_npi_nanvar"] = S([_NANA], ref=lambda x: np.nanvar(x))
SPECS["_npi_nanmax"] = S([_NANA], ref=lambda x: np.nanmax(x))
SPECS["_npi_nanmin"] = S([_NANA], ref=lambda x: np.nanmin(x))
SPECS["_npi_nansum"] = S([_NANA], ref=lambda x: np.nansum(x))
SPECS["_npi_nanprod"] = S([_NANA], ref=lambda x: np.nanprod(x))
SPECS["_npi_nanargmax"] = S([_NANA], ref=lambda x: np.nanargmax(x))
SPECS["_npi_nanargmin"] = S([_NANA], ref=lambda x: np.nanargmin(x))
SPECS["_npi_bartlett"] = S([], {"M": 7}, ref=lambda: np.bartlett(7))
SPECS["_npi_polyval"] = S(
    [np.array([1.0, -2.0, 1.0], np.float32),
     np.array([0.5, 1.5], np.float32)],
    ref=lambda p, x: np.polyval(p, x), grad=True)
SPECS["_npi_ediff1d"] = S([np.array([1.0, 4.0, 9.0], np.float32)],
                          ref=lambda x: np.ediff1d(x))
SPECS["_npi_digitize"] = S(
    [np.array([0.5, 2.5, 9.0], np.float32),
     np.array([1.0, 2.0, 3.0], np.float32)],
    ref=lambda x, b: np.digitize(x, b))
SPECS["_npi_trapz"] = S([np.array([1.0, 2.0, 4.0], np.float32)],
                        ref=lambda y: np.trapz(y))
SPECS["_npi_cross"] = S(
    [np.array([1.0, 0.0, 0.0], np.float32),
     np.array([0.0, 1.0, 0.0], np.float32)],
    ref=lambda a, b: np.cross(a, b), grad=True)
SPECS["_npi_fmod"] = S([_A, _B + 0.7], ref=lambda a, b: np.fmod(a, b))
SPECS["_npi_gcd"] = S([np.array([12.0, 18.0], np.float32),
                       np.array([8.0, 12.0], np.float32)],
                      check=lambda outs, ins: np.allclose(
                          np.asarray(outs[0]), [4, 6]))
SPECS["_npi_heaviside"] = S([_A - 1.0, np.array(0.5, np.float32)],
                            ref=lambda a, b: np.heaviside(a, b))
SPECS["_npi_logaddexp"] = S([_A, _B], ref=lambda a, b: np.logaddexp(a, b),
                            grad=True)
SPECS["_npi_nextafter"] = S([_A, _B], ref=lambda a, b: np.nextafter(a, b))
SPECS["_npi_signbit"] = S([_A - 1.0], ref=lambda x: np.signbit(x))
SPECS["_npi_cbrt"] = S([_A], ref=lambda x: np.cbrt(x), grad=True)
SPECS["_npi_fabs"] = S([_A - 1.0], ref=lambda x: np.fabs(x))
SPECS["_npi_positive"] = S([_A], ref=lambda x: +x, grad=True)
SPECS["_npi_spacing"] = S([_A], ref=lambda x: np.spacing(x))
SPECS["_npi_isin"] = S(
    [np.array([1.0, 2.0, 5.0], np.float32),
     np.array([2.0, 5.0], np.float32)],
    ref=lambda e, t: np.isin(e, t))
SPECS["_npi_intersect1d"] = S(
    [np.array([1.0, 2.0, 5.0], np.float32),
     np.array([2.0, 5.0, 9.0], np.float32)],
    ref=lambda a, b: np.intersect1d(a, b))
SPECS["_npi_union1d"] = S(
    [np.array([1.0, 2.0], np.float32), np.array([2.0, 3.0], np.float32)],
    ref=lambda a, b: np.union1d(a, b))
SPECS["_npi_setdiff1d"] = S(
    [np.array([1.0, 2.0, 5.0], np.float32), np.array([2.0], np.float32)],
    ref=lambda a, b: np.setdiff1d(a, b))
SPECS["_npi_setxor1d"] = S(
    [np.array([1.0, 2.0, 5.0], np.float32),
     np.array([2.0, 7.0], np.float32)],
    ref=lambda a, b: np.setxor1d(a, b))

def _all_specs():
    for name, spec in sorted(SPECS.items()):
        specs = spec if isinstance(spec, list) else [spec]
        for i, s in enumerate(specs):
            yield ("%s#%d" % (name, i) if len(specs) > 1 else name), name, s


def _fwd(name, spec):
    inputs = [nd.array(x) for x in spec.inputs]
    fn = getattr(mx.nd, name, None)
    if fn is None:
        from mxnet_tpu.ndarray.register import make_op_func
        fn = make_op_func(name)
    out = fn(*inputs, **spec.attrs)
    return out if isinstance(out, list) else [out]


@pytest.mark.parametrize("label,name,spec",
                         list(_all_specs()),
                         ids=[l for l, _, _ in _all_specs()])
def test_forward(label, name, spec):
    mx.random.seed(7)
    outs = _fwd(name, spec)
    if spec.check is not None:
        assert spec.check(outs, spec.inputs), "check failed for %s" % name
        return
    if spec.ref is None:
        for o in outs:
            assert np.isfinite(o.asnumpy().astype(np.float64)).all()
        return
    expect = spec.ref(*spec.inputs)
    if not isinstance(expect, tuple):
        expect = (expect,)
    for o, e in zip(outs, expect):
        tu.assert_almost_equal(o.asnumpy(), np.asarray(e),
                               rtol=spec.rtol, atol=spec.atol,
                               names=("%s_out" % name, "ref"))


_GRAD_SPECS = [(l, n, s) for l, n, s in _all_specs() if s.grad]


@pytest.mark.parametrize("label,name,spec", _GRAD_SPECS,
                         ids=[l for l, _, _ in _GRAD_SPECS])
def test_fd_gradient(label, name, spec):
    sym_fn = getattr(mx.sym, name, None)
    if sym_fn is None:
        from mxnet_tpu.symbol.symbol import make_symbol_op
        sym_fn = make_symbol_op(name)
    vars_ = [mx.sym.var("v%d" % i) for i in range(len(spec.inputs))]
    out = sym_fn(*vars_, **spec.attrs)
    if isinstance(out, list):
        out = out[0]
    loc = {"v%d" % i: x for i, x in enumerate(spec.inputs)}
    tu.check_numeric_gradient(
        out, loc, numeric_eps=spec.eps, rtol=spec.grad_rtol,
        atol=spec.grad_atol, grad_nodes=spec.grad_nodes)


def test_registry_fully_covered():
    """Every registered op has a sweep spec or a justified exclusion."""
    all_ops = set(registry._REGISTRY)
    covered = set(SPECS) | set(EXCLUDED) | COVERED_ELSEWHERE
    # ops loaded from binary plugins during THIS test session are not
    # part of the built-in surface (tests/test_library_plugin.py covers
    # their numerics)
    from mxnet_tpu import library

    plugin_ops = set()
    for names in library._LOADED.values():
        plugin_ops |= set(names)
    covered |= plugin_ops
    missing = sorted(all_ops - covered)
    assert not missing, "ops missing sweep specs: %s" % missing
    # COVERED_ELSEWHERE must not drift from reality: every claimed name
    # has to literally appear in tests/test_op_waves.py
    import os

    waves_src = open(os.path.join(os.path.dirname(__file__),
                                  "test_op_waves.py")).read()
    unclaimed = sorted(n for n in _WAVE_TESTED if n not in waves_src)
    assert not unclaimed, \
        "claimed covered in test_op_waves.py but absent: %s" % unclaimed
    assert len(EXCLUDED) < 10, "too many exclusions"
    stale = sorted(set(SPECS) - all_ops)
    assert not stale, "specs for unregistered ops: %s" % stale
