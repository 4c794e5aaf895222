"""The kernels that move rows into a sorted layout, along it and out of it
(``mx_rows_take``, ``mx_rows_relu2``, ``mx_rows_combine``), through the
Pallas interpreter at small shapes, against plain loops over the rows.

Of ``M`` sorted rows the first ``count`` hold something.  What lies past
them is undefined: every input in the layout carries NaN there, and of a
result in the layout only the rows below ``count`` are compared (and the
zeros that ``mx_rows_take`` writes up to the end of the last tile).
``tests/test_tpu_compile.py`` compiles the same kernels at the Nemotron
cell's widths for a described v5e.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_kernels as pk

TILE = pk._GMM_ROWS
S, K, D = 50, 6, 256
M = S * K                     # 300 sorted rows: two whole tiles and one of 44
# how many of the assignments landed; 200 ends inside the second tile
LOADS = {"none": 0, "one_row": 1, "few": 37, "a_whole_tile": TILE,
         "most": 200, "all": M}
DTYPES = ["bfloat16", "float32"]


def _layout(seed=0):
    rng = np.random.default_rng(seed)
    order = rng.permutation(M).astype(np.int32)
    weight = rng.uniform(0.2, 1.0, M).astype(np.float32)
    return order // K, order, weight


def _src(seed=1, d=D):
    return np.random.default_rng(seed).normal(size=(S, d)).astype(np.float32)


def _sorted(count, dtype, seed=2, d=D):
    """An array in the layout: NaN past ``count``."""
    rows = np.random.default_rng(seed).normal(size=(M, d)).astype(np.float32)
    rows[count:] = np.nan
    return jnp.asarray(rows, dtype)


def _run(fn, *args, **kw):
    return jax.jit(functools.partial(fn, interpret=True, **kw))(
        *(jnp.asarray(a) for a in args))


def _count(n):
    return np.array([n], np.int32)


def _take_loop(src, token, order, weight, count):
    out = np.zeros((M, src.shape[1]), np.float32)
    for p in range(count):
        out[p] = weight[order[p]] * src[token[p]]
    return out


def _combine_loop(rows, token, order, weight, count):
    out = np.zeros((S, rows.shape[1]), np.float32)
    for p in range(count):
        out[token[p]] += weight[order[p]] * rows[p]
    return out


def _close(got, want, tol):
    scale = max(1.0, float(np.abs(want).max())) if want.size else 1.0
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=tol * scale)


TOL = {"bfloat16": 8e-3, "float32": 1e-6}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("load", sorted(LOADS))
def test_mx_rows_take_matches_the_loop(load, dtype):
    count = LOADS[load]
    token, order, weight = _layout()
    src = _src()
    out = _run(pk._rows_take_pallas, src, token, order, weight,
               _count(count), m=M, dtype=jnp.dtype(dtype))
    assert out.shape == (M, D) and out.dtype == jnp.dtype(dtype)
    # the landed rows, and zeros to the end of the last tile that holds one
    visited = -(-count // TILE) * TILE
    _close(out[:min(visited, M)],
           _take_loop(src, token, order, weight, count)[:visited], TOL[dtype])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("load", sorted(LOADS))
def test_mx_rows_take_with_the_dots_against_a_sorted_array(load, dtype):
    count = LOADS[load]
    token, order, weight = _layout(3)
    src = _src(4)
    other = _sorted(count, dtype, 5)
    out, dots = _run(pk._rows_take_pallas, src, token, order, weight,
                     _count(count), other, m=M, dtype=jnp.dtype(dtype))
    visited = min(-(-count // TILE) * TILE, M)
    _close(out[:visited],
           _take_loop(src, token, order, weight, count)[:visited], TOL[dtype])
    want = np.zeros(M, np.float32)
    for p in range(count):                  # the source's row, unweighted
        want[p] = src[token[p]] @ np.asarray(other[p], np.float32)
    assert dots.shape == (M,) and dots.dtype == jnp.float32
    # what ``other`` holds past the landed rows reaches no sum
    _close(dots[:visited], want[:visited], 1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("load", sorted(LOADS))
def test_mx_rows_combine_matches_the_loop(load, dtype):
    count = LOADS[load]
    token, order, weight = _layout(6)
    rows = _sorted(count, dtype, 7)
    out = _run(pk._rows_combine_pallas, rows, token, order, weight,
               _count(count), s=S)
    assert out.shape == (S, D) and out.dtype == jnp.float32
    want = _combine_loop(np.asarray(rows, np.float32), token, order, weight,
                         count)
    _close(out, want, 1e-6)
    # a token without a landed row reads zeros, not what was there before
    without = np.setdiff1d(np.arange(S), token[:count])
    assert not np.asarray(out)[without].any()


@pytest.mark.parametrize("load", sorted(LOADS))
def test_each_is_the_others_transpose(load):
    """``<take(src), rows> == <src, combine(rows)>`` over the landed rows:
    ``grouped_ffn``'s backward pass uses each as the other's gradient."""
    count = LOADS[load]
    token, order, weight = _layout(8)
    src = _src(9)
    rows = _sorted(count, "float32", 10)
    taken = _run(pk._rows_take_pallas, src, token, order, weight,
                 _count(count), m=M, dtype=jnp.dtype("float32"))
    combined = _run(pk._rows_combine_pallas, rows, token, order, weight,
                    _count(count), s=S)
    left = float(np.sum(np.asarray(taken[:count], np.float64)
                        * np.asarray(rows[:count], np.float64)))
    right = float(np.sum(src.astype(np.float64)
                         * np.asarray(combined, np.float64)))
    assert left == pytest.approx(right, rel=1e-5, abs=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("load", sorted(LOADS))
def test_mx_rows_relu2_and_its_gradient(load, dtype):
    count = LOADS[load]
    hid = _sorted(count, dtype, 11, d=192 + 64)
    grad = _sorted(count, dtype, 12, d=192 + 64)
    act = _run(pk._rows_relu2_pallas, hid, _count(count))
    back = _run(pk._rows_relu2_pallas, hid, _count(count), grad)
    assert act.dtype == back.dtype == jnp.dtype(dtype)
    h = np.asarray(hid[:count], np.float32)
    relu = np.maximum(h, 0)
    _close(act[:count], relu * relu, TOL[dtype])
    _close(back[:count], 2 * relu * np.asarray(grad[:count], np.float32),
           TOL[dtype])


@pytest.mark.parametrize("load", ["few", "most", "all"])
def test_column_blocks_when_the_indexed_array_does_not_fit(monkeypatch, load):
    """With less room than the whole ``(S, D)`` array takes, the columns
    are cut into equal blocks of a multiple of 128 (the last one partial)
    and nothing changes: not the rows, and not the dots, whose sum runs
    over the blocks."""
    d = 320
    count = LOADS[load]
    room = pk._rows_split(d, S, TILE, 8)
    assert room == (d, 1)
    monkeypatch.setattr(pk, "_GMM_VMEM", 200 * (8 * S + 4 * TILE))
    assert pk._rows_split(d, S, TILE, 0) == (128, 3)
    token, order, weight = _layout(13)
    src = _src(14, d)
    other = _sorted(count, "float32", 15, d)
    out, dots = _run(pk._rows_take_pallas, src, token, order, weight,
                     _count(count), other, m=M, dtype=jnp.dtype("float32"))
    _close(out[:count], _take_loop(src, token, order, weight, count)[:count],
           1e-6)
    want = [src[token[p]] @ np.asarray(other[p]) for p in range(count)]
    _close(dots[:count], np.asarray(want, np.float32), 1e-5)
    got = _run(pk._rows_combine_pallas, other, token, order, weight,
               _count(count), s=S)
    _close(got, _combine_loop(np.asarray(other), token, order, weight, count),
           1e-6)


def test_fewer_rows_than_a_tile():
    """``M`` below 128: one tile of ``M`` rows."""
    s, k = 8, 3
    rng = np.random.default_rng(16)
    order = rng.permutation(s * k).astype(np.int32)
    weight = rng.uniform(0.2, 1.0, s * k).astype(np.float32)
    src = rng.normal(size=(s, 128)).astype(np.float32)
    out = _run(pk._rows_take_pallas, src, order // k, order, weight,
               _count(10), m=s * k, dtype=jnp.dtype("float32"))
    want = np.zeros((s * k, 128), np.float32)
    for p in range(10):
        want[p] = weight[order[p]] * src[order[p] // k]
    _close(out, want, 1e-6)
    back = _run(pk._rows_combine_pallas, out, order // k, order, weight,
                _count(10), s=s)
    ref = np.zeros((s, 128), np.float32)
    for p in range(10):
        ref[order[p] // k] += weight[order[p]] * want[p]
    _close(back, ref, 1e-6)
