"""The grouped-matmul kernels (``mx_gmm``, ``mx_gmm_dw``) and the
``custom_vjp`` that ties them, through the Pallas interpreter at small
shapes, against a plain per-group loop in float32.

Rows past the last group are undefined: every comparison fills them with
NaN first and leaves them out.  ``tests/test_tpu_compile.py`` compiles the
same kernels at the Nemotron cell's widths for a described v5e.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_kernels as pk

TILE = pk._GMM_ROWS

# group sizes over M = 700 rows (five whole 128-row tiles and one of 60)
LAYOUTS = {
    "multiples_of_the_tile": [128, 256, 0, 128, 128],
    "like_195_rows": [195, 190, 11, 200, 97],
    "empty_first": [0, 300, 130, 70, 200],
    "empty_middle": [250, 0, 0, 199, 100],
    "empty_last": [129, 127, 1, 300, 0],
    "nothing_landed": [0, 0, 0, 0, 0],
    "every_row_landed": [100, 200, 300, 60, 40],
    "all_on_one_group": [0, 0, 700, 0, 0],
    "one_row": [0, 0, 0, 1, 0],
}
# (M, K, N): whole lane tiles; a partial one in N, in K (a scaled-down
# 2688 / 1856); fewer rows than a tile; rows that are no multiple of 8
SHAPES = {"aligned": (700, 256, 128), "partial_n": (700, 384, 320),
          "partial_k": (700, 192 + 64 + 64, 384), "short": (96, 128, 192),
          "odd_rows": (203, 128, 128)}


def _inputs(m, k, n, groups, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(m, k)), jnp.float32),
            jnp.asarray(rng.normal(size=(groups, n, k)) / np.sqrt(k),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(m, n)), jnp.float32))


def _scaled(sizes, m):
    """``sizes`` (laid out over 700 rows) over ``m`` rows, zeros kept."""
    if m == 700:
        return list(sizes)
    out = [s * m // 700 for s in sizes]
    return [max(o, 1) if s else 0 for o, s in zip(out, sizes)]


def _past(x, landed):
    """``x`` with NaN in the rows past the last group."""
    rows = np.arange(x.shape[0])[:, None]
    return np.where(rows < landed, np.asarray(x, np.float32), np.nan)


def _loop(rows, w, sizes, transposed=True):
    """``rows_g @ w[g].T`` (or ``@ w[g]``) a group; NaN past the last."""
    out = np.full((rows.shape[0], w.shape[1 if transposed else 2]), np.nan,
                  np.float32)
    at = 0
    for g, size in enumerate(sizes):
        mat = np.asarray(w[g], np.float32)
        out[at:at + size] = np.asarray(rows[at:at + size], np.float32) @ (
            mat.T if transposed else mat)
        at += size
    return out


def _loop_dw(grad, rows, sizes):
    out, at = [], 0
    for size in sizes:
        out.append(np.asarray(grad[at:at + size], np.float32).T
                   @ np.asarray(rows[at:at + size], np.float32))
        at += size
    return np.stack(out)


def _same(got, want, landed=None, tol=2e-5):
    if landed is not None:
        got, want = got[:landed], want[:landed]
        assert not np.isnan(want).any()
    scale = max(1.0, float(np.abs(want).max())) if want.size else 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


def _run(fn, *args, **kw):
    return np.asarray(jax.jit(functools.partial(fn, interpret=True, **kw))(
        *args), np.float32)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_visits_are_the_pairs_that_hold_rows(layout):
    sizes = LAYOUTS[layout]
    ends = np.cumsum(sizes)
    pairs = [(int(t), g) for g, (size, end) in enumerate(zip(sizes, ends))
             for t in range((end - size) // TILE, -(-end // TILE)) if size]
    for empty in (False, True):
        offsets, group, tile, count = (np.asarray(a) for a in pk._gmm_visits(
            jnp.asarray(sizes, jnp.int32), 700, TILE, empty))
        assert offsets.tolist() == [0] + ends.tolist()
        got = list(zip(tile[:count].tolist(), group[:count].tolist()))
        assert [p for p in got if sizes[p[1]]] == pairs
        blanks = [g for _, g in got if not sizes[g]]
        assert blanks == ([g for g, s in enumerate(sizes) if not s]
                          if empty else [])
        # in group order, and never more than the bound the lists have
        assert [g for _, g in got] == sorted(g for _, g in got)
        assert count <= group.shape[0] and (tile < -(-700 // TILE)).all()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_mx_gmm_matches_the_loop_both_ways_round(shape, layout):
    m, k, n = SHAPES[shape]
    sizes = _scaled(LAYOUTS[layout], m)
    landed = sum(sizes)
    rows, w, grad = _inputs(m, k, n, len(sizes))
    sz = jnp.asarray(sizes, jnp.int32)
    out = _run(pk._gmm_pallas, rows, w, sz, transposed=True)
    _same(_past(out, landed), _loop(rows, w, sizes), landed)
    # the rows' gradient: the same blocks read the other way
    back = _run(pk._gmm_pallas, grad, w, sz, transposed=False)
    _same(_past(back, landed), _loop(grad, w, sizes, False), landed)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_mx_gmm_dw_matches_the_loop_and_zeroes_empty_groups(shape, layout):
    m, k, n = SHAPES[shape]
    sizes = _scaled(LAYOUTS[layout], m)
    landed = sum(sizes)
    rows, _, grad = _inputs(m, k, n, len(sizes))
    # what lies past the last group must not reach any sum
    got = _run(pk._gmm_dw_pallas, jnp.asarray(_past(grad, landed)),
               jnp.asarray(_past(rows, landed)),
               jnp.asarray(sizes, jnp.int32))
    assert got.shape == (len(sizes), n, k)
    _same(got, _loop_dw(grad, rows, sizes), tol=1e-4)
    for g, size in enumerate(sizes):
        assert size or not got[g].any()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("shape", ["partial_n", "short"])
def test_the_custom_vjp_is_the_kernels(shape, layout):
    m, k, n = SHAPES[shape]
    sizes = _scaled(LAYOUTS[layout], m)
    landed = sum(sizes)
    rows, w, _ = _inputs(m, k, n, len(sizes), seed=1)
    sz = jnp.asarray(sizes, jnp.int32)
    mask = (jnp.arange(m) < landed)[:, None]

    def ours(rows, w):
        out = pk.grouped_matmul(rows, w, sz)
        return jnp.sum(jnp.sin(jnp.where(mask, out, 0)))

    def loop(rows, w):
        at, total = 0, 0.0
        for g, size in enumerate(sizes):
            total += jnp.sum(jnp.sin(jnp.dot(
                rows[at:at + size], w[g].T,
                precision=jax.lax.Precision.HIGHEST)))
            at += size
        return total
    value, (d_rows, d_w) = jax.value_and_grad(ours, (0, 1))(rows, w)
    want, (w_rows, w_w) = jax.value_and_grad(loop, (0, 1))(rows, w)
    np.testing.assert_allclose(value, want, rtol=1e-5, atol=1e-4)
    _same(_past(d_rows, landed), np.asarray(w_rows), landed, tol=1e-4)
    _same(np.asarray(d_w), np.asarray(w_w), tol=1e-4)
    # and the backward pass holds the two kernels by name, no transpose
    # of anything jax derived
    text = str(jax.make_jaxpr(jax.grad(ours, (0, 1)))(rows, w))
    assert text.count("name=mx_gmm_dw") and "ragged_dot" not in text


@pytest.mark.parametrize("layout", ["like_195_rows", "empty_middle",
                                    "all_on_one_group"])
def test_column_blocks_when_a_whole_matrix_does_not_fit(monkeypatch, layout):
    """With less room than a whole ``(N, K)`` block takes, N is cut into
    equal blocks of a multiple of 128 (the last one partial) and the
    results do not change."""
    m, k, n = 700, 256, 320
    def need(tn):
        return 2 * 4 * (TILE * k + tn * k + TILE * tn) + 4 * TILE * tn
    monkeypatch.setattr(pk, "_GMM_VMEM", need(n) - 1)
    assert pk._gmm_split(n, need, need(n)) == (n, 1)
    assert pk._gmm_split(n, need, need(n) - 1) == (256, 2)
    assert pk._gmm_split(n, need, need(256) - 1) == (128, 3)
    sizes = LAYOUTS[layout]
    landed = sum(sizes)
    rows, w, grad = _inputs(m, k, n, len(sizes), seed=2)
    sz = jnp.asarray(sizes, jnp.int32)
    _same(_past(_run(pk._gmm_pallas, rows, w, sz, transposed=True), landed),
          _loop(rows, w, sizes), landed)
    wide = jnp.swapaxes(w, 1, 2)                      # (G, K, N): N is cut
    _same(_past(_run(pk._gmm_pallas, rows, wide, sz, transposed=False),
                landed), _loop(rows, wide, sizes, False), landed)
    _same(_run(pk._gmm_dw_pallas, grad, rows, sz),
          _loop_dw(grad, rows, sizes), tol=1e-4)


def test_bfloat16_operands_accumulate_in_float32():
    m, k, n = 256, 512, 128
    sizes = [100, 0, 156]
    rows, w, grad = (x.astype(jnp.bfloat16) for x in _inputs(m, k, n, 3))
    sz = jnp.asarray(sizes, jnp.int32)
    out = jax.jit(functools.partial(pk._gmm_pallas, transposed=True,
                                    interpret=True))(rows, w, sz)
    assert out.dtype == jnp.bfloat16
    # one rounding of the float32 sum, not one a partial sum
    want = _loop(rows, w, sizes)
    _same(np.asarray(out, np.float32), want, tol=4e-3)
    dw = jax.jit(functools.partial(pk._gmm_dw_pallas, interpret=True))(
        grad, rows, sz)
    assert dw.dtype == jnp.bfloat16 and dw.shape == (3, n, k)
    _same(np.asarray(dw, np.float32), _loop_dw(grad, rows, sizes), tol=4e-3)
