"""The flash kernels under the block-diffusion mask (``ops/pallas_kernels.py``:
``flash_attention(mask=("block_diffusion", T, L))``, kernels
``mx_flash_fwd_bd``, ``mx_flash_bwd_dq_bd``, ``mx_flash_bwd_dkv_bd``) in the
interpreter, against plain attention under the dense boolean mask
(``_attention_ref``), over blocks of 4 and 16 tokens, tiles of 128 and 512
and float32 and bfloat16 operands; that no dead tile is read; and the tile
counts the counters export.

Tolerances.  In float32 the kernels and the reference are the same sums in
another order: 2e-5 of the largest value.  In bfloat16 both read the same
rounded operands and compute in float32; the kernels round their result
(and the gradients) to bfloat16: 2e-2.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import (BlockDiffusion, _attention_ref,
                                          bd_tiles, block_diffusion_mask,
                                          flash_attention)

D = 64


def _operands(half, dtype, seed=0, bh=(1, 2)):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=bh + (2 * half, D)), jnp.float32)
            .astype(dtype) for _ in range(4)]


def _ours(half, block, tile):
    return lambda q, k, v: flash_attention(
        q, k, v, block_q=tile, block_k=tile,
        mask=("block_diffusion", half, block))


def _ref(half, block):
    def attend(q, k, v):
        b, h, t, d = q.shape
        out = _attention_ref(*(x.reshape(b * h, t, d) for x in (q, k, v)),
                             d ** -0.5, False, BlockDiffusion(half, block))
        return out.reshape(b, h, t, d)
    return attend


def _close(ours, ref, tol):
    ours = np.asarray(ours, np.float32)
    ref = np.asarray(ref, np.float32)
    assert np.isfinite(ours).all()
    assert np.abs(ours - ref).max() <= tol * max(1.0, np.abs(ref).max())


def test_the_dense_mask_is_the_three_rules():
    # blocks of 2 over [x_0 | x_t] of 4 tokens each: a clean token sees its
    # block and the blocks before; a noised one the clean blocks strictly
    # before its own and its own noised block
    m = np.asarray(block_diffusion_mask(4, 2)).astype(int)
    assert m.tolist() == [
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [0, 0, 0, 0, 1, 1, 0, 0],
        [1, 1, 0, 0, 0, 0, 1, 1],
        [1, 1, 0, 0, 0, 0, 1, 1]]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block, tile", [(4, 128), (16, 128), (4, 512),
                                         (16, 512)])
def test_results_and_gradients_match_the_dense_mask(block, tile, dtype):
    # two tiles a half: every kind of tile (whole, partly live, dead) is there
    half, dtype = 2 * tile, jnp.dtype(dtype)
    q, k, v, w = _operands(half, dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    ours, ref = _ours(half, block, tile), _ref(half, block)
    text = str(jax.make_jaxpr(ours)(q, k, v))
    assert "mx_flash_fwd_bd" in text
    _close(ours(q, k, v), ref(q, k, v), tol)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                  * w.astype(jnp.float32))
    got = jax.grad(loss(ours), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, tol)


def test_no_dead_tile_is_read():
    # NaN in every row of one tile of q, k and v: a tile of outputs (and of
    # each gradient) that the mask does not tie to it stays finite, in the
    # forward, dq and dk/dv kernels alike (a dead tile read would spread the
    # NaN through a product; one inside a partly live tile through 0 * nan)
    half, tile, block = 256, 128, 4
    n = half // tile
    q, k, v, w = _operands(half, jnp.float32, seed=1)
    ours = _ours(half, block, tile)
    tiles = np.arange(2 * n)
    # tile a of the queries reads key tile b
    live = np.zeros((2 * n, 2 * n), bool)
    mask = np.asarray(block_diffusion_mask(half, block))
    for a in tiles:
        for b in tiles:
            live[a, b] = mask[a * tile:(a + 1) * tile,
                              b * tile:(b + 1) * tile].any()
    assert live.sum() == n * (n + 2)
    for planted in tiles:
        rows = slice(planted * tile, (planted + 1) * tile)

        def plant(x):
            return x.at[:, :, rows].set(jnp.nan)
        out = ours(q, plant(k), plant(v))
        for a in tiles:
            finite = np.isfinite(np.asarray(
                out[:, :, a * tile:(a + 1) * tile])).all()
            assert finite == (not live[a, planted]), (planted, a)
        dq, dk, dv = jax.grad(
            lambda q, k, v: jnp.sum(ours(q, k, v) * w), (0, 1, 2))(
                plant(q), k, v)
        # the planted query tile reaches only the key tiles it reads (dk
        # there takes the NaN; dv does not, its probabilities being 0 where
        # the logsumexp is not finite); no other query tile's dq is touched
        for b in tiles:
            finite = [np.isfinite(np.asarray(
                g[:, :, b * tile:(b + 1) * tile])).all() for g in (dk, dv)]
            assert finite[0] == (not live[planted, b]), (planted, b)
            assert finite[1] or live[planted, b], (planted, b)
        for a in tiles:
            if a != planted:
                assert np.isfinite(np.asarray(
                    dq[:, :, a * tile:(a + 1) * tile])).all()


def test_the_tile_counts():
    # the cell's shape: 2 x 4096 positions in 512-token tiles, 80 of 256
    assert bd_tiles(4096, 4) == (256, 80)
    assert bd_tiles(4096, 16, 128) == (4096, 32 * 34)
    # blocks that do not divide the tile, or are no power of two, and a
    # sequence below the kernels' 512 take the dense fallback: every tile
    assert bd_tiles(4096, 3) == (256, 256)
    assert bd_tiles(4096, 1024) == (256, 256)
    assert bd_tiles(128, 4) == (1, 1)


@pytest.mark.parametrize("half, block, tiles", [(96, 4, {}),
                                                (256, 3, {}),
                                                (256, 4, {"block_q": 128,
                                                          "block_k": 64})])
def test_shapes_the_kernels_do_not_take_fall_back_to_the_dense_mask(
        half, block, tiles):
    q, k, v, _ = _operands(half, jnp.float32, seed=2)
    ours = flash_attention(q, k, v, mask=("block_diffusion", half, block),
                           **tiles)
    assert "mx_flash" not in str(jax.make_jaxpr(
        lambda *a: flash_attention(*a, mask=("block_diffusion", half, block),
                                   **tiles))(q, k, v))
    _close(ours, _ref(half, block)(q, k, v), 2e-5)


def test_a_mask_is_not_causal():
    q, k, v, _ = _operands(128, jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True,
                        mask=("block_diffusion", 128, 4))
