"""Pallas TPU kernels: fused flash attention (forward AND backward), the
grouped matmul of the routed experts (``grouped_matmul``), and the kernels
that move rows into and out of the sorted layout around it (``rows_take``,
``rows_relu2`` / ``rows_swiglu``, ``rows_combine``, at the end).

The reference's fused-attention story is two CUDA kernels
(``_contrib_interleaved_matmul_selfatt_qk``/``_valatt``,
``src/operator/contrib/transformer.cc:650-780``) that still materialize
the (T, T) score matrix.  TPU-native replacement: Pallas kernels doing
blocked online-softmax attention (flash attention) — scores never leave
VMEM, HBM traffic is O(T·D) instead of O(T²), and the MXU sees back-to-
back (block_q × D)·(D × block_k) matmuls.

Backward is the standard two-pass flash backward (Dao et al.):
the forward saves (q, k, v, o, lse) — O(T·D) residuals — then one kernel
recomputes p blockwise to accumulate dq over k-blocks, and a second
accumulates dk/dv over q-blocks.  No (T, T) buffer exists in either
direction, so long-context TRAINING runs at O(T·D) memory; ring attention
(parallel/ring_attention.py) composes on top to shard T across chips.
The forward and dq kernels hold a head's whole K and V in VMEM (read from
HBM once a head; past the compiler's own 16 MiB they ask for a limit of
their own, ``_flash_params``: T=8192 at 192 / 128) and loop over key
blocks; the dk/dv kernel streams query blocks over a grid axis
into float32 accumulators (whole-T q, dO, lse and delta did not fit at
T=4096).  Under the causal mask no kernel computes a block that the mask
hides.  Values may be narrower than keys (latent attention: keys of 192
channels, values of 128): ``q, k (BH, T, D)``, ``v``, the result and
``dO (BH, T, Dv)``, nothing padded.  What a kernel does with one head
(``_flash_fwd_head``, ``_flash_dq_head``, ``_flash_dkv_pair``) takes the
channels in one part or several, a product each into one float32 score:
``ops/mla_kernels.py`` runs them over latent attention's operands where
the projections wrote them, keys of a head's ``nope`` channels and one
rope key that the heads share.

On non-TPU backends the kernels run through the Pallas interpreter
(tests).  Shapes that do not tile take plain jnp attention on every
platform; a kernel the TPU compiler refuses is an error.
"""
from __future__ import annotations

import collections
import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import sharding as _sharding
from .registry import register

_JIT_CACHE = {}

# minor-dim width for per-row scalars (lse, delta): TPU Mosaic tiles
# require the minor block dim to be a multiple of 128, so row scalars
# ride lane-broadcast as (..., t, 128) exactly like jax's own TPU flash
# kernels' l/m buffers
_LANES = 128


def _platform_pick(run, *args, off_tpu=None):
    """Compiled kernel on tpu; off it the Pallas interpreter, or
    ``off_tpu`` when given.

    ``jax.lax.platform_dependent`` picks when the computation is lowered
    (jax 0.9.0 lowers only the branch of the platform it compiles for),
    so the choice follows the devices the arrays live on or the jit is
    compiled for, not the process's default backend.  Always jitted,
    cached per kernel+attrs: un-jitted interpret-mode pallas dispatches
    one tiny executable per inner op per grid point — minutes instead of
    milliseconds.
    """
    key = (run.func, tuple(sorted(run.keywords.items())), off_tpu and
           (off_tpu.func, tuple(sorted(off_tpu.keywords.items()))))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(
            lax.platform_dependent,
            tpu=functools.partial(run, interpret=False),
            default=off_tpu or functools.partial(run, interpret=True)))
        _JIT_CACHE[key] = fn
    return fn(*args)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _blocks_seen(qi, block_q, block_k, t_kv, causal):
    """Key blocks that query block ``qi`` reads: all of them, or under the
    causal mask those that hold a column at or before its last row (a block
    past them adds exact zeros).  Under the block-diffusion mask the blocks
    are no range: ``_bd_visits`` walks them."""
    n_k = t_kv // block_k
    if not causal or n_k == 1:
        return n_k          # static: the loop is unrolled as it always was
    return jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k, n_k)


# -- the block-diffusion mask ------------------------------------------------
# The sequence is ``[x_0 | x_t]``, ``half`` tokens each, cut into blocks of
# ``block`` tokens.  A clean query ``i < half`` sees the clean keys of its
# block and the blocks before it; a noised query ``half + p`` sees the clean
# keys of the blocks strictly before its own and the noised keys of its own
# block.  With tiles of ``tile`` tokens that ``block`` divides (``n = half /
# tile`` a half), a clean query tile ``qi`` sees clean key tiles ``0 .. qi``,
# the last one partly; a noised query tile ``n + p`` sees clean tiles ``0 ..
# p``, the last one partly, and its own diagonal tile, partly.  Every other
# tile is dead: ``n (n + 2)`` of the ``4 n^2`` are computed.

# the static description of the block-diffusion mask
BlockDiffusion = collections.namedtuple("BlockDiffusion", "half block")


def _kernel_name(name, mask):
    """A kernel's name: the mask's kernels carry ``_bd`` after it."""
    return name if mask is None else name + "_bd"


def block_diffusion_mask(half, block):
    """``(2 half, 2 half)`` bool: which key each query sees (the dense form
    of the mask, for ``_attention_ref`` and the tests)."""
    i = jnp.arange(2 * half)
    noised, blk = i >= half, (i % half) // block
    rn, cn = noised[:, None], noised[None, :]
    rb, cb = blk[:, None], blk[None, :]
    return jnp.where(cn, rn & (cb == rb), jnp.where(rn, cb < rb, cb <= rb))


def bd_tiles(half, block, tile=None):
    """``(tiles of the grid, tiles the kernels compute)`` of
    ``flash_attention`` under the mask over ``2 half`` positions, at
    ``tile``-token tiles (the operator's own choice unless given): ``4 n^2``
    and ``n (n + 2)``; where the kernels do not take the shape (``bd_tile``;
    below 512 positions, where no tile is given, the operator is XLA's
    attention) every tile is computed, by the dense fallback."""
    t = 2 * half
    kernels = tile is not None or t >= 512
    tile = _tiles(t, int(tile) if tile else min(t, 512))
    if tile is None:
        return 1, 1
    n = half // tile
    grid = (t // tile) ** 2
    if not kernels or bd_tile(half, block, tile) is None:
        return grid, grid
    return grid, n * (n + 2)


def bd_tile(half, block, tile):
    """``tile`` where the kernels take the mask at it, else None: the tile
    divides a half, and ``block``, a power of two, divides the tile."""
    if tile is None or half % tile or tile % block or block & (block - 1):
        return None
    return tile


def _bd_visible(qi, ki, mask, tile):
    """Which pairs of query tile ``qi`` and key tile ``ki`` the mask leaves
    live, ``(tile, tile)`` bool: the blocks' distance ``d = key block -
    query block`` within the tiles (tiles start on a block's first token)
    must lie in ``[lo, hi]``: ``(-inf, 0]`` clean to clean, ``(-inf, -1]``
    noised to clean, ``[0, 0]`` noised to noised."""
    n = mask.half // tile
    shift = mask.block.bit_length() - 1

    def blocks(axis):
        return jnp.right_shift(
            lax.broadcasted_iota(jnp.int32, (tile, tile), axis), shift)
    d = blocks(1) - blocks(0)
    key_noised = ki >= n
    hi = jnp.where(key_noised | (qi < n), 0, -1)
    lo = jnp.where(key_noised, 0, -(tile + 1))
    return (d <= hi) & (d >= lo)


def _bd_visits(step, carry, qi, mask, tile):
    """``step(i, carry, visible=None)`` over the key tiles query tile ``qi``
    sees: its own diagonal tile first (every row sees itself there, so no
    row's running maximum stays -inf), for a noised tile the clean tile at
    its position (masked), then the clean tiles before that one, whole."""
    n = mask.half // tile
    noised = (qi >= n).astype(jnp.int32)
    own = qi - n * noised
    carry = step(qi, carry, _bd_visible(qi, qi, mask, tile))
    carry = lax.fori_loop(0, noised, lambda _, c: step(
        own, c, _bd_visible(qi, own, mask, tile)), carry)
    return lax.fori_loop(0, own, step, carry)


def _bd_query_tile(ki, i, n):
    """The ``i``-th query tile that key tile ``ki`` is seen by (the last one
    where ``i`` is past them): a clean key tile by the clean query tiles from
    its own on and then the noised ones from its position on, a noised key
    tile by its own diagonal tile alone."""
    clean = jnp.where(i < n - ki, ki + i, jnp.minimum(2 * ki + i, 2 * n - 1))
    return jnp.where(ki >= n, ki, clean)


def _scores(qs, ks):
    """``q k^T`` (bq, bk) in float32, the channels in one part or in several
    (latent attention: ``nope`` channels a head and ``rope`` channels shared
    by the heads), each part a product into the same sum."""
    s = None
    for q, k in zip(qs, ks):
        part = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        s = part if s is None else s + part
    return s


def _flash_fwd_head(qs, keys, value, t_kv, dv, qi, *, block_q, block_k,
                    causal, mask=None):
    """One head's query block against the key blocks it sees, the online
    softmax: ``qs`` the scaled float32 query block, a tuple of its channel
    parts; ``keys(i)`` key block ``i`` as the same parts and ``value(i)``
    its ``dv`` values, float32.  The running maximum ``m`` and sum ``l``
    ``(bq, 1)`` and the result not yet divided by ``l`` ``(bq, dv)``.
    ``mask`` (a ``BlockDiffusion``; ``causal`` False) walks the tiles that
    mask leaves live (``_bd_visits``)."""
    if mask is None:
        n_k = _blocks_seen(qi, block_q, block_k, t_kv, causal)
    row = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, carry, visible=None):
        m, l, acc = carry
        ks, v = keys(i), value(i)                       # (bk, D), (bk, Dv)
        s = _scores(qs, ks)
        if causal:
            col = i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, -jnp.inf)
        if visible is not None:
            s = jnp.where(visible, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dv), jnp.float32)
    if mask is not None:
        return _bd_visits(body, (m0, l0, acc0), qi, mask, block_q)
    return lax.fori_loop(0, n_k, body, (m0, l0, acc0))


def _flash_lse(m, l, safe_l):
    """logsumexp per row; -inf rows (fully masked) stored as -inf."""
    return jnp.where(l[:, 0] == 0, -jnp.inf, m[:, 0] + jnp.log(safe_l[:, 0]))


def _lane_rows(x, block_q):
    """A row scalar broadcast across a 128-lane minor dimension — TPU
    Mosaic requires block minor dims divisible by 128 (or full), so a
    bare (block_q,) output cannot tile; jax's own TPU flash kernels
    store l/m the same way (flash_attention.py MIN_BLOCK_SIZE)."""
    return lax.broadcast_in_dim(x, (block_q, _LANES), (0,))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                      block_k, scale, causal, mask=None):
    from jax.experimental import pallas as pl

    def block(ref, i):
        return ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32) * scale           # (bq, D)
    m, l, acc = _flash_fwd_head(
        (q,), lambda i: (block(k_ref, i),), lambda i: block(v_ref, i),
        k_ref.shape[1], v_ref.shape[-1], pl.program_id(1), block_q=block_q,
        block_k=block_k, causal=causal, mask=mask)
    safe_l = jnp.where(l == 0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = _lane_rows(_flash_lse(m, l, safe_l), block_q)


def _flash_params(k, v):
    """What the forward and dq kernels are compiled with: nothing where a
    head's K and V, double-buffered, leave the compiler's own 16 MiB of
    scoped VMEM room for the rest (every length to 4096 at 192 / 128); a
    limit that holds them where they do not (T=8192 at 192 / 128: 12 MiB
    of the v5e's 128).  Keeping a head's keys in VMEM reads them from HBM
    once a head; a grid axis over key blocks would read them once a query
    block, 84 MB a head at T=8192, as long as the products take."""
    from jax.experimental.pallas import tpu as pltpu

    def lanes(width):
        return -(-width // _LANES) * _LANES
    held = 2 * k.shape[1] * (lanes(k.shape[2]) * k.dtype.itemsize
                             + lanes(v.shape[2]) * v.dtype.itemsize)
    if held <= 8 << 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=held + (16 << 20))


def _sequence_params(t, lanes, itemsize, semantics=None):
    """A VMEM limit that holds what a step keeps: ``lanes`` lanes of whole
    sequences of ``t`` tokens, double-buffered, and 32 MiB for the streamed
    blocks, the accumulators and the bodies' float32 temporaries (a v5e has
    128 MiB; the compiler's own limit is 16): the kernels of
    ``mla_kernels`` and ``bd_kernels``, which hold a head's whole keys."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=2 * t * lanes * itemsize + (32 << 20))


def _flash_pallas(q, k, v, scale, causal, block_q, block_k,
                  interpret=False, mask=None):
    from jax.experimental import pallas as pl

    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        scale=scale, causal=causal)
    if mask is not None:
        kernel = functools.partial(kernel, mask=mask)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_kv, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, _LANES), jnp.float32),
        ],
        compiler_params=_flash_params(k, v),
        interpret=interpret,
        name=_kernel_name("mx_flash_fwd", mask),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq kernel (parallel over q blocks) + dkv kernel (over k blocks)
# ---------------------------------------------------------------------------


def _flash_dq_head(qs, do, lse, delta, keys, value, t_kv, qi, *, block_q,
                   block_k, scale, causal, mask=None):
    """One head's query block against the key blocks it sees: ``dq`` as the
    parts ``qs`` came in (float32, not scaled).  ``lse`` and ``delta``
    ``(bq, 1)``; ``keys``, ``value`` and ``mask`` as ``_flash_fwd_head``'s."""
    if mask is None:
        n_k = _blocks_seen(qi, block_q, block_k, t_kv, causal)
    row = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, dqs, visible=None):
        ks, v = keys(i), value(i)
        s = scale * _scores(qs, ks)
        if causal:
            col = i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, -jnp.inf)
        if visible is not None:
            s = jnp.where(visible, s, -jnp.inf)
        # p is the NORMALIZED probability (lse folds in the row sum);
        # fully-masked rows have lse=-inf -> exp(-inf - -inf) guarded to 0
        p = jnp.where(jnp.isfinite(lse), jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        ds = p * (dp - delta)
        return tuple(dq + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for dq, k in zip(dqs, ks))

    dqs = tuple(jnp.zeros((block_q, q.shape[-1]), jnp.float32) for q in qs)
    if mask is not None:
        return _bd_visits(body, dqs, qi, mask, block_q)
    return lax.fori_loop(0, n_k, body, dqs)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q, block_k, scale, causal,
                         mask=None):
    from jax.experimental import pallas as pl

    def block(ref, i):
        return ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
    dq, = _flash_dq_head(
        (q_ref[0].astype(jnp.float32),),                # (bq, D)
        do_ref[0].astype(jnp.float32),                  # (bq, Dv)
        lse_ref[0][:, :1], delta_ref[0][:, :1],         # (bq, 1) lane 0
        lambda i: (block(k_ref, i),), lambda i: block(v_ref, i),
        k_ref.shape[1], pl.program_id(1), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, mask=mask)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _first_block_seen(ki, block_q, block_k, causal):
    """The first query block that key block ``ki`` is seen by."""
    return (ki * block_k) // block_q if causal else 0


def _flash_dkv_pair(qs, ks, v, do, lse, delta, ki, qi, *, block_q, block_k,
                    scale, causal, visible=None):
    """One (key block, query block) pair of one head: ``(dks, dv)``, the
    pair's part of dk (the parts ``ks`` came in) and of dv, float32.  ``qs``
    and ``ks`` as ``_scores`` takes them, not scaled.  ``visible`` (bool
    ``(bq, bk)``; ``causal`` False) masks a pair the block-diffusion mask
    leaves partly live."""
    s = scale * _scores(qs, ks)                         # (bq, bk)
    if causal:
        col = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        row = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(col <= row, s, -jnp.inf)
    if visible is not None:
        s = jnp.where(visible, s, -jnp.inf)
    p = jnp.where(jnp.isfinite(lse), jnp.exp(s - lse), 0.0)
    dv = jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bk, Dv)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bq, bk)
    ds = p * (dp - delta)
    return tuple(scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for q in qs), dv    # (bk, D)


def _bd_dkv_visit(pair, dk_acc, dv_acc, ki, i, mask, tile):
    """Grid step ``i`` of key tile ``ki`` under the block-diffusion mask:
    query tile ``_bd_query_tile(ki, i)`` where ``i`` is one of the tiles
    that see it (the first ``2 (n - ki)`` steps of a clean tile, the first
    of a noised one), masked where the pair is partly live (the query tile
    at the key tile's position in either half), whole otherwise; past them
    nothing."""
    from jax.experimental import pallas as pl

    n = mask.half // tile
    qt = _bd_query_tile(ki, i, n)
    live = i < jnp.where(ki >= n, 1, 2 * (n - ki))
    partial_ = (qt == ki) | (qt == ki + n)

    @pl.when(live & partial_)
    def _():
        dk, dv = pair(_bd_visible(qt, ki, mask, tile))
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(live & jnp.logical_not(partial_))
    def _():
        dk, dv = pair()
        dk_acc[...] += dk
        dv_acc[...] += dv


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc=None, dv_acc=None, *,
                          block_q, block_k, scale, causal, mask=None):
    """One (key block, query block) pair a grid step: the query blocks
    stream through VMEM (whole-T operands do not fit at T=4096) and dk, dv
    accumulate in float32 scratch across the innermost grid axis.  Where T
    is one query block there is no scratch: the pair's result is written as
    it is (the kernel of T=512 as it always was)."""
    from jax.experimental import pallas as pl

    ki, qi = pl.program_id(1), pl.program_id(2)
    one_block = dk_acc is None          # T is one query block: no sum to keep

    def pair(visible=None):
        k = k_ref[0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0].astype(jnp.float32)                # (bk, Dv)
        q = q_ref[0].astype(jnp.float32)                # (bq, D)
        do = do_ref[0].astype(jnp.float32)              # (bq, Dv)
        (dk,), dv = _flash_dkv_pair(
            (q,), (k,), v, do, lse_ref[0][:, :1], delta_ref[0][:, :1], ki,
            qi, block_q=block_q, block_k=block_k, scale=scale, causal=causal,
            visible=visible)
        return dk, dv

    if one_block:
        dk, dv = pair()
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if mask is not None:
        _bd_dkv_visit(pair, dk_acc, dv_acc, ki, qi, mask, block_k)
    else:
        # under the causal mask a query block before the key block's first
        # row adds exact zeros: not computed
        @pl.when(qi >= _first_block_seen(ki, block_q, block_k, causal))
        def _():
            dk, dv = pair()
            dk_acc[...] += dk
            dv_acc[...] += dv

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, do, lse, delta, scale, causal, block_q,
                      block_k, interpret=False, mask=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_kv, dv = k.shape[1], v.shape[2]
    static = dict(block_q=block_q, block_k=block_k, scale=scale,
                  causal=causal)
    if mask is not None:
        static["mask"] = mask
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        grid=(bh, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_kv, dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        compiler_params=_flash_params(k, v),
        interpret=interpret,
        name=_kernel_name("mx_flash_bwd_dq", mask),
    )(q, k, v, do, lse, delta)

    def rows(width):
        # a query block the mask hides is not fetched: the index stays at
        # the first block that is seen (the block-diffusion mask: at the
        # last, its blocks being visited in order, ``_bd_query_tile``)
        if mask is not None:
            n = mask.half // block_q
            return pl.BlockSpec((1, block_q, width), lambda b, j, i: (
                b, _bd_query_tile(j, i, n), 0))
        return pl.BlockSpec(
            (1, block_q, width), lambda b, j, i: (b, jnp.maximum(
                i, _first_block_seen(j, block_q, block_k, causal)), 0))

    def cols(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, j, 0))
    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **static),
        grid=(bh, t_kv // block_k, t_q // block_q),
        in_specs=[rows(d), cols(d), cols(dv), rows(dv), rows(_LANES),
                  rows(_LANES)],
        out_specs=[cols(d), cols(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, dv), v.dtype),
        ],
        scratch_shapes=[] if t_q == block_q else [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_kernel_name("mx_flash_bwd_dkv", mask),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv_


def _attention_ref(q, k, v, scale, causal, mask=None):
    """Plain jnp attention (fallback for non-tiling shapes); ``mask`` a
    ``BlockDiffusion``, as a dense boolean."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_kv = s.shape[-2], s.shape[-1]
        row = jnp.arange(t_q)[:, None]
        col = jnp.arange(t_kv)[None, :]
        s = jnp.where(col <= row, s, -jnp.inf)
    if mask is not None:
        s = jnp.where(block_diffusion_mask(*mask), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _pallas_static(fn, scale, causal, block_q, block_k, mask):
    run = functools.partial(fn, scale=scale, causal=causal, block_q=block_q,
                            block_k=block_k)
    return run if mask is None else functools.partial(run, mask=mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, scale, causal, block_q, block_k, mask=None):
    run = _pallas_static(_flash_pallas, scale, causal, block_q, block_k, mask)
    out, _ = _platform_pick(run, q, k, v)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, mask=None):
    run = _pallas_static(_flash_pallas, scale, causal, block_q, block_k, mask)
    out, lse = _platform_pick(run, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, mask, res, g):
    q, k, v, out, lse = res
    # delta_i = sum_d dO_id * O_id  (rowwise), O(T*D) — the only
    # off-kernel piece of the two-pass flash backward.  Broadcast across
    # the 128-lane minor dim to match the lse residual's tiled layout.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    run = _pallas_static(_flash_bwd_pallas, scale, causal, block_q, block_k,
                         mask)
    dq, dk, dv = _platform_pick(run, q, k, v, g, lse, delta)
    return dq, dk, dv


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _flash_per_shard(mesh, axes, q, k, v, *static):
    """The kernel under ``shard_map`` on (B, H, T, D) over ``axes``, the
    context mesh's axes that GSPMD would otherwise partition: batch over
    ``sharding.batch_axis`` (the step's data axis), heads over the others
    (where Megatron rules put them), each only where it divides.  The
    specs decide how much GSPMD has to move, never the result; an axis
    left out of them makes every device along it run the same shard, and
    is warned about."""
    b, h = q.shape[:2]
    batch_axis = _sharding.batch_axis.value
    if batch_axis not in axes or b % mesh.shape[batch_axis]:
        batch_axis = None
    head_axes = tuple(a for a in axes if a != batch_axis)
    if h % math.prod(mesh.shape[a] for a in head_axes):
        head_axes = ()
    idle = [a for a in axes if a != batch_axis and a not in head_axes]
    if idle:
        warnings.warn(
            "flash_attention: batch %d / heads %d do not divide over mesh "
            "axes %s of %s (batch axis %r); the kernel runs replicated "
            "along them" % (b, h, idle, dict(mesh.shape),
                            _sharding.batch_axis.value), stacklevel=3)
    spec = P(batch_axis, head_axes or None, None, None)

    def body(q, k, v):
        b, h, t, _ = q.shape
        out = _flash_attention(*(x.reshape(b * h, -1, x.shape[-1])
                                 for x in (q, k, v)), *static)
        return out.reshape(b, h, t, -1)

    return jax.shard_map(body, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=set(axes), check_vma=False)(q, k, v)


def _tiles(t, preferred):
    """Largest workable block: divides ``t`` AND satisfies the Mosaic
    sublane rule (multiple of 8, or the full axis).  A user-preferred
    block that divides t but breaks the sublane rule is skipped in
    favor of the next conforming candidate rather than forcing the
    O(T^2) reference fallback."""
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= t and t % b == 0 and (b == t or b % 8 == 0):
            return b
    return None


@register("_contrib_flash_attention", inputs=("query", "key", "value"))
def flash_attention(query, key, value, scale=None, causal=False,
                    block_q=None, block_k=None, mask=None):
    """Fused multi-head attention, one Pallas kernel per (batch·head).

    Inputs (B, H, T, D) [or (BH, T, D)]; ``value`` may have another last
    dimension than ``query`` and ``key``, and the result has ``value``'s.
    Scores are computed blockwise with an online softmax; ``scale``
    defaults to 1/sqrt(D).  Falls back to plain XLA attention when T
    doesn't tile.
    Differentiable end-to-end via the blocked flash backward (no (T, T)
    buffer in forward or backward).

    ``block_q``/``block_k`` default to ``min(T, 512)``: the VMEM
    footprint per block at d<=128 stays under ~1MB, and 1024+ puts
    enough pressure on VMEM to serialize the pipeline.  Below T=512
    (and with no explicit block) the op is XLA's attention, not this
    kernel.  Both rules date from an earlier installation; today's
    cells stand on the Pallas side only, at T=512, 2048, 4096 (PR 34) and
    8192 (PR 36) (ROADMAP.md S17, R9), so neither the default nor the
    switch is measured.  Latent attention at shapes that tile does not
    come here: ``ops/mla_kernels.py`` runs the same bodies over the
    projections' own layout (PR 39).

    ``mask=("block_diffusion", T, L)`` (``causal`` False) is the mask of
    block diffusion over a sequence laid out ``[x_0 | x_t]``, ``2 T`` query
    and key positions, blocks of ``L`` tokens: a clean position ``i < T``
    sees the clean keys of its block and the blocks before it, a noised one
    ``T + p`` the clean keys of the blocks strictly before its own and the
    noised keys of its own block.  The kernels compute no tile the mask
    leaves dead (``n (n + 2)`` of ``4 n^2`` tiles, ``n`` a half's) where
    the tile divides ``T`` and ``L``, a power of two, divides the tile, and
    the query and key tiles are one size; any other shape takes plain
    attention under the dense mask.
    """
    if mask is not None:
        kind, half, blk = mask
        if kind != "block_diffusion" or causal:
            raise ValueError("flash_attention: mask %r: only "
                             "('block_diffusion', T, L), not causal" % (mask,))
        mask = BlockDiffusion(half, blk)
    squeeze = query.ndim == 3
    if squeeze:
        query, key, value = (x[:, None] if x.ndim == 3 else x
                             for x in (query, key, value))
    b, h, t_q, d = query.shape
    t_kv = key.shape[2]
    if scale is None or scale == 0:
        scale = 1.0 / (d ** 0.5)
    q3 = query.reshape(b * h, t_q, d)
    k3 = key.reshape(b * h, t_kv, d)
    v3 = value.reshape(b * h, t_kv, value.shape[-1])
    # short sequences go to XLA's attention: the (T,T) buffer is small
    # there.  No cell sits on this side of the switch (ROADMAP.md S17,
    # R9: a BERT-base seq-128 guard cell would).  Explicit block sizes
    # force the kernel (tests, tuning).
    if block_q is None and block_k is None and t_q < 512 and t_kv < 512:
        return _finish(_attention_ref(q3, k3, v3, scale, causal, mask),
                       b, h, t_q, squeeze)
    bq = _tiles(t_q, int(block_q) if block_q else min(t_q, 512))
    bk = _tiles(t_kv, int(block_k) if block_k else min(t_kv, 512))
    if mask is not None and (bq != bk or t_q != t_kv or t_q != 2 * mask.half
                             or bd_tile(mask.half, mask.block, bq) is None):
        bq = None
    if bq is None or bk is None:
        # a shape rule, on every platform: T has no block that both
        # divides it and satisfies the sublane rule
        out3 = _attention_ref(q3, k3, v3, scale, causal, mask)
    else:
        static = (float(scale), bool(causal), bq, bk) \
            + (() if mask is None else (mask,))
        # a program traced under a context mesh (JitTrainStep with a
        # mesh) will be partitioned by GSPMD, and the TPU lowering
        # refuses a Mosaic kernel there ("cannot be automatically
        # partitioned. Please wrap the call in a shard_map").  jax keys
        # its trace caches on the context mesh, so an eager trace of this
        # op is never reused inside such a program.
        # Axes a caller's own shard_map has already made manual are
        # not GSPMD's any more: there the kernel runs as it is.
        mesh = jax.sharding.get_abstract_mesh()
        axes = tuple(a for a in mesh.axis_names
                     if a not in mesh.manual_axes and mesh.shape[a] > 1)
        if axes:
            out3 = _flash_per_shard(mesh, axes, query, key, value, *static)
        else:
            out3 = _flash_attention(q3, k3, v3, *static)
    return _finish(out3, b, h, t_q, squeeze)


def _finish(out3, b, h, t_q, squeeze):
    out = out3.reshape(b, h, t_q, out3.shape[-1])
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# grouped matmul: rows sorted by group, one matrix a group
# ---------------------------------------------------------------------------

# the row tile: what the MXU of a v5e takes at once.  A group of 195 rows
# pays for two or three of them, one of 3072 for 24, and a group's matrix
# stays in VMEM across its consecutive tiles either way
_GMM_ROWS = 128
# what a call's blocks may take of VMEM (a v5e has 128 MiB; the compiler's
# own limit of 16 MiB holds no whole 2688 x 1856 matrix twice)
_GMM_VMEM = 96 << 20


def _gmm_params():
    from jax.experimental.pallas import tpu as pltpu

    # the blocks, and some room for what the compiler keeps beside them
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_GMM_VMEM + (8 << 20))


def _gmm_split(whole, need, room):
    """``(block, blocks)`` along a dimension that no product contracts:
    ``whole`` if ``need(block)`` bytes fit ``room``, else the fewest equal
    blocks of a multiple of 128 that do (the last one may be partial: what
    it reads past the edge lands in columns that are cut off again)."""
    blocks = 1
    while True:
        block = whole if blocks == 1 \
            else -(-whole // (blocks * _LANES)) * _LANES
        if need(block) <= room or block <= _LANES:
            return block, -(-whole // block)
        blocks += 1


def _gmm_visits(sizes, m, tm, empty):
    """The (row tile, group) pairs a grouped kernel walks, in order:
    ``(offsets (G + 1,), group (V,), tile (V,), count)``, all int32, of
    which the first ``count`` entries are pairs that hold rows (and, where
    ``empty``, one pair for each group without rows, so that its result is
    written).  ``V = tiles + G - 1`` bounds the count; the kernel's grid is
    ``count`` long, not ``V``."""
    groups = sizes.shape[0]
    tiles = -(-m // tm)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    first = (ends - sizes) // tm
    span = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, int(empty))
    visit_ends = jnp.cumsum(span, dtype=jnp.int32)
    visit = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= visit_ends[None, :], axis=1,
                dtype=jnp.int32), groups - 1)
    # a visit's tile: its group's first, and its place among the group's
    # visits (a sum over a one-hot row: a gather is a scalar loop on a TPU)
    mine = group[:, None] == jnp.arange(groups, dtype=jnp.int32)[None, :]
    tile = visit + jnp.sum(
        jnp.where(mine, (first - visit_ends + span)[None, :], 0), axis=1,
        dtype=jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, jnp.clip(tile, 0, tiles - 1), visit_ends[-1]


def _gmm_rows_of(offsets, groups, tiles, visit, tm, width):
    """Which rows of this visit's tile belong to this visit's group."""
    g = groups[visit]
    row = tiles[visit] * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, groups, tiles, lhs_ref, rhs_ref, out_ref, *, tm,
                transposed):
    from jax.experimental import pallas as pl

    mine = _gmm_rows_of(offsets, groups, tiles, pl.program_id(1), tm,
                        out_ref.shape[1])
    acc = lax.dot_general(
        lhs_ref[...], rhs_ref[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # a tile that two groups share is visited by each in turn and stays in
    # VMEM between them: each writes its own rows
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def _gmm_pallas(lhs, rhs, sizes, transposed, interpret=False):
    """``out[m] = lhs[m] @ rhs[group(m)]`` (``rhs (G, K, N)``), or ``@
    rhs[group(m)].T`` where ``transposed`` (``rhs (G, N, K)``).  Rows past
    the last group are not visited and stay undefined."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tm = min(_GMM_ROWS, m)
    size = lhs.dtype.itemsize
    tn, n_blocks = _gmm_split(
        n, lambda tn: 2 * size * (tm * k + tn * k + tm * tn) + 4 * tm * tn,
        _GMM_VMEM)
    offsets, groups, tiles, count = _gmm_visits(sizes, m, tm, empty=False)
    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, k),
                                lambda j, v, o, g, t: (g[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn),
                                lambda j, v, o, g, t: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks, count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_gmm_params(),
        interpret=interpret,
        name="mx_gmm",
    )(offsets, groups, tiles, lhs, rhs)


def _gmm_dw_kernel(offsets, groups, tiles, lhs_ref, rhs_ref, out_ref, acc_ref,
                   *, tm):
    from jax.experimental import pallas as pl

    visit = pl.program_id(1)
    g = groups[visit]

    @pl.when((visit == 0) | (groups[jnp.maximum(visit - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        # both sides: a neighbour's rows, or what lies past the last group
        # or the edge, must not meet the sum over rows
        lhs = jnp.where(
            _gmm_rows_of(offsets, groups, tiles, visit, tm,
                         lhs_ref.shape[1]), lhs_ref[...], 0)
        rhs = jnp.where(
            _gmm_rows_of(offsets, groups, tiles, visit, tm,
                         rhs_ref.shape[1]), rhs_ref[...], 0)
        acc_ref[...] += lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = pl.num_programs(1) - 1

    @pl.when((visit == last) | (groups[jnp.minimum(visit + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_dw_pallas(lhs, rhs, sizes, interpret=False):
    """``out[g] = lhs_g.T @ rhs_g`` over the rows of each group: ``lhs (M,
    N)``, ``rhs (M, K)``, ``out (G, N, K)``; zeros for a group without
    rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, n = lhs.shape
    k = rhs.shape[1]
    tm = min(_GMM_ROWS, m)
    size = lhs.dtype.itemsize
    tn, n_blocks = _gmm_split(
        n, lambda tn: 2 * size * (tm * tn + tm * k + tn * k) + 4 * tn * k,
        _GMM_VMEM)
    offsets, groups, tiles, count = _gmm_visits(sizes, m, tm, empty=True)
    return pl.pallas_call(
        functools.partial(_gmm_dw_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks, count),
            in_specs=[
                pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
                pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
            ],
            out_specs=pl.BlockSpec((None, tn, k),
                                   lambda j, v, o, g, t: (g[v], j, 0)),
            scratch_shapes=[pltpu.VMEM((tn, k), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], n, k), lhs.dtype),
        compiler_params=_gmm_params(),
        interpret=interpret,
        name="mx_gmm_dw",
    )(offsets, groups, tiles, lhs, rhs)


@jax.custom_vjp
def grouped_matmul(rows, w, sizes):
    """``out[m] = rows[m] @ w[g].T`` for the group ``g`` that row ``m`` is
    in: ``rows (M, K)`` sorted by group, ``w (G, N, K)``, ``sizes (G,)``
    int32 with ``sum(sizes) <= M``; ``out (M, N)`` in ``rows``' dtype,
    accumulated in float32.  The kernels (``mx_gmm``, ``mx_gmm_dw``) walk
    the (128-row tile, group) pairs that hold rows and no other, so a call
    costs what landed and not the bound ``M``; rows past the last group
    are not visited and stay undefined, here and in the gradient of
    ``rows``.  No one may read them: only a kernel that walks the same
    rows (another grouped product, ``rows_relu2``, ``rows_combine``, or
    ``rows_take``'s ``other``) takes such an array, never arithmetic of
    XLA's (``0 * nan`` is ``nan``)."""
    run = functools.partial(_gmm_pallas, transposed=True)
    return _platform_pick(run, rows, w, sizes)


def _grouped_matmul_fwd(rows, w, sizes):
    return grouped_matmul(rows, w, sizes), (rows, w, sizes)


def _grouped_matmul_bwd(res, g):
    rows, w, sizes = res
    d_rows = _platform_pick(functools.partial(_gmm_pallas, transposed=False),
                            g, w, sizes)
    d_w = _platform_pick(functools.partial(_gmm_dw_pallas), g, rows, sizes)
    return d_rows, d_w, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# ---------------------------------------------------------------------------
# rows into a sorted layout, along it, and out of it: the tiles that hold rows
# ---------------------------------------------------------------------------

# The sorted rows that hold something are the first ``count`` of ``M``.  These
# kernels walk their 128-row tiles and no other, as the grouped products do,
# so a call costs what landed.  A single row is no block and no slice of a
# tiled array (the compiler refuses both), so the array that is indexed by
# token lies whole in VMEM (all rows, the columns cut by ``_rows_split`` where
# they do not fit) and a row moves by a load and a store at a dynamic
# sublane, in float32: bfloat16 packs two rows a sublane.


def _rows_tiles(count, tm):
    """Tiles of ``tm`` sorted rows that hold something."""
    return (count[0] + tm - 1) // tm


def rows_walked(count, m):
    """Rows of a sorted layout of ``m`` that a ``rows_*`` kernel walks when
    the first ``count[0]`` hold something: those of the tiles that do."""
    tm = min(_GMM_ROWS, m)
    return _rows_tiles(count, tm) * tm


def _rows_split(d, rows, tm, tile_bytes):
    """``(block, blocks)`` along the columns: ``rows`` whole float32 rows of
    the array indexed by token, a float32 tile, and ``tile_bytes`` a column
    of the tiles that stream, all double-buffered."""
    return _gmm_split(
        d, lambda td: td * (8 * rows + 4 * tm + 2 * tm * tile_bytes),
        _GMM_VMEM)


def _rows_take_kernel(token, order, weight, count, src_ref, *refs, tm,
                      dotted, d):
    from jax.experimental import pallas as pl

    if dotted:
        other_ref, out_ref, dots_ref, buf, scale = refs
    else:
        out_ref, buf, scale = refs
    base = pl.program_id(1) * tm
    n = jnp.clip(count[0] - base, 0, tm)

    def one(r, carry):
        buf[pl.ds(r, 1), :] = src_ref[pl.ds(token[base + r], 1), :]
        scale[pl.ds(r, 1), :] = jnp.full((1, _LANES),
                                         weight[order[base + r]])
        return carry

    lax.fori_loop(0, n, one, 0)
    # selects, not products: the scratch past ``n`` holds anything
    held = lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < n
    rows = buf[...]
    out_ref[...] = jnp.where(held, rows * scale[:, :1], 0) \
        .astype(out_ref.dtype)
    if dotted:
        # nor the columns that a partial last block reads past the edge
        td = rows.shape[1]
        col = pl.program_id(0) * td + lax.broadcasted_iota(
            jnp.int32, (1, td), 1)
        dots_ref[0] = jnp.sum(jnp.where(
            held & (col < d), rows * other_ref[...].astype(jnp.float32), 0),
            axis=1, keepdims=True)


def _rows_take_pallas(src, token, order, weight, count, other=None, *, m,
                      dtype, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, d = src.shape
    tm = min(_GMM_ROWS, m)
    dotted = other is not None
    td, blocks = _rows_split(d, s, tm, jnp.dtype(dtype).itemsize + (
        other.dtype.itemsize if dotted else 0))
    tile = pl.BlockSpec((tm, td), lambda j, v, *_: (v, j))
    args = [token, order, weight, count, src]
    in_specs = [pl.BlockSpec((s, td), lambda j, v, *_: (0, j))]
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((m, d), dtype)]
    if dotted:
        # a column block's share of the dots: summed below
        args.append(other)
        in_specs.append(tile)
        out_specs.append(pl.BlockSpec((1, tm, 1),
                                      lambda j, v, *_: (j, v, 0)))
        out_shape.append(jax.ShapeDtypeStruct((blocks, m, 1), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_rows_take_kernel, tm=tm, dotted=dotted, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks, _rows_tiles(count, tm)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tm, td), jnp.float32),
                            pltpu.VMEM((tm, _LANES), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=_gmm_params(),
        interpret=interpret,
        name="mx_rows_take",
    )(*args)
    return (out[0], jnp.sum(out[1], axis=0)[:, 0]) if dotted else out[0]


def rows_take(src, token, order, weight, count, m, dtype, other=None):
    """Rows into a sorted layout: ``out[p] = weight[order[p]] *
    src[token[p]]`` for the sorted rows ``p < count[0]``, ``(M, D)`` in
    ``dtype``.  ``src (S, D)`` float32; ``token``, ``order (M,)`` int32 and
    ``weight (M,)`` float32 are read as scalars (sorted row ``p`` is
    assignment ``order[p]`` of token ``token[p]``); ``count (1,)`` int32.
    The kernel ``mx_rows_take`` walks the 128-row tiles that hold rows:
    the rows of the last one past ``count`` are zeros, the tiles past it
    are not visited and stay undefined.  With ``other (M, D)``, an array in
    the sorted layout, also ``dots (M,)`` float32: ``sum(src[token[p]] *
    other[p])``, zeros and undefined the same way."""
    run = functools.partial(_rows_take_pallas, m=int(m),
                            dtype=jnp.dtype(dtype))
    args = (src, token, order, weight, count)
    return _platform_pick(run, *args + (() if other is None else (other,)))


def _rows_combine_kernel(token, order, weight, count, rows_ref, out_ref, buf,
                         *, tm):
    from jax.experimental import pallas as pl

    visit = pl.program_id(1)
    base = visit * tm
    n = jnp.clip(count[0] - base, 0, tm)

    @pl.when(visit == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    buf[...] = rows_ref[...].astype(jnp.float32)

    def one(r, carry):
        at = pl.ds(token[base + r], 1)
        out_ref[at, :] += weight[order[base + r]] * buf[pl.ds(r, 1), :]
        return carry

    lax.fori_loop(0, n, one, 0)


def _rows_combine_pallas(rows, token, order, weight, count, *, s,
                         interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = rows.shape
    tm = min(_GMM_ROWS, m)
    td, blocks = _rows_split(d, s, tm, rows.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rows_combine_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # one visit where nothing landed, for the zeros
            grid=(blocks, jnp.maximum(_rows_tiles(count, tm), 1)),
            in_specs=[pl.BlockSpec((tm, td), lambda j, v, *_: (v, j))],
            out_specs=pl.BlockSpec((s, td), lambda j, v, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((tm, td), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
        compiler_params=_gmm_params(),
        interpret=interpret,
        name="mx_rows_combine",
    )(token, order, weight, count, rows)


def rows_combine(rows, token, order, weight, count, s):
    """Rows out of a sorted layout: ``out[t] = sum(weight[order[p]] *
    rows[p])`` over the sorted rows ``p < count[0]`` with ``token[p] ==
    t``, ``(S, D)`` float32, zeros for a token without one; the transpose
    of ``rows_take``.  ``rows (M, D)``; the rest as there.  The kernel
    ``mx_rows_combine`` walks the tiles that hold rows and reads no other
    row; the sum is float32, in the order of the sorted rows, into a
    result that lies in VMEM and is written once."""
    run = functools.partial(_rows_combine_pallas, s=int(s))
    return _platform_pick(run, rows, token, order, weight, count)


def _rows_relu2_kernel(count, hid_ref, *refs):
    *grad_ref, out_ref = refs
    relu = jnp.maximum(hid_ref[...].astype(jnp.float32), 0)
    if grad_ref:
        out = 2 * relu * grad_ref[0][...].astype(jnp.float32)
    else:
        out = relu * relu
    out_ref[...] = out.astype(out_ref.dtype)


def _rows_swiglu_kernel(count, hid_ref, *refs):
    *grad_ref, out_ref = refs
    f = hid_ref.shape[1] // 2
    gate = hid_ref[:, :f].astype(jnp.float32)
    up = hid_ref[:, f:].astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    if grad_ref:
        g = grad_ref[0][...].astype(jnp.float32)
        # d silu(x) = sig (1 + x (1 - sig))
        out_ref[:, :f] = (g * up * sig * (1 + gate * (1 - sig))) \
            .astype(out_ref.dtype)
        out_ref[:, f:] = (g * gate * sig).astype(out_ref.dtype)
    else:
        out_ref[...] = (gate * sig * up).astype(out_ref.dtype)


# an activation along the sorted layout: its kernel, the kernel's name, and
# the hidden columns a column of its result reads
_ROWS_ACT = {"relu2": (_rows_relu2_kernel, "mx_rows_relu2", 1),
             "swiglu": (_rows_swiglu_kernel, "mx_rows_swiglu", 2)}


def _rows_act_pallas(hid, count, grad=None, *, act, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel, name, fold = _ROWS_ACT[act]
    m, d = hid.shape
    tm = min(_GMM_ROWS, m)

    def tile(width):
        return pl.BlockSpec((tm, width), lambda v, c: (v, 0))
    wide = grad is not None
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_rows_tiles(count, tm),),
            in_specs=[tile(d)] + ([tile(d // fold)] if wide else []),
            out_specs=tile(d if wide else d // fold),
        ),
        out_shape=jax.ShapeDtypeStruct((m, d if wide else d // fold),
                                       hid.dtype),
        interpret=interpret,
        name=name,
    )(count, *((hid,) if grad is None else (hid, grad)))


_rows_relu2_pallas = functools.partial(_rows_act_pallas, act="relu2")
_rows_swiglu_pallas = functools.partial(_rows_act_pallas, act="swiglu")


def rows_relu2(hid, count, grad=None):
    """``relu(hid) ** 2`` along a sorted layout, or with ``grad`` its
    gradient ``grad * 2 * relu(hid)``: ``(M, F)`` in ``hid``'s dtype,
    computed in float32.  The kernel ``mx_rows_relu2`` walks the tiles that
    hold rows (``count (1,)`` int32); what a tile holds past ``count`` goes
    through row by row, the tiles past it stay undefined."""
    args = (hid, count) if grad is None else (hid, count, grad)
    return _platform_pick(_rows_relu2_pallas, *args)


def rows_swiglu(hid, count, grad=None):
    """``silu(hid[:, :F]) * hid[:, F:]`` along a sorted layout, ``hid (M,
    2F)`` holding gate and up side by side: ``(M, F)``; or with ``grad (M,
    F)`` the gradient with respect to ``hid``, ``(M, 2F)``.  The kernel
    ``mx_rows_swiglu``; the rest as ``rows_relu2``."""
    args = (hid, count) if grad is None else (hid, count, grad)
    return _platform_pick(_rows_swiglu_pallas, *args)
