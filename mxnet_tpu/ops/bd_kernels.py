"""Block-diffusion attention: the mask, the flash kernels that compute only
its live tiles with their operands read where the projections wrote them
(``_contrib_bd_flash_attention``), and plain attention under the dense
mask (``_contrib_bd_attention``).

A block-diffusion layer (``gluon.model_zoo.sdar_moe.BDAttention``) reads
``[x_0 | x_t]``, ``2T`` positions whose rotary positions are ``0 .. T-1`` in
each half, through GQA: ``H`` query heads and ``KV`` key and value heads of
``D`` channels, query head ``h`` reading key head ``h // (H / KV)``, under
the block-diffusion mask (``block_diffusion_mask``).  The composition that
the layer keeps for the shapes the kernels do not take (``tiles``) builds
``(B, H, 2T, D)`` arrays for ``bd_attention``: the normed queries and keys
transposed and turned in float32, K and V repeated to the query heads, the
result transposed back, and each of those again for the cotangents.  In
the kernels no such array exists:

- ``q (B, 2T, H * D)`` is the query projection's result as it lies, normed
  over each head's lanes by XLA in that layout (``_head_norm``): head ``h``
  of query tile ``i`` is the block ``(b, i, h)`` of whole 128-lane tiles.
  The forward kernel turns a query block as it loads it (float32, the
  rotation of halves: channel ``c`` and ``c + D / 2`` a pair, as
  ``llama._rope``), the angles' tables ``(T, D)`` an operand whose block
  is ``i mod (T / tile)``, and writes the turned block, float32, where the
  dq and dk/dv kernels read it (the dk/dv kernel would otherwise turn a
  query block again at every live pair); the dq kernel turns ``dq`` back
  before it writes the block ``(b, i, h)`` of ``(B, 2T, H * D)``.
- ``k, v (B, 2T, KV * D)`` are the key and value projections' results.  The
  key is normed and turned by XLA in that layout (an eighth of the queries'
  bytes at 32 / 4 heads) and rounded once to the queries' dtype.  Query
  head ``h`` reads key head ``h // (H / KV)`` by the index map: the forward
  and dq kernels hold it in VMEM across the group's heads and query tiles,
  fetched once.  The dk/dv kernel's grid walks a list of the mask's live
  (key tile, query head of the group, query tile) visits, prefetched as
  scalars (``_dkv_visits``), and sums ``dk`` and ``dv`` over a key tile's
  visits in float32 scratch, written once into ``(B, 2T, KV * D)``.
- the result is written at ``(b, i, h)`` of ``(B, 2T, H * D)``, where the
  output projection reads it; ``dO`` is read the same way, and ``delta =
  rowsum(dO * O)`` is made in the dq kernel, which holds both blocks.

The kernels run the SAME bodies as ``flash_attention``'s
(``_flash_fwd_head`` and ``_flash_dq_head`` over the live tiles that
``_bd_visits`` walks, ``_flash_dkv_pair`` over those of ``_dkv_visits``),
one head a grid step, under the names ``mx_flash_fwd_bd``,
``mx_flash_bwd_dq_bd`` and ``mx_flash_bwd_dkv_bd``.  Off a TPU they run in
the Pallas interpreter (tests).
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .pallas_kernels import (_LANES, _flash_dkv_pair, _flash_dq_head,
                             _flash_fwd_head, _flash_lse, _gspmd_axes,
                             _lane_rows, _platform_pick, _sequence_params,
                             _shard_axes, _tiles)
from .registry import register

# a dk/dv visit's flags: the first and the last of its key tile's visits,
# and a query tile that sees the key tile in part
_FIRST, _LAST, _PARTIAL = 1, 2, 4


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


def block_diffusion_mask(half, block):
    """``(2 half, 2 half)`` bool: which key each query sees (the dense form
    of the mask, for ``bd_attention`` and the tests)."""
    i = jnp.arange(2 * half)
    noised, blk = i >= half, (i % half) // block
    rn, cn = noised[:, None], noised[None, :]
    rb, cb = blk[:, None], blk[None, :]
    return jnp.where(cn, rn & (cb == rb), jnp.where(rn, cb < rb, cb <= rb))


def bd_tiles(half, block, tile=None):
    """``(tiles of the grid, tiles the kernels compute)`` under the mask
    over ``2 half`` positions, at ``tile``-token tiles (512 or fewer, as
    ``flash_attention`` chooses them, unless given): ``4 n^2`` and ``n (n +
    2)``; where the kernels do not take the shape (``bd_tile``; below 512
    positions, where no tile is given) every tile is computed."""
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
    """The walk of the forward and dq bodies: ``step(i, carry, visible)``
    or, where key tile ``i`` is whole, ``step(i, carry)`` over the key
    tiles query tile ``qi`` sees: its own diagonal tile first (every row
    sees itself there, so no row's running maximum stays -inf), for a
    noised tile the clean tile at its position (masked), then the clean
    tiles before that one, whole."""
    n = mask.half // tile
    noised = (qi >= n).astype(jnp.int32)
    own = qi - n * noised
    carry = step(qi, carry, _bd_visible(qi, qi, mask, tile))
    carry = lax.fori_loop(0, noised, lambda _, c: step(
        own, c, _bd_visible(qi, own, mask, tile)), carry)
    return lax.fori_loop(0, own, step, carry)


def tiles(heads, kv_heads, head_dim, half, block):
    """The tile where the kernels take these shapes, else None (the static
    test of ``BDAttention``): the tile ``flash_attention`` would choose at
    ``2 half`` positions, 512 or more, where ``_fits``."""
    t = 2 * half
    if t < 512:
        return None
    return _fits(heads, kv_heads, head_dim, half, block, _tiles(t, 512))


def _fits(heads, kv_heads, head_dim, half, block, tile):
    """``tile`` where the kernels take these shapes at it, else None: a head
    whole 128-lane tiles, the query heads a whole number of key heads'
    groups, and a tile that takes the mask (``bd_tile``).  A mesh does not
    matter: there the kernels run per shard (``_per_shard``)."""
    if head_dim % _LANES or heads % kv_heads \
            or bd_tile(half, block, tile) is None:
        return None
    return tile


def rope_tables(half, d, theta):
    """``(cos, sin)``, each ``(half, d)`` float32, at positions ``0 .. half
    - 1``, the angles as ``llama._rope`` makes them: ``cos`` at both
    channels of a pair, ``-sin`` at the first half's channel and ``sin`` at
    the second's."""
    rate = jnp.exp(jnp.arange(d // 2, dtype=jnp.float32) * (-2.0 / d)
                   * math.log(theta))
    phi = jnp.arange(half, dtype=jnp.float32)[:, None] * rate[None, :]
    cos, sin = jnp.cos(phi), jnp.sin(phi)
    return (jnp.concatenate([cos, cos], axis=1),
            jnp.concatenate([-sin, sin], axis=1))


def _heads(n, d):
    """The 0 / 1 indicator ``(n * d, n)`` of which head a lane is in."""
    return (jnp.arange(n * d)[:, None] // d
            == jnp.arange(n)[None, :]).astype(jnp.float32)


def _head_sums(x, e):
    """``x (..., n * d)`` summed over each head's lanes, float32."""
    return jnp.dot(x, e, precision=lax.Precision.HIGHEST)


def _spread(r, e):
    """``r (..., n)``, a number a head, over the head's lanes."""
    return jnp.dot(r, e.T, precision=lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _head_norm(x, gain, d, eps):
    """``RMSNorm`` over each head's ``d`` lanes of ``x (B, S, n * d)``,
    float32, in that layout: a head's mean square and its factor spread
    over the head's lanes are products with ``_heads``' indicator (as ``(B,
    S, n, d)`` the array would be relaid out, and the factor's broadcast
    written out, in float32).  The backward pass keeps ``x`` and the
    factor a head, and makes the rest again."""
    return _head_norm_fwd(x, gain, d, eps)[0]


def _head_norm_fwd(x, gain, d, eps):
    n = x.shape[-1] // d
    e, f = _heads(n, d), x.astype(jnp.float32)
    r = lax.rsqrt(_head_sums(f * f, e) / d + eps)           # (B, S, n)
    return f * _spread(r, e) * jnp.tile(gain, n), (x, gain, r)


def _head_norm_bwd(d, eps, res, dy):
    # the barrier keeps XLA from taking the forward's spread factor, a
    # float32 array of x's size, as this pass's: it is made again here
    x, gain, r = lax.optimization_barrier(res)
    n = x.shape[-1] // d
    e, f = _heads(n, d), x.astype(jnp.float32)
    spread = _spread(r, e)
    gx = dy * jnp.tile(gain, n)
    # d(f r)/df: r - f r^3 f^T / d within a head
    dx = spread * gx - f * _spread(r ** 3 * _head_sums(gx * f, e) / d, e)
    # sum over tokens of dy f r, a head's r against its own lanes: a
    # product, so that the spread factor is not written out for a sum
    by_head = jnp.einsum("bsh,bsl->hl", r, dy * f,
                         precision=lax.Precision.HIGHEST)        # (n, n d)
    dgain = jnp.sum(by_head * e.T, axis=0).reshape(n, d).sum(0)
    return dx.astype(x.dtype), dgain.astype(gain.dtype)


_head_norm.defvjp(_head_norm_fwd, _head_norm_bwd)


def _turn_keys(k, cos, sin, d):
    """``k (B, 2T, KV * d)`` float32 turned at positions ``0 .. T-1`` of
    each half, in that layout: a lane's partner ``d / 2`` lanes away within
    its head (two rolls and a select)."""
    b, t2, width = k.shape
    first = jnp.arange(width) % d < d // 2
    partner = jnp.where(first, jnp.roll(k, -(d // 2), axis=-1),
                        jnp.roll(k, d // 2, axis=-1))
    n = width // d
    turned = k.reshape(b, 2, t2 // 2, width) * jnp.tile(cos, (1, n)) \
        + partner.reshape(b, 2, t2 // 2, width) * jnp.tile(sin, (1, n))
    return turned.reshape(b, t2, width)


def _turn(x, cos, sin):
    """The rotation of halves on a float32 block ``(rows, d)`` in VMEM: a
    channel's partner is ``d / 2`` lanes away (one roll)."""
    from jax.experimental.pallas import tpu as pltpu

    return x * cos + pltpu.roll(x, x.shape[-1] // 2, 1) * sin


def _turn_back(g, cos, sin):
    """The cotangent of ``_turn``: the roll by half the lanes is its own
    inverse."""
    from jax.experimental.pallas import tpu as pltpu

    return g * cos + pltpu.roll(g * sin, g.shape[-1] // 2, 1)


def _key_blocks(k_ref, v_ref, tile):
    """``keys(i)`` and ``value(i)`` as the bodies ask them, from a head's
    whole keys and values in VMEM."""
    from jax.experimental import pallas as pl

    def rows(ref, i):
        return ref[0, pl.dslice(i * tile, tile), :].astype(jnp.float32)
    return lambda i: (rows(k_ref, i),), lambda i: rows(v_ref, i)


def _fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, lse_ref,
                qt_ref, *, tile, mask):
    """Also writes the turned query block, float32, for the backward
    kernels."""
    from jax.experimental import pallas as pl

    q = _turn(q_ref[0].astype(jnp.float32), cos_ref[...], sin_ref[...])
    qt_ref[0] = q
    m, l, acc = _flash_fwd_head(
        (q * q.shape[-1] ** -0.5,), *_key_blocks(k_ref, v_ref, tile),
        k_ref.shape[1], v_ref.shape[2], pl.program_id(2), block_q=tile,
        block_k=tile, causal=False,
        walk=functools.partial(_bd_visits, mask=mask, tile=tile))
    safe_l = jnp.where(l == 0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0, 0] = _lane_rows(_flash_lse(m, l, safe_l), tile)


def _dq_kernel(qt_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, cos_ref,
               sin_ref, dq_ref, delta_ref, *, tile, mask):
    """Also writes ``delta_i = sum_d dO_id O_id`` a row, as the logsumexp
    lies, for the dk/dv kernel."""
    from jax.experimental import pallas as pl

    do = do_ref[0].astype(jnp.float32)
    delta = jnp.sum(do * o_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True)
    delta_ref[0, 0] = jnp.broadcast_to(delta, (tile, _LANES))
    dq, = _flash_dq_head(
        (qt_ref[0],), do, lse_ref[0, 0][:, :1], delta,
        *_key_blocks(k_ref, v_ref, tile), k_ref.shape[1], pl.program_id(2),
        block_q=tile, block_k=tile, scale=qt_ref.shape[2] ** -0.5,
        causal=False, walk=functools.partial(_bd_visits, mask=mask,
                                             tile=tile))
    dq_ref[0] = _turn_back(dq, cos_ref[...], sin_ref[...]).astype(
        dq_ref.dtype)


def _visit_bits(n, group):
    """The bits of a packed visit's query or key tile (``2 n`` of them) and
    of its query head (``group``)."""
    return (2 * n - 1).bit_length(), (group - 1).bit_length()


def _dkv_visits(n, group):
    """The dk/dv kernel's visits at ``n`` tiles a half and ``group`` query
    heads a key head, in the order the grid walks them: each key tile, each
    of the group's query heads, the query tiles that see the key tile (a
    clean key tile the clean query tiles from its own on and then the noised
    ones from its position on, a noised one its own diagonal tile alone).
    ``group n (n + 2)`` entries, int32, each ``((key tile, query tile),
    head, flags)`` packed (``_visit`` unpacks it); the flags ``_FIRST`` and
    ``_LAST`` fall on a key tile's first and last visits, ``_PARTIAL``
    where the query tile is the key tile's own in either half, the only
    pairs the mask leaves partly live."""
    tile_bits, head_bits = _visit_bits(n, group)
    if 3 + head_bits + 2 * tile_bits > 31:
        raise ValueError("%d tiles a half and %d heads a key head do not "
                         "pack in 31 bits" % (n, group))
    visits = []
    for ki in range(2 * n):
        seen = [ki] if ki >= n else [*range(ki, n), *range(n + ki, 2 * n)]
        for h in range(group):
            for i, qi in enumerate(seen):
                flags = (_FIRST * (h == i == 0)
                         | _LAST * (h == group - 1 and i == len(seen) - 1)
                         | _PARTIAL * (qi in (ki, ki + n)))
                visits.append(
                    ((((ki << tile_bits) | qi) << head_bits | h) << 3)
                    | flags)
    return np.asarray(visits, np.int32)


def _visit(entry, n, group):
    """``(key tile, query head of the group, query tile, flags)`` of a
    packed visit of ``_dkv_visits``."""
    tile_bits, head_bits = _visit_bits(n, group)
    rest = entry >> 3
    return (rest >> (head_bits + tile_bits),
            rest & ((1 << head_bits) - 1),
            (rest >> head_bits) & ((1 << tile_bits) - 1), entry & 7)


def dkv_steps(heads, kv_heads, half, tile):
    """The dk/dv kernel's grid steps a sequence: a visit of the mask's live
    pairs for each key head."""
    return kv_heads * len(_dkv_visits(half // tile, heads // kv_heads))


def _dkv_kernel(visits, qt_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, tile, mask, group):
    """One visit of ``_dkv_visits`` a grid step, a live (key tile, query
    head and tile) pair: ``dk``, ``dv`` sum in float32 scratch over a key
    tile's visits, zeroed at the first and written at the last."""
    from jax.experimental import pallas as pl

    ki, _, qi, flags = _visit(visits[pl.program_id(2)], mask.half // tile,
                              group)

    @pl.when((flags & _FIRST) != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def add(visible=None):
        (dk,), dv = _flash_dkv_pair(
            (qt_ref[0],), (k_ref[0].astype(jnp.float32),),
            v_ref[0].astype(jnp.float32), do_ref[0].astype(jnp.float32),
            lse_ref[0, 0][:, :1], delta_ref[0, 0][:, :1], ki, qi,
            block_q=tile, block_k=tile, scale=qt_ref.shape[2] ** -0.5,
            causal=False, visible=visible)
        dk_acc[...] += dk
        dv_acc[...] += dv

    partial_ = (flags & _PARTIAL) != 0
    pl.when(partial_)(lambda: add(_bd_visible(qi, ki, mask, tile)))
    pl.when(jnp.logical_not(partial_))(add)

    @pl.when((flags & _LAST) != 0)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(d, group, tile, t2, mask, dkv=False):
    """The block specs of the operands by name.  ``dkv``: the dk/dv kernel's
    grid ``(b, key head, visit)`` over the prefetched ``_dkv_visits``, which
    give the key tile, the group's query head and the query tile; else
    ``(b, head, query tile)`` with a key head's whole keys and values as one
    block."""
    from jax.experimental import pallas as pl

    n = mask.half // tile
    if dkv:
        key_rows = tile

        def where(b, c, v, visits):
            ki, h, qi, _ = _visit(visits[v], n, group)
            return b, c * group + h, qi, ki
    else:
        key_rows = t2

        def where(b, h, i):
            return b, h, i, 0

    def spec(shape, pick):      # pick(b, head, query tile, key tile)
        return pl.BlockSpec(shape, lambda *ids: pick(*where(*ids)))
    return {
        "rows": spec((1, tile, d), lambda b, h, q, k: (b, q, h)),
        "stats": spec((1, 1, tile, _LANES), lambda b, h, q, k: (b, h, q, 0)),
        "angles": spec((tile, d), lambda b, h, q, k: (q % n, 0)),
        "keys": spec((1, key_rows, d), lambda b, h, q, k: (b, k, h // group))}


def _fwd_pallas(q, k, v, cos, sin, *, heads, mask, tile, interpret=False):
    """``(out (B, 2T, H * D), lse (B, H, 2T, 128), the turned queries (B,
    2T, H * D) float32)``."""
    from jax.experimental import pallas as pl

    b, t2, width = q.shape
    d = width // heads
    group = heads // (k.shape[2] // d)
    s = _specs(d, group, tile, t2, mask)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile, mask=mask),
        grid=(b, heads, t2 // tile),
        in_specs=[s["rows"], s["keys"], s["keys"], s["angles"],
                  s["angles"]],
        out_specs=[s["rows"], s["stats"], s["rows"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, t2, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct(q.shape, jnp.float32)],
        compiler_params=_sequence_params(t2, 2 * d, k.dtype.itemsize),
        interpret=interpret,
        name="mx_flash_fwd_bd",
    )(q, k, v, cos, sin)


def _bwd_pallas(qt, k, v, out, do, lse, cos, sin, *, heads, mask, tile,
                interpret=False):
    """``(dq, dk, dv)`` as their primals lie (``dq`` as the result), from
    the turned queries ``qt``.  The dq kernel runs first: it also writes
    ``delta``, which the dk/dv kernel reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t2, width = qt.shape
    d = width // heads
    kv = k.shape[2] // d
    group = heads // kv
    s = _specs(d, group, tile, t2, mask)
    dq, delta = pl.pallas_call(
        functools.partial(_dq_kernel, tile=tile, mask=mask),
        grid=(b, heads, t2 // tile),
        in_specs=[s["rows"], s["keys"], s["keys"], s["rows"], s["rows"],
                  s["stats"], s["angles"], s["angles"]],
        out_specs=[s["rows"], s["stats"]],
        out_shape=[jax.ShapeDtypeStruct(out.shape, out.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        compiler_params=_sequence_params(t2, 2 * d, k.dtype.itemsize),
        interpret=interpret,
        name="mx_flash_bwd_dq_bd",
    )(qt, k, v, out, do, lse, cos, sin)

    s = _specs(d, group, tile, t2, mask, dkv=True)
    visits = _dkv_visits(mask.half // tile, group)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, tile=tile, mask=mask, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, len(visits)),
            in_specs=[s["rows"], s["keys"], s["keys"], s["rows"],
                      s["stats"], s["stats"]],
            out_specs=[s["keys"], s["keys"]],
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mx_flash_bwd_dkv_bd",
    )(jnp.asarray(visits), qt, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bd_flash(q, k, v, heads, theta, mask, tile):
    return _bd_flash_fwd(q, k, v, heads, theta, mask, tile)[0]


def _bd_flash_fwd(q, k, v, heads, theta, mask, tile):
    run = functools.partial(_fwd_pallas, heads=heads, mask=mask, tile=tile)
    out, lse, qt = _platform_pick(run, q, k, v, *rope_tables(
        mask.half, q.shape[2] // heads, theta))
    return out, (qt, k, v, out, lse)


def _bd_flash_bwd(heads, theta, mask, tile, res, g):
    qt, k, v, out, lse = res
    run = functools.partial(_bwd_pallas, heads=heads, mask=mask, tile=tile)
    return _platform_pick(run, qt, k, v, out, g, lse, *rope_tables(
        mask.half, qt.shape[2] // heads, theta))


_bd_flash.defvjp(_bd_flash_fwd, _bd_flash_bwd)


@register("_contrib_bd_flash_attention",
          inputs=("query", "key", "value", "q_norm", "k_norm"))
def bd_flash_attention(query, key, value, q_norm, k_norm, num_heads=1,
                       block_length=4, rope_theta=10000.0, eps=1e-6):
    """Attention under the block-diffusion mask over ``[x_0 | x_t]`` in the
    projections' own, token-major layout: ``query (B, 2T, H * D)``, ``key``
    and ``value (B, 2T, KV * D)``, a head's channels side by side; query
    head ``h`` reads key head ``h // (H / KV)``.  The result ``(B, 2T, H *
    D)``.  Query and key are first normed over each head's channels
    (``RMSNorm`` with the gains ``q_norm``, ``k_norm (D,)`` and ``eps``; by
    XLA in float32 in their layout, rounded once to the query's dtype), then
    turned by the rotation of halves at positions ``0 .. T-1`` of each half
    (``rope_theta``; float32 angles as ``llama._rope``'s): the queries
    inside the kernels, the key by XLA in its layout before it is rounded
    (under AMP the gains stay float32: ``amp.lists.TARGET_DTYPE_KEEP``).
    Blocks of ``block_length`` tokens, the scores at ``D ** -0.5``.  The
    shapes have to tile (``tiles``): the caller tests them.  Under a
    context mesh the kernels run per shard (``_per_shard``)."""
    heads, block = int(num_heads), int(block_length)
    b, t2, width = query.shape
    d = q_norm.shape[0]
    tile = None
    if not (width != heads * d or t2 % 2 or key.shape[2] % d
            or value.shape != key.shape):
        tile = tiles(heads, key.shape[2] // d, d, t2 // 2, block)
    if tile is None:
        raise ValueError(
            "bd_flash_attention: %d heads over %d channels, keys of %d, at "
            "%d positions in blocks of %d do not tile"
            % (heads, width, key.shape[2], t2, block))
    static = (block, float(rope_theta), eps, tile)
    mesh, axes = _gspmd_axes()
    if axes:
        return _per_shard(mesh, axes, query, key, value, q_norm, k_norm,
                          heads, *static)
    return _attend(query, key, value, q_norm, k_norm, heads, *static)


def _per_shard(mesh, axes, query, key, value, q_norm, k_norm, heads,
               *static):
    """``_attend`` under ``shard_map`` over the mesh's ``axes``
    (``pallas_kernels._shard_axes``): the batch over the step's data axis,
    the heads' lanes over the others where the key heads divide over them
    (a shard holds whole groups: query heads ``h`` and their key head)."""
    d = q_norm.shape[0]
    batch_axis, head_axes = _shard_axes("bd_flash_attention", mesh, axes,
                                        query.shape[0], key.shape[2] // d)
    split = math.prod(mesh.shape[a] for a in head_axes)
    rows = P(batch_axis, None, head_axes or None)

    def body(q, k, v, q_norm, k_norm):
        return _attend(q, k, v, q_norm, k_norm, heads // split, *static)

    return jax.shard_map(body, in_specs=(rows,) * 3 + (P(),) * 2,
                         out_specs=rows, axis_names=set(axes),
                         check_vma=False)(query, key, value, q_norm, k_norm)


def _attend(query, key, value, q_norm, k_norm, heads, block, theta, eps,
            tile):
    """``bd_flash_attention`` at the tile ``tile``, the shapes tested."""
    t2, d = query.shape[1], q_norm.shape[0]
    q = _head_norm(query, q_norm, d, eps).astype(query.dtype)
    k = _turn_keys(_head_norm(key, k_norm, d, eps),
                   *rope_tables(t2 // 2, d, theta), d)
    return _bd_flash(q, k.astype(query.dtype), value, heads, theta,
                     BlockDiffusion(t2 // 2, block), tile)


@register("_contrib_bd_attention", inputs=("query", "key", "value"))
def bd_attention(query, key, value, block_length=4):
    """Plain attention under the dense block-diffusion mask over ``[x_0 |
    x_t]``: ``query``, ``key`` and ``value (B, H, 2T, D)``, blocks of
    ``block_length`` tokens, the scores at ``D ** -0.5`` and their softmax
    in float32, the result in the query's dtype.
    ``BDAttention``'s composition for the shapes the kernels do not take
    (``tiles``), and what the kernels are held to."""
    t2, d = query.shape[2], query.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", query.astype(jnp.float32),
                   key.astype(jnp.float32)) * d ** -0.5
    s = jnp.where(block_diffusion_mask(t2 // 2, int(block_length)), s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, value.astype(jnp.float32)) \
        .astype(query.dtype)
