"""Latent attention through the flash kernels, its operands read where the
projections wrote them (``_contrib_mla_flash_attention``).

A latent attention layer makes, a token: ``q = W_qb c_q`` as ``[nope |
rope]`` a head, ``kv = W_kvb c`` as ``[k_nope | v]`` a head, and ONE rope key
``k_rope`` that every head shares; the score of head ``h`` is ``q_nope
k_nope^T + rot(q_rope) rot(k_rope)^T``.  The composition it replaces built
``(B, H, T, .)`` arrays for ``pallas_kernels.flash_attention``: a transpose
of ``q``, a rotation pass over it, a transpose of ``kv``, its keys
concatenated with ``k_rope`` repeated over the heads, its values sliced out,
and the result transposed back (1.1 GB a layer at 8192 tokens and 32 heads,
and as much again for the cotangents).  Here no such array exists:

- ``kv (B, T, H * (nope + vd))`` is ``W_kvb``'s result as it lies.  Head
  ``h``'s keys are lanes ``h * (nope + vd) .. + nope`` and its values the
  ``vd`` after them, whole 128-lane tiles that a block's index map and a
  static slice reach.  The dk/dv kernel writes ``dk_nope`` and ``dv`` into
  the same lanes of ONE array, which is ``W_kvb``'s cotangent.
- ``k_rope (B, T, rope)`` is one operand whose block index does not depend
  on the head: fetched once, never repeated.  ``dk_rope`` is summed over the
  heads in float32 in the dk/dv kernel's output block, which stays in VMEM
  along the (sequential) head axis.
- the queries come as two arrays, ``q_nope (B, T, H * nope)`` and ``q_rope
  (B, T, H * rope)``: a head's 192 lanes are no whole number of lane tiles,
  so the WEIGHT ``W_qb`` is split by rows (``MLAMixer``) and the activation
  never is.  The kernels turn a query block's rope channels as they load it
  (float32, ``rope_angles``' tables as operands) and the dq kernel turns
  ``dq_rope`` back before it writes: q and dq cross HBM only as the
  products' results and the kernels' operands.
- the result is written at ``(b, i, h)`` of ``(B, T, H * vd)``, where ``W_o``
  reads it.

A grid step holds ``group`` heads, as many as make a step's rope channels
whole lane tiles (two at ``rope`` 64): grid ``(B, H / group, T / block_q)``
for the forward and dq kernels, which keep the group's ``kv`` and the rope
key in VMEM across the query blocks, ``(B, H / group, T / block_k, T /
block_q)`` for the dk/dv kernel, which streams query blocks into float32
accumulators.  Inside a step the heads run one after the other through the
SAME bodies as ``pallas_kernels``' kernels (``_flash_fwd_head``,
``_flash_dq_head``, ``_flash_dkv_pair``: online softmax, float32 statistics,
blocks the causal mask hides not computed), the score summed from two
products.  So that no slice falls inside a lane tile, the rope key is given
``group`` times side by side and a head's rope product runs over the group's
``group * rope`` lanes with the other heads' lanes of the query zeroed: the
same sum, the same passes of a 128-wide matrix unit.

Off a TPU the kernels run in the Pallas interpreter (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import (_LANES, _first_block_seen, _flash_dkv_pair,
                             _flash_dq_head, _flash_fwd_head, _flash_lse,
                             _lane_rows, _platform_pick, _sequence_params,
                             _tiles)
from .registry import register
from .rotary import rope_angles, rotate_pairs


def tiles(heads, nope, rope, vd, t, block_q=None, block_k=None):
    """``(block_q, block_k, group)`` where the kernels take these shapes,
    else None (the static test of ``MLAMixer``): ``nope`` and ``vd`` whole
    128-lane tiles, ``rope`` 64 or a multiple, ``group`` heads a grid step
    (two where ``rope`` is an odd multiple of 64) dividing ``heads``, ``t``
    tiling as ``flash_attention`` asks (512 tokens or more, unless blocks
    are given), and no mesh axis that GSPMD would partition: a Mosaic
    kernel cannot be partitioned, and under a mesh the composition runs per
    shard through ``flash_attention``."""
    group = 2 if rope % _LANES else 1
    if nope % _LANES or vd % _LANES or rope % (_LANES // 2) or not rope \
            or heads % group:
        return None
    if block_q is None and block_k is None and t < 512:
        return None
    bq = _tiles(t, int(block_q) if block_q else min(t, 512))
    bk = _tiles(t, int(block_k) if block_k else min(t, 512))
    if bq is None or bk is None:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if any(mesh.shape[a] > 1 for a in mesh.axis_names
           if a not in mesh.manual_axes):
        return None
    return bq, bk, group


def _partner(x):
    """Every lane's neighbour in its pair: ``x (rows, lanes)``, ``lanes``
    whole 128-lane tiles, adjacent lanes a pair (two rolls along the lanes
    and a select, as ``rotary.rotate_pairs`` does it)."""
    from jax.experimental.pallas import tpu as pltpu

    even = lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
    return jnp.where(even, pltpu.roll(x, x.shape[-1] - 1, 1),
                     pltpu.roll(x, 1, 1))


def _turn(x, cos, sin):
    """``rotary.rotate_pairs`` on a float32 block in VMEM."""
    return x * cos + _partner(x) * sin


def _turn_back(g, cos, sin):
    """The cotangent of ``_turn``: a pair's exchange is its own transpose."""
    return g * cos + _partner(g * sin)


def _head_lanes(shape, g, rope):
    """Where head ``g`` of a step's group has its rope channels."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= g * rope) & (lane < (g + 1) * rope)


def _rope_query(qr_ref, angles):
    """A step's rope channels ``(bq, group * rope)`` float32, turned where
    the angles' tables are operands."""
    qr = qr_ref[0].astype(jnp.float32)
    if angles:
        qr = _turn(qr, angles[0][...], angles[1][...])
    return qr


def _head_blocks(kv_ref, kr_ref, at, nope, vd, block_k):
    """``keys(i)`` and ``value(i)`` as the bodies ask them, of the head whose
    lanes of a group's ``kv`` begin at ``at``: key block ``i`` as its
    ``nope`` channels and the step's rope lanes, and its values."""
    from jax.experimental import pallas as pl

    def rows(ref, i, lanes):
        return ref[0, pl.dslice(i * block_k, block_k), lanes] \
            .astype(jnp.float32)
    return (lambda i: (rows(kv_ref, i, slice(at, at + nope)),
                       rows(kr_ref, i, slice(None))),
            lambda i: rows(kv_ref, i, slice(at + nope, at + nope + vd)))


def _fwd_kernel(qn_ref, qr_ref, kv_ref, kr_ref, *rest, group, nope, rope,
                vd, block_q, block_k, scale, causal):
    from jax.experimental import pallas as pl

    *angles, o_ref, lse_ref = rest
    qr = _rope_query(qr_ref, angles) * scale
    for g in range(group):
        qn = qn_ref[0, :, g * nope:(g + 1) * nope].astype(jnp.float32)
        m, l, acc = _flash_fwd_head(
            (qn * scale, jnp.where(_head_lanes(qr.shape, g, rope), qr, 0.0)),
            *_head_blocks(kv_ref, kr_ref, g * (nope + vd), nope, vd, block_k),
            kv_ref.shape[1], vd, pl.program_id(2), block_q=block_q,
            block_k=block_k, causal=causal)
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0, :, g * vd:(g + 1) * vd] = (acc / safe_l).astype(o_ref.dtype)
        lse_ref[0, g] = _lane_rows(_flash_lse(m, l, safe_l), block_q)


def _dq_kernel(qn_ref, qr_ref, kv_ref, kr_ref, o_ref, do_ref, lse_ref, *rest,
               group, nope, rope, vd, block_q, block_k, scale, causal):
    """Also writes ``delta_i = sum_d dO_id O_id`` a row and head, as the
    logsumexp lies, for the dk/dv kernel: the two blocks are here, and as a
    pass of XLA's it read both arrays again and turned 134 MB over."""
    from jax.experimental import pallas as pl

    *angles, dqn_ref, dqr_ref, delta_ref = rest
    qr = _rope_query(qr_ref, angles)
    dqr = jnp.zeros_like(qr)
    for g in range(group):
        mine = _head_lanes(qr.shape, g, rope)
        do = do_ref[0, :, g * vd:(g + 1) * vd].astype(jnp.float32)
        delta = jnp.sum(do * o_ref[0, :, g * vd:(g + 1) * vd]
                        .astype(jnp.float32), axis=-1, keepdims=True)
        delta_ref[0, g] = jnp.broadcast_to(delta, (block_q, _LANES))
        dqn, dqr_g = _flash_dq_head(
            (qn_ref[0, :, g * nope:(g + 1) * nope].astype(jnp.float32),
             jnp.where(mine, qr, 0.0)),
            do, lse_ref[0, g][:, :1], delta,
            *_head_blocks(kv_ref, kr_ref, g * (nope + vd), nope, vd, block_k),
            kv_ref.shape[1], pl.program_id(2), block_q=block_q,
            block_k=block_k, scale=scale, causal=causal)
        dqn_ref[0, :, g * nope:(g + 1) * nope] = dqn.astype(dqn_ref.dtype)
        # against the rope key side by side every head's copy reads the same
        dqr = jnp.where(mine, dqr_g, dqr)
    if angles:
        dqr = _turn_back(dqr, angles[0][...], angles[1][...])
    dqr_ref[0] = dqr.astype(dqr_ref.dtype)


def _dkv_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref, delta_ref,
                *rest, group, nope, rope, vd, block_q, block_k, scale,
                causal):
    """One (key block, query block) pair of a group's heads a grid step.
    ``dkv_acc (bk, group * (nope + vd))`` is the output block in float32;
    ``dkr_acc (bk, group * rope)`` holds head ``g``'s ``dk_rope`` in the
    lanes of its copy of the rope key, and the output ``dkr (T, group *
    rope)`` float32, whose block is the whole sequence and the same for
    every head, sums it over the head axis."""
    from jax.experimental import pallas as pl

    *angles, dkv_ref, dkr_ref, dkv_acc, dkr_acc = rest
    hi, ki, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    width = nope + vd

    @pl.when(qi == 0)
    def _():
        dkv_acc[...] = jnp.zeros_like(dkv_acc)
        dkr_acc[...] = jnp.zeros_like(dkr_acc)

    # under the causal mask a query block before the key block's first
    # row adds exact zeros: not computed
    @pl.when(qi >= _first_block_seen(ki, block_q, block_k, causal))
    def _():
        qr = _rope_query(qr_ref, angles)
        kr = kr_ref[0].astype(jnp.float32)
        for g in range(group):
            at = g * width
            (dkn, dkr), dv = _flash_dkv_pair(
                (qn_ref[0, :, g * nope:(g + 1) * nope].astype(jnp.float32),
                 jnp.where(_head_lanes(qr.shape, g, rope), qr, 0.0)),
                (kv_ref[0, :, at:at + nope].astype(jnp.float32), kr),
                kv_ref[0, :, at + nope:at + width].astype(jnp.float32),
                do_ref[0, :, g * vd:(g + 1) * vd].astype(jnp.float32),
                lse_ref[0, g][:, :1], delta_ref[0, g][:, :1], ki, qi,
                block_q=block_q, block_k=block_k, scale=scale, causal=causal)
            dkv_acc[:, at:at + nope] += dkn
            dkv_acc[:, at + nope:at + width] += dv
            dkr_acc[...] += dkr

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dkv_ref[0] = dkv_acc[...].astype(dkv_ref.dtype)
        mine = pl.dslice(ki * block_k, block_k)

        @pl.when(hi == 0)
        def _():
            dkr_ref[0, mine, :] = dkr_acc[...]

        @pl.when(hi > 0)
        def _():
            dkr_ref[0, mine, :] += dkr_acc[...]


def _specs(dims, block_q, block_k, causal=None):
    """The block specs of the operands by name.  ``causal`` given: the dk/dv
    kernel's grid ``(b, h, j, i)``, whose query blocks follow ``i`` (a block
    the mask hides is not fetched: the index stays at the first that is
    seen) and whose key blocks follow ``j``; None: ``(b, h, i)`` with a
    group's whole keys and values as one block."""
    from jax.experimental import pallas as pl

    group, nope, rope, vd, t = dims
    if causal is None:
        key_rows = t

        def where(b, h, i):
            return b, h, i, 0
    else:
        key_rows = block_k

        def where(b, h, j, i):
            return b, h, jnp.maximum(i, _first_block_seen(
                j, block_q, block_k, causal)), j

    def spec(shape, pick):      # pick(b, h, query block, key block)
        return pl.BlockSpec(shape, lambda *ids: pick(*where(*ids)))

    def rows(lanes):
        return spec((1, block_q, lanes), lambda b, h, q, k: (b, q, h))
    return {
        "qn": rows(group * nope), "qr": rows(group * rope),
        "o": rows(group * vd),
        "stats": spec((1, group, block_q, _LANES),
                      lambda b, h, q, k: (b, h, q, 0)),
        "angles": spec((block_q, group * rope), lambda b, h, q, k: (q, 0)),
        "kv": spec((1, key_rows, group * (nope + vd)),
                   lambda b, h, q, k: (b, k, h)),
        "kr": spec((1, key_rows, group * rope),
                   lambda b, h, q, k: (b, k, 0))}


def _fwd_pallas(qn, qr, kv, kr, *angles, heads, scale, causal, block_q,
                block_k, group, interpret=False):
    """``(out (B, T, H * vd), lse (B, H, T, 128))``.  ``kr (B, T, group *
    rope)``: the rope key, turned, ``group`` times side by side; ``angles``:
    nothing, or ``rope_angles``' two tables ``(T, group * rope)``."""
    from jax.experimental import pallas as pl

    b, t, _ = qn.shape
    nope, rope = qn.shape[2] // heads, qr.shape[2] // heads
    vd = kv.shape[2] // heads - nope
    s = _specs((group, nope, rope, vd, t), block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, group=group, nope=nope, rope=rope,
                          vd=vd, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal),
        grid=(b, heads // group, t // block_q),
        in_specs=[s["qn"], s["qr"], s["kv"], s["kr"]]
        + [s["angles"]] * len(angles),
        out_specs=[s["o"], s["stats"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, heads * vd), qn.dtype),
                   jax.ShapeDtypeStruct((b, heads, t, _LANES), jnp.float32)],
        compiler_params=_sequence_params(t, kv.shape[2] // heads * group
                                + group * rope, kv.dtype.itemsize),
        interpret=interpret,
        name="mx_flash_fwd_mla",
    )(qn, qr, kv, kr, *angles)


def _bwd_pallas(qn, qr, kv, kr, out, do, lse, *angles, heads, scale,
                causal, block_q, block_k, group, interpret=False):
    """``(dq_nope, dq_rope, dkv, dk_rope)``: the first three as their
    primals lie, ``dk_rope (B, T, group * rope)`` float32 summed over the
    heads, head ``g`` of a group in the lanes of copy ``g``.  The dq kernel
    runs first: it also writes ``delta``, which the dk/dv kernel reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = qn.shape
    nope, rope = qn.shape[2] // heads, qr.shape[2] // heads
    vd = kv.shape[2] // heads - nope
    dims = (group, nope, rope, vd, t)
    static = dict(group=group, nope=nope, rope=rope, vd=vd, block_q=block_q,
                  block_k=block_k, scale=scale, causal=causal)
    kept = kv.shape[2] // heads * group + group * rope
    s = _specs(dims, block_q, block_k)
    dqn, dqr, delta = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(b, heads // group, t // block_q),
        in_specs=[s["qn"], s["qr"], s["kv"], s["kr"], s["o"], s["o"],
                  s["stats"]] + [s["angles"]] * len(angles),
        out_specs=[s["qn"], s["qr"], s["stats"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        compiler_params=_sequence_params(t, kept, kv.dtype.itemsize),
        interpret=interpret,
        name="mx_flash_bwd_dq_mla",
    )(qn, qr, kv, kr, out, do, lse, *angles)

    s = _specs(dims, block_q, block_k, causal)
    dkv, dkr = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(b, heads // group, t // block_k, t // block_q),
        in_specs=[s["qn"], s["qr"], s["kv"], s["kr"], s["o"], s["stats"],
                  s["stats"]] + [s["angles"]] * len(angles),
        out_specs=[s["kv"],
                   pl.BlockSpec((1, t, group * rope),
                                lambda b, h, j, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   jax.ShapeDtypeStruct((b, t, group * rope), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_k, group * (nope + vd)), jnp.float32),
            pltpu.VMEM((block_k, group * rope), jnp.float32)],
        # the head axis is sequential too: dk_rope sums over it
        compiler_params=_sequence_params(
            t, group * rope, 4,
            ("parallel", "arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="mx_flash_bwd_dkv_mla",
    )(qn, qr, kv, kr, do, lse, delta, *angles)
    return dqn, dqr, dkv, dkr


def _operands(kr, theta, group):
    """The rope key ``group`` times side by side and, where there is a
    rotation, the angles' tables as wide: ``(kr, *angles)``."""
    if theta is None:
        return (jnp.tile(kr, (1, 1, group)),)
    cos, sin = rope_angles(kr.shape[1], kr.shape[2], theta)
    return (jnp.tile(kr, (1, 1, group)), jnp.tile(cos, (1, group)),
            jnp.tile(sin, (1, group)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mla_flash(qn, qr, kv, kr, heads, scale, theta, causal, blocks):
    return _mla_flash_fwd(qn, qr, kv, kr, heads, scale, theta, causal,
                          blocks)[0]


def _mla_flash_fwd(qn, qr, kv, kr, heads, scale, theta, causal, blocks):
    block_q, block_k, group = blocks
    run = functools.partial(_fwd_pallas, heads=heads, scale=scale,
                            causal=causal, block_q=block_q, block_k=block_k,
                            group=group)
    out, lse = _platform_pick(run, qn, qr, kv, *_operands(kr, theta, group))
    return out, (qn, qr, kv, kr, out, lse)


def _mla_flash_bwd(heads, scale, theta, causal, blocks, res, g):
    qn, qr, kv, kr, out, lse = res
    block_q, block_k, group = blocks
    b, t, _ = out.shape
    run = functools.partial(_bwd_pallas, heads=heads, scale=scale,
                            causal=causal, block_q=block_q, block_k=block_k,
                            group=group)
    kr_wide, *angles = _operands(kr, theta, group)
    dqn, dqr, dkv, dkr = _platform_pick(run, qn, qr, kv, kr_wide, out, g,
                                        lse, *angles)
    dkr = dkr.reshape(b, t, group, -1).sum(axis=2).astype(kr.dtype)
    return dqn, dqr, dkv, dkr


_mla_flash.defvjp(_mla_flash_fwd, _mla_flash_bwd)


@register("_contrib_mla_flash_attention",
          inputs=("q_nope", "q_rope", "kv", "k_rope"))
def mla_flash_attention(q_nope, q_rope, kv, k_rope, num_heads=1, scale=None,
                        rope_theta=None, causal=True, block_q=None,
                        block_k=None):
    """Causal latent attention over operands in the projections' own,
    token-major layout: ``q_nope (B, T, H * nope)`` and ``q_rope (B, T, H *
    rope)``, a head's channels side by side; ``kv (B, T, H * (nope + vd))``,
    ``[k_nope | v]`` a head; ``k_rope (B, T, rope)``, one a token.  The
    result ``(B, T, H * vd)``.  ``rope_theta`` (None: no rotation) turns
    ``q_rope`` and ``k_rope`` as ``rotary_embedding`` does, adjacent
    channels a pair, float32 angles from integer positions: the key's
    rotation is XLA's, on its one small array under the scope ``mla_rope``,
    the queries' is the kernels'.  ``scale`` defaults to ``(nope + rope) **
    -0.5``.  The shapes have to tile (``tiles``): the caller tests them."""
    heads = int(num_heads)
    b, t, _ = q_nope.shape
    nope, rope = q_nope.shape[2] // heads, q_rope.shape[2] // heads
    vd = kv.shape[2] // heads - nope
    blocks = tiles(heads, nope, rope, vd, t, block_q, block_k)
    if blocks is None:
        raise ValueError(
            "mla_flash_attention: %d heads of %d + %d / %d channels over %d "
            "tokens do not tile, or a mesh would partition the kernels"
            % (heads, nope, rope, vd, t))
    if scale is None or scale == 0:
        scale = (nope + rope) ** -0.5
    theta = None if rope_theta is None else float(rope_theta)
    if theta is not None:
        with jax.named_scope("mla_rope"):
            k_rope = rotate_pairs(k_rope, *rope_angles(t, rope, theta))
    return _mla_flash(q_nope, q_rope, kv, k_rope, heads, float(scale), theta,
                      bool(causal), blocks)
