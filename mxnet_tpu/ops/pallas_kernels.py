"""Pallas TPU kernels: fused flash attention (forward AND backward).

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

On non-TPU backends the kernels run through the Pallas interpreter
(tests).  Shapes that do not tile take plain jnp attention on every
platform; a kernel the TPU compiler refuses is an error.
"""
from __future__ import annotations

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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                      block_k, scale, causal):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale           # (bq, D)
    t_kv = k_ref.shape[1]
    n_k = t_kv // block_k
    qi = pl.program_id(1)
    row = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)                        # (bk, D)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        if causal:
            col = i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, -jnp.inf)
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
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    safe_l = jnp.where(l == 0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    # logsumexp per row; -inf rows (fully masked) stored as -inf.  The
    # row scalar is broadcast across a 128-lane minor dimension — TPU
    # Mosaic requires block minor dims divisible by 128 (or full), so a
    # bare (block_q,) output cannot tile; jax's own TPU flash kernels
    # store l/m the same way (flash_attention.py MIN_BLOCK_SIZE).
    lse = jnp.where(l[:, 0] == 0, -jnp.inf, m[:, 0] + jnp.log(safe_l[:, 0]))
    lse_ref[0] = lax.broadcast_in_dim(lse, (block_q, _LANES), (0,))


def _flash_pallas(q, k, v, scale, causal, block_q, block_k,
                  interpret=False):
    from jax.experimental import pallas as pl

    bh, t_q, d = q.shape
    t_kv = k.shape[1]
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        scale=scale, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq kernel (parallel over q blocks) + dkv kernel (over k blocks)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q, block_k, scale, causal):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    do = do_ref[0].astype(jnp.float32)                  # (bq, D)
    lse = lse_ref[0][:, :1]                             # (bq, 1) lane 0
    delta = delta_ref[0][:, :1]                         # (bq, 1) lane 0
    t_kv = k_ref.shape[1]
    n_k = t_kv // block_k
    qi = pl.program_id(1)
    row = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :] \
            .astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            col = i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col <= row, s, -jnp.inf)
        # p is the NORMALIZED probability (lse folds in the row sum);
        # fully-masked rows have lse=-inf -> exp(-inf - -inf) guarded to 0
        p = jnp.where(jnp.isfinite(lse), jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        ds = p * (dp - delta)
        return dq + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = lax.fori_loop(0, n_k, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, block_k, scale,
                          causal):
    from jax.experimental import pallas as pl

    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)                    # (bk, D)
    t_q = q_ref.shape[1]
    n_q = t_q // block_q
    ki = pl.program_id(1)
    col = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q), :] \
            .astype(jnp.float32)
        do = do_ref[0, pl.dslice(i * block_q, block_q), :] \
            .astype(jnp.float32)
        lse = lse_ref[0, pl.dslice(i * block_q, block_q), :1]
        delta = delta_ref[0, pl.dslice(i * block_q, block_q), :1]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        if causal:
            row = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(col <= row, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(lse), jnp.exp(s - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, bk)
        ds = p * (dp - delta)
        dk_new = dk + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bk, D)
        return dk_new, dv_new

    z = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    dk, dv = lax.fori_loop(0, n_q, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, do, lse, delta, scale, causal, block_q,
                      block_k, interpret=False):
    from jax.experimental import pallas as pl

    bh, t_q, d = q.shape
    t_kv = k.shape[1]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, scale=scale, causal=causal),
        grid=(bh, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        interpret=interpret,
        name="mx_flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, scale=scale, causal=causal),
        grid=(bh, t_kv // block_k),
        in_specs=[
            pl.BlockSpec((1, t_q, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, t_q, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, t_q, _LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, t_q, _LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
        ],
        interpret=interpret,
        name="mx_flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _attention_ref(q, k, v, scale, causal):
    """Plain jnp attention (fallback for non-tiling shapes)."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_kv = s.shape[-2], s.shape[-1]
        row = jnp.arange(t_q)[:, None]
        col = jnp.arange(t_kv)[None, :]
        s = jnp.where(col <= row, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, scale, causal, block_q, block_k):
    run = functools.partial(_flash_pallas, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k)
    out, _ = _platform_pick(run, q, k, v)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    run = functools.partial(_flash_pallas, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k)
    out, lse = _platform_pick(run, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    # delta_i = sum_d dO_id * O_id  (rowwise), O(T*D) — the only
    # off-kernel piece of the two-pass flash backward.  Broadcast across
    # the 128-lane minor dim to match the lse residual's tiled layout.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))
    run = functools.partial(_flash_bwd_pallas, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k)
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
        b, h, t, d = q.shape
        out = _flash_attention(*(x.reshape(b * h, -1, d) for x in (q, k, v)),
                               *static)
        return out.reshape(b, h, t, d)

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
                    block_q=None, block_k=None):
    """Fused multi-head attention, one Pallas kernel per (batch·head).

    Inputs (B, H, T, D) [or (BH, T, D)]; returns same shape.  Scores are
    computed blockwise with an online softmax; ``scale`` defaults to
    1/sqrt(D).  Falls back to plain XLA attention when T doesn't tile.
    Differentiable end-to-end via the blocked flash backward (no (T, T)
    buffer in forward or backward).

    ``block_q``/``block_k`` default to ``min(T, 512)`` — tuned on v5e
    (tools/llama_ceiling.py block sweep: 512/512 runs the seq-512 llama
    bench 1.5x faster than 128/128; the VMEM footprint per block at
    d<=128 stays under ~1MB so large blocks are safe), while 1024+
    regresses (VMEM pressure starts serializing the pipeline).
    """
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
    v3 = value.reshape(b * h, t_kv, d)
    # short sequences: XLA's fused attention beats the kernel (v5e A/B:
    # BERT seq-128 994 vs 825 samples/s) and the (T,T) buffer is small;
    # the Pallas path earns its keep from T>=512 (llama seq-512: 132k vs
    # 112k tok/s).  Explicit block sizes force the kernel (tests, tuning).
    if block_q is None and block_k is None and t_q < 512 and t_kv < 512:
        return _finish(_attention_ref(q3, k3, v3, scale, causal),
                       b, h, t_q, d, squeeze)
    bq = _tiles(t_q, int(block_q) if block_q else min(t_q, 512))
    bk = _tiles(t_kv, int(block_k) if block_k else min(t_kv, 512))
    if bq is None or bk is None:
        # a shape rule, on every platform: T has no block that both
        # divides it and satisfies the sublane rule
        out3 = _attention_ref(q3, k3, v3, scale, causal)
    else:
        static = (float(scale), bool(causal), bq, bk)
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
    return _finish(out3, b, h, t_q, d, squeeze)


def _finish(out3, b, h, t_q, d, squeeze):
    out = out3.reshape(b, h, t_q, d)
    return out[:, 0] if squeeze else out
