"""Ring attention: sequence/context parallelism over the ICI ring.

No reference counterpart (SURVEY §5.7 — the reference never sharded the
sequence axis); designed TPU-first: Q stays resident per chip while K/V
blocks travel the ring via ``lax.ppermute``, each hop overlapping the
next transfer with the current block's flash-style online-softmax
accumulation.  Communication per step is O(T/n · D) on ICI and the full
(T, T) score matrix never exists on any chip — sequences scale linearly
with ring size.

Usage: inside ``shard_map`` (``ring_attention_sharded`` wraps this), with
q/k/v sharded on the sequence axis across ``axis_name``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _block_scores(q, k, scale, causal, q_off, k_off):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        row = q_off + jnp.arange(q.shape[1])[:, None]
        col = k_off + jnp.arange(k.shape[1])[None, :]
        s = jnp.where(col <= row, s, -jnp.inf)
    return s


def ring_attention(q, k, v, axis_name, scale=None, causal=False):
    """Per-shard body: q/k/v (B, T_local, D) — call inside shard_map.

    Online-softmax accumulation over ring hops; each hop ppermutes the
    (K, V) pair one step around the ring.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    t_local, d = q.shape[1], q.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_off = my * t_local
    m0 = jnp.full(q.shape[:2] + (1,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:2] + (1,), jnp.float32)
    acc0 = jnp.zeros(q.shape[:2] + (d,), jnp.float32)

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # the block we hold at hop i originated on rank (my - i) mod n
        src = (my - i) % n
        s = _block_scores(q, k_cur, scale, causal, q_off, src * t_local)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # all -inf rows (fully masked block): keep m to avoid NaNs
        m_new = jnp.where(jnp.isinf(m_new) & (m_new < 0), m, m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bqk,bkd->bqd", p, v_cur.astype(jnp.float32))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l_new, acc_new

    carry = (k, v, m0, l0, acc0)
    for i in range(n):  # static unroll: n is the mesh axis size
        carry = step(i, carry)
    _, _, m, l, acc = carry
    safe_l = jnp.where(l == 0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", scale=None,
                           causal=False):
    """Shard (B, T, D) [or (B, H, T, D)] on the sequence axis and run
    ring attention over ``axis_name`` of ``mesh``."""
    from ..analysis.collective_check import check_axis
    from .. import sharding as _sharding

    mesh = _sharding.as_jax_mesh(mesh)
    check_axis(mesh, axis_name, op="ring_attention_sharded")
    four_d = q.ndim == 4
    if four_d:
        b, h, t, d = q.shape
        q = q.reshape(b * h, t, d)
        k = k.reshape(b * h, k.shape[2], d)
        v = v.reshape(b * h, v.shape[2], d)

    out = _ring_program(mesh, axis_name, scale, causal)(q, k, v)
    if four_d:
        out = out.reshape(b, h, t, d)
    return out


@functools.lru_cache(maxsize=None)
def _ring_program(mesh, axis_name, scale, causal):
    """The ring over ``mesh``'s ``axis_name`` as one jitted ``shard_map``
    on (B, T, D) sharded by T.  Called outside a jit, a bare ``shard_map``
    runs its body one operation at a time on every device (the unrolled
    hops and their gradient: minutes on a CPU, where the program takes
    seconds to compile)."""
    from jax.sharding import PartitionSpec as P

    from .mesh import shard_map

    spec = P(None, axis_name, None)
    return jax.jit(shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          scale=scale, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
