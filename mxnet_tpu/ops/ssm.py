"""State-space operators: what a Mamba-2 mixer needs beside its projections.

TPU-era additions with no reference counterpart, like ``RMSNorm`` and the
flash-attention kernels: the reference era has no state-space layer.

``_contrib_causal_conv1d``: a depthwise convolution over time that sees
only the past: ``y[b, t, c] = bias[c] + sum_j weight[c, j] * x[b, t - (K-1)
+ j, c]`` with zeros before the sequence's start.

``_contrib_ssd_scan``: the selective state-space recurrence of Mamba-2
(Dao & Gu, "Transformers are SSMs", 2024).  For every head ``h`` with
channels ``p`` and state ``n``::

    dt_t = softplus(dt_raw_t + dt_bias)            A = -exp(A_log)
    H_t  = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T     (P x N, starts at 0)
    y_t  = H_t C_t + D x_t

computed in its chunked ("state-space dual") form: the sequence is cut
into chunks of ``chunk`` tokens; within a chunk the output is a masked
product of ``C B^T`` with the decays between every pair of its tokens
(attention-like, quadratic in ``chunk``), between chunks one state a chunk
is carried (linear in the sequence).  The decays, ``dt`` and the carried
state are float32 whatever the inputs are (both operators are in
``amp.lists.FP32_OPS``, so under AMP their inputs arrive as float32); every
product rounds its operands to bfloat16 once and accumulates in float32 (a
float32 ``einsum`` at the default precision on a TPU).  Nothing of the size
"a state a token" ``(B, T, H, P, N)`` ever exists.

**Where a chunk's decays and state live: a static test of the shapes**
(``ssd_kernels.tiles``).  A group's heads of whole 128-lane rows (``H / G
* P`` a multiple of 128), states of whole rows and chunks of 128 take the
Pallas kernels of ``ops/ssd_kernels.py``: ``mx_ssd_fwd`` makes a chunk's
decays ``(H / G, L, L)``, ``C B^T``, the within-chunk result, the carried
term and the new state in VMEM, with the state of a group's heads in a
scratch that lives across the sequential chunk axis, and only the
operator's operands and result cross HBM; ``mx_ssd_bwd`` walks the chunks
in reverse with the state's cotangent in VMEM.  One ``custom_vjp`` holds the
two: what it keeps for the backward pass is the operator's inputs and the
state each chunk starts from, which the forward writes when a backward pass
will follow.  What is one number a token and head (the softplus, ``dt * A``,
its running sum inside a chunk) stays ``jax.numpy`` around the kernels, with
jax's own gradients.  Every other shape (the tests' 8 channels and 16
states) takes the ``jax.numpy`` form below, ``_ssd_chunked``, on every
platform: every chunked product an ``einsum`` that XLA puts on the MXU, the
backward pass jax's own through the chunked algebra under
``jax.checkpoint`` (the operator's inputs are kept, the chunk states and
within-chunk products made again).  It is the definition the kernels are
held to, with ``ssd_recurrence``.  There is no option and no fallback.

Shapes: ``x (B, T, H, P)``, ``dt (B, T, H)``, ``A_log``/``D``/``dt_bias``
``(H,)`` (or any shape of ``H`` elements), ``B``/``C`` ``(B, T, G, N)``
with ``H % G == 0``: head ``h`` reads group ``h // (H // G)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import ssd_kernels
from .registry import register


@register("_contrib_causal_conv1d", inputs=("data", "weight", "bias"))
def causal_conv1d(data, weight, bias=None):
    """Causal depthwise convolution over time.

    ``data (B, T, C)``, ``weight (C, K)``, ``bias (C,)`` or None; returns
    ``(B, T, C)`` in ``data``'s dtype, accumulated in float32.  ``K`` is
    small (Mamba-2: 4), so it is K shifted multiply-adds, which XLA fuses
    into one pass over ``data``."""
    with jax.named_scope("causal_conv"):
        b, t, c = data.shape
        k = weight.shape[1]
        x = jnp.pad(data.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        out = sum(x[:, j:j + t, :] * w[:, j] for j in range(k))
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(data.dtype)


def _segsum(a):
    """``out[..., l, s] = sum(a[..., s+1 : l+1])`` for ``s <= l``, ``-inf``
    above the diagonal: the log of the decay from token ``s`` to ``l``."""
    n = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)), diff, -jnp.inf)


def _ssd_chunked(x, dt, a_log, bmat, cmat, d_skip, dt_bias, chunk):
    f32 = jnp.float32
    b, t, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    out_dtype = x.dtype
    x, bmat, cmat = x.astype(f32), bmat.astype(f32), cmat.astype(f32)
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32).reshape(h))
    a = -jnp.exp(a_log.astype(f32).reshape(h))
    skip = x * d_skip.astype(f32).reshape(h)[:, None]
    pad = -t % chunk
    if pad:
        # a padded token has dt = 0: it decays nothing and adds nothing
        x, dt, bmat, cmat = (jnp.pad(
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, bmat, cmat))
    c = (t + pad) // chunk
    r = h // g
    # (B, c, L, G, R, ...): chunks apart, heads by their group
    xc = (x * dt[..., None]).reshape(b, c, chunk, g, r, p)
    da = (dt * a).reshape(b, c, chunk, g, r)          # log-decay a token
    bc = bmat.reshape(b, c, chunk, g, n)
    cc = cmat.reshape(b, c, chunk, g, n)
    da_t = jnp.moveaxis(da, 2, -1)                     # (B, c, G, R, L)
    cum = jnp.cumsum(da_t, axis=-1)
    # within a chunk: (C B^T) masked by the decay between the two tokens
    decay = jnp.exp(_segsum(da_t))                     # (B, c, G, R, L, L)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc)
    y = jnp.einsum("bcgls,bcgrls,bcsgrp->bclgrp", cb, decay, xc)
    # the state each chunk adds, decayed to the chunk's end
    to_end = jnp.exp(cum[..., -1:] - cum)              # (B, c, G, R, L)
    states = jnp.einsum("bcsgn,bcgrs,bcsgrp->bcgrpn", bc, to_end, xc)
    # between chunks: the carried state, one a chunk
    total = jnp.exp(cum[..., -1])                      # (B, c, G, R)

    def carry(hprev, inp):
        s, dec = inp
        return hprev * dec[..., None, None] + s, hprev
    _, before = lax.scan(
        carry, jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # state at chunk start
    y = y + jnp.einsum("bclgn,bcgrpn,bcgrl->bclgrp", cc, before,
                       jnp.exp(cum))
    y = y.reshape(b, t + pad, h, p)[:, :t] + skip
    return y.astype(out_dtype)


def _ssd_kernels(x, dt, a_log, bmat, cmat, d_skip, dt_bias, chunk):
    """The same operator with a chunk's decays and state in VMEM
    (``ops/ssd_kernels.py``).  What is one number a token and head stays
    here, float32: the softplus, ``dt * A`` and its running sum inside a
    chunk, and jax's own gradients of them."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    g = bmat.shape[2]
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32).reshape(h))
    a = -jnp.exp(a_log.astype(f32).reshape(h))
    x, bmat, cmat = (v.reshape(b, t, -1) for v in (x, bmat, cmat))
    pad = -t % chunk
    if pad:
        # a padded token has dt = 0: it decays nothing and adds nothing
        x, dt, bmat, cmat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                             for v in (x, dt, bmat, cmat))
    n = (t + pad) // chunk
    dt = dt.reshape(b, n, chunk, h)
    cum = jnp.cumsum(dt * a, axis=2)

    def along_lanes(v):
        # (B, n, L, H) -> (B, H, n, 1, L)
        return jnp.transpose(v, (0, 3, 1, 2))[:, :, :, None]
    y = ssd_kernels.chunk_scan(
        x, along_lanes(dt), along_lanes(cum), bmat, cmat,
        jnp.repeat(d_skip.astype(f32).reshape(h), p)[None], g)
    return y[:, :t].reshape(b, t, h, p)


def ssd_chunks(t, chunk=128):
    """Chunks a head's scan of ``t`` tokens is cut into."""
    return -(-t // int(chunk))


def ssd_kernel_chunks(t, heads, head_dim, groups, state, chunk=128):
    """Those of them whose scan takes the Pallas kernels: all, where a
    group's heads and the states tile, else none."""
    takes = ssd_kernels.tiles(heads, head_dim, groups, state, int(chunk))
    return ssd_chunks(t, chunk) if takes else 0


@register("_contrib_ssd_scan",
          inputs=("x", "dt", "A_log", "B", "C", "D", "dt_bias"))
def ssd_scan(x, dt, A_log, B, C, D, dt_bias, chunk=128):
    """Mamba-2's selective scan, chunked; see the module's docstring.

    Returns ``y (B, T, H, P)`` in ``x``'s dtype.  ``dt`` is the raw
    projection: the softplus and the bias are applied here, in float32."""
    with jax.named_scope("ssd_scan"):
        if ssd_kernels.tiles(*x.shape[2:], *B.shape[2:], int(chunk)):
            fn = functools.partial(_ssd_kernels, chunk=int(chunk))
        else:
            fn = jax.checkpoint(
                functools.partial(_ssd_chunked, chunk=int(chunk)))
        return fn(x, dt, A_log, B, C, D, dt_bias)


def ssd_recurrence(x, dt, A_log, B, C, D, dt_bias):
    """The same operator as the recurrence it is defined by, one token at
    a time in float32: what the tests hold ``ssd_scan`` against."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    xf = x.astype(f32)
    dtf = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32).reshape(h))
    a = -jnp.exp(A_log.astype(f32).reshape(h))
    bh = jnp.repeat(B.astype(f32), rep, axis=2)       # (B, T, H, N)
    ch = jnp.repeat(C.astype(f32), rep, axis=2)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision=lax.Precision.HIGHEST)
    _, y = lax.scan(step, jnp.zeros((b, h, p, n), f32),
                    tuple(jnp.moveaxis(v, 1, 0) for v in (xf, dtf, bh, ch)))
    y = jnp.moveaxis(y, 0, 1) + xf * D.astype(f32).reshape(h)[:, None]
    return y.astype(x.dtype)
