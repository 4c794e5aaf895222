"""Kimi Delta Attention: the gated delta rule with a decay a channel.

A TPU-era addition with no reference counterpart, beside ``ops/ssm.py``
(Kimi Team, "Kimi Linear: An Expressive, Efficient Attention
Architecture", 2025).  For every head, with keys of ``d`` channels, values
of ``e`` and a state ``S (d x e)`` that starts at 0::

    S'  = diag(exp(g_t)) S_{t-1}                 (g_t <= 0, one a channel)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     (the delta rule)
    o_t = S_t^T q_t

``_contrib_kda_scan`` computes it in chunks of ``chunk`` tokens (the WY /
UT transform of the delta rule).  With ``G`` the running sum of ``g``
inside a chunk and ``S_0`` the state the chunk starts from, the rank-one
updates ``u_t = beta_t (v_t - S'^T k_t)`` of a chunk solve::

    (I + diag(beta) A) U = diag(beta) (V - (K e^G) S_0)
    A[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c])     (i < t)

a unit lower-triangular ``(chunk, chunk)`` system a chunk and head, whose
inverse is made once (forward substitution over blocks of ``SUB`` rows,
then block merges: exact, no series) and applied to ``beta K e^G`` and
``beta V``.  Then, one chunk after another (``lax.scan``, the state
float32)::

    U   = T (beta V) - T (beta K e^G) S_0
    O   = (Q e^G) S_0 + B U         B[t, i] = sum_c q k exp(G_t - G_i), i <= t
    S_C = diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

**The overflow rule.**  ``A`` and ``B`` are matrix products only if the
decay between two tokens is split into a factor a token, ``(q e^{G_t -
r})(k e^{r - G_i})``.  With ``r = 0`` the second factor is ``e^{-G_i}``,
which leaves float32 past a running sum of -88: 55 tokens at the
published range (``g`` down to -1.6 a token).  So a chunk is cut into
sub-chunks of ``SUB = 16`` tokens and the rows of sub-chunk ``I`` take ``r
= G`` at its first token: the row factor is at most 1, the column factor
at most 1 for every earlier sub-chunk and at most ``e^{15 |g|}`` inside
the row's own (``e^24`` at the published range; float32 holds ``e^88``,
``g`` down to -5.8 a token in range, but the products of such factors
lose their digits sooner: against the recurrence, at float32 products, the
chunked form holds 1e-4 of the largest value to -5.0 a token and reads
0.12 at -5.5, PR 41).  Columns of later sub-chunks are masked before the
exponential is taken.

Everything is float32 whatever the inputs are (the operator is in
``amp.lists.FP32_OPS``).  No state a token ``(B, T, H, d, e)`` ever exists.

**Where a chunk's factors live: a static test of the shapes.**  Keys and
values of whole 128-lane rows (``d`` and ``e`` multiples of 128; the chunk
is whole sub-chunks by construction) take the Pallas kernels of
``ops/kda_kernels.py``: ``mx_kda_fwd`` makes a chunk's ``cum``, row and
column factors, ``A``, ``B``, the inverse and ``U`` in VMEM with the state in
a scratch that lives across the sequential chunk axis, and only the
operator's operands cross HBM, read as the heads' lanes of ``(B, T, H * d)``
(one launch for every head: the grid walks batch, chunks and, inside a
chunk, groups of eight heads); ``mx_kda_bwd`` walks the chunks in reverse
with the state's cotangent in VMEM.  One ``custom_vjp`` holds the two: its
forward pass, when a backward pass will follow, also writes the state each
chunk starts from, the inverse and ``U`` (112 KB a head and chunk), which
live from there to the backward kernel and no longer.  Every other shape
(the tests' 8 and 12 channels) takes the ``jax.numpy`` form below, on every
platform; there is no option and no fallback: a kernel the TPU compiler
refuses at a tiling shape is an error.  Both are the same equations, the
same overflow rule and the same precision: the running sums, exponentials,
state and accumulations float32; the solve's products (the inverse, its
application, its gradient) at ``HIGHEST``; every other product one bfloat16
pass with float32 accumulation on a TPU (the ``einsum``s' default precision
there; the kernels round their operands themselves).

The ``jax.numpy`` form: the products are ``einsum``s, the inverse forward
substitution over blocks of ``SUB`` rows and block merges, the chunks a
``lax.scan``; the backward pass is jax's through the chunked algebra under a
checkpoint (``_made_again``): the operator's inputs are kept, the scan over
chunks keeps the state each chunk starts from and its ``U``, and the rest is
made again (the column factors of the overflow rule, ``chunk / SUB`` times
the size of ``k``, under a checkpoint of their own).

``_contrib_kda_attention`` is a whole mixer between its projections (the
convolutions, the norms, the decay, ``beta``, the scan, the output norm
and gate) under one checkpoint (``_made_again``): what a layer keeps for its
backward pass is the six projections' results in their own dtype, and the
rest is made again when the cotangent arrives.  Where the shapes tile
(``kda_fused``: heads of whole 128-lane rows, convolutions of at most 17
taps) the kernels take it whole (``kda_kernels.mixer``): they read the
projections' results where the products wrote them, make the convolutions
and SiLU, the unit norms, the decay gate, ``sigmoid(beta)`` and, after the
scan, the output's RMSNorm times ``sigmoid(gate)`` in VMEM from the blocks
they already hold, write the result where ``o_proj`` reads it, and write
the projections' cotangents, the taps', and a chunk's token sums for
``A_log``, ``dt_bias`` and ``o_norm`` (which XLA adds up, a few KB); no
array of the mixer's per-token work exists in HBM.  Every other shape keeps
the composition: the same steps in XLA around ``_kda_chunked``, a group of
``HEADS_AT_ONCE`` heads at a time.

Shapes: ``q, k (B, T, H, d)``, ``v (B, T, H, e)``, ``g (B, T, H, d)``
(log-decay, at most 0), ``beta (B, T, H)``; returns ``(B, T, H, e)`` in
``v``'s dtype.  ``T`` is padded to whole chunks with tokens that neither
decay nor write: ``g = 0`` and ``beta = 0`` where the gates were applied
before the padding; the fused mixer pads the projections' results, whose
gates would make ``softplus(dt_bias)`` and 1/2 of a padded zero, so its
kernels mask a token past ``T`` by its position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kda_kernels
from .kda_kernels import SUB    # tokens of a sub-chunk: see the overflow rule
from .registry import register
from .ssm import causal_conv1d

# heads that ``kda_attention``'s composition runs at once (the shapes the
# kernels do not take whole): its temporaries (the float32 ``(T, d)`` arrays
# of a head group around the scan, and what the scan's forward leaves for its
# backward) grow with the heads, at no cost in operations: 1.8 GB for 32
# heads x 128 in the ``jax.numpy`` form, a quarter of that for eight
HEADS_AT_ONCE = 8
HIGHEST = lax.Precision.HIGHEST


def _substitute(n):
    """``(I + n)^-1`` of strictly lower-triangular ``n (..., s, s)`` by
    forward substitution, a row at a time."""
    s = n.shape[-1]
    eye = jnp.eye(s, dtype=n.dtype)
    t = jnp.broadcast_to(eye, n.shape)
    for r in range(1, s):
        # rows below r of ``t`` are still the identity's and n[r, >= r] = 0
        row = eye[r] - jnp.einsum("...j,...jc->...c", n[..., r, :], t,
                                  precision=HIGHEST)
        t = t.at[..., r, :].set(row)
    return t


@jax.custom_vjp
def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n (..., C, C)``, ``C``
    a power-of-two multiple of ``SUB`` (or at most ``SUB``): the diagonal
    blocks by substitution, then ``[[a, 0], [c, b]]^-1 = [[a^-1, 0],
    [-b^-1 c a^-1, b^-1]]`` until one block is left."""
    c = n.shape[-1]
    size = min(SUB, c)
    lead = n.shape[:-2]
    blocks = n.reshape(lead + (c // size, size, c // size, size))
    diag = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
    inv = _substitute(diag)                            # (..., c/size, s, s)
    while size < c:
        pairs = c // (2 * size)
        below = n.reshape(lead + (pairs, 2, size, pairs, 2, size))
        below = jnp.moveaxis(jnp.diagonal(below, axis1=-6, axis2=-3), -1, -5)
        below = below[..., 1, :, 0, :]                 # (..., pairs, s, s)
        inv = inv.reshape(lead + (pairs, 2, size, size))
        a, b = inv[..., 0, :, :], inv[..., 1, :, :]
        corner = -jnp.einsum("...ij,...jk,...kl->...il", b, below, a,
                             precision=HIGHEST)
        top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([corner, b], axis=-1)], axis=-2)
        size *= 2
    return inv.reshape(n.shape)


def _inverse_fwd(n):
    t = _unit_lower_inverse(n)
    return t, t


def _inverse_bwd(t, g):
    # d(M^-1) = -M^-1 dM M^-1, and only the strict lower part of M moves
    d = -jnp.einsum("...ji,...jk,...lk->...il", t, g, t, precision=HIGHEST)
    return (jnp.tril(d, -1),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _made_again(fn):
    """``fn`` under a checkpoint: the backward pass keeps ``fn``'s arguments
    and makes the rest again.  Unlike ``jax.checkpoint``, the second forward
    pass waits for the cotangent (an optimization barrier ties them): with
    nothing to wait for, the compiler is free to run every layer's second
    pass early, and then holds all their temporaries at once (1.5 GB over
    four layers at 4096 tokens of 32 x 128)."""
    @jax.custom_vjp
    def run(*args):
        return fn(*args)

    def fwd(*args):
        return fn(*args), args

    def bwd(args, ct):
        args, ct = lax.optimization_barrier((args, ct))
        return jax.vjp(fn, *args)[1](ct)
    run.defvjp(fwd, bwd)
    return run


def _chunk_size(t, chunk):
    """The chunk the algebra runs at: ``chunk`` rounded down to ``SUB``
    times a power of two (at least ``SUB``), no longer than the padded
    sequence needs."""
    c = SUB
    while 2 * c <= max(int(chunk), SUB) and c < t:
        c *= 2
    return c


@jax.checkpoint
def _pair_products(q, k, cum):
    """``A[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c])`` and ``B``
    the same with ``q[t]``, for every pair of a chunk's tokens: ``q, k, cum
    (B, H, n, C, d)`` give ``(B, H, n, C, C)`` each.  The overflow rule: rows
    of sub-chunk I are referenced to its first token, columns later than I
    are masked before the exponential.  Under a checkpoint of its own: the
    column factors are ``C / SUB`` times the size of ``k`` and are made
    again for the gradient, not kept beside the scan's chunk states."""
    b, h, n, c, d = k.shape
    subs = c // SUB
    first = cum[..., ::SUB, :]                         # (B, H, n, subs, d)
    rows = jnp.exp(cum.reshape(b, h, n, subs, SUB, d) - first[..., None, :])
    q_rows = q.reshape(rows.shape) * rows
    k_rows = k.reshape(rows.shape) * rows
    sub_of = jnp.arange(c) // SUB
    seen = sub_of[None, :] <= jnp.arange(subs)[:, None]          # (subs, C)
    cols = jnp.exp(jnp.where(
        seen[..., None], first[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))                                     # (B, H, n, subs, C, d)
    k_cols = k[..., None, :, :] * cols
    a = jnp.einsum("...Itc,...Iic->...Iti", k_rows, k_cols)
    bm = jnp.einsum("...Itc,...Iic->...Iti", q_rows, k_cols)
    return a.reshape(b, h, n, c, c), bm.reshape(b, h, n, c, c)


def _kda_chunked(q, k, v, g, beta, chunk):
    f32 = jnp.float32
    b, t, h, d = q.shape
    e = v.shape[-1]
    out_dtype = v.dtype
    c = _chunk_size(t, chunk)
    pad = -t % c
    n = (t + pad) // c

    def chunks(x):
        # (B, T, H, .) -> (B, H, n, C, .); a padded token has g = 0, beta =
        # 0 and zero q, k, v: it decays nothing and writes nothing
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((b, h, n, c) + x.shape[3:])
    if kda_kernels.tiles(d, e, c):
        # the same algebra with a chunk's factors made, used and dropped in
        # VMEM: a static test of the shapes, on every platform.  The
        # kernels read the heads as lanes, (B, T, H * d): no copy
        def lanes(x):
            x = x.astype(f32).reshape(b, t, -1)
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        out = kda_kernels.chunk_scan(*map(lanes, (q, k, v, g, beta)), c)
        return out[:, :t].reshape(b, t, h, e).astype(out_dtype)
    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)[..., None]                     # (B, H, n, C, 1)
    cum = jnp.cumsum(g, axis=-2)                       # G, inclusive
    a, bm = _pair_products(q, k, cum)
    lower = jnp.tril(jnp.ones((c, c), bool))
    bm = jnp.where(lower, bm, 0)
    tinv = _unit_lower_inverse(jnp.where(lower & ~jnp.eye(c, dtype=bool),
                                         beta * a, 0))
    grow = jnp.exp(cum)                                # at most 1
    w = jnp.einsum("...ti,...ic->...tc", tinv, beta * k * grow,
                   precision=HIGHEST)
    uv = jnp.einsum("...ti,...ie->...te", tinv, beta * v, precision=HIGHEST)
    q_in = q * grow
    k_end = k * jnp.exp(cum[..., -1:, :] - cum)        # at most 1
    total = jnp.exp(cum[..., -1, :])                   # (B, H, n, d)

    def carry(state, inp):
        w, uv, q_in, bm, k_end, total = inp
        u = uv - jnp.einsum("bhtc,bhce->bhte", w, state)
        out = jnp.einsum("bhtc,bhce->bhte", q_in, state) \
            + jnp.einsum("bhti,bhie->bhte", bm, u)
        state = state * total[..., None] \
            + jnp.einsum("bhtc,bhte->bhce", k_end, u)
        return state, out
    _, out = lax.scan(
        carry, jnp.zeros((b, h, d, e), f32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (w, uv, q_in, bm, k_end,
                                               total)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, t + pad, e)[:, :, :t]
    return jnp.moveaxis(out, 1, 2).astype(out_dtype)


@register("_contrib_kda_scan", inputs=("q", "k", "v", "g", "beta"))
def kda_scan(q, k, v, g, beta, chunk=64):
    """Kimi Delta Attention, chunked; see the module's docstring.

    ``g`` is the log-decay itself (``-exp(A_log) * softplus(.)``, at most
    0) and ``beta`` the writing strength (a sigmoid): the caller applies
    both, and normalises ``q`` and ``k``."""
    with jax.named_scope("kda_scan"):
        fn = _made_again(functools.partial(_kda_chunked, chunk=int(chunk)))
        return fn(q, k, v, g, beta)


def kda_gate(data, A_log, dt_bias):
    """The log-decay a channel, ``g = -exp(A_log[h]) * softplus(data +
    dt_bias)``, float32: ``data (B, T, H * d)``, ``A_log`` of ``H``
    elements, ``dt_bias`` of ``H * d``; returns ``(B, T, H, d)``."""
    f32 = jnp.float32
    b, t, _ = data.shape
    h = A_log.size
    rate = jax.nn.softplus(data.astype(f32) + dt_bias.astype(f32).reshape(-1))
    return -jnp.exp(A_log.astype(f32)).reshape(h, 1) \
        * rate.reshape(b, t, h, -1)


def _kda_fused(q, k, v, decay, beta, gate, q_conv, k_conv, v_conv, A_log,
               dt_bias, o_norm, chunk, eps):
    """``kda_attention`` where the shapes tile: the kernels read the
    projections' results where they lie and do the rest."""
    f32 = jnp.float32
    b, t, inner = q.shape
    heads = A_log.size
    c = _chunk_size(t, chunk)
    pad = -t % c

    def whole(x):
        # whole chunks; the kernels mask the tokens past ``t`` by position
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    with jax.named_scope("kda_scan"):
        o = kda_kernels.mixer(
            *map(whole, (q, k, v, decay, beta, gate)),
            jnp.repeat(A_log.astype(f32).reshape(-1), inner // heads)[None],
            dt_bias.astype(f32).reshape(1, -1),
            jnp.tile(o_norm.astype(f32).reshape(-1), heads)[None],
            jnp.stack([q_conv, k_conv, v_conv]).astype(f32).transpose(0, 2, 1),
            c, t if pad else None, eps)
    return o[:, :t] if pad else o


def _kda_attention(q, k, v, decay, beta, gate, q_conv, k_conv, v_conv, A_log,
                   dt_bias, o_norm, chunk, eps):
    """``kda_attention`` for the heads it is given (``A_log`` has one
    element a head)."""
    f32 = jnp.float32
    b, t, inner = q.shape
    heads = A_log.size
    hd = inner // heads

    def mixed(x, taps):
        x = jax.nn.silu(causal_conv1d(x.astype(f32), taps))
        return x.reshape(b, t, heads, hd)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    with jax.named_scope("kda_scan"):
        o = _kda_chunked(
            unit(mixed(q, q_conv)) * hd ** -0.5, unit(mixed(k, k_conv)),
            mixed(v, v_conv), kda_gate(decay, A_log, dt_bias),
            jax.nn.sigmoid(beta.astype(f32)), chunk)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * o_norm.astype(f32) \
        * jax.nn.sigmoid(gate.astype(f32)).reshape(b, t, heads, hd)
    return o.reshape(b, t, inner)


@register("_contrib_kda_attention",
          inputs=("q", "k", "v", "decay", "beta", "gate", "q_conv", "k_conv",
                  "v_conv", "A_log", "dt_bias", "o_norm"))
def kda_attention(q, k, v, decay, beta, gate, q_conv, k_conv, v_conv, A_log,
                  dt_bias, o_norm, chunk=64, eps=1e-5):
    """A Kimi Delta Attention mixer between its projections, under ONE
    checkpoint (``_made_again``): from the six projections' results ``q, k,
    v, decay, gate (B, T, H * d)`` and ``beta (B, T, H)`` (in any float
    dtype; under AMP they come in bfloat16 and are what the backward pass
    keeps) ::

        q, k, v = silu(causal_conv1d(., taps))       taps (H * d, K), no bias
        q, k    = x / sqrt(sum x^2 + 1e-6) a head;  q *= d ** -0.5
        g       = -exp(A_log[h]) * softplus(decay + dt_bias)
        o       = kda_scan(q, k, v, g, sigmoid(beta))
        o       = RMSNorm_head(o; o_norm, eps) * sigmoid(gate)

    Returns ``(B, T, H * d)`` float32.  Everything between the casts is
    float32.  A layer made of separate operators keeps some sixteen ``(B,
    T, H * d)`` float32 arrays for its backward pass (1.1 GB at 4096 tokens
    of 32 x 128); this keeps the projections' results and makes the rest
    again when the cotangent arrives.  Where the shapes tile
    (``kda_fused``) every step above is the kernels' (``_kda_fused``): the
    per-token work is done in VMEM on the blocks the scan reads, one launch
    a pass for all heads, and a padded token is masked by its position in
    the kernels.  Every other shape is the composition, ``HEADS_AT_ONCE``
    heads after ``HEADS_AT_ONCE`` heads (``lax.map``: every step of the
    mixer is a head's own), its scan padded after the gates."""
    with jax.named_scope("kda_attention"):
        b, t, inner = q.shape
        heads = A_log.size
        if kda_fused(t, inner // heads, q_conv.shape[1], chunk):
            return _made_again(functools.partial(
                _kda_fused, chunk=int(chunk), eps=float(eps)))(
                q, k, v, decay, beta, gate, q_conv, k_conv, v_conv, A_log,
                dt_bias, o_norm)
        fn = _made_again(functools.partial(
            _kda_attention, chunk=int(chunk), eps=float(eps)))
        groups = max(1, heads // HEADS_AT_ONCE)
        if heads % groups:
            groups = 1

        def wide(x):
            # (B, T, heads * w) -> (groups, B, T, heads / groups * w)
            return jnp.moveaxis(x.reshape(b, t, groups, -1), 2, 0)

        def own(x):
            # a leaf with heads leading -> (groups, heads / groups * w, ...)
            return x.reshape((groups, -1) + x.shape[1:])
        out = lax.map(
            lambda xs: fn(*xs, o_norm),
            (wide(q), wide(k), wide(v), wide(decay), wide(beta), wide(gate),
             own(q_conv), own(k_conv), own(v_conv), own(A_log.reshape(-1)),
             own(dt_bias.reshape(-1))))
        return jnp.moveaxis(out, 0, 2).reshape(b, t, inner)


def kda_chunks(t, chunk=64):
    """Chunks a head's scan of ``t`` tokens is cut into."""
    return -(-t // _chunk_size(t, chunk))


def kda_kernel_chunks(t, d, e, chunk=64):
    """Those of them whose scan takes the Pallas kernels: all, where keys
    of ``d`` and values of ``e`` channels tile, else none."""
    tiles = kda_kernels.tiles(d, e, _chunk_size(t, chunk))
    return kda_chunks(t, chunk) if tiles else 0


def kda_fused(t, d, taps, chunk=64):
    """Whether a mixer of ``t`` tokens, heads of ``d`` channels and
    convolutions of ``taps`` takes the kernels whole (``kda_attention``'s
    static test of the shapes)."""
    return kda_kernels.tiles(d, d, _chunk_size(t, chunk)) \
        and taps <= kda_kernels.SPAN + 1


def kda_recurrence(q, k, v, g, beta):
    """The same operator as the recurrence it is defined by, one token at
    a time in float32: what the tests hold ``kda_scan`` against."""
    f32 = jnp.float32
    b, t, h, d = q.shape

    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        old = jnp.einsum("bhde,bhd->bhe", state, kt, precision=HIGHEST)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - old)[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, qt,
                                 precision=HIGHEST)
    _, out = lax.scan(
        step, jnp.zeros((b, h, d, v.shape[-1]), f32),
        tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)
