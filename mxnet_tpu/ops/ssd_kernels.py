"""Pallas TPU kernels of Mamba-2's chunked scan (``ops/ssm.py`` has the
equations): ``mx_ssd_fwd`` and ``mx_ssd_bwd``.

A grid step is one chunk of ``L`` tokens of one group's ``R`` heads: grid
``(batch, group, chunk)``, the chunk axis sequential.  The mixer's layouts
are read as they lie: ``x (B, T, H * P)`` gives the group's heads as a
column block of ``R * P``, ``B`` and ``C (B, T, G * N)`` the group's block of
``N``.  Inside, a step turns its ``x (L, R * P)`` over once, to ``(R, P,
L)``: the heads are then ONE array with the heads leading and every product
batched over them (or, where the heads share the other operand, one product
over all ``R * P`` rows), and everything that is one number a token and head
(``dt``, the running sum of ``dt * A``, the decays to a chunk's ends) lies
along the lanes as ``(R, 1, L)``.  The float32 state of the group's heads
``(R * P, N)`` lives in a VMEM scratch across the chunk axis.  A step makes
``C B^T`` once for the group, the decays between every pair of its tokens
``(R, L, L)``, the within-chunk result, the carried term and the chunk's new
state in VMEM, adds ``D x`` and writes ``y (L, R * P)``: nothing but the
operator's operands and its result crosses HBM.  Under differentiation the
forward also writes the state each chunk starts from ``(R * P, N)``, which
lives until the backward kernel has read it.  The backward kernel walks the
chunks in reverse with the state's cotangent in the scratch and writes ``dx,
dB, dC`` a chunk (a group's heads are in one step: the sum over them is
inside it), the cotangents of ``dt`` and of the running sum a token and head,
and a chunk's share of ``D``'s.

What is one number a token and head is XLA's, outside: the softplus, ``dt *
A``, its running sum inside a chunk (a float32 ``cumsum``) and their
gradients are passes over ``(B, T, H)`` arrays, a sixty-fourth of ``x``.

Precision: ``dt``, the running sums, the exponentials, the decays, the state
and every accumulation are float32.  Every product rounds its operands to
bfloat16 once and accumulates in float32 (``kda_kernels._dot``), which is
what the ``jax.numpy`` form's default-precision ``einsum`` does on a TPU; no
product is lower.  A decay is the exponential of a difference of running
sums, never a quotient of two exponentials: masked before the exponential,
it is at most 1 and a chunk that decays to nothing reads 0, not ``inf``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .kda_kernels import _column, _dot, _iota
from .pallas_kernels import _LANES, _platform_pick


def tiles(heads, head_dim, groups, state, chunk):
    """Whether the kernels take these shapes: a group's heads of whole
    128-lane rows (``H / G * P``), states of whole rows, a chunk of one row
    of lanes (the static test of ``ops/ssm.py:ssd_scan``)."""
    return heads // groups * head_dim % _LANES == 0 \
        and state % _LANES == 0 and chunk == _LANES


def _chunk(x_ref, dt_ref, cum_ref, b_ref, c_ref):
    """What both kernels make of a chunk's operands: ``x^T (R, P, L)``; ``K
    = C B^T`` times the decay between the two tokens ``(R, L, L)`` (row
    ``l``, column ``s``, zero above the diagonal); the decays ``exp(cum)``
    from the chunk's start, ``exp(cum_last - cum)`` to its end and
    ``exp(cum_last)`` over it."""
    f32 = jnp.float32
    x = x_ref[...].astype(f32)                          # (L, R * P)
    bm, cm = b_ref[...].astype(f32), c_ref[...].astype(f32)
    dt, cum = dt_ref[...], cum_ref[...]                 # (R, 1, L)
    heads, chunk = dt.shape[0], x.shape[0]
    row, col = _iota(chunk)
    xt = x.T.reshape(heads, x.shape[1] // heads, chunk)
    decay = jnp.exp(jnp.where(col <= row, _column(cum, row == col) - cum,
                              -jnp.inf))
    last = cum[..., chunk - 1:]
    return dict(x=x, xt=xt, bm=bm, cm=cm, dt=dt, eye=row == col, decay=decay,
                k=_dot(cm, bm, "nt") * decay, grow=jnp.exp(cum),
                to_end=jnp.exp(last - cum), total=jnp.exp(last))


def _by_head(f, a):
    """``(R * P, .) -> (R, P, .)``: whole sublane tiles, nothing moves."""
    heads, p = f["xt"].shape[:2]
    return a.reshape(heads, p, a.shape[-1])


def _flat(a):
    return a.reshape(a.shape[0] * a.shape[1], a.shape[2])


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                keep):
    from jax.experimental import pallas as pl

    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f = _chunk(x_ref, dt_ref, cum_ref, b_ref, c_ref)
    st = state[...]                                     # (R * P, N)
    if keep:
        rest[0][...] = st
    yt = _dot(f["xt"], f["k"] * f["dt"], "nt") \
        + _by_head(f, _dot(st, f["cm"], "nt")) * f["grow"]
    state[...] = _flat(_by_head(f, st) * f["total"]) \
        + _dot(_flat(f["xt"] * (f["to_end"] * f["dt"])), f["bm"])
    y_ref[...] = (_flat(yt).T + d_ref[...] * f["x"]).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, d_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dd_ref, dstate):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f = _chunk(x_ref, dt_ref, cum_ref, b_ref, c_ref)
    xt, bm, cm, dt, k = f["xt"], f["bm"], f["cm"], f["dt"], f["k"]
    dy = dy_ref[...].astype(jnp.float32)                # (L, R * P)
    dyt = _by_head(f, dy.T)
    st, dst = st_ref[...], dstate[...]                  # (R * P, N)
    chunk = dy.shape[0]
    # y^T = x^T (K dt)^T + grow (S C^T),  S' = total S + (x^T to_end dt) B
    dk = _dot(dyt, xt, "tn")                            # (R, L, L)
    dcb = jnp.sum(dk * (f["decay"] * dt), axis=0)
    # K's entries are C B^T times exp(cum_l - cum_s): K's cotangent times K
    # is the share of cum_l, of -cum_s and (divided by dt) of dt_s
    hk = dk * k
    down = jnp.sum(hk, axis=-2, keepdims=True)          # (R, 1, L), over l
    across = jnp.sum(jnp.where(f["eye"], jnp.sum(hk * dt, axis=-1,
                                                 keepdims=True), 0.0),
                     axis=-2, keepdims=True)            # over s, along lanes
    dsb = _by_head(f, _dot(dst, bm, "nt"))              # (dS' B^T)(R, P, L)
    ends = f["to_end"] * dt                             # (R, 1, L)
    dw = jnp.sum(xt * dsb, axis=-2, keepdims=True) * f["to_end"]
    dgrow = jnp.sum(dyt * _by_head(f, _dot(st, cm, "nt")), axis=-2,
                    keepdims=True) * f["grow"]
    dlast = jnp.sum(dw * dt, axis=-1, keepdims=True) \
        + jnp.sum(jnp.sum(_by_head(f, dst) * _by_head(f, st), axis=-2,
                          keepdims=True), axis=-1, keepdims=True) * f["total"]
    token = lax.broadcasted_iota(jnp.int32, dt.shape, 2)
    ddt_ref[...] = down + dw
    dcum_ref[...] = across - (down + dw) * dt + dgrow \
        + jnp.where(token == chunk - 1, dlast, 0.0)
    dyg = _flat(dyt * f["grow"])
    dc_ref[...] = (_dot(dcb, bm) + _dot(dyg, st, "tn")).astype(dc_ref.dtype)
    db_ref[...] = (_dot(dcb, cm, "tn")
                   + _dot(_flat(xt * ends), dst, "tn")).astype(db_ref.dtype)
    dstate[...] = _flat(_by_head(f, dst) * f["total"]) + _dot(dyg, cm)
    dxt = _dot(dyt, k * dt) + dsb * ends
    dx_ref[...] = (_flat(dxt).T + d_ref[...] * dy).astype(dx_ref.dtype)
    dd_ref[...] = jnp.sum(dy * f["x"], axis=0, keepdims=True)


def _specs(n, chunk, reverse):
    """The block a grid step ``(batch, group, chunk)`` takes of each kind
    of array: a chunk's tokens by the group's columns, a number a token
    and head ``(B, H, n, 1, L)``, a matrix a chunk and group, ``D`` over the
    group's columns."""
    from jax.experimental import pallas as pl

    def at(j):
        return n - 1 - j if reverse else j

    def tokens(width):
        return pl.BlockSpec((None, chunk, width),
                            lambda i, g, j: (i, at(j), g))

    def a_head(heads):
        return pl.BlockSpec((None, heads, None, 1, chunk),
                            lambda i, g, j: (i, g, at(j), 0, 0))

    def a_chunk(rows, width):
        return pl.BlockSpec((None, None, None, rows, width),
                            lambda i, g, j: (i, g, at(j), 0, 0))

    def skip(width):
        return pl.BlockSpec((1, width), lambda i, g, j: (0, g))
    return tokens, a_head, a_chunk, skip


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(x, dt, bmat, groups):
    b, _, width = x.shape
    heads, n, _, chunk = dt.shape[1:]
    return b, n, chunk, heads // groups, width // groups, \
        bmat.shape[2] // groups


def _fwd_pallas(x, dt, cum, bmat, cmat, dskip, groups, keep, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, chunk, heads, width, state = _sizes(x, dt, bmat, groups)
    tokens, a_head, a_chunk, skip = _specs(n, chunk, False)
    out_specs = [tokens(width)]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep:
        out_specs.append(a_chunk(width, state))
        out_shape.append(jax.ShapeDtypeStruct((b, groups, n, width, state),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=(b, groups, n),
        in_specs=[tokens(width), a_head(heads), a_head(heads), tokens(state),
                  tokens(state), skip(width)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="mx_ssd_fwd",
    )(x, dt, cum, bmat, cmat, dskip)


def _bwd_pallas(x, dt, cum, bmat, cmat, dskip, states, dy, groups,
                interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, chunk, heads, width, state = _sizes(x, dt, bmat, groups)
    tokens, a_head, a_chunk, skip = _specs(n, chunk, True)
    f32 = jnp.float32
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, groups, n),
        in_specs=[tokens(width), a_head(heads), a_head(heads), tokens(state),
                  tokens(state), skip(width), a_chunk(width, state),
                  tokens(width)],
        out_specs=[tokens(width), a_head(heads), a_head(heads),
                   tokens(state), tokens(state), a_chunk(1, width)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct(bmat.shape, bmat.dtype),
                   jax.ShapeDtypeStruct(cmat.shape, cmat.dtype),
                   jax.ShapeDtypeStruct((b, groups, n, 1, width), f32)],
        scratch_shapes=[pltpu.VMEM((width, state), f32)],
        compiler_params=_params(), interpret=interpret, name="mx_ssd_bwd",
    )(x, dt, cum, bmat, cmat, dskip, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def chunk_scan(x, dt, cum, bmat, cmat, dskip, groups):
    """The scan over whole chunks: ``x (B, T, H * P)``; ``dt`` (after the
    softplus) and ``cum`` (the running sum of ``dt * A`` inside each chunk)
    ``(B, H, n, 1, L)`` float32 with ``T = n L``; ``bmat, cmat (B, T, G *
    N)``; ``dskip (1, H * P)`` (``D``, a head's value over its channels);
    returns ``y`` like ``x``."""
    return _platform_pick(functools.partial(
        _fwd_pallas, groups=groups, keep=False),
        x, dt, cum, bmat, cmat, dskip)[0]


def _chunk_scan_fwd(x, dt, cum, bmat, cmat, dskip, groups):
    y, states = _platform_pick(functools.partial(
        _fwd_pallas, groups=groups, keep=True), x, dt, cum, bmat, cmat, dskip)
    return y, (x, dt, cum, bmat, cmat, dskip, states)


def _chunk_scan_bwd(groups, res, dy):
    dx, ddt, dcum, db, dc, dd = _platform_pick(
        functools.partial(_bwd_pallas, groups=groups), *res, dy)
    return dx, ddt, dcum, db, dc, \
        jnp.sum(dd, axis=(0, 2, 3)).reshape(res[5].shape)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
