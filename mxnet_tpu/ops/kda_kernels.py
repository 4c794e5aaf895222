"""Pallas TPU kernels of the chunked gated delta rule (``ops/kda.py`` has
the equations and the overflow rule): ``mx_kda_fwd`` and ``mx_kda_bwd``.

A grid step is one chunk of ``C`` tokens of up to ``HEADS_A_STEP`` heads, held
as arrays with the heads leading and every product batched over them; the
chunk axis is sequential and the float32 state of a head lives in a VMEM
scratch across it, transposed ``(e, d)`` so that every product with it is a
plain or a transposed-operand matrix product.  A step reads the chunk's ``q,
k, g (C, d)``, ``v (C, e)`` and ``beta (1, C)``, makes the running sum of
``g``, the row and column factors of the overflow rule, ``A``, ``B``, the
inverse, ``U`` and the new state in VMEM, and writes ``out (C, e)``: nothing
but the operator's operands crosses HBM.  When the backward pass asks, the
forward also writes what a chunk's backward cannot make again from the
operands: the state the chunk starts from ``(e, d)``, the inverse ``(C, C)``
and ``U (C, e)``, 112 KB a head and chunk, which live until the backward
kernel has read them.  The backward kernel walks the chunks in reverse with
the state's cotangent in VMEM and writes ``dq, dk, dv, dg`` and ``dbeta`` a
chunk.

Precision: the running sums, the exponentials, the state and every
accumulation are float32.  The products of the solve (the inverse's, ``U = T
R``, the two products of its gradient) run at float32 precision
(``HIGHEST``), and so do the two running sums (three bfloat16 pieces that
sum to the float32 operand, against ones); every other product rounds its
operands to bfloat16 and accumulates in float32, which is what the
``jax.numpy`` form's default-precision ``einsum`` does on a TPU.

The inverse of the unit lower-triangular ``I + N (C, C)`` is exact: the
diagonal blocks of ``SUB`` rows by elimination, a column after another (the
forward substitution, on the vector unit for every block and head of the
step at once); then, with ``D`` those blocks and ``M = (I + D)^-1 (N - D)``,
block-nilpotent of index ``C / SUB``, ``(I + N)^-1 = (I - M)(I + M^2) ... (I
+ D)^-1``, a finite product of four matrix products at a chunk of 64.  No
series is cut short.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import _LANES, _platform_pick

SUB = 16            # tokens of a sub-chunk: the overflow rule of ``ops/kda.py``
# heads a grid step holds at most, as one array with the heads leading and
# every product batched over them: a head's chain of small dependent
# products leaves the units waiting, and the compiler fills one head's
# waits with another's work only when each operation is issued for all of
# them (bundles a head and chunk as compiled for a v5e, forward / backward:
# 1499 / 1285 for one head, 1072 / 750 for eight, PR 35)
HEADS_A_STEP = 8
HIGHEST = lax.Precision.HIGHEST


def tiles(d, e, c):
    """Whether the kernels take these shapes: keys and values of whole
    128-lane rows, a chunk of whole sub-chunks whose count is a power of
    two (the static test of ``ops/kda.py:_kda_chunked``)."""
    subs = c // SUB
    return d % _LANES == 0 and e % _LANES == 0 and c % SUB == 0 \
        and subs >= 1 and subs & (subs - 1) == 0


def _dims(kind, rank):
    """``dot_general``'s dimension numbers for ``a @ b`` (``nn``), ``a @
    b^T`` (``nt``) or ``a^T @ b`` (``tn``) over the last two axes, the
    leading ones batched."""
    lead = tuple(range(rank - 2))
    last = {"nn": (rank - 1, rank - 2), "nt": (rank - 1, rank - 1),
            "tn": (rank - 2, rank - 2)}[kind]
    return (((last[0],), (last[1],)), (lead, lead))


def _dot(a, b, kind="nn"):
    """One bfloat16 pass with float32 accumulation."""
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           _dims(kind, a.ndim),
                           preferred_element_type=jnp.float32)


def _dot32(a, b, kind="nn"):
    """A product at float32 precision."""
    return lax.dot_general(a, b, _dims(kind, a.ndim), precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _running_sum(ones, x):
    """``ones @ x`` at float32 precision for a matrix of zeros and ones:
    ``x`` in three bfloat16 pieces that sum to it exactly, a pass each (the
    six passes of ``HIGHEST`` would multiply the other three by zero)."""
    ones = jnp.broadcast_to(ones.astype(jnp.bfloat16),
                            x.shape[:-2] + ones.shape)
    total = 0.0
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        total = total + lax.dot_general(
            ones, piece, _dims("nn", x.ndim),
            preferred_element_type=jnp.float32)
        x = x - piece.astype(jnp.float32)
    return total


def _iota(c):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row, col


def _column(row_vector, eye):
    """``(., 1, C)`` along the lanes -> ``(., C, 1)`` down the sublanes."""
    return jnp.sum(jnp.where(eye, row_vector, 0.0), axis=-1, keepdims=True)


def _rows(x, i):
    """Sub-chunk ``i``'s rows of ``x (., C, .)``."""
    return x[..., i * SUB:(i + 1) * SUB, :]


def _factors(q, k, g):
    """What a chunk's products are made of, from ``q, k, g (., C, d)``: the
    inclusive running sum ``cum``; the row factors ``exp(cum - first)``
    (rows referenced to their sub-chunk's first token: at most 1) and a
    column factor a sub-chunk ``exp(first_I - cum)`` (columns of later
    sub-chunks masked before the exponential); ``exp(cum)`` and
    ``exp(cum_last - cum)``, both at most 1."""
    c = q.shape[-2]
    row, col = _iota(c)
    cum = _running_sum(col <= row, g)
    subs = c // SUB
    firsts = [cum[..., i * SUB:i * SUB + 1, :] for i in range(subs)]
    rowf = jnp.exp(cum - jnp.concatenate(
        [jnp.broadcast_to(f, f.shape[:-2] + (SUB, f.shape[-1]))
         for f in firsts], axis=-2))
    token = lax.broadcasted_iota(jnp.int32, cum.shape, cum.ndim - 2)
    colfs = [jnp.exp(jnp.where(token < (i + 1) * SUB, f - cum, -jnp.inf))
             for i, f in enumerate(firsts)]
    last = cum[..., c - 1:c, :]
    kr, qr = k * rowf, q * rowf
    return dict(rowf=rowf, colfs=colfs, kcs=[k * f for f in colfs],
                rows=[jnp.concatenate([_rows(kr, i), _rows(qr, i)], axis=-2)
                      for i in range(subs)],
                grow=jnp.exp(cum), ef=jnp.exp(last - cum),
                total=jnp.exp(last))


def _pairs(f):
    """``A[t, i] = sum_c k[t] k[i] exp(cum[t] - cum[i])`` and ``B`` the same
    with ``q[t]``, unmasked ``(., C, C)`` each: a sub-chunk's rows of both
    from one product."""
    both = [_dot(x, kc, "nt") for x, kc in zip(f["rows"], f["kcs"])]
    # of a product's 2 SUB rows the first SUB are k's, the others q's
    return (jnp.concatenate([_rows(x, 0) for x in both], axis=-2),
            jnp.concatenate([_rows(x, 1) for x in both], axis=-2))


def _pairs_bwd(f, da, dbm):
    """The cotangents of ``_pairs``' two results back to ``k`` through the
    row factors, ``q`` through the row factors and ``k`` through the column
    factors, ``(., C, d)`` each; and what they send to the sub-chunks'
    reference points, a ``(., 1, d)`` a sub-chunk.  The last is zero in
    exact arithmetic (the reference cancels between a row's and a column's
    factor) and is kept all the same: it is the negative of what the rounded
    products leave of that cancellation, and without it the error of
    ``cum``'s cotangent adds up under the reverse running sum (the decay's
    gradient read 2.2e-2 of its norm where the einsums read 2.6e-3)."""
    drows, dfirsts, dk_col = [], [], 0.0
    for i, (x, kc, colf) in enumerate(zip(f["rows"], f["kcs"], f["colfs"])):
        dx = jnp.concatenate([_rows(da, i), _rows(dbm, i)], axis=-2)
        dxr, dkc = _dot(dx, kc), _dot(dx, x, "tn")
        drows.append(dxr)
        dk_col = dk_col + dkc * colf
        dfirsts.append(jnp.sum(dkc * kc, axis=-2, keepdims=True)
                       - jnp.sum(dxr * x, axis=-2, keepdims=True))
    return (jnp.concatenate([_rows(x, 0) for x in drows], axis=-2)
            * f["rowf"],
            jnp.concatenate([_rows(x, 1) for x in drows], axis=-2)
            * f["rowf"], dk_col, dfirsts)


def _inverse(n, row, col):
    """``(I + n)^-1`` for strictly lower-triangular ``n (., C, C)``, exact:
    the diagonal blocks of ``SUB`` rows by elimination (``I + D`` is the
    product of ``I + d_j e_j^T`` over its columns, so its inverse is ``I``
    less ``d_j`` times row ``j``, a column after another: the forward
    substitution, on the vector unit, every block and head at once), then
    the module's finite product over the blocks."""
    c = n.shape[-1]
    blocks = c // SUB
    lead = n.shape[:-2]
    d = jnp.where(row // SUB == col // SUB, n, 0.0)
    # a block's own SUB columns: in its rows the other blocks' are zero
    own = d[..., :SUB]
    for b in range(1, blocks):
        own = own + d[..., b * SUB:(b + 1) * SUB]
    own = own.reshape(lead + (blocks, SUB, SUB))
    inv = (row == col).astype(jnp.float32).reshape(blocks, SUB, c)
    for j in range(SUB - 1):
        inv = inv - own[..., j:j + 1] * inv[..., j:j + 1, :]
    inv = inv.reshape(lead + (c, c))
    if blocks == 1:
        return inv
    m, exponent = _dot32(inv, n - d), 1
    out = inv - _dot32(m, inv)
    while 2 * exponent < blocks:        # (I - M)(I + M^2)(I + M^4)...
        m, exponent = _dot32(m, m), 2 * exponent
        out = out + _dot32(m, out)
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, *rest, keep):
    from jax.experimental import pallas as pl

    state = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    row, col = _iota(q_ref.shape[1])
    q, k, v = q_ref[...], k_ref[...], v_ref[...]        # (heads, C, .)
    f = _factors(q, k, g_ref[...])
    beta = _column(beta_ref[:, 0], row == col)
    st = state[...]                                     # (heads, e, d)
    a, bm = _pairs(f)
    t = _inverse(jnp.where(col < row, beta * a, 0.0), row, col)
    u = _dot32(t, beta * (v - _dot(k * f["grow"], st, "nt")))
    out_ref[...] = _dot(q * f["grow"], st, "nt") \
        + _dot(jnp.where(col <= row, bm, 0.0), u)
    state[...] = st * f["total"] + _dot(u, k * f["ef"], "tn")
    if keep:
        st_ref, t_ref, u_ref = rest[:3]
        st_ref[:, 0] = st
        t_ref[:, 0] = t
        u_ref[:, 0] = u


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, t_ref, u_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    c = q_ref.shape[1]
    row, col = _iota(c)
    eye = row == col
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    st, t, u = st_ref[:, 0], t_ref[:, 0], u_ref[:, 0]
    dst = dstate[...]                                   # (heads, e, d)
    f = _factors(q, k, g_ref[...])
    beta = _column(beta_ref[:, 0], eye)
    a, bm = _pairs(f)
    kg, qg, kend = k * f["grow"], q * f["grow"], k * f["ef"]
    p = v - _dot(kg, st, "nt")
    # out = qg S + B u,  S' = total S + kend^T u,  u = T (beta p)
    du = _dot(jnp.where(col <= row, bm, 0.0), do, "tn") \
        + _dot(kend, dst, "nt")
    dbm = jnp.where(col <= row, _dot(do, u, "nt"), 0.0)
    dqg = _dot(do, st)
    dkend = _dot(u, dst)
    dr = _dot32(t, du, "tn")
    dn = -jnp.where(col < row, _dot32(dr, u, "nt"), 0.0)
    dbeta = jnp.sum(dr * p, axis=-1, keepdims=True) \
        + jnp.sum(dn * a, axis=-1, keepdims=True)
    dp = beta * dr
    da = beta * dn
    dkg = -_dot(dp, st)
    dstate[...] = dst * f["total"] + _dot(do, qg, "tn") - _dot(dp, kg, "tn")
    dk_row, dq_row, dk_col, dfirsts = _pairs_bwd(f, da, dbm)
    dq = dq_row + dqg * f["grow"]
    dk_end = dkend * f["ef"]
    dk = dk_row + dk_col + dkg * f["grow"] + dk_end
    # every factor is k or q times an exponential of cum: its share of cum's
    # cotangent is the operand times the operand's own share; cum_last and
    # the sub-chunks' reference points take the rest
    dcum = k * (dk - 2.0 * (dk_col + dk_end)) + q * dq
    dlast = jnp.sum(k * dk_end, axis=-2, keepdims=True) \
        + jnp.sum(st * dst, axis=-2, keepdims=True) * f["total"]
    token = lax.broadcasted_iota(jnp.int32, dcum.shape, dcum.ndim - 2)
    dcum = dcum + jnp.where(token == c - 1, dlast, 0.0)
    for i, dfirst in enumerate(dfirsts):
        dcum = dcum + jnp.where(token == i * SUB, dfirst, 0.0)
    dq_ref[...] = dq
    dk_ref[...] = dk
    dv_ref[...] = dp
    dg_ref[...] = _running_sum(col >= row, dcum)
    dbeta_ref[:, 0] = jnp.sum(jnp.where(eye, dbeta, 0.0), axis=-2,
                              keepdims=True)


def _specs(n, c, heads, reverse):
    from jax.experimental import pallas as pl

    def at(j):
        return n - 1 - j if reverse else j

    def tokens(width):
        return pl.BlockSpec((heads, c, width), lambda i, j: (i, at(j), 0))

    def a_chunk(rows, width):
        return pl.BlockSpec((heads, 1, rows, width),
                            lambda i, j: (i, at(j), 0, 0))
    return tokens, a_chunk


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _heads_a_step(bh):
    return max(h for h in range(1, HEADS_A_STEP + 1) if bh % h == 0)


def _fwd_pallas(q, k, v, g, beta, c, keep, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    e = v.shape[2]
    n = t // c
    heads = _heads_a_step(bh)
    tokens, a_chunk = _specs(n, c, heads, False)
    f32 = jnp.float32
    out_specs = [tokens(e)]
    out_shape = [jax.ShapeDtypeStruct((bh, t, e), f32)]
    if keep:
        out_specs += [a_chunk(e, d), a_chunk(c, c), a_chunk(c, e)]
        out_shape += [jax.ShapeDtypeStruct((bh, n, e, d), f32),
                      jax.ShapeDtypeStruct((bh, n, c, c), f32),
                      jax.ShapeDtypeStruct((bh, n, c, e), f32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=(bh // heads, n),
        in_specs=[tokens(d), tokens(d), tokens(e), tokens(d),
                  a_chunk(1, c)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, e, d), f32)],
        compiler_params=_params(), interpret=interpret, name="mx_kda_fwd",
    )(q, k, v, g, beta.reshape(bh, n, 1, c))


def _bwd_pallas(q, k, v, g, beta, st, tinv, u, do, c, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    e = v.shape[2]
    n = t // c
    heads = _heads_a_step(bh)
    tokens, a_chunk = _specs(n, c, heads, True)
    f32 = jnp.float32
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(bh // heads, n),
        in_specs=[tokens(d), tokens(d), tokens(e), tokens(d), a_chunk(1, c),
                  a_chunk(e, d), a_chunk(c, c), a_chunk(c, e), tokens(e)],
        out_specs=[tokens(d), tokens(d), tokens(e), tokens(d),
                   a_chunk(1, c)],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), f32),
                   jax.ShapeDtypeStruct((bh, t, d), f32),
                   jax.ShapeDtypeStruct((bh, t, e), f32),
                   jax.ShapeDtypeStruct((bh, t, d), f32),
                   jax.ShapeDtypeStruct((bh, n, 1, c), f32)],
        scratch_shapes=[pltpu.VMEM((heads, e, d), f32)],
        compiler_params=_params(), interpret=interpret, name="mx_kda_bwd",
    )(q, k, v, g, beta.reshape(bh, n, 1, c), st, tinv, u, do)
    return dq, dk, dv, dg, dbeta.reshape(bh, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunk_scan(q, k, v, g, beta, c):
    """The gated delta rule over whole chunks of ``c`` tokens: ``q, k, g
    (BH, T, d)``, ``v (BH, T, e)``, ``beta (BH, T)``, all float32, ``T`` a
    multiple of ``c``; returns ``(BH, T, e)``."""
    return _platform_pick(functools.partial(_fwd_pallas, c=c, keep=False),
                          q, k, v, g, beta)[0]


def _chunk_scan_fwd(q, k, v, g, beta, c):
    out, st, tinv, u = _platform_pick(
        functools.partial(_fwd_pallas, c=c, keep=True), q, k, v, g, beta)
    return out, (q, k, v, g, beta, st, tinv, u)


def _chunk_scan_bwd(c, res, do):
    return _platform_pick(functools.partial(_bwd_pallas, c=c), *res, do)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
