"""Pallas TPU kernels of the chunked gated delta rule (``ops/kda.py`` has
the equations and the overflow rule): ``mx_kda_fwd`` and ``mx_kda_bwd``.

The operands are read where their producers wrote them, a head's channels
as whole lane tiles of ``(B, T, H * d)`` and ``beta`` as ``(B, T, H)``: the
grid is ``(batch, chunk, group)``, a step one chunk of ``C`` tokens of a
group of up to ``HEADS_A_STEP`` heads, whose blocks it splits into arrays
with the heads leading (every product batched over them) and writes back as
lanes.  The chunk axis and the group axis inside it are sequential: the
float32 state of every head lives in a VMEM scratch across the chunks,
transposed ``(e, d)`` so that every product with it is a plain or a
transposed-operand matrix product, and a chunk's ``(C, H)`` block of
``beta``'s cotangent stays in VMEM while the groups fill their lanes.  A
step makes the running sum of ``g``, the row and column factors of the
overflow rule, ``A``, ``B``, the inverse, ``U`` and the new state in VMEM,
and writes ``out``: nothing but the operator's operands crosses HBM.  When
the backward pass asks, the forward also writes what a chunk's backward
cannot make again from the operands: the state the chunk starts from ``(e,
d)``, the inverse ``(C, C)`` and ``U (C, e)``, 112 KB a head and chunk,
which live until the backward kernel has read them.  The backward kernel
walks the chunks in reverse with the state's cotangent in VMEM.

Two operators share the kernels.  ``chunk_scan`` is the scan alone (``q, k,
v, g, beta`` as the algebra reads them).  ``mixer`` is a KDA mixer between
its projections (``ops/kda.py:kda_attention`` where the shapes tile): from
the projections' results a step makes, on the blocks it holds, the causal
convolutions and SiLU (the chunk before read as a block of its own), the
unit norms of ``q`` and ``k``, the decay gate, ``sigmoid(beta)``, and after
the scan the output's RMSNorm times its weight times ``sigmoid(gate)``; the
backward kernel makes the same again, and writes the projections'
cotangents in their own dtype, each chunk's token sums for ``A_log``,
``dt_bias``, the norm's weight and the taps (added up outside, a few KB),
and carries the convolutions' cotangent from a chunk's first tokens to the
chunk before in VMEM.  A token past the sequence (padded to whole chunks)
is masked by its position.

Precision: the running sums, the exponentials, the state, the mixer's
prologue and epilogue and every accumulation are float32.  The products of
the solve (the inverse's, ``U = T R``, the two products of its gradient) run
at float32 precision (``HIGHEST``), and so do the two running sums (three
bfloat16 pieces that sum to the float32 operand, against ones); every other
product rounds its operands to bfloat16 and accumulates in float32, which
is what the ``jax.numpy`` form's default-precision ``einsum`` does on a TPU.

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
# tokens after a chunk whose cotangent the mixer's backward kernel carries
# to the chunk before for the convolutions: a convolution reaches that many
# tokens back at most
SPAN = 16
# the mixer's kernel operands: q, k, v, decay, beta, gate, A_log, dt_bias,
# the output norm, the taps, and q, k, v again for the tokens before a chunk
MIXER_OPERANDS = 13
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


def _heads(x, width):
    """``(., G * width)`` -> ``(G, ., width)``: a head is whole lane tiles, so
    nothing moves."""
    return jnp.stack([x[:, h * width:(h + 1) * width]
                      for h in range(x.shape[-1] // width)])


def _store(ref, x, *lead):
    """``x (G, ., w)`` into ``ref[lead] (., G * w)`` (``lead`` indexes every
    axis but the lanes; all of a 2-D ``ref``), a head's lanes at a time."""
    w = x.shape[-1]
    for h in range(x.shape[0]):
        ref[(lead or (slice(None),)) + (slice(h * w, (h + 1) * w),)] = \
            x[h].astype(ref.dtype)


def _head_lanes(shape, grp, heads):
    """Of a ``(C, H)`` block, the lane of each head of group ``grp``."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return [lane == grp * heads + h for h in range(heads)]


def _sigmoid(x):
    """As ``0.5 + 0.5 tanh(x / 2)``: one transcendental and two vector
    operations where ``1 / (1 + exp(-x))`` takes a division (a thousand
    bundles a grid step in either kernel); float32, within 1e-7 of it."""
    return 0.5 + 0.5 * jnp.tanh(0.5 * x)


def _before(x, prev, s):
    """``x[t - s]`` along a chunk's tokens ``x (G, C, .)``, the first ``s``
    from the chunk before, ``prev (G, C, .)``: one select and one roll."""
    from jax.experimental.pallas import tpu as pltpu

    if s == 0:
        return x
    c = x.shape[1]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return pltpu.roll(jnp.where(row >= c - s, prev, x), s, 1)


def _after(x, nxt, s):
    """``x[t + s]``, the last ``s`` from the chunk after, ``nxt (G, C,
    .)`` (of which the first ``SPAN`` tokens are read)."""
    from jax.experimental.pallas import tpu as pltpu

    c = x.shape[1]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return pltpu.roll(jnp.where(row < s, nxt, x), c - s, 1)


def _operands(refs, grp, at, heads, tokens, mixer):
    """What a grid step's chunk is made of, from the group's blocks: ``q, k,
    g (G, C, d)``, ``v (G, C, e)`` and ``beta (G, C, 1)`` as the algebra
    reads them.  In the mixer's form they are made here from the
    projections' results (the convolutions and SiLU, the unit norms, the
    decay gate, the sigmoid), with what their gradients read beside them.
    ``tokens`` (when the sequence was padded to whole chunks) masks the
    tokens past it by position: they neither decay nor write."""
    f32 = jnp.float32
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs[:5]
    d, e = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    rows = beta_ref[...].astype(f32)                    # (C, H)
    x = dict(lanes=_head_lanes(rows.shape, grp, heads))
    beta = jnp.stack([jnp.sum(jnp.where(lane, rows, 0.0), axis=-1,
                              keepdims=True) for lane in x["lanes"]])
    q, k, v, g = (_heads(r[...].astype(f32), w)
                  for r, w in zip(refs[:4], (d, d, e, d)))
    if mixer:
        a_ref, dt_ref, _, taps_ref = refs[6:10]
        taps, x["conv"] = taps_ref[...], []
        for i, (raw, prev_ref) in enumerate(zip((q, k, v), refs[10:])):
            # the causal convolution over the tokens before the chunk too;
            # there are none before the first
            prev = jnp.where(at == 0, 0.0,
                             _heads(prev_ref[...].astype(f32), d))
            w = _heads(taps[i], d)                      # (G, K, d)
            n = w.shape[1]
            shifted = [_before(raw, prev, s) for s in range(n)]
            y = sum(xs * w[:, n - 1 - s:n - s] for s, xs in enumerate(shifted))
            x["conv"].append(dict(shifted=shifted, w=w, y=y,
                                  sig=_sigmoid(y)))
        q, k, v = (cv["y"] * cv["sig"] for cv in x["conv"])
        x["q_len"] = lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        x["k_len"] = lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        x["q_unit"], x["k_unit"] = q * x["q_len"], k * x["k_len"]
        q, k = x["q_unit"] * d ** -0.5, x["k_unit"]
        z = g + _heads(dt_ref[...], d)                  # decay + dt_bias
        x["z"], x["rate"] = z, -jnp.exp(_heads(a_ref[...], d))
        # softplus(z), as jax.nn.softplus writes it
        g = x["rate"] * (jnp.maximum(z, 0.0)
                         + jnp.log1p(jnp.exp(-jnp.abs(z))))
        beta = _sigmoid(beta)
    if tokens is not None:
        c = rows.shape[0]
        token = at * c + lax.broadcasted_iota(jnp.int32, (1, c, 1), 1)
        x["valid"] = token < tokens
        q, k, v, g, beta = (jnp.where(x["valid"], y, 0.0)
                            for y in (q, k, v, g, beta))
    x.update(q=q, k=k, v=v, g=g, beta=beta)
    return x


def _epilogue(o, gate, norm, eps):
    """The mixer's output from the scan's ``o (G, C, e)``: a head's RMSNorm
    times ``norm``, times ``sigmoid(gate)``; and what its gradient reads."""
    scale = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    unit, s = o * scale, _sigmoid(gate)
    return dict(scale=scale, unit=unit, s=s, y=unit * norm * s)


def _fwd_kernel(*refs, heads, mixer, keep, tokens, eps):
    from jax.experimental import pallas as pl

    n_in = MIXER_OPERANDS if mixer else 5
    ins, outs, state = refs[:n_in], refs[n_in:-1], refs[-1]
    j, grp = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        state[grp] = jnp.zeros(state.shape[1:], state.dtype)

    x = _operands(ins, grp, j, heads, tokens, mixer)
    q, k, v, beta = x["q"], x["k"], x["v"], x["beta"]
    row, col = _iota(q.shape[1])
    f = _factors(q, k, x["g"])
    st = state[grp]                                     # (G, e, d)
    a, bm = _pairs(f)
    t = _inverse(jnp.where(col < row, beta * a, 0.0), row, col)
    u = _dot32(t, beta * (v - _dot(k * f["grow"], st, "nt")))
    o = _dot(q * f["grow"], st, "nt") \
        + _dot(jnp.where(col <= row, bm, 0.0), u)
    state[grp] = st * f["total"] + _dot(u, k * f["ef"], "tn")
    if mixer:
        e = v.shape[-1]
        o = _epilogue(o, _heads(ins[5][...].astype(jnp.float32), e),
                      _heads(ins[8][...], e), eps)["y"]
    _store(outs[0], o)
    if keep:
        st_ref, t_ref, u_ref = outs[1:]
        st_ref[...] = st
        t_ref[...] = t
        u_ref[...] = u


def _bwd_kernel(*refs, heads, mixer, tokens, eps):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n_in = MIXER_OPERANDS if mixer else 5
    ins = refs[:n_in]
    st_ref, t_ref, u_ref, dout_ref = refs[n_in:n_in + 4]
    scratch = refs[-2:] if mixer else refs[-1:]
    outs, dstate = refs[n_in + 4:-len(scratch)], scratch[0]
    j, grp = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        for ref in scratch:
            ref[grp] = jnp.zeros(ref.shape[1:], ref.dtype)

    x = _operands(ins, grp, pl.num_programs(1) - 1 - j, heads, tokens, mixer)
    q, k, v, beta = x["q"], x["k"], x["v"], x["beta"]
    c, e = q.shape[1], v.shape[-1]
    row, col = _iota(c)
    st, t, u = st_ref[...], t_ref[...], u_ref[...]
    dst = dstate[grp]                                   # (G, e, d)
    f = _factors(q, k, x["g"])
    a, bm = _pairs(f)
    bm = jnp.where(col <= row, bm, 0.0)
    kg, qg, kend = k * f["grow"], q * f["grow"], k * f["ef"]
    do = _heads(dout_ref[...].astype(f32), e)
    if mixer:
        # the epilogue made again from the scan's result, and its gradient
        norm = _heads(ins[8][...], e)
        y = _epilogue(_dot(qg, st, "nt") + _dot(bm, u),
                      _heads(ins[5][...].astype(f32), e), norm, eps)
        dunit = do * norm * y["s"]
        dgate = do * y["unit"] * norm * y["s"] * (1.0 - y["s"])
        dnorm = jnp.sum(do * y["unit"] * y["s"], axis=1, keepdims=True)
        do = y["scale"] * (dunit - y["unit"] * jnp.mean(
            dunit * y["unit"], axis=-1, keepdims=True))
    p = v - _dot(kg, st, "nt")
    # out = qg S + B u,  S' = total S + kend^T u,  u = T (beta p)
    du = _dot(bm, do, "tn") + _dot(kend, dst, "nt")
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
    dstate[grp] = dst * f["total"] + _dot(do, qg, "tn") - _dot(dp, kg, "tn")
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
    dg = _running_sum(col >= row, dcum)
    if tokens is not None:
        dq, dk, dp, dg, dbeta = (jnp.where(x["valid"], y, 0.0)
                                 for y in (dq, dk, dp, dg, dbeta))
    if mixer:
        # back through the prologue: the unit norms, the gate, the sigmoid,
        # SiLU and the convolutions (whose cotangent from the next chunk's
        # first tokens the reversed walk carries in ``dnext``)
        d, dnext = q.shape[-1], scratch[1]
        dq = d ** -0.5 * x["q_len"] * (dq - x["q_unit"] * jnp.sum(
            dq * x["q_unit"], axis=-1, keepdims=True))
        dk = x["k_len"] * (dk - x["k_unit"] * jnp.sum(
            dk * x["k_unit"], axis=-1, keepdims=True))
        sums = [jnp.sum(dg * x["g"], axis=1, keepdims=True)]   # A_log's
        dg = dg * x["rate"] * _sigmoid(x["z"])
        sums += [jnp.sum(dg, axis=1, keepdims=True), dnorm]    # dt_bias's
        dbeta = dbeta * beta * (1.0 - beta)
        raw, taps = [], []
        for i, (dm, cv) in enumerate(zip((dq, dk, dp), x["conv"])):
            dy = dm * cv["sig"] * (1.0 + cv["y"] * (1.0 - cv["sig"]))
            nxt = dnext[grp, i]
            dnext[grp, i, :, :SPAN] = dy[:, :SPAN]
            n = cv["w"].shape[1]
            dx = dy * cv["w"][:, n - 1:]
            for s in range(1, n):
                dx = dx + _after(dy, nxt, s) * cv["w"][:, n - 1 - s:n - s]
            raw.append(dx)
            # tap n - 1 - s: the sum over the tokens of dy times x[t - s]
            taps.append([jnp.sum(dy * xs, axis=1, keepdims=True)
                         for xs in cv["shifted"]])
        dq, dk, dp = raw
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref = outs[:5]
    _store(dq_ref, dq)
    _store(dk_ref, dk)
    _store(dv_ref, dp)
    _store(dg_ref, dg)
    # the group's lanes of (C, H); the block stays in VMEM while the group
    # axis, the innermost, walks the heads of the chunk
    merged = dbeta_ref[...].astype(f32)
    for h, lane in enumerate(x["lanes"]):
        merged = jnp.where(lane, dbeta[h], merged)
    dbeta_ref[...] = merged.astype(dbeta_ref.dtype)
    if mixer:
        _store(outs[5], dgate)
        for ref, total in zip(outs[6:9], sums):
            _store(ref, total)
        for i, by_shift in enumerate(taps):
            n = len(by_shift)
            for s, total in enumerate(by_shift):
                tap = n - 1 - s
                _store(outs[9], total, i, slice(tap, tap + 1))


def _specs(n, c, heads, reverse):
    """Block specs over the grid ``(batch, chunk, group)``: ``cols(w)``, a
    chunk's rows and a group's lanes of ``(B, T, H * w)``; ``rows(h)``, a
    chunk's rows of ``(B, T, H)``, whole; ``lanes(w)``, a group's lanes of a
    ``(1, H * w)`` vector; ``kept(...)``, a chunk's block of the group's
    heads of ``(B, n, H, ...)``; ``sums(*lead, w)``, a chunk's ``(B, n,
    *lead, H * w)`` for the group; ``before(w)``, the chunk before of ``(B,
    T, H * w)`` (the first chunk's own: masked)."""
    from jax.experimental import pallas as pl

    def at(j):
        return n - 1 - j if reverse else j

    def cols(width):
        return pl.BlockSpec((None, c, heads * width),
                            lambda i, j, g: (i, at(j), g))

    def rows(h):
        return pl.BlockSpec((None, c, h), lambda i, j, g: (i, at(j), 0))

    def lanes(width):
        return pl.BlockSpec((1, heads * width), lambda i, j, g: (0, g))

    def kept(*shape):
        return pl.BlockSpec((None, None, heads) + shape,
                            lambda i, j, g: (i, at(j), g) + (0,) * len(shape))

    def sums(*shape):
        lead, width = shape[:-1], shape[-1]
        return pl.BlockSpec(
            (None, None) + lead + (heads * width,),
            lambda i, j, g: (i, at(j)) + (0,) * len(lead) + (g,))

    def before(width):
        return pl.BlockSpec((None, c, heads * width),
                            lambda i, j, g: (i, jnp.maximum(at(j) - 1, 0), g))
    return dict(cols=cols, rows=rows, lanes=lanes, kept=kept, sums=sums,
                before=before)


def _params():
    """The chunk axis and the group axis inside it are sequential: the
    state of every group is in VMEM (2 MB at 32 heads of 128 x 128, which
    takes the backward kernel past the compiler's own 16 MiB of scoped VMEM;
    a v5e has 128 MiB)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=32 << 20)


def _heads_a_step(h):
    return max(g for g in range(1, HEADS_A_STEP + 1) if h % g == 0)


def _sizes(ops):
    """``(B, T, H, d, e, G)``: a head's channels from the lanes."""
    b, t, hd = ops[0].shape
    h = ops[4].shape[-1]
    return b, t, h, hd // h, ops[2].shape[-1] // h, _heads_a_step(h)


def _in_specs(specs, ops, d, e, h, heads):
    cols = specs["cols"]
    out = [cols(d), cols(d), cols(e), cols(d), specs["rows"](h)]
    if len(ops) > 5:
        from jax.experimental import pallas as pl

        taps = pl.BlockSpec((3, ops[9].shape[1], heads * d),
                            lambda i, j, g: (0, 0, g))
        out += [cols(e), specs["lanes"](d), specs["lanes"](d),
                specs["lanes"](e), taps, specs["before"](d),
                specs["before"](d), specs["before"](e)]
    return out


def _fwd_pallas(*ops, c, tokens, eps, keep, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d, e, heads = _sizes(ops)
    n = t // c
    specs = _specs(n, c, heads, False)
    f32 = jnp.float32
    out_specs = [specs["cols"](e)]
    out_shape = [jax.ShapeDtypeStruct((b, t, h * e), f32)]
    if keep:
        kept = specs["kept"]
        out_specs += [kept(e, d), kept(c, c), kept(c, e)]
        out_shape += [jax.ShapeDtypeStruct((b, n, h, e, d), f32),
                      jax.ShapeDtypeStruct((b, n, h, c, c), f32),
                      jax.ShapeDtypeStruct((b, n, h, c, e), f32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, mixer=len(ops) > 5,
                          keep=keep, tokens=tokens, eps=eps),
        grid=(b, n, h // heads),
        in_specs=_in_specs(specs, ops, d, e, h, heads),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h // heads, heads, e, d), f32)],
        compiler_params=_params(), interpret=interpret, name="mx_kda_fwd",
    )(*ops)


def _bwd_pallas(*ops, c, tokens, eps, mixer, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ins = ops[:MIXER_OPERANDS if mixer else 5]
    b, t, h, d, e, heads = _sizes(ins)
    n = t // c
    specs = _specs(n, c, heads, True)
    cols, kept, sums = specs["cols"], specs["kept"], specs["sums"]
    f32 = jnp.float32

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    out_specs = [cols(d), cols(d), cols(e), cols(d), specs["rows"](h)]
    out_shape = [like(x) for x in ins[:5]]
    scratch = [pltpu.VMEM((h // heads, heads, e, d), f32)]
    if mixer:
        k = ins[9].shape[1]
        out_specs += [cols(e), sums(1, d), sums(1, d), sums(1, e),
                      sums(3, k, d)]
        out_shape += [like(ins[5])] + [
            jax.ShapeDtypeStruct((b, n, 1, h * w), f32) for w in (d, d, e)] \
            + [jax.ShapeDtypeStruct((b, n, 3, k, h * d), f32)]
        scratch += [pltpu.VMEM((h // heads, 3, heads, c, d), f32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, mixer=mixer,
                          tokens=tokens, eps=eps),
        grid=(b, n, h // heads),
        in_specs=_in_specs(specs, ins, d, e, h, heads)
        + [kept(e, d), kept(c, c), kept(c, e), cols(e)],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_params(), interpret=interpret, name="mx_kda_bwd",
    )(*ops)


def _forward(ops, c, tokens, eps, keep):
    if len(ops) > 5:
        ops = ops + ops[:3]     # the tokens before a chunk: the same arrays
    return _platform_pick(functools.partial(
        _fwd_pallas, c=c, tokens=tokens, eps=eps, keep=keep), *ops)


def _backward(ops, kept, dout, c, tokens, eps):
    mixer = len(ops) > 5
    grads = _platform_pick(functools.partial(
        _bwd_pallas, c=c, tokens=tokens, eps=eps, mixer=mixer),
        *ops, *(ops[:3] if mixer else ()), *kept, dout)
    # the vectors' and the taps' gradients: a chunk's token sums, added
    return tuple(grads[:6]) + tuple(x.sum(axis=(0, 1)) for x in grads[6:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunk_scan(q, k, v, g, beta, c):
    """The gated delta rule over whole chunks of ``c`` tokens: ``q, k, g
    (B, T, H * d)``, ``v (B, T, H * e)``, ``beta (B, T, H)``, all float32,
    ``T`` a multiple of ``c``, a padded token ``g = 0`` and ``beta = 0``;
    returns ``(B, T, H * e)``."""
    return _forward((q, k, v, g, beta), c, None, 0.0, False)[0]


def _chunk_scan_fwd(q, k, v, g, beta, c):
    ops = (q, k, v, g, beta)
    out, *kept = _forward(ops, c, None, 0.0, True)
    return out, (ops, kept)


def _chunk_scan_bwd(c, res, dout):
    return _backward(*res, dout, c, None, 0.0)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12))
def mixer(q, k, v, decay, beta, gate, a, dt_bias, norm, taps, c, tokens,
          eps):
    """A KDA mixer between its projections, over whole chunks of ``c``
    tokens: the projections' results ``q, k, decay (B, T, H * d)``, ``v,
    gate (B, T, H * e)`` (``e = d``), ``beta (B, T, H)``, in any float dtype;
    ``a`` and ``dt_bias (1, H * d)`` (``A_log`` a head, repeated over its
    channels), ``norm (1, H * e)`` (the output norm's weight, repeated over
    the heads), ``taps (3, K, H * d)`` (the three convolutions', ``K`` at
    most ``SPAN + 1``); ``tokens``, where ``T`` was padded to whole chunks,
    the tokens before the padding.  Returns ``(B, T, H * e)`` float32::

        q, k, v = silu(causal_conv1d(x, taps)), in float32
        q, k    = x / sqrt(sum x^2 + 1e-6) a head;  q *= d ** -0.5
        g       = -exp(a) * softplus(decay + dt_bias);  beta = sigmoid(beta)
        o       = the delta rule
        out     = o / sqrt(mean o^2 + eps) * norm * sigmoid(gate), a head"""
    ops = (q, k, v, decay, beta, gate, a, dt_bias, norm, taps)
    return _forward(ops, c, tokens, eps, False)[0]


def _mixer_fwd(q, k, v, decay, beta, gate, a, dt_bias, norm, taps, c, tokens,
               eps):
    ops = (q, k, v, decay, beta, gate, a, dt_bias, norm, taps)
    out, *kept = _forward(ops, c, tokens, eps, True)
    return out, (ops, kept)


def _mixer_bwd(c, tokens, eps, res, dout):
    return _backward(*res, dout, c, tokens, eps)


mixer.defvjp(_mixer_fwd, _mixer_bwd)
