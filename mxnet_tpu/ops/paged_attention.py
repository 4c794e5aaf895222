"""Pallas TPU kernel: paged attention over the serve arena's block tables.

The serving decode/verify hot loop (``serve/model.py build_step_fn``)
historically paid stock XLA paging: ``kv[li, block_table]`` materializes
every lane's full ``(B, max_pages·page_size, KV, D)`` context in HBM each
step, and an int8 arena additionally materializes a full fp32 dequantized
copy before attention starts.  This module is the vLLM/PagedAttention
pattern instead: pages are stored ``(P, KV, page_size, D)``, a Pallas
kernel's grid walks ``(batch-lane, kv-head, page)`` and prefetches the
block table as scalars, so each step DMAs exactly one kv-head's
contiguous ``(page_size, D)`` page tile into VMEM, dequantizes in-register off
the per-(layer, page) scale, and accumulates flash-style online softmax.
HBM traffic drops from O(ctx·KV·D) gathered+dequantized per step to the
pages actually stored, and GQA never replicates K/V ``H/KV``-fold — the
query is folded to ``(B, KV, k1·H/KV, D)`` so grouped heads share one
page load.

Semantics (shared by kernel and reference): query ``j`` of lane ``b``
sits at position ``positions[b] + j`` and attends context positions
``<= positions[b] + j`` on that lane's pages only; page 0 is the arena's
reserved null page and is always masked (an active lane's live context
never maps to page 0, so this only zeroes inactive-lane garbage the
scheduler discards anyway).  Fully-masked query rows return 0.

Registered as ``_contrib_paged_attention`` so the op-consistency harness
and mxlint cover it like any other op; ``use_kernel`` picks the path:
``0`` = pure-jnp reference, ``1`` = the Pallas kernel (compiled on TPU,
interpreter elsewhere — CI parity runs), unset/``auto`` = kernel on TPU,
reference elsewhere (the interpreter is correct but slow; off-TPU
production decode should take the XLA reference, not emulation).  The
choice follows the platform and ``use_kernel`` only: a kernel the TPU
compiler refuses is an error, never a reason to take the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .pallas_kernels import _LANES, _platform_pick
from .registry import register


def _paged_ref(q, k_pages, v_pages, block_table, positions, *scales,
               scale):
    """Pure-jnp reference: gather + (dequant) + grouped-GQA attention.

    Matches the kernel's masking exactly (position AND null-page); the
    softmax is the plain two-pass form with fully-masked rows guarded
    to zero output.
    """
    b, k1, h, d = q.shape
    kv, s_page = k_pages.shape[1], k_pages.shape[2]
    maxp = block_table.shape[1]
    grp = h // kv
    ctx = maxp * s_page
    keys = k_pages[block_table].astype(jnp.float32)  # (B, maxp, KV, S, D)
    vals = v_pages[block_table].astype(jnp.float32)
    if scales:
        ks, vs = scales
        keys = keys * ks[block_table][..., None, None, None]
        vals = vals * vs[block_table][..., None, None, None]
    keys = keys.transpose(0, 1, 3, 2, 4).reshape(b, ctx, kv, d)
    vals = vals.transpose(0, 1, 3, 2, 4).reshape(b, ctx, kv, d)
    qg = q.astype(jnp.float32).reshape(b, k1, kv, grp, d)
    s = jnp.einsum("bkvgd,bcvd->bkvgc", qg, keys) * scale
    posk = positions[:, None] + jnp.arange(k1)[None, :]      # (B, k1)
    ok = (jnp.arange(ctx)[None, None, :] <= posk[..., None]) \
        & jnp.repeat(block_table != 0, s_page, axis=1)[:, None, :]
    s = jnp.where(ok[:, :, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)   # all-masked row -> exp(-inf)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    att = jnp.einsum("bkvgc,bcvd->bkvgd",
                     p / jnp.where(l == 0, 1.0, l), vals)
    return att.reshape(b, k1, h, d).astype(q.dtype)


def _paged_kernel(tbl_ref, pos_ref, *refs, grp, page, scale, quantized):
    """One (lane, kv-head, page) grid step of online-softmax attention.

    The page axis is innermost — Pallas TPU runs the grid sequentially,
    so the VMEM scratch ``(m, l, acc)`` carries across a lane's pages
    and is (re)initialized whenever the page index wraps to 0.  The
    block table itself is a scalar-prefetch operand: the k/v BlockSpec
    index maps read ``tbl[b, p]`` so the pipeline DMAs exactly the page
    the table names (the null page 0 is still fetched but fully masked).
    """
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, \
            acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pid = tbl_ref[b, p]
    q = q_ref[0, 0].astype(jnp.float32) * scale              # (QG, D)
    k = k_ref[0, 0].astype(jnp.float32)                      # (S, D)
    v = v_ref[0, 0].astype(jnp.float32)
    if quantized:
        k = k * ks_ref[pid]
        v = v * vs_ref[pid]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (QG, S)
    qg = s.shape[0]
    # query row r is head-group r % grp of query token r // grp; its
    # absolute position is positions[b] + r // grp
    row = lax.broadcasted_iota(jnp.int32, (qg, page), 0) // grp
    col = p * page + lax.broadcasted_iota(jnp.int32, (qg, page), 1)
    ok = (col <= pos_ref[b] + row) & (pid != 0)
    s = jnp.where(ok, s, -jnp.inf)

    m = m_ref[...][:, :1]                                    # (QG, 1)
    l = l_ref[...][:, :1]
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # a fully-null prefix keeps m_new = -inf; exp against 0 instead so
    # masked rows contribute exact zeros rather than nans
    safe_m = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    pmat = jnp.exp(s - safe_m)
    alpha = jnp.exp(m - safe_m)
    l_new = l * alpha + pmat.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        pmat, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = lax.broadcast_in_dim(m_new[:, 0], m_ref.shape, (0,))
    l_ref[...] = lax.broadcast_in_dim(l_new[:, 0], l_ref.shape, (0,))

    @pl.when(p == n_p - 1)
    def _done():
        lf = l_ref[...][:, :1]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.where(lf == 0, 1.0, lf)).astype(o_ref.dtype)


def _paged_pallas(q, k_pages, v_pages, block_table, positions, *scales,
                  scale, grp, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k1, h, d = q.shape
    kv, s_page = k_pages.shape[1], k_pages.shape[2]
    maxp = block_table.shape[1]
    qg = k1 * grp
    # fold GQA into the query: (B, k1, H, D) -> (B, KV, k1*G, D) with
    # row r = j*G + g <-> head h = kv*G + g (the jnp.repeat ordering),
    # so grouped heads ride one page load instead of replicating K/V
    q4 = q.reshape(b, k1, kv, grp, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kv, qg, d)
    quant = bool(scales)
    kernel = functools.partial(_paged_kernel, grp=grp, page=s_page,
                               scale=scale, quantized=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(scales),
        grid=(b, kv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, qg, d), lambda b, kv, p, *s: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, s_page, d),
                         lambda b, kv, p, *s: (s[0][b, p], kv, 0, 0)),
            pl.BlockSpec((1, 1, s_page, d),
                         lambda b, kv, p, *s: (s[0][b, p], kv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qg, d),
                               lambda b, kv, p, *s: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qg, _LANES), jnp.float32),
            pltpu.VMEM((qg, _LANES), jnp.float32),
            pltpu.VMEM((qg, d), jnp.float32),
        ],
    )
    out4 = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, qg, d), q.dtype),
        interpret=interpret,
        name="mx_paged_attention",
    )(block_table, positions, *scales, q4, k_pages, v_pages)
    return out4.reshape(b, kv, k1, grp, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, k1, h, d)


@register("_contrib_paged_attention",
          inputs=("query", "k_pages", "v_pages", "block_table",
                  "positions", "k_scale", "v_scale"))
def paged_attention(query, k_pages, v_pages, block_table, positions,
                    k_scale=None, v_scale=None, scale=None,
                    use_kernel=None):
    """Paged attention over block tables: ``(B, k1, H, D)`` queries
    against ``(P, KV, S, D)`` K/V pages addressed by a ``(B, maxp)``
    int32 block table, one scalar position per lane.

    ``k1`` is the query width — 1 for decode, ``spec_k + 1`` for
    speculative verify; query ``j`` attends positions
    ``<= positions[b] + j``.  Page 0 is the reserved null page and is
    always masked.  ``k_scale``/``v_scale`` ``(P,)`` f32, when given,
    dequantize int8 pages in-register.  ``scale`` defaults to
    ``1/sqrt(D)``.  ``use_kernel``: ``0`` reference, ``1`` the Pallas
    kernel (interpreter off-TPU), unset = kernel on TPU, reference
    elsewhere.

    TPU note: the kernel's K/V block is one kv-head's whole
    ``(page_size, D)`` tile, so the lowering takes any page size and
    head dim; tiles pad to (8, 128) f32, (16, 128) bf16, (32, 128) int8,
    so a ``page_size`` below those wastes the padding.
    """
    if (k_scale is None) != (v_scale is None):
        raise MXNetError("_contrib_paged_attention needs both k_scale "
                         "and v_scale or neither")
    if query.ndim != 4 or k_pages.ndim != 4:
        raise MXNetError(
            "_contrib_paged_attention wants query (B, k1, H, D) and "
            "pages (P, KV, S, D); got %s / %s"
            % (query.shape, k_pages.shape))
    h, d = query.shape[2], query.shape[3]
    kv = k_pages.shape[1]
    if h % kv or k_pages.shape[3] != d:
        raise MXNetError(
            "_contrib_paged_attention: %d query heads do not group over "
            "%d kv heads (head_dim %d vs %d)"
            % (h, kv, d, k_pages.shape[3]))
    if scale is None or scale == 0:
        scale = 1.0 / (d ** 0.5)
    scale = float(scale)
    block_table = block_table.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    scales = () if k_scale is None else (k_scale.astype(jnp.float32),
                                         v_scale.astype(jnp.float32))
    args = (query, k_pages, v_pages, block_table, positions) + scales
    mode = "auto" if use_kernel is None or str(use_kernel) == "auto" \
        else str(int(use_kernel))
    krun = functools.partial(_paged_pallas, scale=scale, grp=h // kv)
    rrun = functools.partial(_paged_ref, scale=scale)
    if mode == "0":
        return rrun(*args)
    # "1": compiled kernel on TPU, interpreter elsewhere (it traces to
    # plain jax ops, so it serializes into AOT bundles — the CI parity
    # path).  auto: off the TPU take the XLA reference; the interpreter
    # is for parity tests, not production CPU decode.
    return _platform_pick(krun, *args,
                          off_tpu=None if mode == "1" else rrun)
