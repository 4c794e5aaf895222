"""Paged-attention prefill/decode graphs + AOT serving bundles.

The serving tier never runs the gluon model.  Its programs are built
from a :class:`KVGeometry` alone — every one takes the cache state
(donated), then the weight tree ``(embed, layers, norm, head)`` (an
ordinary argument), then the step's inputs — so no program holds a weight;
at export the Llama weights are pulled out of the block tree and written
once, beside the programs.  The graphs —

- ``prefill_<T>`` (one per sequence-length bucket): runs the whole
  prompt through full causal attention, scatters every K/V row into the
  paged arena, and returns the logits of the last real token;
- ``decode``: one token per active slot, batched over the server's
  fixed ``max_batch`` — RoPE at the slot's position, scatter into the
  page the block table names, then attention over the gathered pages;
- ``verify`` (when the geometry carries ``spec_k > 0``): the
  speculative-decoding signature — ``spec_k + 1`` tokens per lane (the
  last accepted token plus ``spec_k`` n-gram drafts), scattered and
  attended causally in one call, returning per-position logits so the
  scheduler can accept the longest exactly-matching draft prefix
  (ISSUE 13; Leviathan et al.).  Verify-K over tokens ``t..t+K`` is
  *exactly* K+1 sequential decodes: each query position only attends
  KV rows at or before its own position, rejected drafts' garbage rows
  sit beyond every accepted query's mask and are overwritten by the
  next call before anything reads them.

The arena stores KV in the model dtype or — when the geometry says
``kv_dtype="int8"`` — as int8 pages with one float32 scale per
``(layer, page)``.  Quantization happens on append inside the compiled
graphs: the row written to a page's **slot 0** fixes that page's scale
(its own absmax with 2x headroom) and later rows in the page quantize
against it, never rescaling what is already stored.  That makes the
quantized arena state a pure function of the token sequence —
independent of how tokens were grouped into prefill/decode/verify calls
— which is what lets the spec-on and spec-off greedy outputs stay
token-for-token identical at int8.  Page reuse is safe for free: a new
owner's first write to a page is always that page's slot 0 (positions
are written in order), which resets the scale.

All of them donate the cache state — one ``(P, KV, page, D)`` buffer a
layer and side (:func:`state_avals`; on a TPU ``D`` is padded to whole
128-lane rows) — so a layer's append is written where its pages lie and
the paged kernel reads them there: no program makes an array the size of
a layer's pages (tests/test_tpu_compile.py).  Each compiled program ships
once, and the weights once, in one PR 7 ``MXAOT1`` bundle whose meta
carries the KV-page geometry; a serving process deserializes the
programs, places the weights on the device once and performs **zero live
jits** (asserted by the serve-smoke CI job).

Numerics match ``gluon.model_zoo.llama`` exactly: RMSNorm in f32
(``lax.rsqrt``), rotate-half RoPE with the same inv-freq table, GQA via
post-projection head repeat — the paged decode's logits agree with the
full-sequence forward to float tolerance (tests/test_serve_e2e.py).
"""
from __future__ import annotations

import math
import os

import numpy as np

from ..base import MXNetError

BUNDLE_KIND = "serving"
# the bundle entry that holds the weight tree; every other entry is a
# serialized program
WEIGHTS_ENTRY = "weights"

# geometry fields a serving bundle must carry; the load-time validator
# refuses a bundle missing any of them (satellite: fail at load, not
# inside XLA on the first mismatched decode).  The others default: the
# arena in the model dtype, speculation and chunked prefill off.
_GEOM_INT_FIELDS = ("num_layers", "num_heads", "num_kv_heads", "head_dim",
                    "units", "hidden_size", "vocab_size", "page_size",
                    "num_pages", "max_pages_per_seq", "max_batch")

# int8 paged-KV quantization constants.  A page's scale is fixed by its
# slot-0 row's absmax with this headroom (later rows clip past it);
# 2x keeps one extra bit of range for K/V magnitude drift within a page
# at the cost of one bit of precision.
_INT8_QMAX = 127.0
_INT8_SCALE_HEADROOM = 2.0
_INT8_MIN_SCALE = 1e-8  # an all-zero slot-0 row must not divide by zero


class KVGeometry:
    """Shape contract between exporter, arena, scheduler and executables.

    Everything the serving process must agree on with the bundle lives
    here: the paged-KV layout (``page_size`` tokens per page,
    ``num_pages`` total — page 0 is reserved as the null page inactive
    slots scribble on), the decode batch width ``max_batch`` the
    executable was compiled for, and the prefill bucket ladder.
    """

    def __init__(self, num_layers, num_heads, num_kv_heads, head_dim,
                 units, hidden_size, vocab_size, page_size, num_pages,
                 max_pages_per_seq, max_batch, prefill_buckets,
                 dtype="float32", rope_base=10000.0, eps=1e-6,
                 tie_embeddings=False, kv_dtype=None, spec_k=0,
                 paged_kernel=None, prefill_chunk=0):
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.units = int(units)
        self.hidden_size = int(hidden_size)
        self.vocab_size = int(vocab_size)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.max_batch = int(max_batch)
        self.prefill_buckets = tuple(sorted(int(b) for b in prefill_buckets))
        self.dtype = str(dtype)
        self.rope_base = float(rope_base)
        self.eps = float(eps)
        self.tie_embeddings = bool(tie_embeddings)
        # default: the arena in the model dtype, speculation off
        self.kv_dtype = str(kv_dtype) if kv_dtype else self.dtype
        self.spec_k = int(spec_k)
        # ISSUE 19: chunked-prefill width.  > 0 additionally compiles a
        # batched mid-sequence ``chunk`` executable (the step graph at
        # k1=prefill_chunk) so long / over-bucket prompts prefill in
        # ladder-sized chunks interleaved with decode steps, and cached
        # prefix splices resume mid-sequence.  0 = off.
        self.prefill_chunk = int(prefill_chunk)
        # PR 14: which decode/verify attention the executables were
        # BUILT with — "auto" (Pallas kernel on TPU, XLA reference
        # elsewhere), "1" (kernel forced; interpreter off-TPU), "0"
        # (reference forced).  Baked at export: a bundle records the
        # choice in its meta, a loaded server inherits it.
        if paged_kernel is None or paged_kernel == "":
            paged_kernel = "auto"
        if isinstance(paged_kernel, bool) or isinstance(paged_kernel, int):
            paged_kernel = str(int(paged_kernel))
        self.paged_kernel = str(paged_kernel).lower()
        self.validate()

    @property
    def max_context(self):
        """Tokens addressable per sequence (prompt + generated)."""
        return self.max_pages_per_seq * self.page_size

    def validate(self):
        if self.page_size <= 0 or self.num_pages <= 1:
            raise MXNetError(
                "KV geometry needs page_size>0 and num_pages>1 (page 0 is "
                "the reserved null page); got page_size=%d num_pages=%d"
                % (self.page_size, self.num_pages))
        if self.max_batch <= 0 or self.max_pages_per_seq <= 0:
            raise MXNetError("KV geometry needs max_batch>0 and "
                             "max_pages_per_seq>0")
        if not self.prefill_buckets:
            raise MXNetError("KV geometry needs at least one prefill bucket")
        if self.prefill_buckets[-1] > self.max_context:
            raise MXNetError(
                "largest prefill bucket (%d) exceeds max context %d "
                "(= max_pages_per_seq %d x page_size %d)"
                % (self.prefill_buckets[-1], self.max_context,
                   self.max_pages_per_seq, self.page_size))
        if self.num_heads % self.num_kv_heads:
            raise MXNetError("num_heads must be a multiple of num_kv_heads")
        if self.kv_dtype not in (self.dtype, "int8"):
            raise MXNetError(
                "kv_dtype must be the model dtype (%r) or 'int8', got %r"
                % (self.dtype, self.kv_dtype))
        if not 0 <= self.spec_k <= 64:
            raise MXNetError("spec_k must be in [0, 64] (draft tokens "
                             "verified per decode call), got %d"
                             % self.spec_k)
        if self.paged_kernel not in ("auto", "0", "1"):
            raise MXNetError(
                "paged_kernel must be 'auto', '0' or '1' (see "
                "MXNET_SERVE_PAGED_KERNEL in docs/env_vars.md), got %r"
                % self.paged_kernel)
        if self.prefill_chunk < 0 or self.prefill_chunk > self.max_context:
            raise MXNetError(
                "prefill_chunk must be in [0, max_context=%d] (0 "
                "disables chunked prefill), got %d"
                % (self.max_context, self.prefill_chunk))

    def to_dict(self):
        return {
            "num_layers": self.num_layers, "num_heads": self.num_heads,
            "num_kv_heads": self.num_kv_heads, "head_dim": self.head_dim,
            "units": self.units, "hidden_size": self.hidden_size,
            "vocab_size": self.vocab_size, "page_size": self.page_size,
            "num_pages": self.num_pages,
            "max_pages_per_seq": self.max_pages_per_seq,
            "max_batch": self.max_batch,
            "prefill_buckets": list(self.prefill_buckets),
            "dtype": self.dtype, "rope_base": self.rope_base,
            "eps": self.eps, "tie_embeddings": self.tie_embeddings,
            "kv_dtype": self.kv_dtype, "spec_k": self.spec_k,
            "paged_kernel": self.paged_kernel,
            "prefill_chunk": self.prefill_chunk,
        }

    @classmethod
    def from_dict(cls, d, origin="bundle"):
        missing = [f for f in _GEOM_INT_FIELDS if f not in d]
        if missing or "prefill_buckets" not in d:
            raise MXNetError(
                "%s: serving bundle geometry is missing %s — re-export "
                "with serve.export_serving_bundle"
                % (origin, ", ".join(missing) or "prefill_buckets"))
        return cls(**d)

    def kv_shape(self):
        """One layer's K (or V) buffer: (P, KV-heads, page, head-dim) —
        the arena is ``2 x num_layers`` of them (:func:`state_avals`).

        A page of one kv-head is the contiguous ``(page, head-dim)`` tile
        the paged-attention kernel DMAs: the TPU lowering takes a block
        only if its last two dims are the array's own or multiples of
        (8, 128), which a block of 1 on a kv-head axis in second-minor
        position is not."""
        return (self.num_pages, self.num_kv_heads, self.page_size,
                self.head_dim)

    # the fields a replacement bundle must agree on for an in-place
    # hot-swap (``LlamaServer.reload``): everything the scheduler and
    # the queued requests already depend on — paging layout, batch
    # width, bucket ladder, vocabulary, arena dtype and verify width.
    # Model internals (layers, heads, weights) are free to change: the
    # arena is rebuilt from the new geometry, and programs, weights and
    # arena swap together.
    HOT_SWAP_FIELDS = ("page_size", "num_pages", "max_pages_per_seq",
                       "max_batch", "prefill_buckets", "vocab_size",
                       "kv_dtype", "spec_k", "prefill_chunk")

    def hot_swap_pins(self):
        """The geometry subset ``reload()`` pins (``check_geometry``
        dict) — a candidate bundle mismatching any of these would strand
        queued requests or tear live block tables."""
        d = self.to_dict()
        return {f: d[f] for f in self.HOT_SWAP_FIELDS}

    @property
    def quantized(self):
        """True when the arena stores int8 pages with per-page scales."""
        return self.kv_dtype == "int8"

    def scale_shape(self):
        """One layer's quantization scales for K (or V): (pages,), one
        float32 scale a page."""
        return (self.num_pages,)

    def arena_bytes(self, padded=False):
        """What the cache takes: ``2 x num_layers`` page buffers, and
        their scale rows when quantized.  ``padded``: as a TPU holds
        them, in (8, 128) tiles and with the head dim in whole 128-lane
        rows (:func:`state_avals`) — 2x at a 64-wide head."""
        rows, lanes = self.page_size, self.head_dim
        if padded:
            rows, lanes = -(-rows // 8) * 8, -(-lanes // 128) * 128
        side = self.num_pages * (
            self.num_kv_heads * rows * lanes
            * np.dtype(self.kv_dtype).itemsize + 4 * self.quantized)
        return 2 * self.num_layers * side

    def describe(self):
        return ("layers=%d heads=%d/%d head_dim=%d pages=%dx%d "
                "max_batch=%d buckets=%s dtype=%s kv_dtype=%s spec_k=%d "
                "paged_kernel=%s prefill_chunk=%d arena=%.3fGB "
                "(%.3fGB in a TPU's tiles)"
                % (self.num_layers, self.num_heads, self.num_kv_heads,
                   self.head_dim, self.num_pages, self.page_size,
                   self.max_batch, list(self.prefill_buckets), self.dtype,
                   self.kv_dtype, self.spec_k, self.paged_kernel,
                   self.prefill_chunk, self.arena_bytes() / 1e9,
                   self.arena_bytes(padded=True) / 1e9))


def _env_int(name, default):
    v = os.environ.get(name, "")
    return int(v) if v.strip() else default


def default_buckets():
    """Prefill bucket ladder from MXNET_SERVE_BUCKETS (docs/env_vars.md)."""
    raw = os.environ.get("MXNET_SERVE_BUCKETS", "").strip()
    if not raw:
        return (32, 128, 512)
    try:
        return tuple(sorted({int(t) for t in raw.split(",") if t.strip()}))
    except ValueError:
        raise MXNetError("MXNET_SERVE_BUCKETS must be comma-separated ints, "
                         "got %r" % raw)


def geometry_from_net(net, page_size=None, num_pages=None, max_batch=None,
                      prefill_buckets=None, max_pages_per_seq=None,
                      kv_dtype=None, spec_k=None, paged_kernel=None,
                      prefill_chunk=None):
    """Derive a :class:`KVGeometry` from a ``LlamaModel`` block tree,
    filling paging knobs from ``MXNET_SERVE_*`` env defaults."""
    blocks = list(net.blocks._children.values())
    if not blocks:
        raise MXNetError("model has no decoder blocks")
    attn = blocks[0].attn
    embed_w = net.embed.weight.data()
    page_size = page_size or _env_int("MXNET_SERVE_PAGE_SIZE", 16)
    num_pages = num_pages or _env_int("MXNET_SERVE_NUM_PAGES", 512)
    max_batch = max_batch or _env_int("MXNET_SERVE_MAX_BATCH", 8)
    kv_dtype = kv_dtype \
        or os.environ.get("MXNET_SERVE_KV_DTYPE", "").strip() or None
    spec_k = spec_k if spec_k is not None \
        else _env_int("MXNET_SERVE_SPEC_K", 0)
    prefill_chunk = prefill_chunk if prefill_chunk is not None \
        else _env_int("MXNET_SERVE_PREFILL_CHUNK", 0)
    if paged_kernel is None:
        paged_kernel = os.environ.get("MXNET_SERVE_PAGED_KERNEL",
                                      "").strip() or None
    buckets = tuple(prefill_buckets) if prefill_buckets \
        else default_buckets()
    if max_pages_per_seq is None:
        # default: a full batch can at most address the whole arena. The
        # block-table width is also the attention context every decode /
        # verify call gathers, so an over-wide table (the old default let
        # one lane claim half the arena) taxes every step with mostly-null
        # pages. Floored so the bucket ladder always fits.
        need = -(-max(buckets) // page_size)
        max_pages_per_seq = max(need + 1, num_pages // max_batch)
    return KVGeometry(
        num_layers=len(blocks), num_heads=attn._heads,
        num_kv_heads=attn._kv_heads,
        head_dim=attn._units // attn._heads, units=net._units,
        hidden_size=blocks[0].ffn.gate.weight.shape[0],
        vocab_size=embed_w.shape[0], page_size=page_size,
        num_pages=num_pages, max_pages_per_seq=max_pages_per_seq,
        max_batch=max_batch, prefill_buckets=buckets,
        dtype=str(embed_w.dtype), rope_base=attn._base,
        eps=blocks[0].attn_norm._eps, tie_embeddings=net._tie,
        kv_dtype=kv_dtype, spec_k=spec_k, paged_kernel=paged_kernel,
        prefill_chunk=prefill_chunk)


def _pull(param):
    """Export-time weight pull — runs once per parameter per export, not
    on any serving path."""
    return param.data().asnumpy()  # mxlint: allow-host-sync


def extract_weights(net):
    """Pull the Llama weights out of the block tree as numpy arrays.

    Returns ``(embed, layers, norm, head)`` where ``layers`` is a list of
    per-block dicts; ``head`` is None for tied embeddings.  Dense weights
    keep the gluon (out, in) layout — the graphs apply ``x @ W.T``.
    """
    embed = _pull(net.embed.weight)
    layers = []
    for blk in net.blocks._children.values():
        layers.append({
            "attn_norm": _pull(blk.attn_norm.weight),
            "q": _pull(blk.attn.q_proj.weight),
            "k": _pull(blk.attn.k_proj.weight),
            "v": _pull(blk.attn.v_proj.weight),
            "o": _pull(blk.attn.o_proj.weight),
            "ffn_norm": _pull(blk.ffn_norm.weight),
            "gate": _pull(blk.ffn.gate.weight),
            "up": _pull(blk.ffn.up.weight),
            "down": _pull(blk.ffn.down.weight),
        })
    norm = _pull(net.norm.weight)
    head = None if net._tie else _pull(net.lm_head.weight)
    return embed, layers, norm, head


def _rmsnorm(x, gamma, eps):
    """f32-accumulated RMSNorm, bitwise-matching ops.nn.RMSNorm."""
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def _rope_tables(positions, head_dim, base):
    """cos/sin tables (…, half) for rotate-half RoPE at ``positions``
    (float32, any leading shape) — same inv-freq form as llama._rope."""
    import jax.numpy as jnp

    half = head_dim // 2
    inv = jnp.arange(0, half, dtype=jnp.float32) * (-2.0 / head_dim)
    inv_freq = jnp.exp(inv * math.log(base))
    freqs = positions[..., None] * inv_freq
    return jnp.cos(freqs), jnp.sin(freqs)


def _rotate(x, cos, sin):
    """Rotate-half on (…, D); cos/sin broadcast over the head axis."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def weight_avals(geometry):
    """The weight tree ``(embed, layers, norm, head)`` as
    ``jax.ShapeDtypeStruct``s — the geometry alone says every shape, so
    the programs compile with no weight anywhere (``head`` is None for
    tied embeddings; dense weights keep the gluon (out, in) layout)."""
    import jax

    g = geometry
    dt = np.dtype(g.dtype)

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    u, f = g.units, g.hidden_size
    hd, kvd = g.num_heads * g.head_dim, g.num_kv_heads * g.head_dim
    layer = {"attn_norm": aval(u), "q": aval(hd, u), "k": aval(kvd, u),
             "v": aval(kvd, u), "o": aval(u, hd), "ffn_norm": aval(u),
             "gate": aval(f, u), "up": aval(f, u), "down": aval(u, f)}
    return (aval(g.vocab_size, u),
            [dict(layer) for _ in range(g.num_layers)], aval(u),
            None if g.tie_embeddings else aval(g.vocab_size, u))


def state_avals(geometry, device=None):
    """The cache state as ``jax.ShapeDtypeStruct``s: one entry a layer,
    each ``((k_pages, k_scale), (v_pages, v_scale))`` — the scale a
    ``scale_shape()`` float32 row for int8 and None otherwise.  Every
    buffer is a donated argument of its own, so a layer's append aliases
    that layer's buffer and the paged kernel reads it where it lies.

    Pages are ``kv_shape()`` in the arena dtype, except that for a TPU
    (``device``, default the first one) the head dim is padded to whole
    128-lane rows.  The chip holds a row-major ``(page, D)`` tile in 128
    lanes whatever ``D`` is, so the padding costs no byte
    (``KVGeometry.arena_bytes(padded=True)``); but left an array whose
    rows are not whole lanes, it lays it out pages-minor instead, and
    every step then turns every layer's buffer over, twice, to feed the
    kernel (PERF.md 7.1)."""
    import jax

    g = geometry
    lanes = g.head_dim
    if (device or jax.devices()[0]).platform == "tpu":
        lanes = -(-lanes // 128) * 128
    side = (jax.ShapeDtypeStruct(g.kv_shape()[:-1] + (lanes,),
                                 np.dtype(g.kv_dtype)),
            jax.ShapeDtypeStruct(g.scale_shape(), np.dtype(np.float32))
            if g.quantized else None)
    return tuple((side, side) for _ in range(g.num_layers))


def _to_lanes(x, lanes):
    """``x`` with zeros after its last axis up to ``lanes`` wide."""
    import jax.numpy as jnp

    pad = lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _absmax(rows):
    """Per-token absmax over (KV, D) in float32 (int8 scale input)."""
    import jax.numpy as jnp

    return jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=(-2, -1))


def build_step_fn(geometry, k1):
    """``k1`` tokens per lane through the paged arena in one call.

    This is the shared body of ``decode`` (``k1=1``), ``verify``
    (``k1=spec_k+1``) and ``chunk`` (``k1=prefill_chunk``):

    ``(state, weights, tokens (B, k1) i32, positions (B,) i32,
    block_table (B, maxp) i32) -> (state, logits (B, k1, V) f32)``

    ``state`` is the cache (:func:`state_avals`), donated by the AOT
    compile; ``weights`` the tree of :func:`weight_avals`, an ordinary
    argument — no program holds a weight.

    Lane ``b``'s token ``j`` sits at position ``positions[b] + j``;
    query ``j`` attends context ``<= positions[b] + j`` only, so the
    per-position logits equal what ``k1`` sequential single-token
    decodes would produce (the exactness speculative acceptance rides
    on).  Inactive slots point their block-table row at the reserved
    null page 0 with position 0 — their scatters land there harmlessly
    (every lane writes the same pad-token rows, so even the duplicate
    null-page scatters are deterministic) and their logits are
    discarded by the scheduler.

    Int8 append: the row landing on a page's slot 0 fixes the page
    scale (own absmax x headroom / 127); rows landing further into a
    page quantize against the page's current scale — the scale of its
    slot-0 write, whether that write happened in this call or in an
    earlier one.  Nothing already stored is ever requantized, so arena
    bytes after token t are independent of call grouping.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.paged_attention import paged_attention as _paged_attn

    g = geometry
    H, KV, D, S = g.num_heads, g.num_kv_heads, g.head_dim, g.page_size
    scale = 1.0 / math.sqrt(D)
    ctx = g.max_pages_per_seq * S
    jidx = np.arange(k1)
    # attention path, resolved at BUILD time (the executable is AOT-
    # compiled for the default backend, so there is nothing to defer):
    # "1" forces the Pallas kernel (interpreter off-TPU — it traces to
    # plain jax ops and serializes into the bundle, the CI parity
    # path), "0" forces the gather + grouped-einsum reference, "auto"
    # takes the kernel on TPU and the reference elsewhere.
    kernel = g.paged_kernel == "1" or (
        g.paged_kernel == "auto" and jax.default_backend() == "tpu")

    def gather(side, block_table, b, dt):
        """This lane's pages as (B, C, KV, D) in the model dtype."""
        pages, sc = side
        got = pages[block_table][..., :D]      # (B, maxp, KV, S, D)
        if sc is not None:
            got = (got.astype(jnp.float32)
                   * sc[block_table][..., None, None, None]).astype(dt)
        return got.transpose(0, 1, 3, 2, 4).reshape(b, ctx, KV, D)

    def step(state, weights, tokens, positions, block_table):
        embed, layers, norm, head = weights
        b = tokens.shape[0]
        x = embed[tokens]                                    # (B, k1, U)
        pos = positions[:, None] + jidx[None, :]             # (B, k1)
        cos, sin = _rope_tables(pos.astype(jnp.float32), D,
                                g.rope_base)                 # (B, k1, half)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        rows_b = jnp.arange(b)
        pid = block_table[rows_b[:, None], pos // S]         # (B, k1)
        slot = pos % S
        valid = jnp.arange(ctx)[None, None, :] <= pos[..., None]

        def append(side, rows):
            """Write ``rows`` (B, k1, KV, D) at ``[pid, :, slot]`` of one
            layer's ``(pages, scale)``; quantize against per-page scales
            when the arena is int8.  The scatter names page, kv-head and
            slot, so each update is one contiguous row (``D`` wide, zeros
            up to the buffer's lanes) of the layout the paged kernel
            reads, and the buffer is updated where it lies."""
            pages, sc = side
            at = (pid[..., None], jnp.arange(KV), slot[..., None])
            if sc is not None:
                # in-call page starts: token j's page began at call
                # offset j - slot[j]; negative means the page's slot 0
                # was written by an earlier call and its stored scale
                # rules
                start = jidx[None, :] - slot                 # (B, k1)
                first = jnp.take_along_axis(
                    _absmax(rows), jnp.clip(start, 0, k1 - 1), axis=1)
                news = jnp.maximum(jnp.where(
                    start >= 0, first * (_INT8_SCALE_HEADROOM / _INT8_QMAX),
                    sc[pid]), _INT8_MIN_SCALE)
                rows = jnp.clip(
                    jnp.round(rows.astype(jnp.float32)
                              / news[..., None, None]),
                    -_INT8_QMAX, _INT8_QMAX)
                # rows of one page all write the page's resolved scale —
                # equal values, so duplicate scatter order cannot matter
                sc = sc.at[pid].set(news)
            rows = _to_lanes(rows.astype(pages.dtype), pages.shape[-1])
            return pages.at[at].set(rows), sc

        new_state = []
        for lw, (k_side, v_side) in zip(layers, state):
            h = _rmsnorm(x, lw["attn_norm"], g.eps)
            q = _rotate((h @ lw["q"].T).reshape(b, k1, H, D), cos, sin)
            k = _rotate((h @ lw["k"].T).reshape(b, k1, KV, D), cos, sin)
            v = (h @ lw["v"].T).reshape(b, k1, KV, D)
            k_side, v_side = append(k_side, k), append(v_side, v)
            new_state.append((k_side, v_side))
            if kernel:
                # fused gather + dequant + online-softmax attention
                # straight off this layer's pages — no (B, ctx, KV, D)
                # HBM materialization, no fp32 dequant copy, no GQA
                # replication (ops/paged_attention.py)
                # (a buffer wider than D holds zeros there: the padded
                # query's scores are the query's, the result's lanes
                # past D are zero)
                att = _paged_attn(_to_lanes(q, k_side[0].shape[-1]),
                                  k_side[0], v_side[0], block_table,
                                  positions, k_scale=k_side[1],
                                  v_scale=v_side[1], scale=scale,
                                  use_kernel=1)[..., :D]
            else:
                # XLA reference: still gathers the context, but attends
                # grouped heads (B, k1, KV, G, ctx) directly — K/V are
                # never replicated H/KV-fold (equal to the jnp.repeat
                # form to float rounding, tests/test_paged_attention
                # .py::test_grouped_einsum_matches_repeat)
                keys = gather(k_side, block_table, b, x.dtype)
                vals = gather(v_side, block_table, b, x.dtype)
                qg = q.reshape(b, k1, KV, H // KV, D)
                scores = jnp.einsum("bkvgd,bcvd->bkvgc", qg, keys) * scale
                scores = jnp.where(valid[:, :, None, None, :],
                                   scores.astype(jnp.float32), -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
                att = jnp.einsum("bkvgc,bcvd->bkvgd", probs, vals) \
                    .reshape(b, k1, H, D)
            x = x + att.reshape(b, k1, H * D) @ lw["o"].T
            h2 = _rmsnorm(x, lw["ffn_norm"], g.eps)
            x = x + (jax.nn.silu(h2 @ lw["gate"].T)
                     * (h2 @ lw["up"].T)) @ lw["down"].T
        xh = _rmsnorm(x, norm, g.eps)
        hw = embed if head is None else head
        logits = (xh @ hw.T).astype(jnp.float32)             # (B, k1, V)
        return tuple(new_state), logits

    return step


def build_decode_fn(geometry):
    """One batched single-token decode step: the ``k1=1`` slice of
    :func:`build_step_fn` with tokens ``(B,)`` and logits ``(B, V)``."""
    step = build_step_fn(geometry, 1)

    def decode(state, weights, tokens, positions, block_table):
        state, logits = step(state, weights, tokens[:, None], positions,
                             block_table)
        return state, logits[:, 0]

    return decode


def build_verify_fn(geometry):
    """The speculative-decoding signature: ``spec_k + 1`` tokens per
    lane — ``tokens[:, 0]`` is the last accepted token, ``tokens[:,
    1:]`` the drafts — returning logits at every position so the
    scheduler accepts the longest draft prefix the model reproduces."""
    if geometry.spec_k <= 0:
        raise MXNetError("verify needs a geometry with spec_k > 0")
    return build_step_fn(geometry, geometry.spec_k + 1)


def build_prefill_fn(geometry, bucket):
    """Whole-prompt pass for one padded bucket length ``T``.

    ``(state, weights, tokens (T,) i32, length () i32,
    block_table (maxp,) i32) -> (state, logits (V,) f32)``, state and
    weights as in :func:`build_step_fn`.

    Every position's K/V is written into the arena a whole page at a
    time (pad positions land on the null page or on this sequence's own
    not-yet-read slots, both harmless: a pad-set page scale is reset by
    the sequence's own later slot-0 write before any masked-in read; the
    table's unallocated entries all name the null page, whose content
    nothing reads); the returned logits are the
    last REAL token's — the first generated token comes straight out of
    prefill.  Attention here runs over the in-call full-precision K/V,
    not the arena, so prefill logits are identical between fp32 and int8
    bundles; only the *stored* pages are quantized.
    """
    import jax
    import jax.numpy as jnp

    g = geometry
    H, KV, D, S = g.num_heads, g.num_kv_heads, g.head_dim, g.page_size
    scale = 1.0 / math.sqrt(D)
    t = int(bucket)

    def prefill(state, weights, tokens, length, block_table):
        embed, layers, norm, head = weights
        x = embed[tokens]                                    # (T, U)
        pos = jnp.arange(t)
        cos, sin = _rope_tables(pos.astype(jnp.float32), D, g.rope_base)
        cos, sin = cos[:, None, :], sin[:, None, :]          # (T, 1, half)
        causal = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] < length)                        # (T, T)
        n_pages = -(-t // S)

        def append(side, rows):
            """``rows`` (T, KV, D) as whole pages at the table's first
            entries: one contiguous ``(KV, S, lanes)`` update a page.
            Every page start is in-call during prefill: a page's slot-0
            row fixes its scale."""
            pages, sc = side
            tiles = jnp.pad(rows, ((0, n_pages * S - t), (0, 0), (0, 0))) \
                .reshape(n_pages, S, KV, D).transpose(0, 2, 1, 3)
            if sc is not None:
                news = jnp.maximum(
                    _absmax(tiles[:, :, 0].reshape(n_pages, KV, D))
                    * (_INT8_SCALE_HEADROOM / _INT8_QMAX), _INT8_MIN_SCALE)
                tiles = jnp.clip(jnp.round(
                    tiles.astype(jnp.float32) / news[:, None, None, None]),
                    -_INT8_QMAX, _INT8_QMAX)
                sc = sc.at[block_table[:n_pages]].set(news)
            tiles = _to_lanes(tiles.astype(pages.dtype), pages.shape[-1])
            return pages.at[block_table[:n_pages]].set(tiles), sc

        new_state = []
        for lw, (k_side, v_side) in zip(layers, state):
            h = _rmsnorm(x, lw["attn_norm"], g.eps)
            q = _rotate((h @ lw["q"].T).reshape(t, H, D), cos, sin)
            k = _rotate((h @ lw["k"].T).reshape(t, KV, D), cos, sin)
            v = (h @ lw["v"].T).reshape(t, KV, D)
            new_state.append((append(k_side, k), append(v_side, v)))
            # grouped-head attention: queries fold to (T, KV, G, D) so
            # K/V are never replicated H/KV-fold (equal to the
            # jnp.repeat form to float rounding; head h = kv*G + g)
            qg = q.reshape(t, KV, H // KV, D)
            scores = jnp.einsum("tvgd,uvd->vgtu", qg, k) * scale
            scores = jnp.where(causal[None, None, :, :],
                               scores.astype(jnp.float32), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            att = jnp.einsum("vgtu,uvd->tvgd", probs, v)
            x = x + att.reshape(t, H * D) @ lw["o"].T
            h2 = _rmsnorm(x, lw["ffn_norm"], g.eps)
            x = x + (jax.nn.silu(h2 @ lw["gate"].T)
                     * (h2 @ lw["up"].T)) @ lw["down"].T
        xh = _rmsnorm(x, norm, g.eps)
        last = jnp.take(xh, length - 1, axis=0)              # (U,)
        hw = embed if head is None else head
        logits = (last @ hw.T).astype(jnp.float32)
        return tuple(new_state), logits

    return prefill


def serving_programs(geometry, device=None):
    """``{name: (fn, avals)}`` for every program the geometry names:
    ``decode``, ``verify`` (``spec_k > 0``), ``chunk`` (``prefill_chunk
    > 0``: the step graph at that width — scatters a chunk of prompt
    tokens and attends causally over arena context, so a prompt resumes
    at any position) and one ``prefill_<T>`` a bucket.  ``avals`` are
    shapes alone, cache state first, weights second, for ``device``
    (default: the first; a described one, which the avals then name,
    compiles with no chip)."""
    import jax

    g = geometry
    i32 = np.dtype(np.int32)
    head = (state_avals(g, device), weight_avals(g))

    def lanes(*tok_shape):
        return head + (
            jax.ShapeDtypeStruct(tok_shape, i32),
            jax.ShapeDtypeStruct((g.max_batch,), i32),
            jax.ShapeDtypeStruct((g.max_batch, g.max_pages_per_seq), i32))

    programs = {"decode": (build_decode_fn(g), lanes(g.max_batch))}
    if g.spec_k > 0:
        programs["verify"] = (build_verify_fn(g),
                              lanes(g.max_batch, g.spec_k + 1))
    if g.prefill_chunk > 0:
        programs["chunk"] = (build_step_fn(g, g.prefill_chunk),
                             lanes(g.max_batch, g.prefill_chunk))
    for b in g.prefill_buckets:
        programs["prefill_%d" % b] = (build_prefill_fn(g, b), head + (
            jax.ShapeDtypeStruct((b,), i32), jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((g.max_pages_per_seq,), i32)))
    if device is not None:
        where = jax.sharding.SingleDeviceSharding(device)
        programs = {name: (fn, jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            avals)) for name, (fn, avals) in programs.items()}
    return programs


def compile_serving_executables(geometry, device=None):
    """AOT-compile :func:`serving_programs` from shapes alone: ``{name:
    jax.stages.Compiled}``.  The cache state (argument 0) is donated,
    so the decode loop updates the pages in place — aliasing survives
    executable serialization on every backend under jax 0.9.0.  The same
    geometry gives the same programs whatever the weights, so jax's
    compile cache serves every export after the first."""
    import jax

    from .. import profiler as _profiler

    with _profiler.setup_span("serve.export"):
        return {name: jax.jit(fn, donate_argnums=(0,)).lower(*avals)
                .compile() for name, (fn, avals)
                in serving_programs(geometry, device).items()}


def export_serving_bundle(net, path, page_size=None, num_pages=None,
                          max_batch=None, prefill_buckets=None,
                          max_pages_per_seq=None, mesh=None,
                          kv_dtype=None, spec_k=None, paged_kernel=None,
                          prefill_chunk=None):
    """Export ``net`` as a self-contained MXAOT1 serving bundle.

    One file: each AOT-compiled program once (decode, per-bucket
    prefill, verify / chunk where the geometry asks), the weights once
    (the ``weights`` entry, host arrays in the model dtype) and the
    :class:`KVGeometry` in the meta, so ``serve.LlamaServer(path)``
    starts with zero live compiles.
    Paging knobs default from ``MXNET_SERVE_*`` (docs/env_vars.md);
    ``kv_dtype="int8"`` quantizes the arena pages, ``spec_k=K`` adds the
    compiled ``verify`` executable for n-gram speculative decoding, and
    ``paged_kernel`` ("auto"/"1"/"0", default from
    ``MXNET_SERVE_PAGED_KERNEL``) picks the decode/verify attention the
    executables are built with — the choice is baked into the compiled
    graphs and recorded in the geometry meta.  Returns the geometry.

    ``mesh`` (a Mesh / axes dict — abstract, no devices needed) runs the
    auto-sharding planner over the weight tree and stores its decision
    under ``meta["planner"]`` — chosen per-weight specs plus a suggested
    KV-arena spec — so a sharded server can be brought up from the
    bundle with zero live jits AND zero hand-written specs
    (``planner.plan_serving``).  The executables themselves stay
    single-device; the planner meta is advisory placement data.
    """
    g = geometry_from_net(net, page_size=page_size, num_pages=num_pages,
                          max_batch=max_batch,
                          prefill_buckets=prefill_buckets,
                          max_pages_per_seq=max_pages_per_seq,
                          kv_dtype=kv_dtype, spec_k=spec_k,
                          paged_kernel=paged_kernel,
                          prefill_chunk=prefill_chunk)
    meta = {}
    if mesh is not None:
        from .. import planner as _planner

        meta["planner"] = _planner.plan_serving(net, g, mesh)
    save_serving_bundle(path, g, extract_weights(net), meta)
    return g


def save_serving_bundle(path, geometry, weights, meta=None):
    """Compile ``geometry``'s programs and write them, with the host
    weight tree ``(embed, layers, norm, head)`` (:func:`weight_avals`'s
    shapes; cast to the model dtype), as one MXAOT1 file — the half of
    :func:`export_serving_bundle` that needs no gluon net."""
    import jax

    from .. import compile_cache as _ccache

    g = geometry
    entries = {name: _ccache.serialize_compiled(c)
               for name, c in compile_serving_executables(g).items()}
    entries[WEIGHTS_ENTRY] = jax.tree_util.tree_map(
        lambda a: np.asarray(a, dtype=g.dtype), weights)
    _ccache.save_bundle(path, entries, meta=dict(
        meta or {}, kind=BUNDLE_KIND, geometry=g.to_dict()))


def read_bundle_geometry(path):
    """Parse + validate a serving bundle's KV geometry WITHOUT
    deserializing any executable (Predictor's redirect error, doctor
    tools).  Returns ``(KVGeometry, doc)``."""
    from .. import compile_cache as _ccache

    doc = _ccache.load_bundle(path)
    meta = doc.get("meta", {})
    if meta.get("kind") != BUNDLE_KIND:
        raise MXNetError(
            "%s is not a serving bundle (kind=%r) — export one with "
            "serve.export_serving_bundle(net, path)"
            % (path, meta.get("kind")))
    return KVGeometry.from_dict(meta.get("geometry", {}), origin=path), doc


def load_serving_executables(path, expect=None):
    """Load a serving bundle: ``(KVGeometry, {name: Compiled},
    weights)`` — the weight tree placed on the device once, to be
    passed to every program after the cache state.

    Validation happens HERE, not on the first decode: the bundle must be
    a serving bundle, its meta must carry a complete geometry, every
    executable named by the geometry and the weights must be present,
    and — when the caller passes ``expect`` (a KVGeometry or partial
    dict) — the KV-page geometry must agree field by field, each
    mismatch named in the error.
    """
    import jax

    from .. import compile_cache as _ccache
    from .. import profiler as _profiler
    from ..telemetry import memdump as _memdump

    g, doc = read_bundle_geometry(path)
    if expect is not None:
        check_geometry(g, expect, origin=path)
    want = list(serving_programs(g))
    entries = doc.get("entries", {})
    if WEIGHTS_ENTRY not in entries:
        raise MXNetError(
            "%s: serving bundle carries no weights (its programs held "
            "them as constants) — re-export with "
            "serve.export_serving_bundle" % path)
    missing = [n for n in want if n not in entries]
    if missing:
        raise MXNetError("%s: serving bundle is missing executables %s "
                         "for geometry [%s]"
                         % (path, missing, g.describe()))
    with _profiler.setup_span("serve.load.programs"):
        exes = {n: _ccache.deserialize_compiled(entries[n]) for n in want}
    def sig(tree):
        return jax.tree_util.tree_map(
            lambda a: (tuple(a.shape), str(a.dtype)), tree)

    if sig(entries[WEIGHTS_ENTRY]) != sig(weight_avals(g)):
        raise MXNetError("%s: the bundle's weights are not the shapes its "
                         "geometry names [%s]" % (path, g.describe()))
    with _profiler.setup_span("serve.load.weights"):
        # to the end of the transfer, so that the stage is the weights'
        weights = jax.block_until_ready(
            jax.device_put(entries[WEIGHTS_ENTRY]))
        for leaf in jax.tree_util.tree_leaves(weights):
            _memdump.tag(leaf, origin="param", label="serving_weight")
    return g, exes, weights


def check_geometry(got, expect, origin="bundle"):
    """Field-by-field KV geometry comparison with a clear error.

    ``expect``: KVGeometry or a dict of the subset to pin (e.g.
    ``{"page_size": 16, "dtype": "float32"}``).
    """
    exp = expect.to_dict() if isinstance(expect, KVGeometry) else dict(expect)
    gd = got.to_dict()
    bad = []
    for field, want in exp.items():
        if field not in gd:
            raise MXNetError("%s: unknown geometry field %r" % (origin,
                                                                field))
        have = gd[field]
        if field == "prefill_buckets":
            want = list(want)
        if have != want:
            bad.append("%s: bundle has %r, caller expects %r"
                       % (field, have, want))
    if bad:
        raise MXNetError(
            "%s: KV-page geometry mismatch — refusing to serve (this "
            "would fail inside XLA on the first decode):\n  %s"
            % (origin, "\n  ".join(bad)))
