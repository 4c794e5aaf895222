"""Kimi Linear: a hybrid decoder of Kimi Delta Attention and latent
attention layers over routed SwiGLU experts (``model_type``
``kimi_linear``; Kimi Team, "Kimi Linear: An Expressive, Efficient
Attention Architecture", 2025, and the ``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct``).

Every layer is TWO sub-blocks, each behind its own pre-norm residual: ``x =
x + mixer(RMSNorm(x)); x = x + ffn(RMSNorm(x))``.  Layers count from 1;
``kda_layers`` and ``full_attn_layers`` say which mixer a layer has, the
first ``first_k_dense`` layers have the dense feed-forward and every later
one the routed.

- ``KDAMixer`` (scope ``kda``): ``q, k, v = silu(causal_conv1d(W u))``
  (depthwise, no bias); ``q, k`` L2-normalised over a head's channels, ``q``
  scaled by ``head_dim ** -0.5``; a log-decay a channel ``g = -exp(A_log) *
  softplus(W_fb W_fa u + dt_bias)``; ``beta = sigmoid(W_b u)``; the gated
  delta rule; ``o = RMSNorm_head(o) * sigmoid(W_gb W_ga u)``; ``W_o``.
  Everything between the projections is one operator,
  ``F.contrib.kda_attention`` (float32 inside, the chunked scan under the
  scope ``kda_scan``; one checkpoint, so that a layer keeps its
  projections' results and not sixteen float32 arrays of them); where the
  shapes tile, its kernels take it whole and read the projections'
  results where the products wrote them (``ops/kda_kernels.py:mixer``).
- ``MLAMixer`` (scope ``mla``): latent attention materialised for
  training, here with **no rotary embedding** (``mla_use_nope``: the
  ``rope`` channels are plain channels) and a full-rank query: ``q = W_q
  u`` as heads of ``nope + rope``; ``[c | k_r] = W_kva u``; ``[k_n | v] =
  W_kvb RMSNorm(c)`` a head; ``k = [k_n | k_r]``, ``k_r`` shared by all
  heads; causal softmax attention through the flash kernels with keys of
  ``nope + rope`` channels and values of ``v_head_dim``; ``W_o``.  No
  absorbed projections, no cache.  The same class, given ``q_lora_rank``
  and ``rope_theta``, is ``joyai_llm_flash``'s mixer.  Where the shapes
  tile, the kernels read the projections' results where the products
  wrote them and ``k`` is never built (``ops/mla_kernels.py``).
- ``MoEFeedForward`` (scope ``moe``, with ``moe_router``, ``moe_experts``,
  ``moe_shared`` inside): ``nemotron_h.MoEMixer`` with the ``swiglu``
  activation: sigmoid top-k routing with a score-correction bias, the held
  experts' ``down(silu(gate) * up)`` as grouped products without drops, a
  shared expert every token passes; ``held = (first, count)``,
  ``force_load_balancing`` and the step statistic as there.
- ``DenseFeedForward`` (scope ``mlp``): the same SwiGLU, dense.

No projection has a bias.  Weights are their block's own parameters in the
order the benchmark's plain reference writes them down
(``benchmark/chip/archs/kimi_linear.py``); gate and up-projection are one
stacked leaf ``(2F, D)`` (an expert layer's ``(count, 2F, D)``).  A
``KDAMixer`` declares two step statistics: the chunks its scan ran
(``kda/<layer>``), which feeds ``mxnet_kda_chunks_total``, and those of them
whose scan took the Pallas kernels (``kda_kernel/<layer>``: all or none, by
the shapes), which feeds ``mxnet_kda_kernel_chunks_total``; and the layer
(``kda_layer/<layer>``, ``mxnet_kda_layers_total``) and the layer where the
kernels took the mixer whole (``kda_layer_fused/<layer>``,
``mxnet_kda_fused_layers_total``).  An ``MLAMixer``
declares two of the same kind: the layer (``mla/<layer>``) and the layer
where its kernels read in place (``mla_kernel/<layer>``).

Not built: the latent paged cache and the absorbed decode of the MLA
layers, a single-token form of the delta rule (serving).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import mla_kernels
from ...ops.kda import kda_chunks, kda_fused, kda_kernel_chunks
from ...telemetry import metrics
from .. import nn
from ..block import HybridBlock, record_step_stat
from .llama import RMSNorm
from .nemotron_h import (MoEMixer, _dense, _feed_forward, _Mixer,
                         chunk_counters)

STAT_PREFIX = "kda/"      # a layer's statistic: "kda/<layer>"
KERNEL_STAT_PREFIX = "kda_kernel/"
LAYER_STAT_PREFIX = "kda_layer/"
FUSED_STAT_PREFIX = "kda_layer_fused/"
MLA_STAT_PREFIX = "mla/"
MLA_KERNEL_STAT_PREFIX = "mla_kernel/"


class KDAMixer(_Mixer):
    def __init__(self, units, num_heads, head_dim, conv_kernel=4,
                 chunk_size=64, eps=1e-5, layer=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        inner = num_heads * head_dim
        self._cfg = (num_heads, head_dim, int(chunk_size), eps, conv_kernel)
        self._stat = STAT_PREFIX + str(int(layer))
        self._kernel_stat = KERNEL_STAT_PREFIX + str(int(layer))
        self._layer_stat = LAYER_STAT_PREFIX + str(int(layer))
        self._fused_stat = FUSED_STAT_PREFIX + str(int(layer))
        self._declare([
            ("q_proj", (inner, units), None),
            ("k_proj", (inner, units), None),
            ("v_proj", (inner, units), None),
            ("q_conv", (inner, conv_kernel), None),
            ("k_conv", (inner, conv_kernel), None),
            ("v_conv", (inner, conv_kernel), None),
            # 2-D: matrices to the benchmark's seeded initialiser
            ("A_log", (1, num_heads), "zeros"),
            ("f_a_proj", (head_dim, units), None),
            ("f_b_proj", (inner, head_dim), None),
            ("dt_bias", (num_heads, head_dim), "zeros"),
            ("b_proj", (num_heads, units), None),
            ("g_a_proj", (head_dim, units), None),
            ("g_b_proj", (inner, head_dim), None),
            ("o_norm", (head_dim,), "ones"),
            ("o_proj", (units, inner), None)])

    def step_stat_specs(self):
        """Chunks the scan ran: batch x heads x chunks of the sequence; and
        those whose scan took the Pallas kernels.  The mixer run, and run
        whole in the kernels (one each)."""
        return {name: ((1,), jnp.uint32)
                for name in (self._stat, self._kernel_stat, self._layer_stat,
                             self._fused_stat)}

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, q_conv, k_conv,
                       v_conv, A_log, f_a_proj, f_b_proj, dt_bias, b_proj,
                       g_a_proj, g_b_proj, o_norm, o_proj):
        heads, hd, chunk, eps, taps = self._cfg
        b, t, _ = u.shape
        with jax.named_scope("kda"):
            def low_rank(a, b):
                return _dense(F, _dense(F, u, a), b)
            o = F.contrib.kda_attention(
                _dense(F, u, q_proj), _dense(F, u, k_proj),
                _dense(F, u, v_proj), low_rank(f_a_proj, f_b_proj),
                _dense(F, u, b_proj), low_rank(g_a_proj, g_b_proj), q_conv,
                k_conv, v_conv, A_log, dt_bias, o_norm, chunk=chunk, eps=eps)
            record_step_stat(self._stat, jnp.full(
                (1,), b * heads * kda_chunks(t, chunk), jnp.uint32))
            record_step_stat(self._kernel_stat, jnp.full(
                (1,), b * heads * kda_kernel_chunks(t, hd, hd, chunk),
                jnp.uint32))
            record_step_stat(self._layer_stat, jnp.ones((1,), jnp.uint32))
            record_step_stat(self._fused_stat, jnp.full(
                (1,), kda_fused(t, hd, taps, chunk), jnp.uint32))
            return _dense(F, o, o_proj)


class MLAMixer(_Mixer):
    """Latent attention.  ``q_lora_rank`` (None: ``q = W_q u`` at full
    rank, the leaf ``q_proj``) compresses the query, ``q = W_qb
    RMSNorm(W_qa u)`` (leaves ``q_a_proj``, ``q_a_norm``, ``q_b_proj`` in
    ``q_proj``'s place); ``rope_theta`` (None: no rotation, the ``rope``
    channels are plain channels) is the base of a rotary embedding on the
    ``rope`` channels of every query head and of the one rope key a token,
    adjacent channels a pair.  Under the scope ``mla``.

    Where the shapes tile (``ops/mla_kernels.py:tiles``, a static test:
    ``nope`` and ``v_head_dim`` whole 128-lane tiles, ``rope`` 64 or a
    multiple, 512 tokens or more, no mesh) nothing between the projections
    and the flash kernels is rewritten in HBM:
    ``F.contrib.mla_flash_attention`` reads ``W_kvb``'s result and the one
    rope key as they lie and writes where ``W_o`` reads.  The query's weight
    is split by rows, not its activation (a head's ``nope + rope`` lanes
    are no whole lane tiles): the leaf stays as published, and its ``nope``
    rows and ``rope`` rows give ``q_nope`` and ``q_rope`` as two products.
    The kernels turn the queries' rope channels; the scope ``mla_rope``
    (inside the operator) holds what stays XLA's, the rotation of the rope
    key.  Every other shape (the tier-1 models' widths, any mesh) takes the
    composition: heads transposed to ``(B, H, T, .)``, and under ``mla_rope``
    the rotation of q and the rope key, its repeat over the heads and the
    concatenation that builds the keys, then ``F.contrib.flash_attention``.

    Two step statistics (``layer`` names them): ``mla/<layer>`` counts the
    layer, ``mla_kernel/<layer>`` counts it where its operands were read in
    place (``mxnet_mla_layers_total``, ``mxnet_mla_kernel_layers_total``)."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, eps=1e-5, q_lora_rank=None,
                 rope_theta=None, layer=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = (num_heads, kv_lora_rank, qk_nope_head_dim,
                     qk_rope_head_dim, v_head_dim, eps, rope_theta)
        self._stat = MLA_STAT_PREFIX + str(int(layer))
        self._kernel_stat = MLA_KERNEL_STAT_PREFIX + str(int(layer))
        qk = qk_nope_head_dim + qk_rope_head_dim
        self._declare(
            ([("q_proj", (num_heads * qk, units), None)]
             if q_lora_rank is None else
             [("q_a_proj", (q_lora_rank, units), None),
              ("q_a_norm", (q_lora_rank,), "ones"),
              ("q_b_proj", (num_heads * qk, q_lora_rank), None)]) + [
            ("kv_a_proj", (kv_lora_rank + qk_rope_head_dim, units), None),
            ("kv_a_norm", (kv_lora_rank,), "ones"),
            ("kv_b_proj", (num_heads * (qk_nope_head_dim + v_head_dim),
                           kv_lora_rank), None),
            ("o_proj", (units, num_heads * v_head_dim), None)])

    def step_stat_specs(self):
        """The layer, and the layer where the kernels read in place."""
        return {self._stat: ((1,), jnp.uint32),
                self._kernel_stat: ((1,), jnp.uint32)}

    def hybrid_forward(self, F, u, kv_a_proj, kv_a_norm, kv_b_proj, o_proj,
                       q_proj=None, q_a_proj=None, q_a_norm=None,
                       q_b_proj=None):
        h, rank, nope, rope, vd, eps, theta = self._cfg
        b, t, _ = u.shape
        in_place = mla_kernels.tiles(h, nope, rope, vd, t) is not None
        record_step_stat(self._stat, jnp.ones((1,), jnp.uint32))
        record_step_stat(self._kernel_stat,
                         jnp.full((1,), int(in_place), jnp.uint32))
        with jax.named_scope("mla"):
            if q_proj is not None:
                q_in, q_w = u, q_proj
            else:
                q_in, q_w = F.RMSNorm(_dense(F, u, q_a_proj), q_a_norm,
                                      axis=-1, eps=eps), q_b_proj
            kv_a = _dense(F, u, kv_a_proj)
            latent = F.RMSNorm(F.slice_axis(kv_a, axis=2, begin=0, end=rank),
                               kv_a_norm, axis=-1, eps=eps)
            k_rope = F.slice_axis(kv_a, axis=2, begin=rank, end=None)
            kv = _dense(F, latent, kv_b_proj)       # [k_nope | v] a head
            if in_place:
                def rows(begin, end):
                    # these rows of every head's nope + rope, as a weight
                    per_head = F.reshape(q_w, shape=(h, nope + rope, -1))
                    return F.reshape(F.slice_axis(per_head, axis=1,
                                                  begin=begin, end=end),
                                     shape=(h * (end - begin), -1))
                out = F.contrib.mla_flash_attention(
                    _dense(F, q_in, rows(0, nope)),
                    _dense(F, q_in, rows(nope, nope + rope)), kv, k_rope,
                    num_heads=h, scale=(nope + rope) ** -0.5,
                    rope_theta=theta)
                return _dense(F, out, o_proj)

            def heads(x, width):
                return F.transpose(F.reshape(x, shape=(b, t, -1, width)),
                                   axes=(0, 2, 1, 3))
            q = heads(_dense(F, q_in, q_w), nope + rope)
            k_rope = heads(k_rope, rope)              # one a token
            kv = heads(kv, nope + vd)
            with jax.named_scope("mla_rope"):
                if theta is not None:
                    q = F.contrib.rotary_embedding(q, theta=theta,
                                                   rotary_dim=rope)
                    k_rope = F.contrib.rotary_embedding(k_rope, theta=theta)
                k = F.concat(F.slice_axis(kv, axis=3, begin=0, end=nope),
                             F.broadcast_axis(k_rope, axis=1, size=h), dim=3)
            v = F.slice_axis(kv, axis=3, begin=nope, end=None)
            out = F.contrib.flash_attention(
                q, k, v, scale=(nope + rope) ** -0.5, causal=True)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(b, t, h * vd))
            return _dense(F, out, o_proj)


class MoEFeedForward(MoEMixer):
    """``nemotron_h.MoEMixer`` with SwiGLU experts."""

    def __init__(self, units, n_experts, top_k, moe_hidden, shared_hidden,
                 **kwargs):
        super().__init__(units, n_experts, top_k, moe_hidden, shared_hidden,
                         activation="swiglu", **kwargs)


class DenseFeedForward(_Mixer):
    def __init__(self, units, hidden, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._declare([("gate_up", (2 * hidden, units), None),
                       ("down", (units, hidden), None)])

    def hybrid_forward(self, F, u, gate_up, down):
        with jax.named_scope("mlp"):
            return _feed_forward(F, u, gate_up, down, "swiglu")


class KimiLinearBlock(HybridBlock):
    """``x = x + mixer(RMSNorm(x)); x = x + ffn(RMSNorm(x))``."""

    def __init__(self, units, mixer, ffn, eps=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.mixer_norm = RMSNorm(units, eps, prefix="mixer_norm_")
            self.mixer = mixer(prefix="mixer_")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            self.ffn = ffn(prefix="ffn_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.mixer_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class KimiLinearModel(HybridBlock):
    """Decoder-only LM.  forward(tokens (B, T)) -> logits (B, T, V).
    ``kda_layers`` and ``full_attn_layers`` number the layers from 1 and
    together name every one of ``num_layers``."""

    def __init__(self, vocab_size, units, num_layers, *, kda_layers,
                 full_attn_layers, kda_num_heads, kda_head_dim,
                 conv_kernel=4, chunk_size=64, num_heads, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 dense_hidden, first_k_dense=1, n_routed_experts,
                 num_experts_per_tok, moe_hidden, shared_hidden,
                 routed_scaling_factor=1.0, norm_topk_prob=True, held=None,
                 force_load_balancing=False, eps=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        kda, full = set(kda_layers), set(full_attn_layers)
        if kda & full or kda | full != set(range(1, num_layers + 1)):
            raise ValueError(
                "kda_layers %r and full_attn_layers %r do not name each of "
                "the %d layers once" % (sorted(kda), sorted(full),
                                        num_layers))

        def mixer(i):
            if i in kda:
                return lambda prefix: KDAMixer(
                    units, kda_num_heads, kda_head_dim, conv_kernel,
                    chunk_size, eps, layer=i, prefix=prefix)
            return lambda prefix: MLAMixer(
                units, num_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, eps, layer=i, prefix=prefix)

        def ffn(i):
            if i <= first_k_dense:
                return lambda prefix: DenseFeedForward(units, dense_hidden,
                                                       prefix=prefix)
            return lambda prefix: MoEFeedForward(
                units, n_routed_experts, num_experts_per_tok, moe_hidden,
                shared_hidden, scale=routed_scaling_factor,
                normalize=norm_topk_prob, held=held, layer=i,
                force_load_balancing=force_load_balancing, prefix=prefix)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(1, num_layers + 1):
                self.blocks.add(KimiLinearBlock(
                    units, mixer(i), ffn(i), eps, prefix="block%d_" % i))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.blocks(self.embed(tokens))))


def kimi_linear_48b_a3b(vocab_size=163840, **kwargs):
    """Kimi-Linear-48B-A3B (27 layers: 20 KDA, 7 latent attention, a dense
    feed-forward in layer 1 and 256 routed experts after; 49.12B
    parameters)."""
    full = [4, 8, 12, 16, 20, 24, 27]
    cfg = dict(
        units=2304, num_layers=27,
        kda_layers=[i for i in range(1, 28) if i not in full],
        full_attn_layers=full, kda_num_heads=32, kda_head_dim=128,
        conv_kernel=4, num_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, dense_hidden=9216,
        first_k_dense=1, n_routed_experts=256, num_experts_per_tok=8,
        moe_hidden=1024, shared_hidden=1024, routed_scaling_factor=2.446,
        norm_topk_prob=True)
    cfg.update(kwargs)
    return KimiLinearModel(vocab_size, **cfg)


metrics.register_collector(
    chunk_counters("kda", "Kimi Delta Attention scans", "mx_kda_*"))
metrics.register_collector(chunk_counters(
    "kda", "Kimi Delta Attention mixers",
    "mx_kda_fwd / mx_kda_bwd whole, the projections' results read where "
    "they lie", unit="layers", each="every such layer and train step",
    kernel="fused", stat="kda_layer"))
metrics.register_collector(chunk_counters(
    "mla", "latent attention mixers",
    "mx_flash_*_mla, their operands read where the projections wrote them",
    unit="layers", each="every such layer and train step"))
