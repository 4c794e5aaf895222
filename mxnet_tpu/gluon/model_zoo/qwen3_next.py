"""Qwen3-Next: a hybrid decoder of Gated DeltaNet and gated softmax
attention layers over routed SwiGLU experts beside a gated shared expert
(``model_type`` ``qwen3_next``; the ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct``; Yang et al., "Gated Delta Networks",
arXiv:2412.06464, for the linear attention).

Every layer is two sub-blocks behind pre-norm residuals, ``x = x +
mixer(norm(x)); x = x + moe(norm(x))``; layer ``i`` (from 0) attends in full
where ``(i + 1) % full_attention_interval == 0`` and is a Gated DeltaNet
layer otherwise.  Every norm is Qwen3-Next's zero-centred RMSNorm, ``x /
rms(x) * (1 + w)``.

- ``GatedDeltaNet`` (scope ``gdn``): ``[q | k | v | z] = u W_qkvz`` (``q, k``
  of ``key_heads``, ``v, z`` of ``value_heads``, all of ``head_dim``) and
  ``[b | a] = u W_ba``; everything between the projections is one operator,
  ``F.contrib.gdn_attention`` (``ops/kda.py``; the scan under the scope
  ``gdn_scan``): a causal depthwise convolution of ``[q | k | v]`` and SiLU,
  unit norms of ``q`` and ``k``, a decay a head ``g = -exp(A_log) *
  softplus(a + dt_bias)``, ``beta = sigmoid(b)``, the gated delta rule with
  value head ``j`` reading key head ``j // (value_heads / key_heads)``, and
  ``RMSNorm_head(o) * norm * silu(z)``; ``W_out``.  Where the shapes tile
  the delta-rule kernels take it whole and read the two projections'
  results where the products wrote them.
- ``GatedAttention`` (scope ``gated_attention``): ``W_q`` gives each query
  head ``[query | gate]``; ``q`` and ``k`` take a zero-centred RMSNorm over
  a head; the first ``rotary_dim`` channels of each head turn (the rotation
  of halves, ``rotary_embedding(halves=True)``), the rest pass; causal
  softmax attention at ``head_dim ** -0.5`` through the flash kernels, K and
  V repeated to the query heads; ``o * sigmoid(gate)``; ``W_o``.
- ``Qwen3NextMoE`` (scope ``moe``): ``sdar_moe.SDARMoE``'s softmax routing
  over the held experts (``held``, ``force_load_balancing`` and the step
  statistic as there) beside a SwiGLU shared expert that every token passes,
  scaled by ``sigmoid(u w_sg)`` (scope ``moe_shared``).

No projection has a bias.  Weights are their block's own parameters in the
order the benchmark's plain reference writes them down
(``benchmark/chip/archs/qwen3_next.py``); an expert's gate and up are one
stacked leaf ``(count, 2F, D)``.  A ``GatedDeltaNet`` declares two step
statistics: the layer (``gdn/<layer>``, ``mxnet_gdn_layers_total``) and the
layer whose mixer the kernels took whole (``gdn_kernel/<layer>``,
``mxnet_gdn_kernel_layers_total``).

Not built: the multi-token prediction module, and a single-token form of
the delta rule and a cache for serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.kda import gdn_fused
from ...telemetry import metrics
from .. import nn
from ..block import HybridBlock, record_step_stat
from .nemotron_h import _dense, _feed_forward, _Mixer, chunk_counters
from .sdar_moe import SDARMoE

LAYER_STAT_PREFIX = "gdn/"        # a layer's statistic: "gdn/<layer>"
KERNEL_STAT_PREFIX = "gdn_kernel/"


class ZeroCenteredRMSNorm(HybridBlock):
    """``x / sqrt(mean x^2 + eps) * (1 + weight)``; the weight starts at
    zero."""

    def __init__(self, units, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = eps
        self.weight = self.params.get("weight", shape=(units,), init="zeros",
                                      allow_deferred_init=False)

    def hybrid_forward(self, F, x, weight=None):
        return F.RMSNorm(x, weight + 1, axis=-1, eps=self._eps)


class GatedDeltaNet(_Mixer):
    def __init__(self, units, key_heads, value_heads, head_dim,
                 conv_kernel=4, chunk_size=64, eps=1e-6, layer=0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if value_heads % key_heads:
            raise ValueError("%d value heads over %d key heads"
                             % (value_heads, key_heads))
        keys, values = key_heads * head_dim, value_heads * head_dim
        self._cfg = (key_heads, value_heads, head_dim, int(chunk_size), eps,
                     conv_kernel)
        self._layer_stat = LAYER_STAT_PREFIX + str(int(layer))
        self._kernel_stat = KERNEL_STAT_PREFIX + str(int(layer))
        self._declare([
            ("in_proj_qkvz", (2 * keys + 2 * values, units), None),
            ("in_proj_ba", (2 * value_heads, units), None),
            ("conv", (2 * keys + values, conv_kernel), None),
            # 2-D: matrices to the benchmark's seeded initialiser
            ("dt_bias", (1, value_heads), "zeros"),
            ("A_log", (1, value_heads), "zeros"),
            ("norm", (head_dim,), "ones"),
            ("out_proj", (units, values), None)])

    def step_stat_specs(self):
        """The mixer run, and run whole in the kernels (one each)."""
        return {name: ((1,), jnp.uint32)
                for name in (self._layer_stat, self._kernel_stat)}

    def hybrid_forward(self, F, u, in_proj_qkvz, in_proj_ba, conv, dt_bias,
                       A_log, norm, out_proj):
        hk, hv, hd, chunk, eps, taps = self._cfg
        t = u.shape[1]
        record_step_stat(self._layer_stat, jnp.ones((1,), jnp.uint32))
        record_step_stat(self._kernel_stat, jnp.full(
            (1,), gdn_fused(t, hd, hk, hv, taps, chunk), jnp.uint32))
        with jax.named_scope("gdn"):
            o = F.contrib.gdn_attention(
                _dense(F, u, in_proj_qkvz), _dense(F, u, in_proj_ba), conv,
                A_log, dt_bias, norm, chunk=chunk, eps=eps)
            return _dense(F, o, out_proj)


class GatedAttention(_Mixer):
    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 rotary_dim, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads over %d key heads"
                             % (num_heads, num_kv_heads))
        self._cfg = (num_heads, num_kv_heads, head_dim, float(rope_theta),
                     int(rotary_dim), eps)
        self._declare([
            ("q_proj", (num_heads * 2 * head_dim, units), None),
            ("k_proj", (num_kv_heads * head_dim, units), None),
            ("v_proj", (num_kv_heads * head_dim, units), None),
            ("q_norm", (head_dim,), "zeros"),
            ("k_norm", (head_dim,), "zeros"),
            ("o_proj", (units, num_heads * head_dim), None)])

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, q_norm, k_norm,
                       o_proj):
        h, kv, d, theta, rotary, eps = self._cfg
        b, t, _ = u.shape
        with jax.named_scope("gated_attention"):
            # each query head's [query | gate]
            qg = F.reshape(_dense(F, u, q_proj), shape=(b, t, h, 2 * d))
            gate = F.reshape(F.slice_axis(qg, axis=3, begin=d, end=None),
                             shape=(b, t, h * d))

            def turned(x, gain):
                x = F.RMSNorm(x, gain + 1, axis=-1, eps=eps)
                return F.contrib.rotary_embedding(
                    F.transpose(x, axes=(0, 2, 1, 3)), theta=theta,
                    rotary_dim=rotary, halves=True)

            def kv_heads(w):
                return F.reshape(_dense(F, u, w), shape=(b, t, kv, d))
            q = turned(F.slice_axis(qg, axis=3, begin=0, end=d), q_norm)
            k = turned(kv_heads(k_proj), k_norm)
            v = F.transpose(kv_heads(v_proj), axes=(0, 2, 1, 3))
            if kv != h:
                k = F.repeat(k, repeats=h // kv, axis=1)
                v = F.repeat(v, repeats=h // kv, axis=1)
            out = F.contrib.flash_attention(q, k, v, scale=d ** -0.5,
                                            causal=True)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(b, t, h * d))
            return _dense(F, out * F.sigmoid(gate), o_proj)


class Qwen3NextMoE(SDARMoE):
    """``SDARMoE``'s routed experts beside a SwiGLU shared expert behind a
    sigmoid gate of its own, ``sigmoid(u w_sg)``."""

    def __init__(self, units, n_experts, top_k, moe_hidden, shared_hidden,
                 **kwargs):
        super().__init__(units, n_experts, top_k, moe_hidden, **kwargs)
        self._declare([
            ("shared_gate_up", (2 * shared_hidden, units), None),
            ("shared_down", (units, shared_hidden), None),
            ("shared_gate", (1, units), None)])

    def hybrid_forward(self, F, u, router, gate_up, down, shared_gate_up,
                       shared_down, shared_gate):
        routed = super().hybrid_forward(F, u, router, gate_up, down)
        with jax.named_scope("moe"), jax.named_scope("moe_shared"):
            shared = _feed_forward(F, u, shared_gate_up, shared_down,
                                   "swiglu")
            return routed + shared * F.sigmoid(_dense(F, u, shared_gate))


class Qwen3NextBlock(HybridBlock):
    """``x = x + mixer(norm(x)); x = x + moe(norm(x))``, zero-centred
    norms."""

    def __init__(self, units, mixer, moe, eps=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.mixer_norm = ZeroCenteredRMSNorm(units, eps,
                                                  prefix="mixer_norm_")
            self.mixer = mixer(prefix="mixer_")
            self.ffn_norm = ZeroCenteredRMSNorm(units, eps, prefix="ffn_norm_")
            self.moe = moe(prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.mixer_norm(x))
        return x + self.moe(self.ffn_norm(x))


class Qwen3NextModel(HybridBlock):
    """Decoder-only LM.  forward(tokens (B, T)) -> logits (B, T, V)."""

    def __init__(self, vocab_size, units, num_layers, *,
                 full_attention_interval=4, linear_key_heads=16,
                 linear_value_heads=32, linear_head_dim=128, conv_kernel=4,
                 chunk_size=64, num_heads=16, num_kv_heads=2, head_dim=256,
                 rope_theta=1e7, rotary_dim=64, moe_hidden=512,
                 shared_hidden=512, n_experts=512, top_k=10,
                 norm_topk_prob=True, held=None, force_load_balancing=False,
                 eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

        def mixer(i):
            if (i + 1) % full_attention_interval == 0:
                return lambda prefix: GatedAttention(
                    units, num_heads, num_kv_heads, head_dim, rope_theta,
                    rotary_dim, eps, prefix=prefix)
            return lambda prefix: GatedDeltaNet(
                units, linear_key_heads, linear_value_heads, linear_head_dim,
                conv_kernel, chunk_size, eps, layer=i, prefix=prefix)

        def moe(i):
            return lambda prefix: Qwen3NextMoE(
                units, n_experts, top_k, moe_hidden, shared_hidden,
                norm_topk_prob=norm_topk_prob, held=held, layer=i,
                force_load_balancing=force_load_balancing, prefix=prefix)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(Qwen3NextBlock(units, mixer(i), moe(i), eps,
                                               prefix="block%d_" % i))
            self.norm = ZeroCenteredRMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.blocks(self.embed(tokens))))


metrics.register_collector(chunk_counters(
    "gdn", "Gated DeltaNet mixers",
    "mx_kda_fwd / mx_kda_bwd whole, in the GDN form, the two projections' "
    "results read where they lie", unit="layers",
    each="every such layer and train step"))
