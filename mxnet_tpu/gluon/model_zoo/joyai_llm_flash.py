"""JoyAI-LLM Flash: a decoder of rotary latent attention with a low-rank
query in every layer, over routed SwiGLU experts (``model_type``
``joyai_llm_flash``; the ``config.json`` of ``jdopensource/JoyAI-LLM-Flash``,
which uses the DeepSeek-V3 schema key for key; the prediction module is the
one of the DeepSeek-V3 technical report, section 2.2).

Every layer is two sub-blocks, each behind its own pre-norm residual
(``kimi_linear.KimiLinearBlock``): ``x = x + attn(RMSNorm(x)); x = x +
ffn(RMSNorm(x))``.  Layers count from 0; the first ``first_k_dense`` have the
dense feed-forward and every later one the routed.

- ``kimi_linear.MLAMixer`` with ``q_lora_rank`` and ``rope_theta`` (scope
  ``mla``, the rotation, the repeat of the rope key and the keys'
  concatenation under ``mla_rope`` inside it): ``q = W_qb RMSNorm(W_qa
  u)``; ``[c | k_r] = W_kva u``; ``[k_n | v] = W_kvb RMSNorm(c)`` a head; a
  rotary embedding
  (adjacent channels a pair, float32 angles from integer positions) on the
  ``rope`` channels of every query head and of the one ``k_r`` a token,
  which is then repeated over the heads; causal softmax attention through
  the flash kernels at ``(nope + rope) ** -0.5``; ``W_o``.  Nothing is made
  again in the backward pass (``docs/moe_ssm.md`` says what that keeps).
- ``kimi_linear.MoEFeedForward`` (scope ``moe``) and
  ``kimi_linear.DenseFeedForward`` (scope ``mlp``) as that model has them:
  sigmoid top-k routing with a score-correction bias, ``held = (first,
  count)``, ``force_load_balancing``, the step statistic ``moe/<layer>/
  <first>``.
- with ``num_nextn_predict_layers`` 1, ``MTPModule`` (scope ``mtp``):
  ``h'_i = W_eh [RMSNorm(E[t_{i+1}]) ; RMSNorm(h_i)]`` from the last
  layer's result ``h`` (before the final norm) and the model's own
  embedding ``E``, one more layer of the routed kind, a norm, and the
  model's own head; the model then returns ``(logits (B, T, V), the
  module's (B, T-1, V))``, and ``MTPLoss`` is the block ``(tokens, labels)
  -> loss`` that ``parallel.JitTrainStep(net, loss=None)`` trains.  The
  embedding and the head are used twice and their gradients add.

Weights are their block's own parameters in the order the benchmark's plain
reference writes them down (``benchmark/chip/archs/joyai_llm_flash.py``).

Not built: the latent paged cache and the absorbed decode (serving), and
speculative decoding with the prediction module.
"""
from __future__ import annotations

import jax

from .. import nn
from ..block import HybridBlock
from .kimi_linear import DenseFeedForward, KimiLinearBlock, MLAMixer, \
    MoEFeedForward
from .llama import RMSNorm


class MTPModule(HybridBlock):
    """One multi-token-prediction depth: ``(hidden (B, T, D), the next
    tokens' embeddings (B, T, D)) -> (B, T, D)``, normed for the head."""

    def __init__(self, units, block, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.embed_norm = RMSNorm(units, eps, prefix="embed_norm_")
            self.hidden_norm = RMSNorm(units, eps, prefix="hidden_norm_")
            self.proj = nn.Dense(units, flatten=False, use_bias=False,
                                 in_units=2 * units, prefix="proj_")
            self.block = block(prefix="block_")
            self.norm = RMSNorm(units, eps, prefix="norm_")

    def hybrid_forward(self, F, hidden, ahead):
        with jax.named_scope("mtp"):
            both = F.concat(self.embed_norm(ahead), self.hidden_norm(hidden),
                            dim=2)
            return self.norm(self.block(self.proj(both)))


class JoyAIFlashModel(HybridBlock):
    """Decoder-only LM.  forward(tokens (B, T)) -> logits (B, T, V), or,
    with ``num_nextn_predict_layers`` 1, ``(logits, (B, T-1, V))``: the
    prediction module's logits for the token after the next."""

    def __init__(self, vocab_size, units, num_layers, *, num_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta, dense_hidden,
                 first_k_dense=1, n_routed_experts, num_experts_per_tok,
                 moe_hidden, shared_hidden, routed_scaling_factor=1.0,
                 norm_topk_prob=True, held=None, force_load_balancing=False,
                 num_nextn_predict_layers=0, eps=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers %r: none or one"
                             % (num_nextn_predict_layers,))

        def mixer(i):
            return lambda prefix: MLAMixer(
                units, num_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, eps, q_lora_rank=q_lora_rank,
                rope_theta=rope_theta, layer=i, prefix=prefix)

        def ffn(i, routed):
            if not routed:
                return lambda prefix: DenseFeedForward(units, dense_hidden,
                                                       prefix=prefix)
            return lambda prefix: MoEFeedForward(
                units, n_routed_experts, num_experts_per_tok, moe_hidden,
                shared_hidden, scale=routed_scaling_factor,
                normalize=norm_topk_prob, held=held, layer=i,
                force_load_balancing=force_load_balancing, prefix=prefix)

        def layer(i, routed):
            return lambda prefix: KimiLinearBlock(
                units, mixer(i), ffn(i, routed), eps, prefix=prefix)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(layer(i, i >= first_k_dense)(
                    prefix="block%d_" % i))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="head_")
            self.mtp = None
            if num_nextn_predict_layers:
                # the layer after the last, of the routed kind
                self.mtp = MTPModule(units, layer(num_layers, True), eps,
                                     prefix="mtp_")

    def hybrid_forward(self, F, tokens):
        hidden = self.blocks(self.embed(tokens))
        logits = self.lm_head(self.norm(hidden))
        if self.mtp is None:
            return logits
        # E[t_{i+1}] at position i; the last position is fed t_0 and
        # dropped (causal: it moves no other position)
        ahead = F.concat(F.slice_axis(tokens, axis=1, begin=1, end=None),
                         F.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)
        more = self.lm_head(self.mtp(hidden, self.embed(ahead)))
        return logits, F.slice_axis(more, axis=1, begin=0, end=-1)


class MTPLoss(HybridBlock):
    """``(tokens (B, T), labels (B, T) or (B * T,)) -> loss``: the mean
    cross-entropy of the next token plus ``weight`` times the mean
    cross-entropy of the token after it under the prediction module
    (positions ``0 .. T-2``).  Wraps a ``JoyAIFlashModel`` built with
    ``num_nextn_predict_layers=1``; ``parallel.JitTrainStep(MTPLoss(net),
    loss=None)`` trains it."""

    def __init__(self, net, weight=0.3, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.net = net
        self._weight = float(weight)

    def hybrid_forward(self, F, tokens, labels):
        logits, more = self.net(tokens)
        b, t = tokens.shape
        labels = F.reshape(labels, shape=(b, t))

        def cross_entropy(pred, label):
            return F.mean(-F.pick(F.log_softmax(pred, axis=-1), label,
                                  axis=-1))
        return cross_entropy(logits, labels) + self._weight * cross_entropy(
            more, F.slice_axis(labels, axis=1, begin=1, end=None))


def joyai_llm_flash_48b_a3b(vocab_size=129280, **kwargs):
    """JoyAI-LLM-Flash (40 layers of latent attention, a dense feed-forward
    in layer 0 and 256 routed experts of 768 after; 48.94B parameters, and
    1.25B more with ``num_nextn_predict_layers=1``, as published)."""
    cfg = dict(
        units=2048, num_layers=40, num_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=32000000, dense_hidden=7168,
        first_k_dense=1, n_routed_experts=256, num_experts_per_tok=8,
        moe_hidden=768, shared_hidden=768, routed_scaling_factor=2.5,
        norm_topk_prob=True)
    cfg.update(kwargs)
    return JoyAIFlashModel(vocab_size, **cfg)
