"""SDAR-MoE: the Qwen3-MoE decoder trained as a block-diffusion model
(``model_type`` ``sdar_moe``; the ``config.json`` of
``JetLM/SDAR-30B-A3B-Chat``; the objective of Arriola et al., "Block
Diffusion", arXiv:2503.09573, BD3-LM, which SDAR's conversion of an
autoregressive model follows).

A sequence ``x_0`` of ``T`` tokens is cut into blocks of ``block_length``;
each token of block ``j`` is replaced by the mask id with probability
``t_j`` (``F.contrib.block_diffusion_noise``, drawn in the program from the
batch, scope ``bd_noise``).  The model reads the clean and the noised copy
side by side, ``[x_0 | x_t]``, ``2T`` positions whose RoPE positions are
``0 .. T-1`` in each half, under the block-diffusion mask
(``F.contrib.flash_attention(mask=("block_diffusion", T, block_length))``:
a noised block sees itself and the clean blocks strictly before it, a clean
block the clean blocks up to itself), and returns the head over the noised
half, ``(B, T, V)``.

Every layer is two sub-blocks behind pre-norm residuals, ``x = x +
attn(RMSNorm(x)); x = x + moe(RMSNorm(x))``:

- ``BDAttention`` (scope ``bd_attention``): GQA, ``num_heads`` query heads
  and ``num_kv_heads`` key and value heads of ``head_dim``; an RMSNorm over
  each head's channels of q and of k (qk-norm), then RoPE (``rope_theta``,
  the rotation of halves, ``llama._rope``); the flash kernels under the
  mask at ``head_dim ** -0.5``, query head ``h`` reading key and value head
  ``h // (num_heads / num_kv_heads)``; ``W_o``.  Where the shapes tile
  (``ops/bd_kernels.py:tiles``, a static test: a head whole 128-lane
  tiles, the query heads a whole number of key heads' groups, a tile that
  takes the mask, no mesh) ``F.contrib.bd_flash_attention`` takes the three
  projections' results as they lie, ``(B, 2T, heads * head_dim)``: head
  ``h`` of a query tile is lanes ``h * head_dim ..`` of its rows, and of K
  and V the lanes of head ``h // (num_heads / num_kv_heads)``, read by the
  kernels' index maps; the qk-norm is XLA's, float32 in that layout; the
  key's rotation XLA's too, on its ``num_kv_heads`` heads; the queries'
  rotation happens in VMEM as the forward kernel loads a block, and the
  backward kernels read the turned block it writes (the dq kernel turns
  ``dq`` back); the result is written where ``W_o`` reads it.  Every other
  shape takes the composition: heads transposed to ``(B, H, 2T, D)``,
  ``llama._rope``, K and V repeated to the query heads,
  ``flash_attention(mask=...)``, the result transposed back.  Five step
  statistics: ``flash_tiles/<layer>`` (the tiles of the kernels' grid) and
  ``bd_flash_tiles/<layer>`` (those computed), exported as
  ``mxnet_flash_tiles_total`` and ``mxnet_flash_tiles_computed_total``;
  ``bd/<layer>`` (the layer) and ``bd_kernel/<layer>`` (the layer where
  the kernels read in place), exported as ``mxnet_bd_layers_total`` and
  ``mxnet_bd_kernel_layers_total``; ``bd_dkv_steps/<layer>`` (the dk/dv
  kernel's grid steps: its live visits in place, ``bd_kernels.dkv_steps``,
  every tile of the grid in the composition), exported as
  ``mxnet_flash_dkv_steps_total``.
- ``SDARMoE`` (scope ``moe``): Qwen3-MoE's routing, a float32 softmax over
  all ``n_experts`` router logits, the ``top_k`` largest, renormalised
  (``norm_topk_prob``; ``router_topk(scoring="softmax")``), no bias, no
  shared expert; the held experts' ``down(silu(gate) * up)`` as grouped
  products without drops (``grouped_ffn(activation="swiglu")``); ``held =
  (first, count)``, ``force_load_balancing`` and the step statistic
  ``moe/<layer>/<first>`` as ``nemotron_h.MoEMixer`` has them.

No projection has a bias.  Weights are their block's own parameters in the
order the benchmark's plain reference writes them down
(``benchmark/chip/archs/sdar_moe.py``); gate and up-projection of an
expert are one stacked leaf ``(count, 2F, D)``.  ``BlockDiffusionLoss`` is
the true objective over the model (the benchmark's step trains the
harness's mean cross-entropy instead).

Not built: block-wise denoising decode (serving).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import bd_kernels
from ...ops.pallas_kernels import bd_tiles
from ...telemetry import metrics
from .. import nn
from ..block import HybridBlock, record_step_stat
from .llama import RMSNorm, _rope
from .nemotron_h import _dense, _Mixer, chunk_counters

TILES_STAT_PREFIX = "flash_tiles/"        # a layer's statistic
COMPUTED_STAT_PREFIX = "bd_flash_tiles/"
LAYER_STAT_PREFIX = "bd/"
KERNEL_STAT_PREFIX = "bd_kernel/"
DKV_STAT_PREFIX = "bd_dkv_steps/"


class BDAttention(_Mixer):
    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 block_length, eps=1e-6, layer=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads over %d key heads"
                             % (num_heads, num_kv_heads))
        self._cfg = (num_heads, num_kv_heads, head_dim, float(rope_theta),
                     int(block_length), eps)
        self._stat = TILES_STAT_PREFIX + str(int(layer))
        self._computed_stat = COMPUTED_STAT_PREFIX + str(int(layer))
        self._layer_stat = LAYER_STAT_PREFIX + str(int(layer))
        self._kernel_stat = KERNEL_STAT_PREFIX + str(int(layer))
        self._dkv_stat = DKV_STAT_PREFIX + str(int(layer))
        self._declare([
            ("q_proj", (num_heads * head_dim, units), None),
            ("k_proj", (num_kv_heads * head_dim, units), None),
            ("v_proj", (num_kv_heads * head_dim, units), None),
            ("q_norm", (head_dim,), "ones"),
            ("k_norm", (head_dim,), "ones"),
            ("o_proj", (units, num_heads * head_dim), None)])

    def step_stat_specs(self):
        """The tiles of the flash kernels' grid, and those computed; the
        layer, and the layer where the kernels read in place; the dk/dv
        kernel's grid steps."""
        return {name: ((1,), jnp.uint32) for name in (
            self._stat, self._computed_stat, self._layer_stat,
            self._kernel_stat, self._dkv_stat)}

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, q_norm, k_norm,
                       o_proj):
        h, kv, d, theta, block, eps = self._cfg
        b, t2, _ = u.shape
        half = t2 // 2
        grid, computed = bd_tiles(half, block)
        tile = bd_kernels.tiles(h, kv, d, half, block)
        in_place = tile is not None
        dkv_steps = bd_kernels.dkv_steps(h, kv, half, tile) if in_place \
            else h * grid
        record_step_stat(self._stat, jnp.full((1,), b * h * grid, jnp.uint32))
        record_step_stat(self._computed_stat,
                         jnp.full((1,), b * h * computed, jnp.uint32))
        record_step_stat(self._layer_stat, jnp.ones((1,), jnp.uint32))
        record_step_stat(self._kernel_stat,
                         jnp.full((1,), int(in_place), jnp.uint32))
        record_step_stat(self._dkv_stat,
                         jnp.full((1,), b * dkv_steps, jnp.uint32))
        with jax.named_scope("bd_attention"):
            if in_place:
                out = F.contrib.bd_flash_attention(
                    _dense(F, u, q_proj), _dense(F, u, k_proj),
                    _dense(F, u, v_proj), q_norm, k_norm, num_heads=h,
                    block_length=block, rope_theta=theta, eps=eps)
                return _dense(F, out, o_proj)

            def heads(w, n, gain=None):
                x = F.reshape(_dense(F, u, w), shape=(b, t2, n, d))
                if gain is not None:
                    x = F.RMSNorm(x, gain, axis=-1, eps=eps)
                x = F.transpose(x, axes=(0, 2, 1, 3))
                if gain is None:
                    return x
                # RoPE at positions 0 .. T-1 in each half
                turned = _rope(F, F.reshape(x, shape=(b, 2 * n, half, d)),
                               theta)
                return F.reshape(turned, shape=(b, n, t2, d))
            q = heads(q_proj, h, q_norm)
            k, v = heads(k_proj, kv, k_norm), heads(v_proj, kv)
            if kv != h:
                k = F.repeat(k, repeats=h // kv, axis=1)
                v = F.repeat(v, repeats=h // kv, axis=1)
            out = F.contrib.flash_attention(
                q, k, v, scale=d ** -0.5,
                mask=("block_diffusion", half, block))
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(b, t2, h * d))
            return _dense(F, out, o_proj)


class SDARMoE(_Mixer):
    """Routed SwiGLU experts under softmax routing, no shared expert."""

    def __init__(self, units, n_experts, top_k, moe_hidden,
                 norm_topk_prob=True, held=None, layer=0,
                 force_load_balancing=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = held if held is not None else (0, n_experts)
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError("held=%r is not a share of %d experts"
                             % (held, n_experts))
        self._cfg = (int(top_k), bool(norm_topk_prob), int(first), int(count))
        self._stat = "moe/%d/%d" % (layer, first)
        self._balance_seed = int(layer) if force_load_balancing else None
        self._declare([
            ("router", (n_experts, units), None),
            ("gate_up", (count, 2 * moe_hidden, units), None),
            ("down", (count, units, moe_hidden), None)])

    def step_stat_specs(self):
        """Rows landed on each held expert, assignments in all, dropped,
        rows of the sorted layout walked."""
        return {self._stat: ((self._cfg[3] + 3,), jnp.uint32)}

    def hybrid_forward(self, F, u, router, gate_up, down):
        k, normalize, first, _ = self._cfg
        b, t, d = u.shape
        with jax.named_scope("moe"):
            flat = F.reshape(u, shape=(b * t, d))
            idx, w = F.contrib.moe_router_topk(
                flat, router, k=k, normalize=normalize,
                balance_seed=self._balance_seed, scoring="softmax")
            routed, counts = F.contrib.moe_grouped_ffn(
                flat, idx, w, gate_up, down, first=first,
                activation="swiglu")
            record_step_stat(self._stat, counts)
            return F.reshape(routed, shape=(b, t, d))


class SDARBlock(HybridBlock):
    """``x = x + attn(RMSNorm(x)); x = x + moe(RMSNorm(x))``."""

    def __init__(self, units, attn, moe, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            self.attn = attn(prefix="attn_")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            self.moe = moe(prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.attn_norm(x))
        return x + self.moe(self.ffn_norm(x))


class SDARMoEModel(HybridBlock):
    """Block-diffusion LM.  forward(tokens (B, T)) -> the noised half's
    logits (B, T, V); ``T`` a multiple of ``block_length``.  The
    vocabulary's last id replaces a noised token; ``noise_seed`` seeds the
    noise, which is drawn from the batch."""

    def __init__(self, vocab_size, units, num_layers, *, num_heads=32,
                 num_kv_heads=4, head_dim=128, rope_theta=1e6,
                 moe_hidden=768, n_experts=128, top_k=8, norm_topk_prob=True,
                 held=None, block_length=4, noise_seed=0,
                 force_load_balancing=False, eps=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self.noise = dict(block=int(block_length), seed=int(noise_seed),
                          mask_id=int(vocab_size) - 1)

        def layer(i):
            return SDARBlock(
                units,
                lambda prefix: BDAttention(
                    units, num_heads, num_kv_heads, head_dim, rope_theta,
                    block_length, eps, layer=i, prefix=prefix),
                lambda prefix: SDARMoE(
                    units, n_experts, top_k, moe_hidden, norm_topk_prob,
                    held, layer=i, force_load_balancing=force_load_balancing,
                    prefix=prefix),
                eps, prefix="block%d_" % i)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(layer(i))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="head_")

    def hybrid_forward(self, F, tokens):
        t = tokens.shape[1]
        with jax.named_scope("bd_noise"):
            x_t, _, _ = F.contrib.block_diffusion_noise(tokens, **self.noise)
        x = self.blocks(self.embed(F.concat(tokens, x_t, dim=1)))
        return self.lm_head(self.norm(F.slice_axis(x, axis=1, begin=t,
                                                   end=None)))


class BlockDiffusionLoss(HybridBlock):
    """``tokens (B, T) -> loss``: BD3-LM's objective under the linear
    schedule, ``sum over masked positions of CE(logits, x_0) / t``, over
    ``B * T`` (every position of the batch counts in the mean, a masked one
    weighted ``1 / t`` of its block).  Wraps an ``SDARMoEModel``, whose
    noise it draws again from the batch; ``parallel.JitTrainStep(
    BlockDiffusionLoss(net), loss=None)`` trains it."""

    def __init__(self, net, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.net = net

    def hybrid_forward(self, F, tokens):
        b, t = tokens.shape
        logits = self.net(tokens)
        with jax.named_scope("bd_noise"):
            _, masked, level = F.contrib.block_diffusion_noise(
                tokens, **self.net.noise)
        nll = -F.pick(F.log_softmax(logits, axis=-1), tokens, axis=-1)
        return F.sum(nll * masked / level) / (b * t)


metrics.register_collector(chunk_counters(
    "flash", "block-diffusion flash attention grids",
    "mx_flash_*_bd (the tiles the mask leaves live)", unit="tiles",
    each="batch x heads x tiles, every such layer and train step",
    stat="flash_tiles", kernel_stat="bd_flash_tiles",
    kernel_family="mxnet_flash_tiles_computed_total"))
metrics.register_collector(chunk_counters(
    "flash_dkv", "block-diffusion attention layers' dk/dv grids",
    "mx_flash_bwd_dkv_bd", unit="steps",
    each="batch x key heads x live visits in place, batch x heads x tiles "
         "in the composition, every such layer and train step",
    stat="bd_dkv_steps", kernel=None))
metrics.register_collector(chunk_counters(
    "bd", "block-diffusion attention layers",
    "mx_flash_*_bd, their operands read where the projections wrote them",
    unit="layers", each="every such layer and train step"))
