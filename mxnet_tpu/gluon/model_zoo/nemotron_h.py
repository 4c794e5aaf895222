"""Nemotron-H: a hybrid decoder of Mamba-2, routed-expert and attention
layers (``model_type`` ``nemotron_h``; NVIDIA, "Nemotron-H: A Family of
Accurate and Efficient Hybrid Mamba-Transformer Models", 2025, and the
``config.json`` of its later mixture-of-experts members).

Every layer is ONE mixer behind a pre-norm residual, ``x = x + mixer(
RMSNorm(x))``, its kind given by one character of ``pattern`` (the
published ``hybrid_override_pattern``):

- ``M``, Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(
  causal_conv1d(xBC))``; ``xBC -> x (heads x head_dim), B, C (groups x
  state)``; the selective scan (``F.contrib.ssd_scan``: softplus, decays
  and the carried state in float32); ``RMSNorm`` over groups of
  ``inner / groups`` channels of ``y * silu(z)``; ``out_proj``.
- ``E``, routed experts: sigmoid top-k routing with a score-correction
  bias (``F.contrib.moe_router_topk``), the held experts' non-gated relu²
  feed-forward as grouped products without drops
  (``F.contrib.moe_grouped_ffn``), and a shared expert of the same form
  that every token passes.  ``held = (first, count)`` says which experts of
  the layer live here (all of them by default); the router keeps its full
  width.  ``force_load_balancing`` (off; for measuring throughput at random
  weights) makes each expert layer choose by fixed pseudo-random scores,
  seeded by its index among the layers (``router_topk``'s
  ``balance_seed``), as Megatron-LM's switch of that name does.
- ``*``, attention: grouped-query causal softmax attention through the
  flash kernels, **no rotary embedding** (the published model code applies
  none in these layers).
No projection has a bias.  All weights of a mixer are its block's own
parameters, declared in the order the benchmark's plain reference writes
them down (``benchmark/chip/archs/nemotron_h.py``); a layer's experts are
stacked leaves ``(count, moe_hidden, units)`` and ``(count, units,
moe_hidden)``.  Blocks open ``jax.named_scope``s (``mamba2``, ``moe`` with
``moe_router`` / ``moe_experts`` / ``moe_shared`` inside, ``attention``)
so that a device trace tells the kinds apart.  An ``E`` block
declares a step statistic (``step_stat_specs``): the assignments that
landed on each held expert, accumulated by ``parallel.JitTrainStep``.  An
``M`` block declares two: the chunks its scan ran (``ssd/<layer>``), which
feeds ``mxnet_ssd_chunks_total``, and those of them whose scan took the
Pallas kernels (``ssd_kernel/<layer>``: all or none, by the shapes), which
feeds ``mxnet_ssd_kernel_chunks_total``.

Not built: the second (denoiser) tower, adaLN and block-diffusion
generation of the TwoTower release.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from ...ops.ssm import ssd_chunks, ssd_kernel_chunks
from ...telemetry import metrics
from .. import nn
from ..block import HybridBlock, record_step_stat
from .llama import RMSNorm


def _dense(F, x, weight):
    return F.FullyConnected(x, weight, no_bias=True, flatten=False,
                            num_hidden=weight.shape[0])


def _feed_forward(F, u, up, down, activation):
    """``down(act(up(u)))`` over ``u (B, T, D)``: ``relu2``, ``relu(.)^2``
    of ``up (F, D)``; or ``swiglu``, ``silu(gate) * up`` of ``up (2F, D)``
    holding the gate's rows and then the up-projection's."""
    hid = _dense(F, u, up)
    if activation == "swiglu":
        f = up.shape[0] // 2
        hid = F.Activation(F.slice_axis(hid, axis=2, begin=0, end=f),
                           act_type="silu") \
            * F.slice_axis(hid, axis=2, begin=f, end=None)
    else:
        hid = F.square(F.relu(hid))
    return _dense(F, hid, down)


class _Mixer(HybridBlock):
    """A mixer whose weights are its own parameters, in the order
    ``_declare`` is given them: ``[(name, shape, init)]``."""

    def _declare(self, weights):
        for name, shape, init in weights:
            setattr(self, name, self.params.get(
                name, shape=shape, init=init, allow_deferred_init=False))


def chunk_counters(stem, scans, kernels, unit="chunks",
                   each="sequences x heads x chunks, every such layer and "
                        "train step", kernel="kernel", stat=None,
                   kernel_stat=None, kernel_family=None):
    """A telemetry collector for the counts of one kind of scan, or of
    whatever else a layer counts in ``unit`` (latent attention: layers;
    block-diffusion attention: tiles).
    They leave a ``JitTrainStep`` program as the statistics
    ``<stat>/<layer>`` (chunks the scans ran) and
    ``<stat>_<kernel>/<layer>`` (those that ran in the Pallas kernels;
    ``stat`` is ``stem`` unless given, the second ``<kernel_stat>/<layer>``
    where that is given), which it accumulates on the device;
    a snapshot fetches them (once, both kinds) and adds what is new (modulo
    the accumulators' 32 bits) to ``mxnet_<stem>_<unit>_total`` and
    ``mxnet_<stem>_<kernel>_<unit>_total`` (or ``kernel_family``).  With
    ``kernel`` None there is the first statistic alone, counted in the
    Pallas kernels ``kernels``."""
    stat = stat or stem
    in_kernels = "ran in the Pallas kernels " + kernels
    families = {stat + "/": ("mxnet_%s_%s_total" % (stem, unit),
                             in_kernels if kernel is None else "ran")}
    if kernel is not None:
        second = (kernel_stat or "%s_%s" % (stat, kernel)) + "/"
        families[second] = (kernel_family
                            or "mxnet_%s_%s_%s_total" % (stem, kernel, unit),
                            in_kernels)
    read = os.path.commonprefix(list(families))
    seen = {}               # (step, statistic) -> the count last read

    def collect():
        from ...parallel.train_step import read_step_stats

        new = {}        # no step of this process scans: no family either
        for owner, stats in read_step_stats(read):
            for name, count in stats.items():
                prefix = name[:name.index("/") + 1]
                if prefix in families:
                    count = int(count[0])
                    new[prefix] = new.get(prefix, 0) \
                        + (count - seen.get((owner, name), 0)) % (1 << 32)
                    seen[(owner, name)] = count
        for prefix, count in new.items():
            family, what = families[prefix]
            metrics.counter(family,
                            help="%s the %s %s (%s)"
                                 % (unit, scans, what, each)).inc(count)
    return collect


class Mamba2Mixer(_Mixer):
    def __init__(self, units, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk_size=128, eps=1e-5, layer=0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        inner = num_heads * head_dim
        conv = inner + 2 * n_groups * state_size
        self._cfg = (inner, num_heads, head_dim, n_groups, state_size,
                     int(chunk_size), eps)
        self._stat = "ssd/%d" % layer
        self._kernel_stat = "ssd_kernel/%d" % layer
        self._declare([
            ("in_proj", (2 * inner + 2 * n_groups * state_size + num_heads,
                         units), None),
            ("conv_weight", (conv, conv_kernel), None),
            ("conv_bias", (conv,), "zeros"),
            # (1, heads): a matrix to the benchmark's seeded initialiser
            ("dt_bias", (1, num_heads), "zeros"),
            ("A_log", (1, num_heads), "zeros"),
            ("D", (num_heads,), "ones"),
            ("norm_weight", (inner,), "ones"),
            ("out_proj", (units, inner), None)])

    def step_stat_specs(self):
        """Chunks the scan ran: batch x heads x chunks of the sequence; and
        those whose scan took the Pallas kernels."""
        return {self._stat: ((1,), jnp.uint32),
                self._kernel_stat: ((1,), jnp.uint32)}

    def hybrid_forward(self, F, u, in_proj, conv_weight, conv_bias, dt_bias,
                       A_log, D, norm_weight, out_proj):
        inner, heads, hd, groups, state, chunk, eps = self._cfg
        b, t, _ = u.shape
        gn = groups * state
        with jax.named_scope("mamba2"):
            zxbcdt = _dense(F, u, in_proj)
            z = F.slice_axis(zxbcdt, axis=2, begin=0, end=inner)
            xbc = F.slice_axis(zxbcdt, axis=2, begin=inner,
                               end=2 * inner + 2 * gn)
            dt = F.slice_axis(zxbcdt, axis=2, begin=2 * inner + 2 * gn,
                              end=None)
            xbc = F.Activation(
                F.contrib.causal_conv1d(xbc, conv_weight, conv_bias),
                act_type="silu")
            x = F.reshape(F.slice_axis(xbc, axis=2, begin=0, end=inner),
                          shape=(b, t, heads, hd))
            bm = F.reshape(F.slice_axis(xbc, axis=2, begin=inner,
                                        end=inner + gn),
                           shape=(b, t, groups, state))
            cm = F.reshape(F.slice_axis(xbc, axis=2, begin=inner + gn,
                                        end=None),
                           shape=(b, t, groups, state))
            y = F.contrib.ssd_scan(x, dt, A_log, bm, cm, D, dt_bias,
                                   chunk=chunk)
            record_step_stat(self._stat, jnp.full(
                (1,), b * heads * ssd_chunks(t, chunk), jnp.uint32))
            record_step_stat(self._kernel_stat, jnp.full(
                (1,), b * heads * ssd_kernel_chunks(
                    t, heads, hd, groups, state, chunk), jnp.uint32))
            y = F.reshape(y, shape=(b, t, inner)) \
                * F.Activation(z, act_type="silu")
            y = F.RMSNorm(
                F.reshape(y, shape=(b, t, groups, inner // groups)),
                F.reshape(norm_weight, shape=(groups, inner // groups)),
                axis=-1, eps=eps)
            return _dense(F, F.reshape(y, shape=(b, t, inner)), out_proj)


class MoEMixer(_Mixer):
    """Routed experts and a shared expert, both of one ``activation``:
    ``relu2`` (two matrices an expert) or ``swiglu`` (three: the gate's and
    the up-projection's rows stacked in ``up``, ``(count, 2 * moe_hidden,
    units)``)."""

    def __init__(self, units, n_experts, top_k, moe_hidden, shared_hidden,
                 scale=1.0, normalize=True, held=None, layer=0,
                 force_load_balancing=False, activation="relu2", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = held if held is not None else (0, n_experts)
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError("held=%r is not a share of %d experts"
                             % (held, n_experts))
        self._cfg = (int(top_k), float(scale), bool(normalize), int(first),
                     int(count))
        self._stat = "moe/%d/%d" % (layer, first)
        self._balance_seed = int(layer) if force_load_balancing else None
        self._activation = activation
        wide = 2 if activation == "swiglu" else 1
        self._declare([
            ("router", (n_experts, units), None),
            ("router_bias", (n_experts,), "zeros"),
            ("up", (count, wide * moe_hidden, units), None),
            ("down", (count, units, moe_hidden), None),
            ("shared_up", (wide * shared_hidden, units), None),
            ("shared_down", (units, shared_hidden), None)])

    def step_stat_specs(self):
        """Rows landed on each held expert, assignments in all, dropped,
        rows of the sorted layout walked."""
        return {self._stat: ((self._cfg[4] + 3,), jnp.uint32)}

    def hybrid_forward(self, F, u, router, router_bias, up, down, shared_up,
                       shared_down):
        k, scale, normalize, first, _ = self._cfg
        b, t, d = u.shape
        with jax.named_scope("moe"):
            flat = F.reshape(u, shape=(b * t, d))
            idx, w = F.contrib.moe_router_topk(
                flat, router, router_bias, k=k, scale=scale,
                normalize=normalize, balance_seed=self._balance_seed)
            routed, counts = F.contrib.moe_grouped_ffn(
                flat, idx, w, up, down, first=first,
                activation=self._activation)
            record_step_stat(self._stat, counts)
            with jax.named_scope("moe_shared"):
                shared = _feed_forward(F, u, shared_up, shared_down,
                                       self._activation)
            return F.reshape(routed, shape=(b, t, d)) + shared


class AttentionMixer(_Mixer):
    def __init__(self, units, num_heads, num_kv_heads, head_dim, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._cfg = (num_heads, num_kv_heads, head_dim)
        self._declare([
            ("q_proj", (num_heads * head_dim, units), None),
            ("k_proj", (num_kv_heads * head_dim, units), None),
            ("v_proj", (num_kv_heads * head_dim, units), None),
            ("o_proj", (units, num_heads * head_dim), None)])

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, o_proj):
        h, kv, d = self._cfg
        b, t, _ = u.shape
        with jax.named_scope("attention"):
            def heads(w, n):
                return F.transpose(F.reshape(_dense(F, u, w),
                                             shape=(b, t, n, d)),
                                   axes=(0, 2, 1, 3))
            q, k, v = heads(q_proj, h), heads(k_proj, kv), heads(v_proj, kv)
            if kv != h:
                k = F.repeat(k, repeats=h // kv, axis=1)
                v = F.repeat(v, repeats=h // kv, axis=1)
            out = F.contrib.flash_attention(
                q, k, v, scale=1.0 / math.sqrt(d), causal=True)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(b, t, h * d))
            return _dense(F, out, o_proj)


class NemotronHBlock(HybridBlock):
    """``x + mixer(RMSNorm(x))``."""

    def __init__(self, units, mixer, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.mixer = mixer(prefix="mixer_")

    def hybrid_forward(self, F, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(HybridBlock):
    """Decoder-only LM.  forward(tokens (B, T)) -> logits (B, T, V)."""

    def __init__(self, vocab_size, units, pattern, *, mamba_num_heads,
                 mamba_head_dim, n_groups, ssm_state_size, conv_kernel=4,
                 chunk_size=128, num_heads, num_kv_heads, head_dim,
                 n_routed_experts, num_experts_per_tok, moe_hidden,
                 shared_hidden, routed_scaling_factor=1.0,
                 norm_topk_prob=True, held=None,
                 force_load_balancing=False, eps=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._pattern = pattern

        def mixer(i, kind):
            if kind == "M":
                return lambda prefix: Mamba2Mixer(
                    units, mamba_num_heads, mamba_head_dim, n_groups,
                    ssm_state_size, conv_kernel, chunk_size, eps, layer=i,
                    prefix=prefix)
            if kind == "E":
                return lambda prefix: MoEMixer(
                    units, n_routed_experts, num_experts_per_tok,
                    moe_hidden, shared_hidden, routed_scaling_factor,
                    norm_topk_prob, held, layer=i,
                    force_load_balancing=force_load_balancing,
                    prefix=prefix)
            if kind == "*":
                return lambda prefix: AttentionMixer(
                    units, num_heads, num_kv_heads, head_dim, prefix=prefix)
            raise ValueError("pattern %r: no layer kind %r (M, E, *)"
                             % (pattern, kind))

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i, kind in enumerate(pattern):
                self.blocks.add(NemotronHBlock(
                    units, mixer(i, kind), eps, prefix="block%d_" % i))
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, in_units=units,
                                    prefix="head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.blocks(self.embed(tokens))))


def nemotron_h_30b_a3b(vocab_size=131072, **kwargs):
    """The language-model tower of Nemotron-Labs-TwoTower-30B-A3B-Base
    (52 layers: 23 Mamba-2, 23 expert, 6 attention; 31.58B parameters)."""
    cfg = dict(
        units=2688,
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128, num_heads=32,
        num_kv_heads=2, head_dim=128, n_routed_experts=128,
        num_experts_per_tok=6, moe_hidden=1856, shared_hidden=3712,
        routed_scaling_factor=2.5, norm_topk_prob=True)
    cfg.update(kwargs)
    return NemotronHModel(vocab_size, **cfg)


metrics.register_collector(chunk_counters("ssd", "Mamba-2 scans", "mx_ssd_*"))
