"""Qwen3-Next: the program (``gluon.model_zoo.qwen3_next``: Gated DeltaNet
through ``F.contrib.gdn_attention``, gated attention with a quarter of each
head turned in halves, softmax routing over ``grouped_ffn(activation=
"swiglu")`` beside a gated shared expert, zero-centred norms) against the
benchmark's plain reference (``benchmark/chip/archs/qwen3_next.py``) on
seeded weights at tiny widths, on the CPU; ``gdn_attention`` in the
delta-rule kernels (the interpreter) and in its composition against the
recurrence; the rotation, the shared expert's gate and the share of the
experts.

Tolerances.  Without AMP both sides are float32 and differ in the order of
their sums and, in the delta rule, chunked algebra against the recurrence:
1e-4 of the largest value (bfloat16 in the place of float32, 8 bits of
mantissa, moves every value by about 4e-3 of it).  The kernels round the
operands of their products outside the solve to bfloat16 as a TPU's
default precision does, so the kernels' own test patches those products to
float32 (``kda_kernels._dot`` to ``_dot32``: the same kernels and algebra)
and holds them to 1e-4 as well.
"""
import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import qwen3_next
from mxnet_tpu.ops import kda, kda_kernels, pallas_kernels, rotary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "chip"))

import archs  # noqa: E402
import reference  # noqa: E402

TINY = dict(
    model_type="qwen3_next", hidden_size=32, num_hidden_layers=2,
    full_attention_interval=2, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=10000000, rope_scaling=None,
    rms_norm_eps=1e-6, moe_intermediate_size=12,
    shared_expert_intermediate_size=12, num_experts=4,
    router_num_experts=16, held_experts_first=4, num_experts_per_tok=3,
    norm_topk_prob=True, mlp_only_layers=[], decoder_sparse_step=1,
    tie_word_embeddings=False, vocab_size=64)
ARCH = archs.load("qwen3_next")
EIN = reference._einsum("float32")


def _close(ours, ref, tol=1e-4):
    ours, ref = np.asarray(ours), np.asarray(ref)
    assert ours.shape == ref.shape
    assert np.isfinite(ours).all()
    assert np.abs(ours - ref).max() <= tol * max(1.0, np.abs(ref).max()), \
        (np.abs(ours - ref).max(), np.abs(ref).max())


@functools.lru_cache(maxsize=None)
def _made_weights(cfg_json, seed):
    return reference.make_weights(json.loads(cfg_json), seed)


def _net_and_weights(cfg=TINY, seed=5):
    # the leaves do not depend on how the router chooses: made once for both
    weights = _made_weights(json.dumps(
        dict(cfg, moe_router_force_load_balancing=False), sort_keys=True),
        seed)
    net = ARCH.build(cfg, mx.cpu())
    params = list(net.collect_params().values())
    assert [tuple(p.shape) for p in params] == \
        [s for _, s in ARCH.leaf_specs(cfg)]
    for p, w in zip(params, weights):
        p.set_data(w)
    return net, params, weights


def _batch(cfg, b, t, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, cfg["vocab_size"], (b, t)).astype(np.int32),
            rng.integers(0, cfg["vocab_size"], (b * t,)).astype(np.int32))


# -- the whole model against the reference ----------------------------------------------

@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_logits_and_every_leafs_gradient_match_the_reference(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, params, weights = _net_and_weights(cfg)
    assert ARCH.kinds(cfg) == ["gdn", "attn"]
    toks, labels = _batch(cfg, 2, 24)
    with autograd.record():
        logits = net(mx.nd.array(toks, dtype="int32"))
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(
            mx.nd.reshape(logits, shape=(-1, cfg["vocab_size"])),
            mx.nd.array(labels.astype(np.float32))).mean()
    loss.backward()
    ref_logits, ref_loss, ref_grads = jax.jit(lambda ws: (
        reference.forward(cfg, ws, jnp.asarray(toks)),
        *jax.value_and_grad(lambda ws: reference.loss_fn(
            cfg, ws, jnp.asarray(toks), jnp.asarray(labels)))(ws)))(weights)
    assert logits.shape == (2, 24, 64)
    _close(logits.asnumpy(), ref_logits)
    _close(loss.asnumpy(), ref_loss)
    for (name, _), p, g in zip(ARCH.leaf_specs(cfg), params, ref_grads):
        assert np.abs(np.asarray(g)).max() > 0, name    # every leaf counts
        _close(p.grad().asnumpy(), g)


# -- the GDN operator against the recurrence ----------------------------------------------

def _recurrence(qkvz, ba, conv, A_log, dt_bias, o_norm, eps=1e-6):
    """The GDN mixer between its projections written out with
    ``kda_recurrence``: q and k repeated to the value heads."""
    hk, hv, d = kda._gdn_sizes(qkvz, A_log, o_norm)
    b, t, _ = qkvz.shape
    x = qkvz.astype(jnp.float32)
    m = jax.nn.silu(ARCH._conv(x[..., :2 * hk * d + hv * d], conv))

    def unit(y):
        return y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    def key_heads(y):
        return jnp.repeat(unit(y.reshape(b, t, hk, d)), hv // hk, axis=2)
    g = -jnp.exp(A_log.reshape(hv)) * jax.nn.softplus(
        ba[..., hv:] + dt_bias.reshape(hv))
    o = kda.kda_recurrence(
        key_heads(m[..., :hk * d]) * d ** -0.5, key_heads(m[..., hk * d:
                                                            2 * hk * d]),
        m[..., 2 * hk * d:].reshape(b, t, hv, d),
        jnp.broadcast_to(g[..., None], (b, t, hv, d)),
        jax.nn.sigmoid(ba[..., :hv]))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * o_norm \
        * jax.nn.silu(x[..., 2 * hk * d + hv * d:]).reshape(b, t, hv, d)
    return o.reshape(b, t, hv * d)


@pytest.fixture
def exact_products(monkeypatch):
    # ``_platform_pick`` keeps what it jitted: neither what an earlier test
    # traced is this one's, nor what this one traces the next one's
    pallas_kernels._JIT_CACHE.clear()
    monkeypatch.setattr(kda_kernels, "_dot", kda_kernels._dot32)
    yield
    pallas_kernels._JIT_CACHE.clear()


@pytest.mark.parametrize("d,fused", [(128, True), (8, False)],
                         ids=["kernels", "composition"])
def test_gdn_attention_is_the_recurrence(d, fused, exact_products):
    # one key head read by two value heads, 80 tokens: a padded tail of 48
    # tokens past the last whole chunk of 64; one head decays slowly (its
    # state carries across the chunk) and one at up to -30 a token, past the
    # edge of a decay a channel (ops/kda.py: -5.0)
    hk, hv, t = 1, 2, 80
    assert kda.gdn_fused(t, d, hk, hv, 4) is fused
    rng = np.random.default_rng(0)
    args = [jnp.asarray(a, jnp.float32) for a in (
        rng.normal(size=(1, t, 2 * hk * d + 2 * hv * d)),
        rng.normal(size=(1, t, 2 * hv)),
        rng.normal(size=(2 * hk * d + hv * d, 4)) * 0.5,
        np.log([[0.05, 16.0]]), rng.uniform(-0.4, 0.4, (1, hv)),
        rng.uniform(0.5, 1.5, (d,)))]
    cot = jnp.asarray(rng.normal(size=(1, t, hv * d)), jnp.float32)
    text = str(jax.make_jaxpr(kda.gdn_attention)(*args))
    assert ("mx_kda_fwd" in text) is fused
    out, grads = jax.jit(lambda *a: (lambda o, f: (o, f(cot)))(
        *jax.vjp(kda.gdn_attention, *a)))(*args)
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(_recurrence, *args)
        wants = vjp(cot)
    _close(out, want)
    for a, b in zip(grads, wants):
        _close(a, b)


def test_which_gdn_mixers_the_kernels_take_whole():
    # the cell's: 16 key heads, 32 value heads of 128, 4 taps, 8192 tokens
    assert kda.gdn_fused(8192, 128, 16, 32, 4)
    assert not kda.gdn_fused(8192, 64, 16, 32, 4)       # no whole lane tile
    assert not kda.gdn_fused(8192, 128, 12, 32, 4)      # 32 over 12
    assert not kda.gdn_fused(8192, 128, 16, 32, 18)     # taps past the span


# -- the rotation, the shared expert and the share --------------------------------------

def test_a_quarter_of_each_head_turns_in_halves():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 2, 6, 16)).astype(np.float32)
    got = np.asarray(rotary.rotary_embedding(jnp.asarray(x), theta=1e7,
                                             rotary_dim=4, halves=True))
    want = x.copy()
    for t in range(6):
        for i in range(2):
            phi = t * 1e7 ** (-2.0 * i / 4)
            a, b = x[..., t, i], x[..., t, i + 2]
            want[..., t, i] = a * np.cos(phi) - b * np.sin(phi)
            want[..., t, i + 2] = b * np.cos(phi) + a * np.sin(phi)
    _close(got, want, 1e-6)
    assert np.array_equal(got[..., 4:], x[..., 4:])     # 12 pass as they are
    _close(ARCH._rotary(jnp.asarray(x).transpose(0, 2, 1, 3), 1e7, 4),
           want.transpose(0, 2, 1, 3), 1e-6)


def _moe_leaves(cfg, seed):
    w = dict(zip([n for n, _ in ARCH.leaf_specs(cfg)],
                 reference.make_weights(cfg, seed)))
    return {k[len("layer0.moe."):]: v for k, v in w.items()
            if k.startswith("layer0.moe.")}


def _share(cfg, lw, first, count):
    layer = qwen3_next.Qwen3NextMoE(
        cfg["hidden_size"], cfg["router_num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["shared_expert_intermediate_size"], held=(first, count))
    layer.initialize(mx.init.Zero())
    for (name, _), p in zip(ARCH._specs(cfg, "moe"),
                            layer.collect_params().values()):
        x = lw[name]
        p.set_data(x[first:first + count] if name in ("gate_up", "down")
                   else x)
    return layer


def _shared_expert(lw, u):
    """``swiglu(u W_su) W_sd * sigmoid(u w_sg)``, by hand in numpy."""
    hid = u @ np.asarray(lw["shared_gate_up"]).T
    f = hid.shape[-1] // 2
    plain = (hid[..., :f] / (1 + np.exp(-hid[..., :f])) * hid[..., f:]) \
        @ np.asarray(lw["shared_down"]).T
    return plain, 1 / (1 + np.exp(-(u @ np.asarray(lw["shared_gate"]).T)))


def test_the_shared_expert_is_gated_by_a_sigmoid_of_its_own():
    # a share whose experts are zero is the shared expert alone
    cfg = dict(TINY, num_hidden_layers=1)
    lw = _moe_leaves(cfg, 3)
    u = np.random.default_rng(4).normal(size=(2, 5, 32)).astype(np.float32)
    plain, gate = _shared_expert(lw, u)
    lw = dict(lw, gate_up=np.zeros_like(lw["gate_up"]),
              down=np.zeros_like(lw["down"]))
    out = _share(cfg, lw, 0, 4)(mx.nd.array(u)).asnumpy()
    _close(out, plain * gate, 1e-5)
    assert np.abs(gate - 0.5).max() > 1e-2     # the gate is no constant
    assert np.abs(out - plain).max() > 1e-2


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    # 16 experts over 4 chips, 4 held a share: the shares' outputs add up to
    # the reference's layer with all 16 held once the shared expert, which
    # every chip computes alike, is counted once
    cfg = dict(TINY, num_hidden_layers=1, num_experts=16,
               held_experts_first=0)
    lw = _moe_leaves(cfg, 9)
    u = np.random.default_rng(0).normal(size=(2, 10, 32)).astype(np.float32)
    whole = ARCH._moe(cfg, lw, jnp.asarray(u), EIN)
    shares = [_share(cfg, lw, first, 4)(mx.nd.array(u)).asnumpy()
              for first in range(0, 16, 4)]
    plain, gate = _shared_expert(lw, u)
    _close(sum(shares) - 3 * plain * gate, whole)
