"""SDAR-MoE trained as a block-diffusion model: the program
(``gluon.model_zoo.sdar_moe``: the noising in the program, the ``[x_0 |
x_t]`` pass through the flash kernels under the block-diffusion mask,
qk-norm, the rotation of halves, Qwen3-MoE's softmax routing over
``grouped_ffn(activation="swiglu")``) against the benchmark's plain
reference (``benchmark/chip/archs/sdar_moe.py``) on seeded weights at tiny
widths, on the CPU: logits, loss and every gradient with the router free and
forced, three AdamW steps through ``JitTrainStep``, ``BlockDiffusionLoss``
against a plain reference of the masked ``1/t`` objective, the noise, the
share of the experts, ``router_topk(scoring="softmax")`` and the counters.

Tolerances.  Without AMP both sides are float32 and differ only in the
order of their sums (the flash kernels' online softmax against whole rows,
grouped against dense experts): 1e-4 of the largest value.  Under
``amp.init('bfloat16')`` every matrix product rounds its operands to 8
bits of mantissa: 2e-2 of the loss.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, parallel
from mxnet_tpu.gluon.model_zoo import sdar_moe
from mxnet_tpu.ops import block_diffusion
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.telemetry import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "chip"))

import archs  # noqa: E402
import reference  # noqa: E402

TINY = dict(
    model_type="sdar_moe", hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=1000000, rope_scaling=None, rms_norm_eps=1e-6,
    moe_intermediate_size=12, num_experts=4, router_num_experts=16,
    held_experts_first=4, num_experts_per_tok=3, norm_topk_prob=True,
    mlp_only_layers=[], decoder_sparse_step=1, tie_word_embeddings=False,
    vocab_size=64, block_length=4, noise_seed=7)
OPT = {"name": "adamw", "learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.1}
ARCH = archs.load("sdar_moe")
EIN = reference._einsum("float32")


def _close(ours, ref, tol=1e-4):
    ours, ref = np.asarray(ours), np.asarray(ref)
    assert np.isfinite(ours).all()
    assert np.abs(ours - ref).max() <= tol * max(1.0, np.abs(ref).max())


def _net_and_weights(cfg=TINY, seed=5):
    weights = reference.make_weights(cfg, seed)
    net = ARCH.build(cfg, mx.cpu())
    params = list(net.collect_params().values())
    assert [tuple(p.shape) for p in params] == \
        [s for _, s in ARCH.leaf_specs(cfg)]
    for p, w in zip(params, weights):
        p.set_data(w)
    return net, params, weights


def _batch(cfg, b, t, seed=0):
    rng = np.random.default_rng(seed)
    # ids below the mask id, as the cell's slice of the vocabulary draws
    return (rng.integers(0, cfg["vocab_size"] - 1, (b, t)).astype(np.int32),
            rng.integers(0, cfg["vocab_size"], (b * t,)).astype(np.int32))


# -- the whole model against the reference ----------------------------------------------

@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_logits_loss_and_every_leafs_gradient_match_the_reference(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, params, weights = _net_and_weights(cfg)
    toks, labels = _batch(cfg, 2, 24)
    loss_block = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        logits = net(mx.nd.array(toks, dtype="int32"))
        loss = loss_block(
            mx.nd.reshape(logits, shape=(-1, cfg["vocab_size"])),
            mx.nd.array(labels.astype(np.float32))).mean()
    loss.backward()
    ref_loss, ref_grads = jax.value_and_grad(
        lambda ws: reference.loss_fn(cfg, ws, jnp.asarray(toks),
                                     jnp.asarray(labels)))(weights)
    assert logits.shape == (2, 24, 64)
    _close(logits.asnumpy(), reference.forward(cfg, weights,
                                               jnp.asarray(toks)))
    _close(loss.asnumpy(), ref_loss)
    for (name, _), p, g in zip(ARCH.leaf_specs(cfg), params, ref_grads):
        assert np.abs(np.asarray(g)).max() > 0, name    # every leaf counts
        _close(p.grad().asnumpy(), g)
    if forced:
        free = reference.forward(TINY, weights, jnp.asarray(toks))
        assert np.abs(np.asarray(free) - logits.asnumpy()).max() > 1e-3


def test_a_noised_position_sees_its_block_and_the_clean_blocks_before_it():
    # swap two clean tokens of block 3 (positions 12, 13; the ids' sum, and
    # so the noise, stays): the logits of the noised blocks 0-2 do not move
    # (they see clean blocks strictly before their own), those of the noised
    # blocks after block 3 do
    cfg = dict(TINY, num_hidden_layers=1)
    net, _, _ = _net_and_weights(cfg)
    toks, _ = _batch(cfg, 1, 24, seed=3)
    moved = toks.copy()
    moved[0, 12], moved[0, 13] = toks[0, 13], toks[0, 12]   # same sum
    x_t, _, _ = ARCH.noise(cfg, jnp.asarray(toks))
    x_t2, _, _ = ARCH.noise(cfg, jnp.asarray(moved))
    assert np.array_equal(np.asarray(x_t[0, :12]), np.asarray(x_t2[0, :12]))
    a = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    b = net(mx.nd.array(moved, dtype="int32")).asnumpy()
    assert toks[0, 12] != toks[0, 13]
    assert np.array_equal(a[0, :12], b[0, :12])
    assert np.abs(a[0, 16:] - b[0, 16:]).max() > 1e-4


def _lm(net, vocab):
    class LM(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, toks):
            return F.reshape(self.inner(toks), shape=(-1, vocab))
    return LM(net)


def _reference_steps(weights, batches, loss_fn):
    @jax.jit
    def step(weights, m, v, t, toks, lab):
        loss, grads = jax.value_and_grad(loss_fn)(weights, toks, lab)
        out = [reference.adamw(OPT, w, g, mi, vi, t.astype(jnp.float32))
               for w, g, mi, vi in zip(weights, grads, m, v)]
        return ([o[0] for o in out], [o[1] for o in out],
                [o[2] for o in out], loss)
    m = [jnp.zeros_like(w) for w in weights]
    v = [jnp.zeros_like(w) for w in weights]
    weights, losses = list(weights), []
    for t, (toks, lab) in enumerate(batches, 1):
        weights, m, v, loss = step(weights, m, v, jnp.int32(t),
                                   jnp.asarray(toks), jnp.asarray(lab))
        losses.append(float(loss))
    return losses, weights


def _train_step(net):
    return parallel.JitTrainStep(
        _lm(net, 64), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {k: v for k, v in OPT.items() if k != "name"})


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_three_adamw_steps_match_the_reference_and_count(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, _, weights = _net_and_weights(cfg, seed=7)
    net.hybridize()
    batches = [_batch(cfg, 2, 16, seed=s) for s in range(3)]
    step = _train_step(net)
    losses = [float(step.step(t, lab.astype(np.float32)))
              for t, lab in batches]
    ref_losses, ref = _reference_steps(
        weights, batches,
        lambda ws, toks, lab: reference.loss_fn(cfg, ws, toks, lab))
    _close(losses, ref_losses)
    for a, b, w0 in zip(step._weights, ref, weights):
        _close(np.asarray(a) - np.asarray(w0), np.asarray(b) - np.asarray(w0),
               2e-3)
    stats = step.step_stats()
    # two routed layers, experts 4..7 held; 2 x 2 x 16 positions x 3 a step
    assert sorted(k for k in stats if k.startswith("moe/")) == \
        ["moe/0/4", "moe/1/4"]
    for k, counts in stats.items():
        if k.startswith("moe/"):
            assert counts[-3] == 3 * 64 * 3 and counts[-2] == 0
    # below 512 positions the operator is XLA's attention: one tile, computed
    assert sorted(k for k in stats if "flash_tiles" in k) == \
        ["bd_flash_tiles/0", "bd_flash_tiles/1", "flash_tiles/0",
         "flash_tiles/1"]
    assert all(int(stats[k][0]) == 3 * 2 * 4 for k in stats
               if "flash_tiles" in k)


def test_trains_under_amp_with_the_attention_in_bfloat16():
    cfg = dict(TINY, moe_router_force_load_balancing=True)
    net, _, weights = _net_and_weights(cfg)
    net.hybridize()
    batches = [_batch(cfg, 2, 16, seed=s) for s in range(3)]
    ref_losses, _ = _reference_steps(
        weights, batches,
        lambda ws, toks, lab: reference.loss_fn(cfg, ws, toks, lab))
    seen = {}
    orig = amp.transform_inputs

    def spy(op_name, datas):
        out = orig(op_name, datas)
        seen.setdefault(op_name, [getattr(d, "dtype", None) for d in out])
        return out
    amp.init("bfloat16")
    amp.transform_inputs = spy
    try:
        step = _train_step(net)
        losses = [float(step.step(t, lab.astype(np.float32)))
                  for t, lab in batches]
    finally:
        amp.transform_inputs = orig
        amp.turn_off()
    assert np.allclose(losses, ref_losses, rtol=2e-2)
    f32, bf16 = jnp.dtype("float32"), jnp.dtype("bfloat16")
    assert all(d == f32 for d in seen["_contrib_moe_router_topk"])
    assert seen["_contrib_flash_attention"] == [bf16] * 3
    assert all(d == f32 for d in seen["RMSNorm"])
    data, idx, weight, up, down = seen["_contrib_moe_grouped_ffn"]
    assert (data, up, down) == (bf16, bf16, bf16) and weight == f32


# -- the objective and the noise ----------------------------------------------------------

def _plain_bd_loss(cfg, weights, toks):
    """The masked 1/t objective written out by hand from the reference's
    logits and noise."""
    logits = np.asarray(reference.forward(cfg, weights, jnp.asarray(toks)),
                        np.float64)
    _, masked, level = (np.asarray(x) for x in ARCH.noise(
        cfg, jnp.asarray(toks)))
    logp = logits - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                           .sum(-1, keepdims=True)) - logits.max(-1,
                                                                 keepdims=True)
    total = 0.0
    b, t = toks.shape
    for i in range(b):
        for j in range(t):
            if masked[i, j]:
                total += -logp[i, j, toks[i, j]] / level[i, j]
    return total / (b * t)


def test_the_block_diffusion_loss_is_the_masked_one_over_t_objective():
    net, params, weights = _net_and_weights()
    toks, _ = _batch(TINY, 2, 24, seed=4)
    block = sdar_moe.BlockDiffusionLoss(net)
    with autograd.record():
        loss = block(mx.nd.array(toks, dtype="int32"))
    loss.backward()
    want = _plain_bd_loss(TINY, weights, toks)
    _close(loss.asnumpy(), want)
    _close(ARCH.bd_loss(TINY, weights, jnp.asarray(toks), EIN), want)
    grads = jax.grad(lambda ws: ARCH.bd_loss(TINY, ws, jnp.asarray(toks),
                                             EIN))(weights)
    for p, g in zip(params, grads):
        _close(p.grad().asnumpy(), g)
    # trains through the step that has no loss of its own
    step = parallel.JitTrainStep(block, None, "adamw",
                                 {k: v for k, v in OPT.items()
                                  if k != "name"})
    first = float(step.step(toks))
    _close(first, want)


def test_the_same_batch_is_noised_alike_and_another_otherwise():
    toks, _ = _batch(TINY, 2, 64, seed=1)
    other, _ = _batch(TINY, 2, 64, seed=2)
    ours = block_diffusion.block_diffusion_noise(
        jnp.asarray(toks), block=4, mask_id=63, seed=7)
    ref = ARCH.noise(TINY, jnp.asarray(toks))
    for a, b in zip(ours, ref):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    x_t, masked, level = (np.asarray(x) for x in ours)
    # masked positions carry the mask id, the others the clean token
    assert np.array_equal(x_t, np.where(masked > 0, 63, toks))
    # one level a block, in [1e-3, 1]
    blocks = level.reshape(2, 16, 4)
    assert np.all(blocks == blocks[..., :1])
    assert level.min() >= 1e-3 and level.max() <= 1.0
    assert 0 < masked.mean() < 1
    # a float feed of the same ids is noised alike
    same = block_diffusion.block_diffusion_noise(
        jnp.asarray(toks, jnp.float32), block=4, mask_id=63, seed=7)
    assert np.array_equal(np.asarray(same[1]), masked)
    again = ARCH.noise(TINY, jnp.asarray(other))
    assert not np.array_equal(np.asarray(again[1]), masked)
    with pytest.raises(ValueError):
        block_diffusion.noise(jnp.asarray(toks[:, :30]), 4, 63, 7)


# -- the share, the router and the counters -------------------------------------------------

def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    # 16 experts over 8 chips, 2 held a share: the shares' outputs add up to
    # the reference's layer with all 16 held (nothing else is computed in
    # the routed layer: no shared expert)
    cfg = dict(TINY, num_hidden_layers=1, num_experts=16,
               held_experts_first=0)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(cfg)],
                 reference.make_weights(cfg, 9)))
    lw = {k[len("layer0.moe."):]: v for k, v in w.items()
          if k.startswith("layer0.moe.")}
    u = jnp.asarray(np.random.default_rng(0).normal(size=(2, 10, 32)),
                    jnp.float32)
    whole = ARCH._moe(cfg, lw, u, EIN)
    total = np.zeros(whole.shape, np.float32)
    for first in range(0, 16, 2):
        share = sdar_moe.SDARMoE(32, 16, 3, 12, held=(first, 2))
        share.initialize(mx.init.Zero())
        for p, name in zip(share.collect_params().values(),
                           ("router", "gate_up", "down")):
            x = lw[name]
            p.set_data(x if name == "router" else x[first:first + 2])
        total += share(mx.nd.array(np.asarray(u))).asnumpy()
    _close(total, whole)


@pytest.mark.parametrize("normalize", [True, False])
def test_softmax_routing_is_a_plain_softmax_top_k(normalize):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    w = rng.normal(size=(12, 16)).astype(np.float32)
    idx, weight = moe_ops.router_topk(jnp.asarray(x), jnp.asarray(w), k=4,
                                      normalize=normalize, scoring="softmax")
    logits = x.astype(np.float64) @ w.T.astype(np.float64)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    want = np.argsort(-p, axis=1, kind="stable")[:, :4]
    assert np.array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(p, want, 1)
    if normalize:
        picked /= picked.sum(1, keepdims=True)
    _close(weight, picked, 1e-5)
    # forced: the choice from the fixed draw, the weights still the softmax
    idx, weight = moe_ops.router_topk(jnp.asarray(x), jnp.asarray(w), k=4,
                                      normalize=normalize, scoring="softmax",
                                      balance_seed=2)
    draw = np.asarray(jax.random.uniform(jax.random.PRNGKey(2), (40, 12)))
    want = np.argsort(-draw, axis=1, kind="stable")[:, :4]
    assert np.array_equal(np.asarray(idx), want)
    picked = np.take_along_axis(p, want, 1)
    if normalize:
        picked /= picked.sum(1, keepdims=True)
    _close(weight, picked, 1e-5)
    with pytest.raises(ValueError):
        moe_ops.router_topk(jnp.asarray(x), jnp.asarray(w),
                            jnp.zeros((12,)), scoring="softmax")
    with pytest.raises(ValueError):
        moe_ops.router_topk(jnp.asarray(x), jnp.asarray(w), scoring="top")


def test_the_tile_counters_leave_the_step_as_two_families():
    cfg = dict(TINY, num_hidden_layers=1)
    net, _, _ = _net_and_weights(cfg)
    net.hybridize()
    before = metrics.snapshot()

    def total(snap, name):
        return sum(s["value"] for s in snap.get(name, {}).get("series", []))
    step = _train_step(net)
    for s in range(2):
        toks, lab = _batch(cfg, 2, 16, seed=s)
        step.step(toks, lab.astype(np.float32))
    after = metrics.snapshot()
    # one layer, 2 x 4 heads, one tile each (XLA's attention at 32 positions)
    for name in ("mxnet_flash_tiles_total", "mxnet_flash_tiles_computed_total"):
        assert total(after, name) - total(before, name) == 2 * 8
