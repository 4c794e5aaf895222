"""JoyAI-LLM Flash, the whole model and its prediction module:
``gluon.model_zoo.joyai_llm_flash`` through autograd and through
``parallel.JitTrainStep`` (without AMP and under ``amp.init('bfloat16')``,
router free and forced) against the benchmark's plain reference at tiny
widths, on the CPU.  Helpers and tolerances are ``test_joyai_llm_flash.py``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, parallel
from mxnet_tpu.gluon.model_zoo import joyai_llm_flash

from test_joyai_llm_flash import (ARCH, EIN, OPT, TINY, _batch, _close,
                                  _net_and_weights, reference)

WITH_MODULE = dict(TINY, num_nextn_predict_layers=1)


# -- the whole model against the reference ----------------------------------------------

@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_logits_loss_and_every_leafs_gradient_match_the_reference(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, params, weights = _net_and_weights(cfg)
    toks, labels = _batch(cfg, 2, 37)
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
    _close(logits.asnumpy(), reference.forward(cfg, weights,
                                               jnp.asarray(toks)))
    _close(loss.asnumpy(), ref_loss)
    for (name, _), p, g in zip(ARCH.leaf_specs(cfg), params, ref_grads):
        _close(p.grad().asnumpy(), g)
        if name.endswith("router_bias"):
            assert not np.asarray(g).any() and not p.grad().asnumpy().any()
    if forced:
        free = reference.forward(TINY, weights, jnp.asarray(toks))
        assert np.abs(np.asarray(free) - logits.asnumpy()).max() > 1e-3


def _lm(net, vocab):
    class LM(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, toks):
            return F.reshape(self.inner(toks), shape=(-1, vocab))
    return LM(net)


def _reference_steps(weights, batches, loss_fn):
    """AdamW (``reference.adamw``) over ``batches`` on ``loss_fn(weights,
    tokens, labels)``: the losses and the weights after."""
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


def _main_loss(cfg):
    return lambda ws, toks, lab: reference.loss_fn(cfg, ws, toks, lab)


def _train_step(net):
    return parallel.JitTrainStep(
        _lm(net, 64), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {k: v for k, v in OPT.items() if k != "name"})


def _changes_close(ours, ref, start):
    for a, b, w0 in zip(ours, ref, start):
        # Adam's first steps move every element by about the learning
        # rate: the change is compared, against its own size
        _close(np.asarray(a) - np.asarray(w0),
               np.asarray(b) - np.asarray(w0), 2e-3)


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_three_adamw_steps_match_the_reference_and_count_the_rows(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, _, weights = _net_and_weights(cfg, seed=7)
    net.hybridize()
    batches = [_batch(cfg, 2, 20, seed=s) for s in range(3)]
    step = _train_step(net)
    losses = [float(step.step(t, lab.astype(np.float32)))
              for t, lab in batches]
    ref_losses, ref = _reference_steps(weights, batches, _main_loss(cfg))
    _close(losses, ref_losses)
    _changes_close(step._weights, ref, weights)
    stats = step.step_stats()
    # two routed layers (1 and 2), experts 4..7 held, 40 tokens x 3 a step
    assert sorted(k for k in stats if k.startswith("moe/")) == \
        ["moe/1/4", "moe/2/4"]
    for k, counts in stats.items():
        if k.startswith("moe/"):
            assert counts[-3] == 3 * 40 * 3 and counts[-2] == 0
    # three latent attention layers counted three steps each; at the tiny
    # widths none reads its operands in place
    assert sorted(k for k in stats if k.startswith("mla")) == \
        ["mla/0", "mla/1", "mla/2", "mla_kernel/0", "mla_kernel/1",
         "mla_kernel/2"]
    assert all(int(stats["mla/%d" % i][0]) == 3
               and int(stats["mla_kernel/%d" % i][0]) == 0 for i in range(3))


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_trains_under_amp_with_the_attention_in_bfloat16(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, _, weights = _net_and_weights(cfg)
    net.hybridize()
    batches = [_batch(cfg, 2, 20, seed=s) for s in range(3)]
    ref_losses, _ = _reference_steps(weights, batches, _main_loss(cfg))
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
    # queries, keys and values in bfloat16, and the rotation takes and
    # hands back bfloat16 (it is float32 inside: test_joyai_llm_flash.py)
    assert seen["_contrib_flash_attention"] == [bf16] * 3
    assert seen["_contrib_rotary_embedding"] == [bf16]
    assert all(d == f32 for d in seen["RMSNorm"])
    data, idx, weight, up, down = seen["_contrib_moe_grouped_ffn"]
    assert (data, up, down) == (bf16, bf16, bf16) and weight == f32


# -- the prediction module ---------------------------------------------------------------

def test_the_modules_logits_and_loss_match_the_reference():
    net, _, weights = _net_and_weights(WITH_MODULE)
    toks, labels = _batch(WITH_MODULE, 2, 24)
    logits, more = net(mx.nd.array(toks, dtype="int32"))
    want, want_more = ARCH.mtp_logits(WITH_MODULE, weights,
                                      jnp.asarray(toks), EIN)
    assert more.shape == (2, 23, 64)
    _close(logits.asnumpy(), want)
    _close(more.asnumpy(), want_more)
    # the main logits are the model's without the module
    plain = dict(zip([n for n, _ in ARCH.leaf_specs(WITH_MODULE)], weights))
    _close(logits.asnumpy(), reference.forward(
        TINY, [plain[n] for n, _ in ARCH.leaf_specs(TINY)],
        jnp.asarray(toks)))
    loss = joyai_llm_flash.MTPLoss(net, 0.3)(
        mx.nd.array(toks, dtype="int32"),
        mx.nd.array(labels.astype(np.float32)))
    _close(loss.asnumpy(), ARCH.mtp_loss(
        WITH_MODULE, weights, jnp.asarray(toks),
        jnp.asarray(labels).reshape(2, 24), EIN))
    # position i of the module sees tokens 0 .. i + 1 and no later one
    moved = toks.copy()
    moved[:, 12:] = (moved[:, 12:] + 1) % 64
    _, more2 = net(mx.nd.array(moved, dtype="int32"))
    assert np.array_equal(more2.asnumpy()[:, :11], more.asnumpy()[:, :11])
    assert np.abs(more2.asnumpy()[:, 11] - more.asnumpy()[:, 11]).max() > 1e-3


def test_the_embedding_and_the_head_take_the_sum_of_their_two_uses():
    cfg = WITH_MODULE
    net, params, weights = _net_and_weights(cfg)
    toks, labels = _batch(cfg, 2, 24)
    block = joyai_llm_flash.MTPLoss(net, 0.3)
    with autograd.record():
        loss = block(mx.nd.array(toks, dtype="int32"),
                     mx.nd.array(labels.astype(np.float32)))
    loss.backward()
    names = [n for n, _ in ARCH.leaf_specs(cfg)]
    w = dict(zip(names, weights))
    toks_j, lab = jnp.asarray(toks), jnp.asarray(labels).reshape(2, 24)

    def parts(embed_trunk, embed_ahead, head_main, head_module):
        """The loss with each use of the two shared leaves its own."""
        hidden = ARCH._trunk(cfg, dict(w, embed=embed_trunk), toks_j, EIN)
        g = ARCH._mtp_hidden(cfg, dict(w, embed=embed_ahead), hidden,
                             toks_j, EIN)
        main = ARCH._head(cfg, dict(w, head=head_main), hidden, w["norm"],
                          EIN)
        ahead = ARCH._head(cfg, dict(w, head=head_module), g,
                           w["mtp.norm"], EIN)[:, :-1]
        return ARCH._cross_entropy(main, lab) \
            + 0.3 * ARCH._cross_entropy(ahead, lab[:, 1:])
    _close(loss.asnumpy(), parts(w["embed"], w["embed"], w["head"],
                                 w["head"]))
    d_trunk, d_ahead, d_main, d_module = jax.grad(
        parts, argnums=(0, 1, 2, 3))(w["embed"], w["embed"], w["head"],
                                     w["head"])
    got = dict(zip(names, (p.grad().asnumpy() for p in params)))
    for use in (d_trunk, d_ahead, d_main, d_module):
        assert np.abs(np.asarray(use)).max() > 1e-4     # each use counts
    _close(got["embed"], d_trunk + d_ahead, 1e-5)
    _close(got["head"], d_main + d_module, 1e-5)
    # and every leaf's gradient is the reference's
    ref = jax.grad(lambda ws: ARCH.mtp_loss(cfg, ws, toks_j, lab, EIN))(
        weights)
    for name, g in zip(names, ref):
        _close(got[name], g)


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_three_steps_with_the_module_through_the_step_that_has_no_loss(forced):
    cfg = dict(WITH_MODULE, moe_router_force_load_balancing=forced)
    net, _, weights = _net_and_weights(cfg, seed=7)
    net.hybridize()
    batches = [_batch(cfg, 2, 20, seed=s) for s in range(3)]
    step = parallel.JitTrainStep(
        joyai_llm_flash.MTPLoss(net, cfg["mtp_loss_weight"]), None, "adamw",
        {k: v for k, v in OPT.items() if k != "name"})
    losses = [float(step.step(t, lab.astype(np.float32)))
              for t, lab in batches]
    ref_losses, ref = _reference_steps(
        weights, batches, lambda ws, toks, lab: ARCH.mtp_loss(
            cfg, ws, toks, lab.reshape(toks.shape), EIN))
    _close(losses, ref_losses)
    _changes_close(step._weights, ref, weights)
    # the module's layer is numbered after the last, 3: its routed block
    # and its latent attention mixer
    stats = sorted(step.step_stats())
    assert [k for k in stats if k.startswith("moe/")] == \
        ["moe/1/4", "moe/2/4", "moe/3/4"]
    assert [k for k in stats if k.startswith("mla/")] == \
        ["mla/%d" % i for i in range(4)]
