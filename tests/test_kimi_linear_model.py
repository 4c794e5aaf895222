"""Kimi Linear, the whole model: ``gluon.model_zoo.kimi_linear`` through
autograd and through ``parallel.JitTrainStep`` (without AMP and under
``amp.init('bfloat16')``, router free and forced) against the benchmark's
plain reference at tiny widths, on the CPU.  Helpers and tolerances are
``test_kimi_linear.py``'s.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, parallel
from mxnet_tpu.telemetry import metrics

from test_kimi_linear import (ARCH, CHIP, OPT, TINY, _batch, _close,
                              _net_and_weights, reference)


# -- the whole model against the reference ----------------------------------------------

def _reference_forward(cfg, weights, toks):
    # the reference as one program: its primitives one at a time would each
    # be compiled and dispatched alone
    return jax.jit(lambda ws, t: reference.forward(cfg, ws, t))(
        weights, jnp.asarray(toks))

def _loss_and_grads(cfg, net, params, weights, toks, labels):
    loss_block = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        logits = net(mx.nd.array(toks, dtype="int32"))
        loss = loss_block(
            mx.nd.reshape(logits, shape=(-1, cfg["vocab_size"])),
            mx.nd.array(labels.astype(np.float32))).mean()
    loss.backward()
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda ws: reference.loss_fn(cfg, ws, jnp.asarray(toks),
                                     jnp.asarray(labels))))(weights)
    _close(logits.asnumpy(), _reference_forward(cfg, weights, toks))
    _close(loss.asnumpy(), ref_loss)
    for (name, _), p, g in zip(ARCH.leaf_specs(cfg), params, ref_grads):
        _close(p.grad().asnumpy(), g)
        if name.endswith("router_bias"):
            assert not np.asarray(g).any() and not p.grad().asnumpy().any()
    return logits.asnumpy()


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_logits_loss_and_every_leafs_gradient_match_the_reference(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, params, weights = _net_and_weights(cfg)
    toks, labels = _batch(cfg, 2, 37)         # 2.3 chunks of 16
    logits = _loss_and_grads(cfg, net, params, weights, toks, labels)
    if forced:
        free = _reference_forward(TINY, weights, toks)
        assert np.abs(np.asarray(free) - logits).max() > 1e-3


def _lm(net, vocab):
    class LM(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, toks):
            return F.reshape(self.inner(toks), shape=(-1, vocab))
    return LM(net)


def _reference_steps(cfg, weights, batches):
    step = reference.make_step(cfg, OPT)
    m = [jnp.zeros_like(w) for w in weights]
    v = [jnp.zeros_like(w) for w in weights]
    losses = []
    weights = [jnp.array(w) for w in weights]
    for t, (toks, lab) in enumerate(batches, 1):
        weights, m, v, loss, _ = step(weights, m, v, jnp.int32(t),
                                      jnp.asarray(toks), jnp.asarray(lab))
        losses.append(float(loss))
    return losses, weights


def _train_step(net):
    return parallel.JitTrainStep(
        _lm(net, 64), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {k: v for k, v in OPT.items() if k != "name"})


def _kda_chunks_counted():
    series = metrics.snapshot().get("mxnet_kda_chunks_total", {}) \
        .get("series", [])
    return sum(s["value"] for s in series)


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_three_adamw_steps_match_the_reference_and_count_the_chunks(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = _kda_chunks_counted()
    # weights at which no token's third choice changes hands between the
    # two sides after an update (at seed 5 one does in the second step, and
    # a flipped choice is a whole expert's row: 7e-4 of the loss)
    net, _, weights = _net_and_weights(cfg, seed=7)
    net.hybridize()
    batches = [_batch(cfg, 2, 20, seed=s) for s in range(3)]
    step = _train_step(net)
    losses = [float(step.step(t, lab.astype(np.float32)))
              for t, lab in batches]
    ours = [np.asarray(w) for w in step._weights]
    ref_losses, ref = _reference_steps(cfg, weights, batches)
    _close(losses, ref_losses)
    for a, b, w0 in zip(ours, ref, weights):
        # Adam's first steps move every element by about the learning
        # rate: the change is compared, against its own size
        _close(a - np.asarray(w0), np.asarray(b) - np.asarray(w0), 2e-3)
    stats = step.step_stats()
    # four KDA layers: 2 sequences x 4 heads x 2 chunks of 16, three steps
    assert sorted(k for k in stats if k.startswith("kda/")) == \
        ["kda/1", "kda/2", "kda/3", "kda/5"]
    assert all(int(stats[k][0]) == 3 * 2 * 4 * 2 for k in stats
               if k.startswith("kda/"))
    # four routed layers, experts 4..7 held, 40 tokens x 3 a step
    assert sorted(k for k in stats if k.startswith("moe/")) == \
        ["moe/2/4", "moe/3/4", "moe/4/4", "moe/5/4"]
    for k, counts in stats.items():
        if k.startswith("moe/"):
            assert counts[-3] == 3 * 40 * 3 and counts[-2] == 0
    assert _kda_chunks_counted() - before == 4 * 3 * 2 * 4 * 2


@pytest.mark.parametrize("forced", [False, True], ids=["free", "forced"])
def test_trains_under_amp_with_the_delta_rule_in_float32(forced):
    cfg = dict(TINY, moe_router_force_load_balancing=forced)
    net, _, weights = _net_and_weights(cfg)
    net.hybridize()
    batches = [_batch(cfg, 2, 20, seed=s) for s in range(3)]
    ref_losses, _ = _reference_steps(cfg, weights, batches)
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
    # the mixer's operator takes the projections' results as they come
    # (and is float32 inside); its own leaves stay float32
    kda_in = seen["_contrib_kda_attention"]
    assert kda_in[:6] == [bf16] * 6 and kda_in[6:] == [f32] * 6
    assert all(d == bf16 for d in seen["_contrib_flash_attention"])
    data, idx, weight, up, down = seen["_contrib_moe_grouped_ffn"]
    assert (data, up, down) == (bf16, bf16, bf16) and weight == f32


def test_a_planted_rotary_embedding_shows_in_the_logits():
    """The fault that ``correct``'s norms cannot see (a rotation keeps
    them: ``benchmark/chip/tests/faults_kimi_linear.py``): planted under the
    program, the logits leave the reference's by far more than 1e-4."""
    sys.path.insert(0, os.path.join(CHIP, "tests"))
    import faults_kimi_linear

    reg = mx.ops.registry.get("_contrib_flash_attention")
    forward = reg.forward
    net, _, weights = _net_and_weights()
    toks, _ = _batch(TINY, 2, 24)
    want = _reference_forward(TINY, weights, toks)
    try:
        faults_kimi_linear.plant("rotary")
        got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    finally:
        reg.forward = forward
        # the traces made under the fault go with their jitted callables
        mx.ops.registry._jitted.cache_clear()
    assert np.abs(got - np.asarray(want)).max() > 1e-2 * np.abs(want).max()
    _close(net(mx.nd.array(toks[:, :20], dtype="int32")).asnumpy(),
           _reference_forward(TINY, weights, toks[:, :20]))
