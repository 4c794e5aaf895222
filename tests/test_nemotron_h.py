"""Nemotron-H (Mamba-2 + routed relu² experts + GQA layers): the program
(``gluon.model_zoo.nemotron_h``, ``ops/ssm.py``, ``ops/moe.py``,
``JitTrainStep``'s step statistics) against the benchmark's plain
reference (``benchmark/chip/archs/nemotron_h.py``) on seeded weights at
tiny widths, on the CPU.

Tolerances.  Without AMP both sides are float32 and differ only in the
order of their sums (chunked against token-by-token scan, grouped against
dense experts): 1e-4 of the largest value, which is 50-100x what is read.
Under ``amp.init('bfloat16')`` every matrix product rounds its operands to
8 bits of mantissa: 2e-2 of the loss.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, parallel
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import ssm
from mxnet_tpu.telemetry import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
sys.path.insert(0, CHIP)

import archs  # noqa: E402
import compare  # noqa: E402
import reference  # noqa: E402

CELL = "train_nemotronh_p7_b2s2048"
TINY = dict(
    model_type="nemotron_h", hidden_size=32, hybrid_override_pattern="MEM*E",
    num_hidden_layers=5, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, n_routed_experts=4,
    router_num_experts=16, held_experts_first=4, num_experts_per_tok=3,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    routed_scaling_factor=2.5, norm_topk_prob=True, layer_norm_epsilon=1e-5,
    vocab_size=64)
OPT = {"name": "adamw", "learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.1}
ARCH = archs.load("nemotron_h")


def _published_ranges(cfg, weights, seed):
    """``A`` in -[1, 16] and ``dt`` in [0.001, 0.1], as the model is
    published, where the benchmark's initialiser draws +-0.3."""
    rng = np.random.default_rng(seed)
    out = []
    for (name, shape), w in zip(ARCH.leaf_specs(cfg), weights):
        if name.endswith("A_log"):
            w = jnp.asarray(np.log(rng.uniform(1, 16, shape)), jnp.float32)
        elif name.endswith("dt_bias"):
            dt = rng.uniform(0.001, 0.1, shape)
            w = jnp.asarray(np.log(np.expm1(dt)), jnp.float32)
        elif name.endswith("router_bias"):
            w = jnp.asarray(rng.normal(0, 0.05, shape), jnp.float32)
        out.append(w)
    return out


@functools.lru_cache(maxsize=None)
def _made_weights(cfg_json, seed):
    # the reference's seeded leaves, made (and their program compiled) once
    # a module for each configuration and seed
    return reference.make_weights(json.loads(cfg_json), seed)


def _net_and_weights(cfg=TINY, seed=5):
    weights = _published_ranges(
        cfg, _made_weights(json.dumps(cfg, sort_keys=True), seed), seed)
    net = ARCH.build(cfg, mx.cpu())
    params = list(net.collect_params().values())
    assert [tuple(p.shape) for p in params] == \
        [s for _, s in ARCH.leaf_specs(cfg)]
    for p, w in zip(params, weights):
        p.set_data(w)
    return net, params, weights


def _reference_forward(cfg, weights, toks):
    # the reference as one program: its primitives one at a time would each
    # be compiled and dispatched alone
    return jax.jit(lambda ws, t: reference.forward(cfg, ws, t))(
        weights, jnp.asarray(toks))

def _batch(cfg, b, t, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, cfg["vocab_size"], (b, t)).astype(np.int32),
            rng.integers(0, cfg["vocab_size"], (b * t,)).astype(np.int32))


def _close(ours, ref, tol=1e-4):
    ours, ref = np.asarray(ours), np.asarray(ref)
    assert np.abs(ours - ref).max() <= tol * max(1.0, np.abs(ref).max())


# -- the program against the reference ----------------------------------------

def test_logits_loss_and_every_leafs_gradient_match_the_reference():
    net, params, weights = _net_and_weights()
    toks, labels = _batch(TINY, 2, 20)        # 2.5 chunks of 8
    loss_block = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        logits = net(mx.nd.array(toks, dtype="int32"))
        loss = loss_block(mx.nd.reshape(logits, shape=(-1, 64)),
                          mx.nd.array(labels.astype(np.float32))).mean()
    loss.backward()
    ref_logits = _reference_forward(TINY, weights, toks)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda ws: reference.loss_fn(TINY, ws, jnp.asarray(toks),
                                     jnp.asarray(labels))))(weights)
    _close(logits.asnumpy(), ref_logits)
    _close(loss.asnumpy(), ref_loss)
    for (name, _), p, g in zip(ARCH.leaf_specs(TINY), params, ref_grads):
        _close(p.grad().asnumpy(), g)
        if name.endswith("router_bias"):
            # a leaf that takes no gradient from the loss
            assert not np.asarray(g).any() and not p.grad().asnumpy().any()


def _follow(step, cfg, batches):
    losses = [float(step.step(t, lab.astype(np.float32)))
              for t, lab in batches]
    return losses, [np.asarray(w) for w in step._weights]


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


def test_three_adamw_steps_match_the_reference_and_count_every_assignment():
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = _moe_counters()
    net, _, weights = _net_and_weights()
    net.hybridize()
    batches = [_batch(TINY, 2, 16, seed=s) for s in range(3)]
    step = parallel.JitTrainStep(
        _lm(net, 64), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {k: v for k, v in OPT.items() if k != "name"})
    losses, ours = _follow(step, TINY, batches)
    ref_losses, ref = _reference_steps(TINY, weights, batches)
    _close(losses, ref_losses)
    for (name, _), a, b, w0 in zip(ARCH.leaf_specs(TINY), ours, ref,
                                   weights):
        # Adam's first steps move every element by about the learning
        # rate: the change is compared, against its own size
        _close(a - np.asarray(w0), np.asarray(b) - np.asarray(w0), 2e-3)
    # the routing counts rode out of the step program: two expert layers,
    # 32 tokens x 3 experts a step, three steps; nothing fetched till now
    stats = step.step_stats()
    # the two Mamba-2 layers' chunk counts ride along (PR 37): 2 sequences
    # x 4 heads x 2 chunks of 8 a step, none of them in the Pallas kernels
    # at 8 channels a head
    scans = {k: stats.pop(k) for k in sorted(stats) if k.startswith("ssd")}
    assert sorted(scans) == ["ssd/0", "ssd/2", "ssd_kernel/0", "ssd_kernel/2"]
    assert [int(v[0]) for v in scans.values()] == [3 * 16, 3 * 16, 0, 0]
    assert sorted(stats) == ["moe/1/4", "moe/4/4"]
    for counts in stats.values():
        assert counts.dtype == np.uint32 and counts.shape == (4 + 3,)
        assert counts[-3] == 3 * 32 * 3 and counts[-2] == 0
        assert 0 < counts[:4].sum() < counts[-3]
        # 96 assignments a layer and step are one tile of the sorted layout
        assert counts[-1] == 3 * 96
    after = _moe_counters()
    assert after["total"] - before["total"] == 2 * 3 * 32 * 3
    assert after["walked"] - before["walked"] == 2 * 3 * 96
    assert after["dropped"] == before["dropped"] == 0
    held = sum(int(c[:4].sum()) for c in stats.values())
    assert after["held"] - before["held"] == held
    assert {lab["layer"] for lab in after["labels"]} >= {"1", "4"}
    assert {lab["expert"] for lab in after["labels"]} <= {"4", "5", "6", "7"}


def _moe_counters():
    snap = metrics.snapshot()

    def series(name):
        return snap.get(name, {}).get("series", [])
    held = series("mxnet_moe_assignments_held_total")
    return {"total": sum(s["value"] for s in series(
                "mxnet_moe_assignments_total")),
            "dropped": sum(s["value"] for s in series(
                "mxnet_moe_dropped_total")),
            "walked": sum(s["value"] for s in series(
                "mxnet_moe_sorted_rows_walked_total")),
            "held": sum(s["value"] for s in held),
            "labels": [s["labels"] for s in held]}


def test_trains_under_amp_with_the_routing_and_the_scan_in_float32():
    net, _, weights = _net_and_weights()
    net.hybridize()
    batches = [_batch(TINY, 2, 16, seed=s) for s in range(3)]
    ref_losses, _ = _reference_steps(TINY, weights, batches)
    seen = {}
    orig = amp.transform_inputs

    def spy(op_name, datas):
        out = orig(op_name, datas)
        seen.setdefault(op_name, [getattr(d, "dtype", None) for d in out])
        return out
    amp.init("bfloat16")
    amp.transform_inputs = spy
    try:
        step = parallel.JitTrainStep(
            _lm(net, 64), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
            {k: v for k, v in OPT.items() if k != "name"})
        losses, _ = _follow(step, TINY, batches)
    finally:
        amp.transform_inputs = orig
        amp.turn_off()
    assert np.allclose(losses, ref_losses, rtol=2e-2)
    f32, bf16 = jnp.dtype("float32"), jnp.dtype("bfloat16")
    for op in ("_contrib_moe_router_topk", "_contrib_ssd_scan",
               "_contrib_causal_conv1d"):
        assert op in amp.lists.FP32_OPS
        assert all(d == f32 for d in seen[op]), (op, seen[op])
    # the experts' products in bfloat16, the routing weights as they came
    data, idx, weight, up, down = seen["_contrib_moe_grouped_ffn"]
    assert (data, up, down) == (bf16, bf16, bf16)
    assert idx == jnp.dtype("int32") and weight == f32


# -- the scan against the recurrence --------------------------------------------

def _scan_inputs(t, b=2, h=4, p=8, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)
    dt_bias = np.log(np.expm1(rng.uniform(0.001, 0.1, (1, h))))
    vals = (rng.normal(size=(b, t, h, p)), rng.normal(size=(b, t, h)),
            np.log(rng.uniform(1, 16, (1, h))), rng.normal(size=(b, t, g, n)),
            rng.normal(size=(b, t, g, n)), rng.normal(size=(h,)), dt_bias)
    return tuple(jnp.asarray(v, jnp.float32) for v in vals)


@pytest.mark.parametrize("chunks", [1, 2.5, 4])
def test_chunked_scan_matches_the_token_by_token_recurrence(chunks):
    chunk = 16
    args = _scan_inputs(int(chunks * chunk))
    with jax.default_matmul_precision("highest"):
        y = ssm.ssd_scan(*args, chunk=chunk)
        want = ssm.ssd_recurrence(*args)
        _close(y, want, 1e-5)

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))
        # each gradient one program (one primitive at a time, each is
        # compiled and dispatched alone)
        got = jax.jit(jax.grad(
            loss(functools.partial(ssm.ssd_scan, chunk=chunk)),
            argnums=tuple(range(7))))(*args)
        ref = jax.jit(jax.grad(loss(ssm.ssd_recurrence),
                               argnums=tuple(range(7))))(*args)
    for a, b in zip(got, ref):
        _close(a, b, 1e-5)


def test_the_scan_is_an_operator_and_the_convolution_is_causal():
    args = _scan_inputs(12)
    y = mx.nd.contrib.ssd_scan(*[mx.nd.array(np.asarray(a)) for a in args],
                               chunk=8)
    _close(y.asnumpy(), ssm.ssd_recurrence(*args), 1e-5)
    x = np.zeros((1, 6, 2), np.float32)
    x[0, 2] = [1.0, 2.0]                       # an impulse at token 2
    w = np.array([[1, 2, 3, 4], [0, 0, 0, 1]], np.float32)
    out = mx.nd.contrib.causal_conv1d(
        mx.nd.array(x), mx.nd.array(w), mx.nd.array([0.5, 0.0])).asnumpy()
    # tap K-1 is the present, tap 0 the token K-1 back; nothing before 2
    assert out[0, :, 0].tolist() == [0.5, 0.5, 4.5, 3.5, 2.5, 1.5]
    assert out[0, :, 1].tolist() == [0, 0, 2, 0, 0, 0]


# -- routing ----------------------------------------------------------------------

def _moe_inputs(s=32, d=16, e=16, f=24, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(v, jnp.float32) for v in (
        rng.normal(size=(s, d)), rng.normal(size=(e, d)),
        rng.normal(0, 0.1, (e,)), rng.normal(size=(e, f, d)) * 0.3,
        rng.normal(size=(e, d, f)) * 0.3))


def _dense_experts(x, idx, w, up, down):
    """Every expert on every token, weighted where chosen."""
    hid = jnp.square(jax.nn.relu(jnp.einsum("sd,efd->sef", x, up)))
    gate = jnp.sum(jax.nn.one_hot(idx, up.shape[0]) * w[..., None], axis=1)
    return jnp.einsum("sef,edf,se->sd", hid, down, gate)


def test_every_token_on_one_held_expert_loses_nothing():
    x, router, bias, up, down = _moe_inputs()
    # skewed scores: expert 5 wins every token by far, then 6 and 7
    bias = bias.at[5].set(30.0).at[6].set(20.0).at[7].set(10.0)
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.5)
    assert (np.asarray(idx) == [5, 6, 7]).all()
    assert np.allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)
    out, counts = moe_ops.grouped_ffn(x, idx, w, up[4:8], down[4:8], first=4)
    assert counts.tolist() == [0, 32, 32, 32, 96, 0, 96]  # no capacity
    _close(out, _dense_experts(x, idx, w, up, down))
    # and one held expert alone takes all of its 32 rows
    out, counts = moe_ops.grouped_ffn(x, idx, w, up[5:6], down[5:6], first=5)
    assert counts.tolist() == [32, 96, 0, 96]
    only5 = jnp.where(idx == 5, w, 0.0)
    _close(out, _dense_experts(x, idx, only5, up, down))


def _poisoned(monkeypatch):
    """Every array in the sorted layout that a kernel of ``grouped_ffn``
    writes, forward and backward, with NaN in every row past the landed
    count: the gathered rows and the result's gradient (``rows_take``, and
    its dots), both products and their rows' gradients
    (``grouped_matmul``), the activation and its gradient
    (``rows_relu2``).  Returns the names of what was poisoned."""
    seen = []

    def poison(x, landed, name):
        seen.append(name)
        rows = jnp.arange(x.shape[0]).reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(rows < landed, x, jnp.nan)

    real = moe_ops.grouped_matmul

    @jax.custom_vjp
    def product(rows, w, sizes):
        return poison(real(rows, w, sizes), jnp.sum(sizes), "product")

    def fwd(rows, w, sizes):
        return product(rows, w, sizes), (rows, w, sizes)

    def bwd(res, g):
        rows, w, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), rows, w)
        d_rows, d_w = vjp(g)
        return poison(d_rows, jnp.sum(sizes), "product's rows"), d_w, None
    product.defvjp(fwd, bwd)
    take, relu2 = moe_ops.rows_take, moe_ops.rows_relu2

    def rows_take(src, token, order, weight, count, m, dtype, other=None):
        out = take(src, token, order, weight, count, m, dtype, other=other)
        if other is None:
            return poison(out, count[0], "take")
        return tuple(poison(o, count[0], "take with dots") for o in out)

    def rows_relu2(hid, count, grad=None):
        return poison(relu2(hid, count, grad=grad), count[0],
                      "relu2" if grad is None else "relu2's gradient")
    monkeypatch.setattr(moe_ops, "grouped_matmul", product)
    monkeypatch.setattr(moe_ops, "rows_take", rows_take)
    monkeypatch.setattr(moe_ops, "rows_relu2", rows_relu2)
    return seen


def _routed(load):
    """Inputs, and a routing of 32 tokens x 3 over experts 4..7 held:
    ``(x, w, up, down, idx, mine)``."""
    x, router, bias, up, down = _moe_inputs()
    if load in ("few", "most"):
        # scores that keep most tokens off experts 4..7, or draw them there
        bias = bias.at[4:8].set(-0.25 if load == "few" else 0.25)
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.5)
    if load == "none":
        idx = jnp.where((idx >= 4) & (idx < 8), idx + 4, idx)
    elif load == "all":                 # every token on three of the held
        idx = 4 + (jnp.arange(32)[:, None] + jnp.arange(3)[None, :]) % 4
    elif load == "one_expert":          # one of them takes every token
        idx = jnp.broadcast_to(jnp.array([6, 0, 12]), (32, 3))
    elif load == "a_tile_shared":
        # of the 96 rows (one tile) expert 4 takes 32 and expert 7 takes 32
        idx = jnp.broadcast_to(jnp.array([4, 1, 7]), (32, 3))
    mine = (idx >= 4) & (idx < 8)
    return x, w, up, down, idx.astype(jnp.int32), mine


def _against_the_dense_experts(load, after_forward=lambda: None):
    """``grouped_ffn``'s result and every gradient against the dense
    experts at ``_routed(load)``; returns the counts and how many landed."""
    x, w, up, down, idx, mine = _routed(load)

    def ours(x, w, up, down):
        out, counts = moe_ops.grouped_ffn(x, idx, w, up[4:8], down[4:8],
                                          first=4)
        return jnp.sum(jnp.sin(out)), counts

    def dense(x, w, up, down):
        return jnp.sum(jnp.sin(_dense_experts(
            x, idx, jnp.where(mine, w, 0.0), up, down)))
    value, counts = ours(x, w, up, down)
    _close(value, dense(x, w, up, down))
    after_forward()
    got = jax.grad(ours, argnums=(0, 1, 2, 3), has_aux=True)(
        x, w, up, down)[0]
    want = jax.grad(dense, argnums=(0, 1, 2, 3))(x, w, up, down)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b)
    landed = int(mine.sum())
    assert counts[:4].sum() == landed and counts[-2] == 0
    return counts, landed


@pytest.mark.parametrize("load", ["none", "few", "most", "all", "one_expert",
                                  "a_tile_shared"])
def test_result_and_gradients_match_the_dense_experts_at_every_load(load):
    counts, landed = _against_the_dense_experts(load)
    assert {"none": landed == 0, "few": 0 < landed <= 24,
            "most": 24 < landed < 96, "all": landed == 96}.get(
                load, landed in (32, 64))
    assert counts[-1] == (96 if landed else 0)


@pytest.mark.parametrize("load", ["few", "most"])
def test_rows_past_the_last_group_are_never_read(monkeypatch, load):
    """The kernels of ``grouped_ffn`` do not visit the sorted rows past the
    last tile that holds a landed row, and the grouped products (``mx_gmm``,
    PR 31) not even the rest of that tile: those rows stay undefined (on the
    chip whatever the buffer held).  With NaN planted in every row past the
    landed count of every array that a kernel writes in the sorted layout
    (eight of them, forward and backward), the layer's result and every
    gradient still match the dense experts, whether few of the assignments
    land on the held experts or most of them: only kernels that walk the
    landed rows read such an array, and no undefined value meets
    arithmetic in either direction (PR 30: 0 * nan took the router's
    gradient, and with it every layer below, on the chip; the selects that
    stood beside the products since went with PR 33)."""
    seen = _poisoned(monkeypatch)

    def forward_only():
        assert sorted(set(seen)) == ["product", "relu2", "take"]
    _, landed = _against_the_dense_experts(load, forward_only)
    assert (0 < landed <= 24) if load == "few" else landed > 24
    assert sorted(set(seen)) == [
        "product", "product's rows", "relu2", "relu2's gradient", "take",
        "take with dots"]


def test_the_dropped_count_sees_a_row_kept_out_of_the_products(monkeypatch):
    """``counts[-2]`` is the assignments the indices send here less the
    rows ``_computed`` lets into the products: with the ``dropped`` fault
    planted there (a capacity of the held experts' mean load) it reads
    what the capacity left out, and the result lacks exactly those."""
    sys.path.insert(0, os.path.join(CHIP, "tests"))
    import faults_nemotron_h
    x, router, bias, up, down = _moe_inputs()
    bias = bias.at[5].set(30.0)               # expert 5 takes every token
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.5)
    flat = np.asarray(idx).reshape(-1)
    sizes = [int((flat == e).sum()) for e in range(4, 8)]
    capacity = sum(sizes) // 4
    assert sizes[1] == 32 > capacity
    _, counts = moe_ops._grouped_ffn(x, idx, w, up[4:8], down[4:8], 4)
    assert counts.tolist() == sizes + [96, 0, 96]
    monkeypatch.setattr(moe_ops, "_computed", moe_ops._computed)  # put back
    faults_nemotron_h.plant("dropped")
    out, counts = moe_ops._grouped_ffn(x, idx, w, up[4:8], down[4:8], 4)
    # an expert's queue is in token order: it keeps its first ``capacity``
    keep = np.zeros(flat.shape, bool)
    for e in range(4, 8):
        keep[(flat == e).nonzero()[0][:capacity]] = True
    assert counts.tolist() == sizes + [96, sum(sizes) - int(keep.sum()),
                                       96]
    assert counts[-2] >= 32 - capacity
    _close(out, _dense_experts(
        x, idx, jnp.where(keep.reshape(idx.shape), w, 0.0), up, down))


def test_a_score_tie_resolves_as_in_the_reference():
    x, router, bias, _, _ = _moe_inputs()
    router = router.at[9].set(router[3]).at[12].set(router[3])   # three ties
    bias = jnp.zeros_like(bias)
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.5)
    cfg = dict(TINY, num_experts_per_tok=3)
    scores = jax.nn.sigmoid(jnp.einsum(
        "sd,ed->se", x, router, precision=jax.lax.Precision.HIGHEST))
    want_idx, want_w = ARCH.route(cfg, scores, bias)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    _close(w, want_w, 1e-6)
    # where the tied experts are chosen at all, the lower index came first
    rows = np.asarray(idx)
    assert any(3 in r for r in rows)
    for r in rows:
        tied = [e for e in r if e in (3, 9, 12)]
        assert tied == sorted(tied) and tied == [3, 9, 12][:len(tied)]


def test_the_bias_moves_the_choice_and_not_the_weight():
    x, router, bias, _, _ = _moe_inputs()
    idx0, _ = moe_ops.router_topk(x, router, jnp.zeros_like(bias), k=3)
    big = jnp.zeros_like(bias).at[11].set(5.0)
    idx, w = moe_ops.router_topk(x, router, big, k=3, normalize=False)
    assert (np.asarray(idx)[:, 0] == 11).all()
    assert not (np.asarray(idx0)[:, 0] == 11).all()
    s = jax.nn.sigmoid(jnp.einsum("sd,ed->se", x, router,
                                  precision=jax.lax.Precision.HIGHEST))
    _close(w[:, 0], s[:, 11], 1e-6)
    g = jax.grad(lambda b: jnp.sum(moe_ops.router_topk(x, router, b, k=3)[1]
                                   ))(big)
    assert not np.asarray(g).any()


def test_a_forced_balance_chooses_alike_whatever_the_weights():
    """``balance_seed`` (Megatron-LM's ``--moe-router-force-load-
    balancing``): the choice comes from fixed pseudo-random numbers, the
    same for any weights, bias and data; every expert's expected load is
    the same; the weights are still the model's scores at the chosen, and
    the reference's ``route`` chooses the same."""
    x, router, bias, _, _ = _moe_inputs(s=2048)
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.5,
                                 balance_seed=4)
    skew = router.at[5].set(50.0 * jnp.sign(x.mean(0)))
    idx2, _ = moe_ops.router_topk(2 * x + 1, skew, bias + 3, k=3,
                                  balance_seed=4)
    assert (np.asarray(idx) == np.asarray(idx2)).all()
    other, _ = moe_ops.router_topk(x, router, bias, k=3, balance_seed=5)
    assert (np.asarray(idx) != np.asarray(other)).any()
    load = np.bincount(np.asarray(idx).ravel(), minlength=16)
    assert load.max() <= 1.2 * load.mean()          # 384 +- 18 an expert
    free, _ = moe_ops.router_topk(2 * x + 1, skew, bias, k=3)
    assert np.bincount(np.asarray(free).ravel(),
                       minlength=16).max() > 3 * load.mean()
    cfg = dict(TINY, num_experts_per_tok=3,
               moe_router_force_load_balancing=True)
    scores = jax.nn.sigmoid(jnp.einsum(
        "sd,ed->se", x, router, precision=jax.lax.Precision.HIGHEST))
    want_idx, want_w = ARCH.route(cfg, scores, bias, 4)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    _close(w, want_w, 1e-6)
    g = jax.grad(lambda r: jnp.sum(jnp.sin(moe_ops.router_topk(
        x, r, bias, k=3, normalize=False, balance_seed=4)[1])))(router)
    assert np.asarray(g).any()          # the router still learns its scores


def test_the_model_under_a_forced_balance_matches_the_reference():
    cfg = dict(TINY, moe_router_force_load_balancing=True)
    net, params, weights = _net_and_weights(cfg)
    toks, labels = _batch(cfg, 2, 20)
    loss_block = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        logits = net(mx.nd.array(toks, dtype="int32"))
        loss = loss_block(mx.nd.reshape(logits, shape=(-1, 64)),
                          mx.nd.array(labels.astype(np.float32))).mean()
    loss.backward()
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda ws: reference.loss_fn(cfg, ws, jnp.asarray(toks),
                                     jnp.asarray(labels))))(weights)
    _close(logits.asnumpy(), _reference_forward(cfg, weights, toks))
    _close(loss.asnumpy(), ref_loss)
    for p, g in zip(params, ref_grads):
        _close(p.grad().asnumpy(), g)
    free = _reference_forward(TINY, weights, toks)
    assert np.abs(np.asarray(free) - logits.asnumpy()).max() > 1e-3


def test_the_share_is_the_models():
    """16 experts over 4 shares of 4: the routed parts that the four
    shares compute, with the shared expert counted once, add up to the
    uncut reference layer."""
    whole = dict(TINY, hybrid_override_pattern="E", num_hidden_layers=1,
                 n_routed_experts=16, held_experts_first=0)
    weights = _published_ranges(whole, reference.make_weights(whole, 9), 9)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(whole)], weights))
    lw = {k[len("layer0."):]: v for k, v in w.items()
          if k.startswith("layer0.")}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    ein = reference._einsum("float32")
    uncut = ARCH._experts(whole, lw, u, ein)
    shared = ein("btf,if->bti", ARCH._relu2(
        ein("bti,fi->btf", u, lw["shared_up"])), lw["shared_down"])
    flat = u.reshape(24, 32)
    idx, wt = moe_ops.router_topk(flat, lw["router"], lw["router_bias"], k=3,
                                  scale=2.5)
    total, landed = shared.reshape(24, 32), 0
    for first in (0, 4, 8, 12):
        part, counts = moe_ops.grouped_ffn(
            flat, idx, wt, lw["up"][first:first + 4],
            lw["down"][first:first + 4], first=first)
        # the reference, given the same share, computes the same part
        share = dict(whole, n_routed_experts=4, held_experts_first=first)
        ref_part = ARCH._experts(share, dict(
            lw, up=lw["up"][first:first + 4],
            down=lw["down"][first:first + 4]), u, ein) - shared
        _close(part, ref_part.reshape(24, 32))
        total = total + part
        landed += int(counts[:4].sum())
        assert counts[-2] == 0
    assert landed == 24 * 3            # every assignment landed somewhere
    _close(total, uncut.reshape(24, 32))


def test_the_old_top1_layer_is_still_handed_out():
    from mxnet_tpu.parallel import moe

    assert moe.router_topk is moe_ops.router_topk
    assert moe.grouped_ffn is moe_ops.grouped_ffn
    assert callable(moe.moe_ffn) and callable(moe.router_top1)
    for name in ("_contrib_moe_router_topk", "_contrib_moe_grouped_ffn",
                 "_contrib_ssd_scan", "_contrib_causal_conv1d"):
        assert name in mx.ops.registry.list_ops()


# -- the count ------------------------------------------------------------------------

def _cell_cfg():
    return json.load(open(os.path.join(
        CHIP, "configs", "nemotron_h_30b_p7_e8.json")))


def test_the_configuration_counts_as_published_and_as_cut():
    cfg = _cell_cfg()
    pub = cfg["published"]
    whole = dict(cfg, num_hidden_layers=pub["num_hidden_layers"],
                 hybrid_override_pattern=pub["hybrid_override_pattern"],
                 n_routed_experts=pub["n_routed_experts"],
                 vocab_size=pub["vocab_size"])
    assert ARCH.param_count(whole) == pub["parameters"] == 31_577_940_288
    assert ARCH.param_count(cfg) == 528_093_120
    by_kind = {k: sum(int(np.prod(s)) for _, s in ARCH._layer_specs(cfg, k))
               + cfg["hidden_size"] for k in "ME*"}
    assert by_kind == {"M": 38_744_896, "E": 100_125_440, "*": 23_399_040}
    assert cfg["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][:7] == "MEMEM*E"
    # every width is the published one: only the listed keys differ
    assert sorted(cfg["reduced"]) == sorted(
        k for k in pub if k != "parameters" and cfg[k] != pub[k])


def test_the_programs_model_at_the_published_sizes_counts_the_same():
    """Shapes alone: nothing is initialised."""
    from mxnet_tpu.gluon.model_zoo import nemotron_h

    net = nemotron_h.nemotron_h_30b_a3b()
    shapes = [p.shape for p in net.collect_params().values()]
    assert sum(int(np.prod(s)) for s in shapes) == 31_577_940_288
    cfg = _cell_cfg()
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items() if k != "parameters"})
    assert [tuple(s) for s in shapes] == \
        [s for _, s in ARCH.leaf_specs(whole)]


def test_flops_a_token_follow_the_stated_rule():
    cfg = _cell_cfg()
    h = 2688
    mamba = 10304 * h + h * 4096
    attn = 2 * 4096 * h + 2 * 256 * h
    moe = 128 * h + 2 * h * 3712 + int(8 * 2 * h * 1856 * 6 / 128)
    scan = (8 * 2 * 128 * 128 + 64 * 2 * 64 * 128) // 2 + 2 * 64 * 2 * 64 * 128
    want = 6 * (3 * mamba + attn + 3 * moe + 16384 * h) \
        + 3 * (3 * scan + 4 * 2048 * 4096 // 2)
    assert ARCH.train_flops_per_token(cfg, 2048) == want
    fwd, bwd = ARCH.ssd_calls(cfg, 2, 2048)
    assert fwd["flops"] == 4096 * scan and bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4096 * 4 * (4096 + 2048 + 64 + 4096)
    calls = ARCH.grouped_calls(cfg, 1536)
    assert len(calls) == 6 and {c["flops"] for c in calls} == \
        {2 * 1536 * h * 1856}
    assert ARCH.grouped_calls(cfg, 0)[0]["flops"] == 0


# -- planted faults come out not correct ------------------------------------------------

@pytest.mark.parametrize("fault", ["no_carry", "seventh_expert",
                                   "not_normalised", "dropped"])
def test_a_planted_fault_comes_out_not_correct(fault, rehearsal_env):
    """Through the benchmark's own rehearsal of the cell at its tiny size
    (``run.py`` -> driver -> ``compare.train_numbers`` -> the cell's
    ``tiny.limits``), the fault planted under it in the program."""
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tests", "faults_nemotron_h.py"),
         fault, "--workload", CELL, "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=rehearsal_env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < compare.NEVER for v, _ in doc["check"].values())
