"""Kimi Linear (Kimi Delta Attention + NoPE latent attention over routed
SwiGLU experts): the program (``gluon.model_zoo.kimi_linear``,
``ops/kda.py``, the flash kernels with values narrower than keys,
``grouped_ffn(activation="swiglu")``) against the benchmark's plain
reference (``benchmark/chip/archs/kimi_linear.py``) on seeded weights at
tiny widths, on the CPU.  This file holds the operators, the shares and the
counts; ``test_kimi_linear_model.py`` the whole model (a file of its own, so
that the two run side by side).

Tolerances.  Without AMP both sides are float32 and differ only in the
order of their sums (chunked against token-by-token delta rule, grouped
against dense experts): 1e-4 of the largest value.  Under
``amp.init('bfloat16')`` every matrix product rounds its operands to 8 bits
of mantissa: 2e-2 of the loss.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu.ops import kda
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops.pallas_kernels import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
sys.path.insert(0, CHIP)

import archs  # noqa: E402
import reference  # noqa: E402

TINY = dict(
    model_type="kimi_linear", hidden_size=32, num_hidden_layers=5,
    first_k_dense_replace=1, moe_layer_freq=1,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=4, head_dim=8,
                            short_conv_kernel_size=4),
    num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
    moe_intermediate_size=24, num_shared_experts=1, num_experts=4,
    router_num_experts=16, held_experts_first=4, num_experts_per_token=3,
    routed_scaling_factor=2.446, moe_renormalize=True, rms_norm_eps=1e-5,
    vocab_size=64, kda_chunk_size=16)
OPT = {"name": "adamw", "learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.1}
ARCH = archs.load("kimi_linear")
EIN = reference._einsum("float32")


def _published_ranges(cfg, weights, seed):
    """``A`` in [1, 16] and ``dt_bias`` with ``softplus`` in [0.001, 0.1],
    as the model is published (``g`` down to -1.6 a token), and a drawn
    score-correction bias, where the benchmark's initialiser draws +-0.4
    and ones."""
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


def _net_and_weights(cfg=TINY, seed=5):
    weights = _published_ranges(cfg, reference.make_weights(cfg, seed), seed)
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


def _close(ours, ref, tol=1e-4):
    ours, ref = np.asarray(ours), np.asarray(ref)
    assert np.isfinite(ours).all()
    assert np.abs(ours - ref).max() <= tol * max(1.0, np.abs(ref).max())


# -- the delta rule: chunked against token by token -------------------------------

def _kda_inputs(t, b=2, h=3, d=8, e=8, seed=0, g_low=-1.6, g_high=0.0):
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, b, t, h, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q, k, rng.normal(size=(b, t, h, e)),
        rng.uniform(g_low, g_high, (b, t, h, d)),
        rng.uniform(0, 1, (b, t, h))))


def _scan_against(other, t, chunk, **kw):
    args = _kda_inputs(t, **kw)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape),
                    jnp.float32)
    _close(kda.kda_scan(*args, chunk=chunk), other(*args))
    got = jax.grad(lambda *a: jnp.sum(kda.kda_scan(*a, chunk=chunk) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(other(*a) * w),
                    argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b)


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("t", [32, 37, 5])
def test_chunked_delta_rule_matches_the_recurrence(t, chunk):
    # whole chunks, 2.3 (or 1.2) chunks, and less than a sub-chunk
    _scan_against(kda.kda_recurrence, t, chunk)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_delta_rule_matches_the_references_recurrence(chunk):
    _scan_against(lambda *a: ARCH._delta_rule(*a, EIN), 40, chunk,
                  e=12)        # values wider than keys


@pytest.mark.parametrize("chunk", [32, 64])
def test_the_published_decay_range_overflows_nothing(chunk):
    # g = -1.6 on every channel and token, 128 tokens: a running sum of
    # -102 inside a chunk of 64, past what float32's exp holds (-88); and
    # a mix of channels that decay at once and channels that never do
    _scan_against(kda.kda_recurrence, 128, chunk, b=1, g_low=-1.6,
                  g_high=-1.6)
    args = list(_kda_inputs(128, b=1))
    args[3] = jnp.where(jnp.arange(8) % 2 == 0, -1.6, -1e-4) \
        * jnp.ones_like(args[3])
    out = kda.kda_scan(*args, chunk=chunk)
    _close(out, kda.kda_recurrence(*args))
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(kda.kda_scan(
        *a, chunk=chunk))), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(kda.kda_recurrence(*a))),
                    argnums=range(5))(*args)
    for a, b in zip(grads, want):
        _close(a, b)


def test_the_delta_rule_overwrites_a_key():
    # one key written twice at beta = 1 and no decay: the second value
    # replaces the first, where a plain linear attention would add them
    k = jnp.zeros((1, 2, 1, 4)).at[..., 0].set(1.0)
    v = jnp.asarray([[[[1.0, 2.0]], [[5.0, 7.0]]]])
    out = kda.kda_scan(k, k, v, jnp.zeros_like(k), jnp.ones((1, 2, 1)))
    _close(out[0, :, 0], [[1.0, 2.0], [5.0, 7.0]])


def test_the_scan_is_an_operator_and_float32_under_amp():
    assert "_contrib_kda_scan" in mx.ops.registry.list_ops()
    assert "_contrib_kda_attention" in mx.ops.registry.list_ops()
    assert "_contrib_kda_scan" in amp.lists.FP32_OPS
    q, k, v, g, beta = _kda_inputs(20)
    out = mx.nd.contrib.kda_scan(*(mx.nd.array(np.asarray(x))
                                   for x in (q, k, v, g, beta)), chunk=16)
    _close(out.asnumpy(), kda.kda_recurrence(q, k, v, g, beta))
    raw = np.random.default_rng(3).normal(size=(2, 6, 12)).astype(np.float32)
    a_log = np.log(np.array([[1.0, 4.0, 16.0]], np.float32))
    gate = np.asarray(kda.kda_gate(jnp.asarray(raw), jnp.asarray(a_log),
                                   jnp.full((3, 4), 0.2)))
    want = -np.array([1.0, 4.0, 16.0])[:, None] \
        * np.log1p(np.exp(raw + 0.2)).reshape(2, 6, 3, 4)
    _close(gate, want)
    assert gate.shape == (2, 6, 3, 4) and (gate < 0).all()
    assert kda.kda_chunks(4096, 64) == 64 and kda.kda_chunks(37, 16) == 3
    assert kda.kda_chunks(37, 64) == 1        # one chunk of 64 holds it


def test_heads_in_groups_give_what_all_heads_at_once_give(monkeypatch):
    """``kda_attention`` runs ``HEADS_AT_ONCE`` heads at a time: two, four
    (all) and three (no divisor of four: all at once) give one result and
    one gradient."""
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 20, 4, 8
    wide = [jnp.asarray(rng.normal(size=(b, t, h * d)), jnp.float32)
            for _ in range(5)]
    beta = jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32)
    taps = [jnp.asarray(rng.normal(size=(h * d, 4)) * 0.5, jnp.float32)
            for _ in range(3)]
    rest = (jnp.asarray(rng.uniform(-0.5, 0.5, (1, h)), jnp.float32),
            jnp.asarray(rng.uniform(-0.5, 0.5, (h, d)), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, (d,)), jnp.float32))
    q, k, v, decay, gate = wide
    args = (q, k, v, decay, beta, gate, *taps, *rest)

    def value_and_grads(at_once):
        monkeypatch.setattr(kda, "HEADS_AT_ONCE", at_once)
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(kda.kda_attention(*a, chunk=16))),
            argnums=range(12))(*args)
    whole, grads = value_and_grads(4)
    for at_once in (2, 3, 1):
        value, other = value_and_grads(at_once)
        _close(value, whole, 1e-6)
        for a, g in zip(other, grads):
            assert a.shape == g.shape
            _close(a, g, 1e-5)


def test_the_mixers_operator_keeps_its_inputs_and_nothing_wider():
    """``kda_attention`` under bfloat16 inputs: float32 out, bfloat16
    cotangents back, and the residuals of its backward pass are its inputs
    (no float32 array of a projection's size is kept)."""
    rng = np.random.default_rng(0)
    b, t, h, d = 1, 24, 2, 8
    wide = [jnp.asarray(rng.normal(size=(b, t, h * d)), jnp.bfloat16)
            for _ in range(5)]
    beta = jnp.asarray(rng.normal(size=(b, t, h)), jnp.bfloat16)
    taps = [jnp.asarray(rng.normal(size=(h * d, 4)) * 0.5, jnp.float32)
            for _ in range(3)]
    rest = (jnp.zeros((1, h)), jnp.zeros((h, d)), jnp.ones((d,)))
    q, k, v, decay, gate = wide

    def f(q, k, v, decay, beta, gate):
        return kda.kda_attention(q, k, v, decay, beta, gate, *taps, *rest,
                                 chunk=16)
    out, vjp = jax.vjp(f, q, k, v, decay, beta, gate)
    assert out.dtype == jnp.float32 and out.shape == (b, t, h * d)
    assert all(g.dtype == jnp.bfloat16 for g in vjp(jnp.ones_like(out)))
    kept = [x for x in jax.tree_util.tree_leaves(vjp)
            if hasattr(x, "shape") and x.size >= b * t * h * d]
    assert len(kept) == 5 and all(x.dtype == jnp.bfloat16 for x in kept)


# -- latent attention: values narrower than keys through the flash kernels ----------

def _plain_attention(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    t = q.shape[2]
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), s,
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("blocks", [None, (16, 8), (8, 16)],
                         ids=["xla", "flash_16_8", "flash_8_16"])
def test_attention_with_values_narrower_than_keys(blocks):
    # keys of 12 channels, values of 8: the kernel (explicit blocks) and
    # the XLA side of the switch (a short sequence, no blocks)
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(2, 3, 32, 12)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(2, 3, 32, 8)), jnp.float32)
            for _ in range(2))
    kw = {} if blocks is None else {"block_q": blocks[0],
                                    "block_k": blocks[1]}

    def ours(q, k, v):
        return flash_attention(q, k, v, scale=12 ** -0.5, causal=True, **kw)
    out = ours(q, k, v)
    assert out.shape == (2, 3, 32, 8)
    _close(out, _plain_attention(q, k, v, 12 ** -0.5), 2e-4)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_attention(
        *a, 12 ** -0.5) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, 2e-4)


def _one_block(cls_args, kind, seed=3):
    """A mixer of the program and the reference's weights for it."""
    cfg = dict(TINY, num_hidden_layers=1, first_k_dense_replace=1,
               linear_attn_config=dict(
                   TINY["linear_attn_config"],
                   kda_layers=[1] if kind == "kda" else [],
                   full_attn_layers=[1] if kind == "mla" else []))
    weights = _published_ranges(cfg, reference.make_weights(cfg, seed), seed)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(cfg)], weights))
    return cfg, {k[len("layer1.mixer."):]: v for k, v in w.items()
                 if k.startswith("layer1.mixer.")}


@pytest.mark.parametrize("t", [24, 520], ids=["xla_side", "flash_side"])
def test_the_mla_mixer_matches_the_reference(t):
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    cfg, lw = _one_block(None, "mla")
    mixer = kimi_linear.MLAMixer(32, 4, 16, 8, 4, 8)
    mixer.initialize(mx.init.Zero())
    for p, w in zip(mixer.collect_params().values(), lw.values()):
        p.set_data(w)
    u = np.random.default_rng(1).normal(size=(1, t, 32)).astype(np.float32)
    _close(mixer(mx.nd.array(u)).asnumpy(),
           ARCH._mla(cfg, lw, jnp.asarray(u), EIN), 2e-4)


def test_rotary_channels_are_plain_channels():
    # mla_use_nope: shifting the whole sequence by a token shifts the
    # result and changes nothing else (a rotary embedding would)
    cfg, lw = _one_block(None, "mla")
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, 12, 32)),
                    jnp.float32)
    padded = jnp.concatenate([u[:, :1], u], axis=1)
    a = ARCH._mla(cfg, lw, u, EIN)
    b = ARCH._mla(cfg, lw, padded, EIN)
    # token 0 twice attends to two copies of itself: the same result
    _close(b[:, 1], a[:, 0])


# -- SwiGLU experts in the grouped products --------------------------------------------

def _moe_inputs(s=32, d=16, e=16, f=24, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(v, jnp.float32) for v in (
        rng.normal(size=(s, d)), rng.normal(size=(e, d)),
        rng.normal(0, 0.1, (e,)), rng.normal(size=(e, 2 * f, d)) * 0.3,
        rng.normal(size=(e, d, f)) * 0.3))


def _dense_experts(x, idx, w, gate_up, down):
    """Every expert on every token, weighted where chosen."""
    hid = ARCH._swiglu(jnp.einsum("sd,efd->sef", x, gate_up))
    gate = jnp.sum(jax.nn.one_hot(idx, gate_up.shape[0]) * w[..., None],
                   axis=1)
    return jnp.einsum("sef,edf,se->sd", hid, down, gate)


def _routed(load):
    x, router, bias, gate_up, down = _moe_inputs()
    if load == "skewed":      # expert 5 wins every token, then 6 and 7
        bias = bias.at[5].set(30.0).at[6].set(20.0).at[7].set(10.0)
    idx, w = moe_ops.router_topk(x, router, bias, k=3, scale=2.446)
    if load == "an_expert_without_rows":
        idx = jnp.where(idx == 6, 12, idx)
    elif load == "none":
        idx = jnp.where((idx >= 4) & (idx < 8), idx + 4, idx)
    return x, w, gate_up, down, idx.astype(jnp.int32)


@pytest.mark.parametrize("load", ["free", "skewed", "an_expert_without_rows",
                                  "none"])
def test_swiglu_experts_match_the_dense_experts(load):
    x, w, gate_up, down, idx = _routed(load)
    mine = (idx >= 4) & (idx < 8)

    def ours(x, w, gate_up, down):
        out, counts = moe_ops.grouped_ffn(
            x, idx, w, gate_up[4:8], down[4:8], first=4,
            activation="swiglu")
        return jnp.sum(jnp.sin(out)), counts

    def dense(x, w, gate_up, down):
        return jnp.sum(jnp.sin(_dense_experts(
            x, idx, jnp.where(mine, w, 0.0), gate_up, down)))
    value, counts = ours(x, w, gate_up, down)
    _close(value, dense(x, w, gate_up, down))
    got = jax.grad(ours, argnums=(0, 1, 2, 3), has_aux=True)(
        x, w, gate_up, down)[0]
    want = jax.grad(dense, argnums=(0, 1, 2, 3))(x, w, gate_up, down)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        _close(a, b)
    per_expert = [int((idx == e).sum()) for e in range(4, 8)]
    assert counts[:4].tolist() == per_expert
    assert counts[4:].tolist() == [96, 0, 96 if sum(per_expert) else 0]
    if load == "skewed":
        assert per_expert == [0, 32, 32, 32]
    if load == "an_expert_without_rows":
        assert per_expert[2] == 0 and sum(per_expert) > 0


def test_an_unknown_activation_is_refused():
    x, w, gate_up, down, idx = _routed("free")
    with pytest.raises(ValueError, match="gelu"):
        moe_ops.grouped_ffn(x, idx, w, gate_up, down, activation="gelu")


def test_the_share_is_the_models():
    """16 experts over 4 shares of 4: the routed parts that the four
    shares compute, with the shared expert counted once, add up to the
    uncut reference layer."""
    whole = dict(TINY, num_hidden_layers=2, num_experts=16,
                 held_experts_first=0,
                 linear_attn_config=dict(TINY["linear_attn_config"],
                                         kda_layers=[1, 2],
                                         full_attn_layers=[]))
    weights = _published_ranges(whole, reference.make_weights(whole, 9), 9)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(whole)], weights))
    lw = {k[len("layer2.ffn."):]: v for k, v in w.items()
          if k.startswith("layer2.ffn.")}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    uncut = ARCH._moe(whole, lw, u, EIN)
    shared = EIN("btf,if->bti", ARCH._swiglu(
        EIN("bti,fi->btf", u, lw["shared_gate_up"])), lw["shared_down"])
    flat = u.reshape(24, 32)
    idx, wt = moe_ops.router_topk(flat, lw["router"], lw["router_bias"], k=3,
                                  scale=2.446)
    total, landed = shared.reshape(24, 32), 0
    for first in (0, 4, 8, 12):
        part, counts = moe_ops.grouped_ffn(
            flat, idx, wt, lw["gate_up"][first:first + 4],
            lw["down"][first:first + 4], first=first, activation="swiglu")
        # the reference, given the same share, computes the same part
        share = dict(whole, num_experts=4, held_experts_first=first)
        ref_part = ARCH._moe(share, dict(
            lw, gate_up=lw["gate_up"][first:first + 4],
            down=lw["down"][first:first + 4]), u, EIN) - shared
        _close(part, ref_part.reshape(24, 32))
        total = total + part
        landed += int(counts[:4].sum())
        assert counts[-2] == 0
    assert landed == 24 * 3            # every assignment landed somewhere
    _close(total, uncut.reshape(24, 32))


# -- the count ------------------------------------------------------------------------

def _cell_cfg():
    return json.load(open(os.path.join(
        CHIP, "configs", "kimi_linear_48b_p5_e8.json")))


def test_the_configuration_counts_as_published_and_as_cut():
    cfg = _cell_cfg()
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items() if k != "parameters"})
    count = ARCH.param_count(whole)
    assert count == pub["parameters"] == 49_122_681_728
    assert abs(count - 49.1e9) < 0.01 * 49.1e9
    assert count == sum(int(np.prod(s)) for _, s in ARCH.leaf_specs(whole))
    assert ARCH.param_count(cfg) == 602_434_432
    by_kind = {k: sum(int(np.prod(s)) for _, s in ARCH._specs(cfg, k))
               for k in ("kda", "mla", "mlp", "moe")}
    assert by_kind == {"kda": 39_514_272, "mla": 29_114_880,
                       "mlp": 63_700_992, "moe": 64_291_072}
    # the model's own first five layers, and every width the published one
    lin, pub_lin = cfg["linear_attn_config"], pub["linear_attn_config"]
    assert lin["kda_layers"] == [i for i in pub_lin["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == \
        [i for i in pub_lin["full_attn_layers"] if i <= 5]
    assert {k: v for k, v in lin.items() if not k.endswith("layers")} == \
        {k: v for k, v in pub_lin.items() if not k.endswith("layers")}
    assert sorted(cfg["reduced"]) == sorted(
        k for k in pub if k != "parameters" and cfg[k] != pub[k])
    for key in ("source", "assumed", "departures", "deployment", "tiny"):
        assert cfg[key]


def test_the_catalogs_numbers_are_kept():
    """Every number of the catalog's ``config`` stands in the file under
    the same key, but for the keys listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cfg = _cell_cfg()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"])


def test_the_programs_model_at_the_published_sizes_counts_the_same():
    """Shapes alone: nothing is initialised."""
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    net = kimi_linear.kimi_linear_48b_a3b()
    shapes = [p.shape for p in net.collect_params().values()]
    assert sum(int(np.prod(s)) for s in shapes) == 49_122_681_728
    cfg = _cell_cfg()
    whole = dict(cfg, **{k: v for k, v in cfg["published"].items()
                         if k != "parameters"})
    whole["router_num_experts"] = whole["num_experts"]
    assert [tuple(s) for s in shapes] == \
        [s for _, s in ARCH.leaf_specs(whole)]


def test_layers_have_to_be_named_once():
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    with pytest.raises(ValueError, match="once"):
        kimi_linear.kimi_linear_48b_a3b(full_attn_layers=[4, 8])
    with pytest.raises(ValueError, match="once"):
        ARCH.leaf_specs(dict(TINY, num_hidden_layers=6))


def test_flops_a_token_follow_the_stated_rule():
    cfg = _cell_cfg()
    h = 2304
    kda_mats = 4 * 4096 * h + 2 * (128 * h + 4096 * 128) + 32 * h \
        + 3 * 4096 * 4
    mla_mats = 6144 * h + 576 * h + 8192 * 512 + h * 4096
    moe_mats = 256 * h + 3 * h * 1024 + int(8 * 3 * h * 1024 * 8 / 256)
    mlp_mats = 3 * h * 9216
    delta = 32 * 3 * 2 * 128 * 128
    attention = 2 * 4096 * 32 * (192 + 128) // 2
    want = 6 * (4 * kda_mats + mla_mats + 4 * moe_mats + mlp_mats
                + 20480 * h) + 3 * (4 * delta + attention)
    assert ARCH.train_flops_per_token(cfg, 4096) == want
    fwd, bwd = ARCH.kda_calls(cfg, 1, 4096)
    assert fwd["flops"] == 4096 * delta and bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4096 * 32 * 4 * (4 * 128 + 1 + 128)
    f, dq, dkv = ARCH.mla_flash_calls(cfg, 1, 4096)
    qk, pv = 32 * 4096 * 4096 * 192, 32 * 4096 * 4096 * 128
    assert (f["flops"], dq["flops"], dkv["flops"]) == \
        (qk + pv, 2 * qk + pv, 2 * qk + 2 * pv)
    calls = ARCH.grouped_calls(cfg, 1024)
    assert [c["flops"] for c in calls] == \
        [2 * 1024 * h * 2048] * 3 + [2 * 1024 * h * 1024] * 3
    assert ARCH.grouped_calls(cfg, 0)[0]["flops"] == 0
