"""JoyAI-LLM Flash (rotary latent attention with a low-rank query in every
layer, over routed SwiGLU experts): the program (``kimi_linear.MLAMixer``
with ``q_lora_rank`` and ``rope_theta``, ``ops/rotary.py``, the flash kernels,
``grouped_ffn(activation="swiglu")``) against the benchmark's plain
reference (``benchmark/chip/archs/joyai_llm_flash.py``) on seeded weights at
tiny widths, on the CPU.  This file holds the operators, the rotation, the
shares and the counts; ``test_joyai_llm_flash_model.py`` the whole model and
the prediction module (a file of its own, so that the two run side by side).

Tolerances.  Without AMP both sides are float32 and differ only in the
order of their sums (flash blocks against whole rows, grouped against dense
experts): 1e-4 of the largest value (2e-4 through the kernels' online
softmax).  Under ``amp.init('bfloat16')`` every matrix product rounds its
operands to 8 bits of mantissa: 2e-2 of the loss.
"""
import contextlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon.model_zoo import joyai_llm_flash, kimi_linear
from mxnet_tpu.gluon.model_zoo.nemotron_h import _dense
from mxnet_tpu.ops import rotary
from mxnet_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
sys.path.insert(0, CHIP)

import archs  # noqa: E402
import reference  # noqa: E402

TINY = dict(
    model_type="joyai_llm_flash", hidden_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, moe_layer_freq=1, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rope_theta=32000000, rope_interleave=True,
    rope_scaling=None, intermediate_size=48, moe_intermediate_size=24,
    n_shared_experts=1, n_routed_experts=4, router_num_experts=16,
    held_experts_first=4, num_experts_per_tok=3, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-6, vocab_size=64,
    num_nextn_predict_layers=0, mtp_loss_weight=0.3)
OPT = {"name": "adamw", "learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8, "wd": 0.1}
ARCH = archs.load("joyai_llm_flash")
EIN = reference._einsum("float32")
MIXER = (32, 4, 16, 8, 4, 8, 1e-6)      # TINY's latent attention


def _drawn_bias(cfg, weights, seed):
    """A drawn score-correction bias, where the benchmark's initialiser
    gives ones (which move no choice)."""
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(0, 0.05, shape), jnp.float32)
            if name.endswith("router_bias") else w
            for (name, shape), w in zip(ARCH.leaf_specs(cfg), weights)]


def _net_and_weights(cfg=TINY, seed=5):
    weights = _drawn_bias(cfg, reference.make_weights(cfg, seed), seed)
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


def _mixer_weights(seed=3):
    """The reference's leaves of layer 0's mixer, by name."""
    cfg = dict(TINY, num_hidden_layers=1)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(cfg)],
                 reference.make_weights(cfg, seed)))
    return {k[len("layer0.mixer."):]: v for k, v in w.items()
            if k.startswith("layer0.mixer.")}


def _mixer(lw):
    mixer = kimi_linear.MLAMixer(*MIXER, q_lora_rank=24,
                                 rope_theta=TINY["rope_theta"])
    mixer.initialize(mx.init.Zero())
    for p, w in zip(mixer.collect_params().values(), lw.values()):
        assert p.shape == w.shape
        p.set_data(w)
    return mixer


# -- the rotation --------------------------------------------------------------------

def test_the_rotation_turns_adjacent_pairs_by_float32_angles():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 8)), jnp.float32)  # (B,T,H,r)
    cos, sin = rotary.rope_angles(5, 8, 32000000)
    ours = rotary.rotate_pairs(jnp.transpose(x, (0, 2, 1, 3)), cos, sin)
    _close(jnp.transpose(ours, (0, 2, 1, 3)), ARCH._rotary(x, 32000000),
           1e-6)
    # position 0 turns nothing; at position 1 pair 0 turns by one radian
    assert np.array_equal(np.asarray(ours[:, :, 0]), np.asarray(x[:, 0]))
    a, b = np.asarray(x[0, 1, 0, :2])
    np.testing.assert_allclose(
        np.asarray(ours[0, 0, 1, :2]),
        [a * np.cos(1) - b * np.sin(1), a * np.sin(1) + b * np.cos(1)],
        rtol=1e-5)
    # the slowest pair at theta 3.2e7: 3.2e7 ** (-6/8) rad a token, kept
    assert cos.dtype == sin.dtype == jnp.float32
    np.testing.assert_allclose(float(-sin[4, 6]),
                               np.sin(4 * 32000000.0 ** -0.75), rtol=1e-5)
    # bfloat16 in, bfloat16 out, rotated in float32
    half = rotary.rotate_pairs(x.astype(jnp.bfloat16)[:, :, 0], cos, sin)
    assert half.dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rotary_dim", [None, 4])
def test_the_rotary_operator_turns_the_last_channels(rotary_dim, dtype):
    # (B, H, T, D) as the mixer hands it over: the last ``rotary_dim``
    # channels turned, the rest as they were; its gradient is the reverse
    # rotation of the cotangent; the data's dtype in and out
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 3, 7, 12)), dtype)
    w = jnp.asarray(rng.normal(size=(2, 3, 7, 12)), jnp.float32)
    width = rotary_dim or 12

    def ours(x):
        return rotary.rotary_embedding(x, theta=32000000.0,
                                       rotary_dim=rotary_dim)

    def ref(x):
        x = jnp.moveaxis(x.astype(jnp.float32), 2, 1)      # (B, T, H, D)
        out = jnp.concatenate([x[..., :12 - width],
                               ARCH._rotary(x[..., 12 - width:],
                                            32000000)], -1)
        return jnp.moveaxis(out, 1, 2)
    out = ours(x)
    assert out.dtype == x.dtype and out.shape == x.shape
    tol = 1e-6 if dtype == "float32" else 1e-2
    _close(out.astype(jnp.float32), ref(x), tol)
    assert np.array_equal(np.asarray(out[..., :12 - width], np.float32),
                          np.asarray(x[..., :12 - width], np.float32))
    got = jax.grad(lambda x: jnp.sum(ours(x).astype(jnp.float32) * w))(x)
    want = jax.grad(lambda x: jnp.sum(ref(x) * w))(x)
    assert got.dtype == x.dtype
    _close(got.astype(jnp.float32), want.astype(jnp.float32), tol)
    # through the registry, as ``F.contrib.rotary_embedding``
    nd = mx.nd.contrib.rotary_embedding(
        mx.nd.array(np.asarray(x, np.float32)), theta=32000000.0,
        rotary_dim=rotary_dim)
    _close(nd.asnumpy(), ref(x), tol)


def test_the_rotary_operator_refuses_an_odd_width():
    x = jnp.zeros((1, 1, 4, 6))
    for bad in (3, 8, 0):
        with pytest.raises(ValueError, match="rotary_dim"):
            rotary.rotary_embedding(x, rotary_dim=bad)


# -- the mixer against the reference ---------------------------------------------------

def _mixer_value_and_grads(mixer, u, w):
    x = mx.nd.array(u)
    x.attach_grad()
    with autograd.record():
        out = mixer(x)
        loss = (out * mx.nd.array(w)).sum()
    loss.backward()
    return (out.asnumpy(), x.grad.asnumpy(),
            [p.grad().asnumpy() for p in mixer.collect_params().values()])


@pytest.mark.parametrize("side", ["xla_side", "flash_16_8", "flash_8_16"])
def test_the_mla_mixer_matches_the_reference_forward_and_every_gradient(side):
    # 32 tokens: XLA's attention without blocks, the kernels (interpreted;
    # keys of 12 channels, values of 8) with them
    lw = _mixer_weights()
    blocks = {} if side == "xla_side" else dict(
        zip(("block_q", "block_k"), map(int, side.split("_")[1:])))
    mixer = _mixer(lw)
    rng = np.random.default_rng(1)
    u = rng.normal(size=(2, 32, 32)).astype(np.float32)
    w = rng.normal(size=(2, 32, 32)).astype(np.float32)
    seen = []

    def with_blocks(f):
        def attend(query, key, value, **kw):
            seen.append((query.shape, value.shape))
            return f(query, key, value, **dict(kw, **blocks))
        return attend
    with _planted(forward={"_contrib_flash_attention": with_blocks}):
        out, du, dws = _mixer_value_and_grads(mixer, u, w)
    assert (2, 4, 32, 12) in [q for q, _ in seen] \
        and (2, 4, 32, 8) in [v for _, v in seen]

    def ref(u, lw):
        return jnp.sum(ARCH._mla(TINY, lw, u, EIN) * w)
    _close(out, ARCH._mla(TINY, lw, jnp.asarray(u), EIN), 2e-4)
    want_u, want_w = jax.grad(ref, argnums=(0, 1))(jnp.asarray(u), lw)
    _close(du, want_u, 2e-4)
    assert list(lw) == ["q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                        "kv_b", "o"]
    for got, name in zip(dws, lw):
        _close(got, want_w[name], 2e-4)


def test_the_mla_mixer_takes_the_kernels_at_a_length_that_tiles():
    # no explicit blocks: 520 tokens are the kernels' side of the switch
    lw = _mixer_weights()
    u = np.random.default_rng(1).normal(size=(1, 520, 32)).astype(np.float32)
    _close(_mixer(lw)(mx.nd.array(u)).asnumpy(),
           ARCH._mla(TINY, lw, jnp.asarray(u), EIN), 2e-4)


# -- the rotation is held by the logits --------------------------------------------------

@contextlib.contextmanager
def _planted(forward=None, **patches):
    """``ops/rotary.py`` with names replaced (``patches``) and registered
    operators with their forward wrapped (``forward``: ``{operator:
    make(forward) -> forward}``).  jax keeps a trace by the function traced,
    and the engine a segment's executable by the operators' names: the
    operators' runners and the segments go, before and after, so that
    nothing traced on one side is met on the other."""
    def forget():
        mx.ops.registry._jitted.cache_clear()
        mx.ops.registry._op_run.cache_clear()
        for tier in mx.engine._SEG_TIERS:
            tier.clear()
    was = {k: getattr(rotary, k) for k in patches}
    regs = {op: mx.ops.registry.get(op) for op in forward or {}}
    forwards = {op: reg.forward for op, reg in regs.items()}
    for k, v in patches.items():
        setattr(rotary, k, v)
    for op, reg in regs.items():
        reg.forward = forward[op](reg.forward)
    forget()
    try:
        yield
    finally:
        for k, v in was.items():
            setattr(rotary, k, v)
        for op, reg in regs.items():
            reg.forward = forwards[op]
        forget()


def _halves(x, cos, sin):
    """Rotate-halves: channel ``i`` pairs with ``i + width / 2``."""
    half = x.shape[-1] // 2
    c, s = cos[:, ::2], -sin[:, ::2]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


TRUE_ANGLES, TRUE_ROTATE = rotary.rope_angles, rotary.rotate_pairs


def _angles_from(start, theta=None):
    def angles(t, width, th):
        cos, sin = TRUE_ANGLES(t + start, width, theta or th)
        return cos[start:], sin[start:]
    return angles


def _a_head_its_own(repeat):
    """The rope key turned again after the repeat, head ``h`` as if at
    position ``t + h``."""
    def repeated(data, **kw):
        out = repeat(data, **kw)
        if data.ndim != 4 or data.shape[1] != 1:
            return out
        t, width = data.shape[2:]
        heads = []
        for h in range(out.shape[1]):
            cos, sin = TRUE_ANGLES(t + h, width, 32000000.0)
            # a turn by the angle of ``h`` positions, at every position
            heads.append(TRUE_ROTATE(out[:, h:h + 1], cos[h:h + 1],
                                     sin[h:h + 1]))
        return jnp.concatenate(heads, axis=1)
    return repeated


def _keys_from_one(x, cos, sin):
    if x.shape[-3] != 1:                       # the queries' heads
        return TRUE_ROTATE(x, cos, sin)
    c, s = TRUE_ANGLES(x.shape[-2] + 1, x.shape[-1], 32000000)
    return TRUE_ROTATE(x, c[1:], s[1:])


PLANTS = {
    "no_rotation": dict(rotate_pairs=lambda x, cos, sin: x),
    "rotate_halves": dict(rotate_pairs=_halves),
    "key_rotated_a_head": dict(
        forward={"broadcast_axis": _a_head_its_own}),
    "theta_10000": dict(rope_angles=_angles_from(0, theta=10000.0)),
    "keys_positions_from_1": dict(rotate_pairs=_keys_from_one),
}


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_a_planted_fault_of_the_rotation_moves_the_logits(plant):
    """``correct``'s norms cannot see a rotation (``PERF.md`` 7.14): the
    logits hold it.  Each plant moves them by far more than the 1e-4 they
    agree to; the program as it is agrees before and after."""
    net, _, weights = _net_and_weights()
    toks, _ = _batch(TINY, 2, 24)
    want = np.asarray(reference.forward(TINY, weights, jnp.asarray(toks)))
    _close(net(mx.nd.array(toks, dtype="int32")).asnumpy(), want)
    with _planted(**PLANTS[plant]):
        got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()
    _close(net(mx.nd.array(toks, dtype="int32")).asnumpy(), want)


def test_positions_shifted_alike_are_the_same_rotation():
    # what a rotary embedding is: queries and keys both counted from 1
    # give the same scores, so that is no fault to plant
    net, _, weights = _net_and_weights()
    toks, _ = _batch(TINY, 2, 24)
    want = np.asarray(reference.forward(TINY, weights, jnp.asarray(toks)))
    with _planted(rope_angles=_angles_from(1)):
        _close(net(mx.nd.array(toks, dtype="int32")).asnumpy(), want)


# -- one class for both models -------------------------------------------------------

class _SeparateOperators(kimi_linear.MLAMixer):
    """Kimi Linear's mixer as PR 34 wrote it, with neither a query rank
    nor a rotation to ask about."""

    def hybrid_forward(self, F, u, kv_a_proj, kv_a_norm, kv_b_proj, o_proj,
                       q_proj=None):
        h, rank, nope, rope, vd, eps, _ = self._cfg
        b, t, _ = u.shape

        def heads(x, width):
            return F.transpose(F.reshape(x, shape=(b, t, -1, width)),
                               axes=(0, 2, 1, 3))
        q = heads(_dense(F, u, q_proj), nope + rope)
        kv_a = _dense(F, u, kv_a_proj)
        latent = F.RMSNorm(F.slice_axis(kv_a, axis=2, begin=0, end=rank),
                           kv_a_norm, axis=-1, eps=eps)
        k_rope = F.broadcast_axis(
            heads(F.slice_axis(kv_a, axis=2, begin=rank, end=None), rope),
            axis=1, size=h)
        kv = heads(_dense(F, latent, kv_b_proj), nope + vd)
        k = F.concat(F.slice_axis(kv, axis=3, begin=0, end=nope), k_rope,
                     dim=3)
        v = F.slice_axis(kv, axis=3, begin=nope, end=None)
        out = F.contrib.flash_attention(q, k, v, scale=(nope + rope) ** -0.5,
                                        causal=True)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(b, t, h * vd))
        return _dense(F, out, o_proj)


def test_without_a_query_rank_and_a_theta_it_is_kimi_linears_mixer():
    rng = np.random.default_rng(0)
    new = kimi_linear.MLAMixer(*MIXER[:6])
    old = _SeparateOperators(*MIXER[:6])
    assert [p.name.split("_", 1)[1] for p in new.collect_params().values()] \
        == ["q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"]
    for m in (new, old):
        m.initialize(mx.init.Zero())
    for pn, po in zip(new.collect_params().values(),
                      old.collect_params().values()):
        assert pn.shape == po.shape
        w = rng.normal(size=pn.shape).astype(np.float32) * 0.3
        pn.set_data(mx.nd.array(w))
        po.set_data(mx.nd.array(w))
    u = rng.normal(size=(2, 24, 32)).astype(np.float32)
    w = rng.normal(size=(2, 24, 32)).astype(np.float32)
    got, want = (_mixer_value_and_grads(m, u, w) for m in (new, old))
    assert np.array_equal(got[0], want[0])          # bit for bit
    _close(got[1], want[1], 1e-6)
    for a, b in zip(got[2], want[2]):
        _close(a, b, 1e-6)


# -- the kernels at 8192 tokens ---------------------------------------------------------

def test_the_kernels_limit_follows_the_keys_held_in_vmem():
    from mxnet_tpu.ops.pallas_kernels import _flash_params

    def params(t, d, dv):
        return _flash_params(jax.ShapeDtypeStruct((32, t, d), jnp.bfloat16),
                             jax.ShapeDtypeStruct((32, t, dv), jnp.bfloat16))
    # every length the other cells run: the compiler's own limit
    assert params(512, 128, 128) is None and params(2048, 128, 128) is None
    assert params(4096, 192, 128) is None
    # 8192 tokens at 192 / 128: K pads to 256 lanes, 12 MiB double-buffered
    assert params(8192, 192, 128).vmem_limit_bytes == (12 + 16) << 20


# -- SwiGLU experts of a width that is no power of two -----------------------------------

@pytest.mark.parametrize("first", [4, 12])
def test_swiglu_experts_with_an_expert_that_gets_no_row(first):
    # the cell's experts are 768 wide (six lanes' worth, gate and up 1536):
    # here 24 and 48, experts first .. first + 3 of 16, one left without a
    # row; against the reference given the same share
    cfg = dict(TINY, num_hidden_layers=2, held_experts_first=first)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(cfg)],
                 _drawn_bias(cfg, reference.make_weights(cfg, 9), 9)))
    lw = {k[len("layer1.ffn."):]: v for k, v in w.items()
          if k.startswith("layer1.ffn.")}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    flat = u.reshape(24, 32)
    idx, wt = moe_ops.router_topk(flat, lw["router"], lw["router_bias"], k=3,
                                  scale=2.5)
    empty = first + 2
    idx = jnp.where(idx == empty, (empty + 5) % 16, idx)

    def ours(flat, wt, gate_up, down):
        out, counts = moe_ops.grouped_ffn(flat, idx, wt, gate_up, down,
                                          first=first, activation="swiglu")
        return jnp.sum(jnp.sin(out)), (out, counts)

    def dense(flat, wt, gate_up, down):
        hid = ARCH._swiglu(jnp.einsum("sd,efd->sef", flat, gate_up))
        gate = jnp.sum(jnp.where(
            idx[..., None] == first + jnp.arange(4), wt[..., None], 0.0), 1)
        out = jnp.einsum("sef,edf,se->sd", hid, down, gate)
        return jnp.sum(jnp.sin(out)), out
    args = (flat, wt, lw["gate_up"], lw["down"])
    (_, (out, counts)), got = jax.value_and_grad(
        ours, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    (_, want_out), want = jax.value_and_grad(
        dense, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    _close(out, want_out)
    for a, b in zip(got, want):
        _close(a, b)
    assert counts[2] == 0 and counts[:4].sum() > 0 and counts[-2] == 0
    assert not np.asarray(got[2][2]).any()      # the empty expert's gradient


def test_the_share_is_the_models():
    """16 experts over 4 shares of 4: the routed parts that the four
    shares compute, with the shared expert counted once, add up to the
    uncut reference layer."""
    whole = dict(TINY, num_hidden_layers=2, n_routed_experts=16,
                 held_experts_first=0)
    weights = _drawn_bias(whole, reference.make_weights(whole, 9), 9)
    w = dict(zip([n for n, _ in ARCH.leaf_specs(whole)], weights))
    lw = {k[len("layer1.ffn."):]: v for k, v in w.items()
          if k.startswith("layer1.ffn.")}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 32)),
                    jnp.float32)
    uncut = ARCH._moe(whole, lw, u, EIN)
    shared = EIN("btf,if->bti", ARCH._swiglu(
        EIN("bti,fi->btf", u, lw["shared_gate_up"])), lw["shared_down"])
    flat = u.reshape(24, 32)
    idx, wt = moe_ops.router_topk(flat, lw["router"], lw["router_bias"], k=3,
                                  scale=2.5)
    total, landed = shared.reshape(24, 32), 0
    for first in (0, 4, 8, 12):
        part, counts = moe_ops.grouped_ffn(
            flat, idx, wt, lw["gate_up"][first:first + 4],
            lw["down"][first:first + 4], first=first, activation="swiglu")
        # the reference, given the same share, computes the same part
        share = dict(whole, n_routed_experts=4, held_experts_first=first)
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
        CHIP, "configs", "joyai_llm_flash_p5_e8.json")))


def _uncut(cfg):
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items()
                         if not k.startswith("parameters")})
    whole["router_num_experts"] = whole["n_routed_experts"]
    return whole


def test_the_configuration_counts_as_published_and_as_cut():
    cfg = _cell_cfg()
    pub, whole = cfg["published"], _uncut(cfg)
    with_module = ARCH.param_count(whole)
    count = ARCH.param_count(dict(whole, num_nextn_predict_layers=0))
    assert count == pub["parameters"] == 48_942_542_592
    assert abs(count - 48e9) < 0.03 * 48e9          # the "48B" of the name
    assert with_module == pub["parameters_with_the_prediction_module"] \
        == 50_190_491_648
    assert with_module - count == 1_247_949_056
    assert with_module == sum(int(np.prod(s))
                              for _, s in ARCH.leaf_specs(whole))
    assert ARCH.param_count(cfg) == 413_959_168
    assert "413,959,168" in cfg["deployment"]
    by_kind = {k: sum(int(np.prod(s)) for _, s in ARCH._specs(cfg, k))
               for k in ("mla", "mlp", "moe")}
    assert by_kind == {"mla": 26_347_520, "mlp": 44_040_192,
                       "moe": 42_991_872}
    assert sorted(cfg["reduced"]) == sorted(
        k for k in pub if not k.startswith("parameters")
        and cfg[k] != pub[k]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "num_nextn_predict_layers"])
    for key in ("source", "assumed", "departures", "deployment", "tiny"):
        assert cfg[key]
    assert ARCH.attention_layers(cfg) == 5


def test_the_catalogs_numbers_are_kept():
    """Every number of the catalog's ``config`` stands in the file under
    the same key, but for the keys listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "JoyAI-LLM-Flash")
    cfg = _cell_cfg()
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"])


@pytest.mark.parametrize("nextn", [0, 1])
def test_the_programs_model_at_the_published_sizes_counts_the_same(nextn):
    """Shapes alone: nothing is initialised."""
    net = joyai_llm_flash.joyai_llm_flash_48b_a3b(
        num_nextn_predict_layers=nextn)
    shapes = [p.shape for p in net.collect_params().values()]
    assert sum(int(np.prod(s)) for s in shapes) == \
        (50_190_491_648 if nextn else 48_942_542_592)
    whole = dict(_uncut(_cell_cfg()), num_nextn_predict_layers=nextn)
    assert [tuple(s) for s in shapes] == \
        [s for _, s in ARCH.leaf_specs(whole)]


def test_only_one_prediction_depth_is_built():
    with pytest.raises(ValueError, match="none or one"):
        joyai_llm_flash.joyai_llm_flash_48b_a3b(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="none or one"):
        ARCH.leaf_specs(dict(TINY, num_nextn_predict_layers=2))


def test_flops_a_token_follow_the_stated_rule():
    cfg = _cell_cfg()
    h = 2048
    mla_mats = 1536 * h + 6144 * 1536 + 576 * h + 8192 * 512 + h * 4096
    moe_mats = 256 * h + 3 * h * 768 + int(8 * 3 * h * 768 * 8 / 256)
    mlp_mats = 3 * h * 7168
    attention = 2 * 8192 * 32 * (192 + 128) // 2
    want = 6 * (5 * mla_mats + 4 * moe_mats + mlp_mats + 16160 * h) \
        + 3 * 5 * attention
    assert ARCH.train_flops_per_token(cfg, 8192) == want
    f, dq, dkv = ARCH.mla_flash_calls(cfg, 1, 8192)
    qk, pv = 32 * 8192 * 8192 * 192, 32 * 8192 * 8192 * 128
    assert (f["flops"], dq["flops"], dkv["flops"]) == \
        (qk + pv, 2 * qk + pv, 2 * qk + 2 * pv)
    # the kernels of five layers are most of a step's operations
    assert 5 * (f["flops"] + dq["flops"] + dkv["flops"]) \
        > 0.7 * want * 8192
    calls = ARCH.grouped_calls(cfg, 2048)
    assert [c["flops"] for c in calls] == \
        [2 * 2048 * h * 1536] * 3 + [2 * 2048 * h * 768] * 3
    assert ARCH.grouped_calls(cfg, 0)[0]["flops"] == 0
