"""Latent attention through the flash kernels with its operands read where
the projections wrote them (``ops/mla_kernels.py``: ``mx_flash_fwd_mla``,
``mx_flash_bwd_dq_mla``, ``mx_flash_bwd_dkv_mla``) in the interpreter, at
lane-tiling shapes kept small (128 / 64 / 128 channels, two or four heads,
256 to 512 tokens), against the composition it replaces: heads transposed
to ``(B, H, T, .)``, ``rotary_embedding``, the rope key repeated over the
heads and concatenated, ``flash_attention``.

Tolerances.  In float32 the two are the same sums in another order: 2e-5 of
the largest value.  In bfloat16 the composition rounds the turned queries
and keys to bfloat16 before its kernels read them and the in-place kernels
turn a block in float32: 2e-2.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon, parallel
from mxnet_tpu.gluon.model_zoo import kimi_linear
from mxnet_tpu.ops import mla_kernels
from mxnet_tpu.ops.pallas_kernels import flash_attention
from mxnet_tpu.ops.rotary import rope_angles, rotary_embedding
from mxnet_tpu.telemetry import metrics

NOPE, ROPE, VD = 128, 64, 128


def _operands(t, b=1, h=4, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    widths = (h * NOPE, h * ROPE, h * (NOPE + VD), ROPE, h * VD)
    return tuple(jnp.asarray(rng.normal(size=(b, t, w)), jnp.float32)
                 .astype(dtype) for w in widths)


def _composition(qn, qr, kv, kr, h, theta, block_q, block_k):
    b, t, _ = qn.shape

    def heads(x, width):
        return x.reshape(b, t, -1, width).transpose(0, 2, 1, 3)
    q = jnp.concatenate([heads(qn, NOPE), heads(qr, ROPE)], axis=-1)
    kv, kr = heads(kv, NOPE + VD), heads(kr, ROPE)
    if theta is not None:
        q = rotary_embedding(q, theta=theta, rotary_dim=ROPE)
        kr = rotary_embedding(kr, theta=theta)
    k = jnp.concatenate([kv[..., :NOPE],
                         jnp.broadcast_to(kr, (b, h, t, ROPE))], axis=-1)
    out = flash_attention(q, k, kv[..., NOPE:], scale=(NOPE + ROPE) ** -0.5,
                          causal=True, block_q=block_q, block_k=block_k)
    return out.transpose(0, 2, 1, 3).reshape(b, t, h * VD)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), \
        (np.abs(a - b).max(), np.abs(b).max())


@pytest.mark.parametrize("block_k", [256, 64],
                         ids=["one_key_block", "four_key_blocks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("theta", [None, 3.2e7], ids=["nope", "rotary"])
def test_the_operator_is_the_composition(theta, dtype, block_k):
    # two groups of two heads, two query blocks: the result and the
    # gradient of every operand
    h, blocks = 4, dict(block_q=128, block_k=block_k)
    *args, w = _operands(256, h=h, dtype=jnp.dtype(dtype))
    tol = 2e-5 if dtype == "float32" else 2e-2

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a) * w).astype(jnp.float32).sum(),
            argnums=range(4)))(*args)
    new = functools.partial(mla_kernels.mla_flash_attention, num_heads=h,
                            rope_theta=theta, **blocks)
    old = functools.partial(_composition, h=h, theta=theta, **blocks)
    assert "mx_flash_fwd_mla" in str(jax.make_jaxpr(new)(*args))
    got = new(*args)
    assert got.dtype == args[0].dtype and got.shape == w.shape
    _close(got, old(*args), tol)
    (value, grads), (want_value, want) = value_and_grads(new), \
        value_and_grads(old)
    _close(value, want_value, tol)
    for a, g in zip(grads, want):
        assert a.dtype == g.dtype
        _close(a, g, tol)


def test_dk_rope_is_the_sum_over_the_heads():
    # a cotangent planted a head at a time: the rope key's gradient under
    # the whole cotangent is the sum of the four, each its own (the dk/dv
    # kernel sums in float32 along its sequential head axis)
    h = 4
    *args, w = _operands(256, h=h, seed=3)
    fn = functools.partial(mla_kernels.mla_flash_attention, num_heads=h,
                           rope_theta=3.2e7, block_q=128, block_k=128)
    _, vjp = jax.vjp(fn, *args)
    lanes = np.arange(h * VD) // VD
    a_head = [np.asarray(vjp(jnp.where(lanes == j, w, 0.0))[3])
              for j in range(h)]
    for j in range(1, h):
        assert np.abs(a_head[j] - a_head[0]).max() > 1e-2
    _close(sum(a_head), vjp(w)[3], 1e-5)
    want = jax.grad(lambda *a: (_composition(*a, h, 3.2e7, 128, 128)
                                * jnp.where(lanes == 2, w, 0.0)).sum(),
                    argnums=3)(*args)
    _close(a_head[2], want, 2e-5)


def test_blocks_past_the_causal_diagonal_are_never_read():
    # four blocks of 64 tokens.  NaN in the last key block: the first three
    # query blocks' results and dq do not see it (a masked score times a NaN
    # value would be NaN).  NaN in the first query block: the later key
    # blocks' dk, dv and dk_rope do not see it
    t, h, theta = 256, 2, 3.2e7
    qn, qr, kv, kr, do = _operands(t, h=h, seed=5)
    static = dict(heads=h, scale=(NOPE + ROPE) ** -0.5, causal=True,
                  block_q=64, block_k=64, group=2, interpret=True)
    kr_wide, *angles = mla_kernels._operands(kr, theta, 2)

    def forward(qn, qr, kv, kr_wide):
        return jax.jit(functools.partial(mla_kernels._fwd_pallas, **static))(
            qn, qr, kv, kr_wide, *angles)

    def backward(qn, qr, kv, kr_wide, out, do, lse):
        return jax.jit(functools.partial(mla_kernels._bwd_pallas, **static))(
            qn, qr, kv, kr_wide, out, do, lse, *angles)
    out, lse = forward(qn, qr, kv, kr_wide)
    dqn, dqr, dkv, dkr = backward(qn, qr, kv, kr_wide, out, do, lse)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in (out, lse, dqn, dqr, dkv, dkr))

    late = (jnp.arange(t) >= 192)[None, :, None]
    kv_bad, kr_bad = jnp.where(late, jnp.nan, kv), \
        jnp.where(late, jnp.nan, kr_wide)
    out_bad, lse_bad = forward(qn, qr, kv_bad, kr_bad)
    np.testing.assert_array_equal(out_bad[:, :192], out[:, :192])
    np.testing.assert_array_equal(lse_bad[:, :, :192], lse[:, :, :192])
    assert np.isnan(np.asarray(out_bad[:, 192:])).all()
    dqn_bad, dqr_bad, _, _ = backward(qn, qr, kv_bad, kr_bad, out, do, lse)
    np.testing.assert_array_equal(dqn_bad[:, :192], dqn[:, :192])
    np.testing.assert_array_equal(dqr_bad[:, :192], dqr[:, :192])

    early = (jnp.arange(t) < 64)[None, :, None]
    _, _, dkv_bad, dkr_bad = backward(
        jnp.where(early, jnp.nan, qn), jnp.where(early, jnp.nan, qr), kv,
        kr_wide, out, jnp.where(early, jnp.nan, do),
        jnp.where(early[:, None], jnp.nan, lse))
    np.testing.assert_array_equal(dkv_bad[:, 64:], dkv[:, 64:])
    np.testing.assert_array_equal(dkr_bad[:, 64:], dkr[:, 64:])
    assert np.isnan(np.asarray(dkv_bad[:, :64])).all()


def test_the_kernels_turn_a_block_as_rotary_embedding_does():
    # the rotation inside the kernels against ops/rotary.py's, through the
    # scores: with keys that are zero but for the rope key and values that
    # are the identity of a position, a head's result is the softmax of its
    # rope scores alone
    t, h = 128, 2
    rng = np.random.default_rng(7)
    qr = jnp.asarray(rng.normal(size=(1, t, h * ROPE)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(1, t, ROPE)), jnp.float32)
    values = jnp.tile(jnp.concatenate(
        [jnp.zeros((t, NOPE)), jnp.eye(t, VD)], axis=1), (1, h))[None]
    got = mla_kernels.mla_flash_attention(
        jnp.zeros((1, t, h * NOPE)), qr, values, kr, num_heads=h,
        rope_theta=1e4, block_q=64, block_k=64)
    cos, sin = rope_angles(t, ROPE, 1e4)
    for j in range(h):
        q = rotary_embedding(qr[0, :, j * ROPE:(j + 1) * ROPE], theta=1e4)
        s = q @ rotary_embedding(kr[0], theta=1e4).T * (NOPE + ROPE) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        _close(got[0, :, j * VD:(j + 1) * VD], jax.nn.softmax(s, axis=-1),
               2e-5)
    assert cos.shape == (t, ROPE) and sin.dtype == jnp.float32


def test_the_path_is_a_static_test_of_the_shapes():
    tiles = mla_kernels.tiles
    # the two cells: 512-token blocks, two heads a grid step
    assert tiles(32, 128, 64, 128, 8192) == (512, 512, 2)
    assert tiles(32, 128, 64, 128, 4096) == (512, 512, 2)
    assert tiles(8, 256, 128, 128, 1024) == (512, 512, 1)
    # the tier-1 models' widths; a rope that is no half row of lanes;
    # values narrower than a row; an odd count of heads at two a step
    assert tiles(4, 8, 4, 8, 520) is None
    assert tiles(4, 128, 32, 128, 1024) is None
    assert tiles(4, 128, 64, 64, 1024) is None
    assert tiles(3, 128, 64, 128, 1024) is None
    # sequences flash_attention hands to XLA, or that no block divides
    assert tiles(4, 128, 64, 128, 256) is None
    assert tiles(4, 128, 64, 128, 256, block_q=128) == (128, 256, 2)
    assert tiles(4, 128, 64, 128, 1021) is None
    # a context mesh: GSPMD cannot partition a Mosaic kernel
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with jax.set_mesh(mesh):
        assert tiles(32, 128, 64, 128, 8192) is None
    with pytest.raises(ValueError, match="do not tile"):
        mla_kernels.mla_flash_attention(*_operands(24, h=4)[:4], num_heads=4)


def _mixer(rank, theta, widths=(NOPE, ROPE, VD), units=64, heads=2,
           layer=0):
    mixer = kimi_linear.MLAMixer(units, heads, 32, *widths, q_lora_rank=rank,
                                 rope_theta=theta, layer=layer)
    mixer.initialize(mx.init.Xavier())
    return mixer


def _mixer_grads(mixer, u, w):
    x = mx.nd.array(u)
    x.attach_grad()
    with mx.autograd.record():
        out = mixer(x)
        loss = (out * mx.nd.array(w)).sum()
    loss.backward()
    return out.asnumpy(), {"u": x.grad.asnumpy(), **{
        name: p.grad().asnumpy()
        for name, p in mixer.collect_params().items()}}


def _ops_traced(mixer, u):
    with mx.autograd.pause():
        fn = lambda x: mixer(mx.nd.NDArray(x)).data()     # noqa: E731
        return str(jax.make_jaxpr(fn)(jnp.asarray(u)))


@pytest.mark.parametrize("rank, theta", [(None, None), (48, 3.2e7)],
                         ids=["kimi_linear", "joyai_llm_flash"])
def test_a_mixer_reads_in_place_what_the_composition_copies(
        rank, theta, monkeypatch):
    # one layer at tiling widths, 512 tokens: the result and the gradient
    # of every leaf (the query's weight is split by rows and its gradient
    # put together again; W_kvb's cotangent is the dk/dv kernel's one
    # array; the rope key's reaches W_kva), against the same mixer sent to
    # the composition
    mixer = _mixer(rank, theta)
    rng = np.random.default_rng(11)
    u = rng.normal(size=(1, 512, 64)).astype(np.float32)
    w = rng.normal(size=(1, 512, 64)).astype(np.float32)
    assert "mx_flash_fwd_mla" in _ops_traced(mixer, u)
    out, grads = _mixer_grads(mixer, u, w)
    monkeypatch.setattr(mla_kernels, "tiles", lambda *a, **k: None)
    text = _ops_traced(mixer, u)
    assert "mx_flash_fwd_mla" not in text and "mx_flash_fwd" in text
    want_out, want = _mixer_grads(mixer, u, w)
    _close(out, want_out, 2e-5)
    assert sorted(grads) == sorted(want) and len(grads) == (5 if rank is None
                                                            else 7) + 1
    for name in want:
        assert np.abs(want[name]).max() > 0, name
        _close(grads[name], want[name], 5e-5)


def test_the_tiny_widths_and_a_mesh_take_the_composition():
    u = np.zeros((1, 520, 32), np.float32)
    assert "mx_flash_fwd_mla" not in _ops_traced(
        _mixer(24, 1e4, widths=(8, 4, 8), units=32, heads=4), u)
    mixer = _mixer(48, 3.2e7)
    u = np.zeros((2, 512, 64), np.float32)
    assert "mx_flash_fwd_mla" in _ops_traced(mixer, u)
    with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("data",))):
        text = _ops_traced(mixer, u)
    assert "mx_flash_fwd_mla" not in text and "mx_flash_fwd" in text


def test_under_amp_the_operator_takes_bfloat16():
    assert "_contrib_mla_flash_attention" in amp.lists.TARGET_DTYPE_OPS
    mixer = _mixer(48, 3.2e7)
    u = np.random.default_rng(13).normal(size=(1, 512, 64)) \
        .astype(np.float32)
    want = mixer(mx.nd.array(u)).asnumpy()
    seen, orig = {}, amp.transform_inputs

    def spy(op_name, datas):
        out = orig(op_name, datas)
        seen.setdefault(op_name, [getattr(d, "dtype", None) for d in out])
        return out
    amp.init("bfloat16")
    amp.transform_inputs = spy
    try:
        got = mixer(mx.nd.array(u))
    finally:
        amp.transform_inputs = orig
        amp.turn_off()
    assert seen["_contrib_mla_flash_attention"] == [jnp.bfloat16] * 4
    _close(got.asnumpy().astype(np.float32), want, 3e-2)


def _counted(name):
    series = metrics.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series)


@pytest.mark.parametrize("widths, units, heads, kernel",
                         [((8, 4, 8), 32, 4, 0), ((NOPE, ROPE, VD), 64, 2, 1)],
                         ids=["tiny_widths", "lane_tiles"])
def test_the_counter_counts_the_layers_read_in_place(widths, units, heads,
                                                     kernel):
    """A train step over one latent attention mixer:
    ``mxnet_mla_layers_total`` counts the layer a step,
    ``mxnet_mla_kernel_layers_total`` the same where the shapes tile and
    nothing where the mixer took the composition."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.mixer = kimi_linear.MLAMixer(
                units, heads, 32, *widths, q_lora_rank=48, rope_theta=3.2e7,
                layer=3)

        def hybrid_forward(self, F, x):
            return self.mixer(x)
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = [_counted("mxnet_mla_layers_total"),
              _counted("mxnet_mla_kernel_layers_total")]
    net = Net()
    net.initialize()
    net.hybridize()
    step = parallel.JitTrainStep(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = np.random.default_rng(0)
    for _ in range(2):
        loss = float(step.step(
            rng.normal(size=(1, 512, units)).astype(np.float32),
            rng.normal(size=(1, 512, units)).astype(np.float32)))
        assert np.isfinite(loss)
    stats = step.step_stats()
    assert int(stats["mla/3"][0]) == 2
    assert int(stats["mla_kernel/3"][0]) == 2 * kernel
    assert _counted("mxnet_mla_layers_total") - before[0] == 2
    assert _counted("mxnet_mla_kernel_layers_total") - before[1] == 2 * kernel
