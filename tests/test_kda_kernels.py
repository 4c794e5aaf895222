"""The delta-rule scan's Pallas kernels (``ops/kda_kernels.py``: ``mx_kda_fwd``,
``mx_kda_bwd``) in the interpreter, at tiling shapes kept small (keys and
values of 128 channels, two heads, 128 to 160 tokens), against the recurrence
they compute and against the ``jax.numpy`` form of the same algebra.

Tolerances.  The kernels round the operands of every product outside the
solve to bfloat16, as a TPU's default-precision ``einsum`` does and this
CPU's does not: 2e-2 of the largest value against the float32 recurrence.
With those products at float32 too (``_dot`` patched to ``_dot32`` by the
test: same kernels, same algebra) the two agree to 1e-4, which is what
holds the algebra.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import kimi_linear
from mxnet_tpu.ops import kda, kda_kernels, pallas_kernels
from mxnet_tpu.telemetry import metrics


def _inputs(t, b=1, h=2, d=128, e=128, seed=0, g_low=-1.6, g_high=0.0):
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, b, t, h, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q, k, rng.normal(size=(b, t, h, e)),
        rng.uniform(g_low, g_high, (b, t, h, d)),
        rng.uniform(0, 1, (b, t, h))))


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), \
        (np.abs(a - b).max(), np.abs(b).max())


def _value_and_grads(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a)) * w), argnums=range(5)))(*args)


def _against(other, args, tol, chunk=64):
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape),
                    jnp.float32)
    _close(kda.kda_scan(*args, chunk=chunk), other(*args), tol)
    value, grads = _value_and_grads(
        lambda *a: kda.kda_scan(*a, chunk=chunk), args, w)
    want_value, want = _value_and_grads(other, args, w)
    _close(value, want_value, tol)
    for a, b in zip(grads, want):
        _close(a, b, tol)
    return grads, want


def _takes_the_kernels(*args, **kw):
    text = str(jax.make_jaxpr(lambda *a: kda.kda_scan(*a, **kw))(*args))
    return "mx_kda_fwd" in text


@pytest.fixture
def exact_products(monkeypatch):
    # ``_platform_pick`` keeps what it jitted: neither what an earlier test
    # traced is this one's, nor what this one traces the next one's
    pallas_kernels._JIT_CACHE.clear()
    monkeypatch.setattr(kda_kernels, "_dot", kda_kernels._dot32)
    yield
    pallas_kernels._JIT_CACHE.clear()


@pytest.mark.parametrize("t", [128, 160], ids=["whole_chunks", "2.5_chunks"])
def test_the_algebra_is_the_recurrences(t, exact_products):
    # every product at float32: forward and all five gradients to 1e-4
    args = _inputs(t)
    assert _takes_the_kernels(*args, chunk=64)
    _against(kda.kda_recurrence, args, 1e-4)


def test_bfloat16_products_stay_near_the_recurrence_and_the_einsums():
    # the kernels as the chip runs them; the jax.numpy form at the same
    # inputs (float32 products on this CPU) is as near as the recurrence
    args = _inputs(128, seed=3)
    grads, want = _against(kda.kda_recurrence, args, 2e-2)
    # the decay's gradient summed over the tokens (what reaches ``A_log``
    # and ``dt_bias``): the rounded products' errors must not add up under
    # the reverse running sum.  Without the sub-chunks' reference points'
    # shares (``_pairs_bwd``) this reads 0.10, and the cell's
    # ``grad_norm_gap`` left its limit on one seed of three
    miss = np.asarray(grads[3] - want[3], np.float64)
    assert np.linalg.norm(miss) < 1.5e-2 * np.linalg.norm(want[3])
    assert np.linalg.norm(miss.sum(1)) < 5e-2 * np.linalg.norm(
        np.asarray(want[3], np.float64).sum(1))
    out = kda.kda_scan(*args, chunk=64)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda_kernels, "tiles", lambda *a: False)
        assert not _takes_the_kernels(*args, chunk=64)
        _close(out, kda.kda_scan(*args, chunk=64), 2e-2)


def test_the_published_decay_range_overflows_nothing(exact_products):
    # the first head: g = -1.6 on every channel and token, 128 tokens: a
    # running sum of -102 inside a chunk of 64, past what float32's exp
    # holds (-88); the second: channels that decay at once beside channels
    # that never do
    args = list(_inputs(128))
    mix = jnp.where(jnp.arange(128) % 2 == 0, -1.6, -1e-4)
    args[3] = jnp.stack([jnp.full((1, 128, 128), -1.6),
                         jnp.broadcast_to(mix, (1, 128, 128))], axis=2)
    _against(kda.kda_recurrence, args, 1e-4)
    out = kda.kda_scan(*args, chunk=64)
    assert bool(jnp.isfinite(out).all())


def test_bfloat16_inputs_through_the_mixers_operator_a_head_at_a_time(
        monkeypatch):
    """``kda_attention`` at 128 channels a head: bfloat16 in, float32 out,
    bfloat16 cotangents back, nothing wider than its inputs kept, and heads
    one at a time (a grid step then holds one head, and two groups share
    each chunk's ``beta`` block) give what both at once give."""
    rng = np.random.default_rng(0)
    b, t, h, d = 1, 80, 2, 128
    wide = [jnp.asarray(rng.normal(size=(b, t, h * d)), jnp.bfloat16)
            for _ in range(5)]
    beta = jnp.asarray(rng.normal(size=(b, t, h)), jnp.bfloat16)
    taps = [jnp.asarray(rng.normal(size=(h * d, 4)) * 0.5, jnp.float32)
            for _ in range(3)]
    rest = (jnp.zeros((1, h)), jnp.zeros((h, d)), jnp.ones((d,)))
    q, k, v, decay, gate = wide

    def f(q, k, v, decay, beta, gate):
        return kda.kda_attention(q, k, v, decay, beta, gate, *taps, *rest,
                                 chunk=64)
    assert "mx_kda_fwd" in str(jax.make_jaxpr(f)(q, k, v, decay, beta, gate))
    results = []
    for at_once in (2, 1):
        monkeypatch.setattr(kda_kernels, "HEADS_A_STEP", at_once)
        pallas_kernels._JIT_CACHE.clear()
        out, vjp = jax.vjp(f, q, k, v, decay, beta, gate)
        assert out.dtype == jnp.float32 and out.shape == (b, t, h * d)
        grads = vjp(jnp.cos(out))
        assert all(g.dtype == jnp.bfloat16 for g in grads)
        kept = [x for x in jax.tree_util.tree_leaves(vjp)
                if hasattr(x, "shape") and x.size >= b * t * h * d]
        assert len(kept) == 5 and all(x.dtype == jnp.bfloat16 for x in kept)
        results.append((out, grads))
    (whole, grads), (single, others) = results
    _close(single, whole, 1e-6)
    for a, g in zip(others, grads):
        _close(a.astype(jnp.float32), g.astype(jnp.float32), 1e-2)


def test_the_path_is_a_static_test_of_the_shapes():
    # 8 or 12 channels: the jax.numpy form; 128: the kernels, whatever the
    # chunk asked for rounds to; values narrower than a lane row: not
    assert not _takes_the_kernels(*_inputs(32, d=8, e=8), chunk=16)
    assert not _takes_the_kernels(*_inputs(32, d=128, e=12), chunk=16)
    assert not _takes_the_kernels(*_inputs(32, d=8, e=128), chunk=16)
    for chunk in (16, 32, 64, 100):
        assert _takes_the_kernels(*_inputs(32, d=128, e=256), chunk=chunk)
    assert kda.kda_kernel_chunks(4096, 128, 128, 64) == 64
    assert kda.kda_kernel_chunks(37, 128, 128, 16) == 3
    assert kda.kda_kernel_chunks(4096, 8, 8, 64) == 0
    assert kda.kda_kernel_chunks(4096, 128, 12, 64) == 0
    assert kda_kernels.tiles(128, 128, 64) and kda_kernels.tiles(256, 128, 16)
    assert not kda_kernels.tiles(64, 128, 64)


def _counted(name):
    series = metrics.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series)


@pytest.mark.parametrize("head_dim, kernel", [(8, 0), (128, 1)],
                         ids=["8_channels", "128_channels"])
def test_the_counter_counts_the_chunks_the_kernels_ran(head_dim, kernel):
    """A train step over one KDA mixer: ``mxnet_kda_chunks_total`` counts
    sequences x heads x chunks; ``mxnet_kda_kernel_chunks_total`` the same
    where the shapes tile and nothing where they do not."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.mixer = kimi_linear.KDAMixer(16, 2, head_dim, chunk_size=32,
                                              layer=3)

        def hybrid_forward(self, F, x):
            return self.mixer(x)
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = [_counted("mxnet_kda_chunks_total"),
              _counted("mxnet_kda_kernel_chunks_total")]
    net = Net()
    net.initialize()
    net.hybridize()
    step = parallel.JitTrainStep(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 40, 16)).astype(np.float32)
    for _ in range(2):
        loss = float(step.step(x, rng.normal(size=(1, 40, 16))
                               .astype(np.float32)))
        assert np.isfinite(loss)
    stats = step.step_stats()
    # one sequence x two heads x two chunks of 32, two steps
    assert int(stats["kda/3"][0]) == 2 * 1 * 2 * 2
    assert int(stats["kda_kernel/3"][0]) == kernel * 2 * 1 * 2 * 2
    assert _counted("mxnet_kda_chunks_total") - before[0] == 8
    assert _counted("mxnet_kda_kernel_chunks_total") - before[1] == kernel * 8


# -- the mixer whole in the kernels (PR 41) ------------------------------------

_MIXER_ARGS = ("q", "k", "v", "decay", "beta", "gate", "q_conv", "k_conv",
               "v_conv", "A_log", "dt_bias", "o_norm")


def _mixer_inputs(b, t, h=2, d=128, seed=0, decay=None):
    """The projections' results and the mixer's own weights.  ``decay``
    None: gates of every size (``A_log`` in [-1, 1], ``decay`` standard
    normal); else ``A_log = 0``, ``dt_bias = 0`` and ``decay`` about that
    number, so that ``g`` is about ``-softplus(decay)`` on every channel
    and token."""
    rng = np.random.default_rng(seed)
    q, k, v, dec, gate = (jnp.asarray(rng.normal(size=(b, t, h * d)),
                                      jnp.float32) for _ in range(5))
    a_log = rng.uniform(-1, 1, (1, h))
    dt_bias = rng.normal(size=(h, d)) * 0.5
    if decay is not None:
        dec = jnp.asarray(decay + 0.01 * rng.normal(size=(b, t, h * d)),
                          jnp.float32)
        a_log, dt_bias = np.zeros((1, h)), np.zeros((h, d))
    taps = [jnp.asarray(rng.normal(size=(h * d, 4)) * 0.5, jnp.float32)
            for _ in range(3)]
    return (q, k, v, dec, jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32),
            gate, *taps, jnp.asarray(a_log, jnp.float32),
            jnp.asarray(dt_bias, jnp.float32),
            jnp.asarray(1 + 0.1 * rng.normal(size=(d,)), jnp.float32))


def _fused(*args):
    return kda.kda_attention(*args, chunk=64)


def _composed(*args):
    # today's composition, all heads at once: the convolutions, norms and
    # gates in XLA around the scan (its kernels at these shapes)
    return kda._made_again(functools.partial(
        kda._kda_attention, chunk=64, eps=1e-5))(*args)


def _recurrent(*args):
    # the same composition with the recurrence in the scan's place
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda, "_kda_chunked",
                      lambda q, k, v, g, beta, chunk:
                      kda.kda_recurrence(q, k, v, g, beta))
        return _composed(*args)


def _mixer_grads(fn, args):
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=args[0].shape[:2] + (args[2].shape[-1],)), jnp.float32)
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a)) * w),
        argnums=range(len(args))))(*args)


@pytest.mark.parametrize("b, t, decay", [
    (1, 128, None), (2, 100, None), (1, 128, 1.4), (1, 128, 5.0)],
    ids=["whole_chunks", "b2_padded", "published_decay", "overflow_edge"])
def test_the_fused_mixer_is_the_composition_and_the_recurrence(
        b, t, decay, exact_products):
    """Every product at float32: the fused kernels' forward and all twelve
    gradients (the six projections' results, the three taps, ``A_log``,
    ``dt_bias``, ``o_norm``) against today's composition and against the
    recurrence, to 1e-4 of the largest value (1e-3 for the two token
    sums).  Two sequences; 100 tokens,
    which the kernels see as two chunks with 28 padded tokens that neither
    decay nor write (masked by position: ``softplus(0 + dt_bias)`` is not
    0 and ``sigmoid(0)`` is 1/2); ``g`` about -1.6 a token (the published
    range) and about -5.0: the edge of the overflow rule as measured (past
    it the chunked algebra leaves the recurrence, the parent's as this
    one's: 1.1e-4 of the largest value at -5.3, 0.12 at -5.5, where
    ``ops/kda.py`` wrote -5.8)."""
    args = _mixer_inputs(b, t, decay=decay)
    assert "mx_kda_fwd" in str(jax.make_jaxpr(_fused)(*args))
    value, grads = _mixer_grads(_fused, args)
    assert bool(jnp.isfinite(value))
    for other in (_composed, _recurrent):
        want_value, want = _mixer_grads(other, args)
        _close(value, want_value, 1e-4)
        for name, got, expect in zip(_MIXER_ARGS, grads, want):
            assert got.shape == expect.shape, name
            # A_log's and dt_bias's: sums over every token of terms that
            # cancel, as near the recurrence in the composition
            _close(got, expect, 1e-3 if name in ("A_log", "dt_bias")
                   else 1e-4)
    _close(_fused(*args), _recurrent(*args), 1e-4)


def test_the_fused_mixer_with_bfloat16_products_is_the_composition():
    # the kernels as the chip runs them: the same rounded products as the
    # composition's scan, and the prologue and epilogue in float32
    args = _mixer_inputs(2, 100, h=16, seed=4)
    value, grads = _mixer_grads(_fused, args)
    want_value, want = _mixer_grads(_composed, args)
    _close(value, want_value, 2e-3)
    for got, expect in zip(grads, want):
        _close(got, expect, 2e-3)


@pytest.mark.parametrize("t, d, taps, fused", [
    (4096, 128, 4, True), (40, 128, 4, True), (4096, 128, 18, False),
    (4096, 8, 4, False), (4096, 64, 4, False)],
    ids=["cell", "short", "long_conv", "8_channels", "64_channels"])
def test_which_mixers_the_kernels_take_whole(t, d, taps, fused):
    assert kda.kda_fused(t, d, taps) is fused


@pytest.mark.parametrize("head_dim, fused", [(8, 0), (128, 1)],
                         ids=["8_channels", "128_channels"])
def test_the_counter_counts_the_mixers_the_kernels_took_whole(head_dim,
                                                              fused):
    """A train step over one KDA mixer: ``mxnet_kda_layers_total`` counts
    the layer a step; ``mxnet_kda_fused_layers_total`` the same where the
    kernels took it whole and nothing where it kept the composition."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.mixer = kimi_linear.KDAMixer(16, 2, head_dim, chunk_size=32,
                                              layer=2)

        def hybrid_forward(self, F, x):
            return self.mixer(x)
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = [_counted("mxnet_kda_layers_total"),
              _counted("mxnet_kda_fused_layers_total")]
    net = Net()
    net.initialize()
    net.hybridize()
    step = parallel.JitTrainStep(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = np.random.default_rng(0)
    for _ in range(3):
        loss = float(step.step(rng.normal(size=(1, 40, 16)).astype(np.float32),
                               rng.normal(size=(1, 40, 16)).astype(np.float32)))
        assert np.isfinite(loss)
    stats = step.step_stats()
    assert int(stats["kda_layer/2"][0]) == 3
    assert int(stats["kda_layer_fused/2"][0]) == 3 * fused
    assert _counted("mxnet_kda_layers_total") - before[0] == 3
    assert _counted("mxnet_kda_fused_layers_total") - before[1] == 3 * fused
