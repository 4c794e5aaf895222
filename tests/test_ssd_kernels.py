"""The Mamba-2 scan's Pallas kernels (``ops/ssd_kernels.py``: ``mx_ssd_fwd``,
``mx_ssd_bwd``) in the interpreter, at tiling shapes kept small (one or two
groups of two heads x 64 channels, 128 states, chunks of 128, 256 to 320
tokens, batch 2), against the recurrence they compute and against the
``jax.numpy`` form of the same algebra.

Tolerances.  The kernels round the operands of every product to bfloat16, as
a TPU's default-precision ``einsum`` does and this CPU's does not: 2e-2 of
the largest value against the float32 recurrence.  With the products at
float32 too (``_dot`` patched to ``_dot32`` by the test: same kernels, same
algebra) the two agree to 1e-4, which is what holds the algebra.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import amp, gluon, parallel
from mxnet_tpu.gluon.model_zoo import nemotron_h
from mxnet_tpu.ops import kda_kernels, pallas_kernels, ssd_kernels, ssm
from mxnet_tpu.telemetry import metrics


def _inputs(t, b=2, h=2, p=64, g=1, n=128, seed=0, dt_high=0.1):
    """At the published ranges: ``A`` in [1, 16], ``dt`` (after the bias
    and the softplus) up to about ``dt_high``."""
    rng = np.random.default_rng(seed)
    dt_bias = np.log(np.expm1(rng.uniform(0.001, dt_high, (1, h))))
    vals = (rng.normal(size=(b, t, h, p)), rng.normal(size=(b, t, h)) * 0.5,
            np.log(rng.uniform(1, 16, (1, h))),
            rng.normal(size=(b, t, g, n)) * n ** -0.25,
            rng.normal(size=(b, t, g, n)) * n ** -0.25,
            rng.normal(size=(h,)), dt_bias)
    return tuple(jnp.asarray(v, jnp.float32) for v in vals)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), \
        (np.abs(a - b).max(), np.abs(b).max())


def _einsums(*args):
    return ssm._ssd_chunked(*args, chunk=128)


def _value_and_grads(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a)) * w), argnums=range(7)))(*args)


def _against(others, args, tol, but=()):
    """The result and all seven gradients (``but`` those named) against
    each of ``others``."""
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape),
                    jnp.float32)
    out = ssm.ssd_scan(*args)
    value, grads = _value_and_grads(ssm.ssd_scan, args, w)
    for other in others:
        _close(out, other(*args), tol)
        want_value, want = _value_and_grads(other, args, w)
        _close(value, want_value, tol)
        for i, (a, b) in enumerate(zip(grads, want)):
            if i not in but:
                _close(a, b, tol)
    return out, grads


def _takes_the_kernels(*args, **kw):
    text = str(jax.make_jaxpr(
        jax.grad(lambda *a: jnp.sum(ssm.ssd_scan(*a, **kw))))(*args))
    assert ("mx_ssd_fwd" in text) == ("mx_ssd_bwd" in text)
    return "mx_ssd_fwd" in text


@pytest.fixture
def exact_products(monkeypatch):
    # ``_platform_pick`` keeps what it jitted: neither what an earlier test
    # traced is this one's, nor what this one traces the next one's
    pallas_kernels._JIT_CACHE.clear()
    monkeypatch.setattr(ssd_kernels, "_dot", kda_kernels._dot32)
    yield
    pallas_kernels._JIT_CACHE.clear()


@pytest.mark.parametrize("groups", [1, 2], ids=["one_group", "two_groups"])
@pytest.mark.parametrize("t", [256, 320], ids=["whole_chunks", "2.5_chunks"])
def test_the_algebra_is_the_recurrences(t, groups, exact_products):
    # every product at float32: forward and all seven gradients to 1e-4
    args = _inputs(t, h=2 * groups, g=groups)
    assert _takes_the_kernels(*args)
    _against((ssm.ssd_recurrence, _einsums), args, 1e-4)


@pytest.mark.parametrize("t", [256, 320], ids=["whole_chunks", "2.5_chunks"])
def test_bfloat16_products_stay_near_the_recurrence_and_the_einsums(t):
    # the kernels as the chip runs them; the jax.numpy form at the same
    # inputs (float32 products on this CPU) is as near as the recurrence
    args = _inputs(t, h=4, g=2, seed=3)
    _, grads = _against((ssm.ssd_recurrence, _einsums), args, 2e-2)
    # what reaches ``A_log`` and ``dt_bias`` is summed over every token: the
    # rounded products' errors must not add up on the way
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape),
                    jnp.float32)
    _, want = _value_and_grads(ssm.ssd_recurrence, args, w)
    for i in (2, 5, 6):
        miss = np.asarray(grads[i] - want[i], np.float64)
        assert np.linalg.norm(miss) < 2e-2 * np.linalg.norm(want[i])


@pytest.mark.parametrize("dt_high", [0.1, 30.0],
                         ids=["published", "a_chunk_decays_to_nothing"])
def test_the_published_ranges_overflow_nothing(dt_high, exact_products):
    # A in [1, 16] and dt up to 0.1: a chunk's running sum reaches -200,
    # past what float32's exp holds (-88); dt near 30: every token's decay
    # underflows and the state is the last token's alone
    args = _inputs(256, seed=5, dt_high=dt_high)
    underflows = dt_high > 1
    # where every decay underflows nothing depends on ``A`` any more, and
    # what reaches ``A_log`` and ``dt_bias`` through the running sum is what
    # float32 leaves of a token's own share cancelling (1e-7 of terms near
    # 30, which this CPU's fused multiply-add does not round alike) times
    # dt A (350) over 512 tokens: 1e-3 of ``dt_bias``'s gradient, and all
    # there is of ``A_log``'s
    out, grads = _against((ssm.ssd_recurrence,), args,
                          1e-3 if underflows else 1e-4,
                          but=(2,) if underflows else ())
    assert bool(jnp.isfinite(out).all())
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    if underflows:
        dt = jax.nn.softplus(args[1] + args[6])
        assert float(jnp.exp(-(dt * jnp.exp(args[2])).min())) == 0.0
        assert float(jnp.abs(grads[2]).max()) \
            < 1e-2 * float(jnp.abs(grads[6]).max())


def test_bfloat16_operands_are_read_and_written_as_they_are():
    """``x, B, C`` in bfloat16: the kernels cast a block as they load it,
    ``y`` and the three cotangents come back in bfloat16, the others in
    float32, near what float32 operands give."""
    args = _inputs(256, seed=7)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    assert _takes_the_kernels(*low)

    def f(*a):
        return jnp.sum(jnp.sin(ssm.ssd_scan(*a).astype(jnp.float32)))
    y = ssm.ssd_scan(*low)
    grads = jax.grad(f, argnums=range(7))(*low)
    assert y.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [a.dtype for a in low]
    _close(y.astype(jnp.float32), ssm.ssd_scan(*args), 3e-2)
    for a, b in zip(grads, jax.grad(f, argnums=range(7))(*args)):
        _close(a.astype(jnp.float32), b, 5e-2)


class _Net(gluon.HybridBlock):
    """One Mamba-2 mixer of two heads in one group, 128 states."""

    def __init__(self, head_dim):
        super().__init__()
        self.mixer = nemotron_h.Mamba2Mixer(16, 2, head_dim, 1, 128, layer=3)

    def hybrid_forward(self, F, x):
        return self.mixer(x)


def _steps(head_dim, tokens, steps=2):
    net = _Net(head_dim)
    net.initialize()
    net.hybridize()
    step = parallel.JitTrainStep(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = np.random.default_rng(0)
    losses = [float(step.step(
        rng.normal(size=(2, tokens, 16)).astype(np.float32),
        rng.normal(size=(2, tokens, 16)).astype(np.float32)))
        for _ in range(steps)]
    return step, losses


def test_the_mixer_trains_under_amp_through_the_kernels():
    # bfloat16 activations around the scan, float32 inside it (FP32_OPS)
    _, plain = _steps(64, 160)
    amp.init("bfloat16")
    try:
        _, low = _steps(64, 160)
    finally:
        amp.turn_off()
    assert "_contrib_ssd_scan" in amp.lists.FP32_OPS
    assert np.isfinite(low).all()
    assert np.allclose(low, plain, rtol=2e-2)


def test_the_path_is_a_static_test_of_the_shapes():
    # the tier-1 models' tiny heads and states: the jax.numpy form; a
    # group's heads of whole 128-lane rows and 128 states: the kernels;
    # another chunk than 128, a group narrower than a row, 64 states: not
    assert not _takes_the_kernels(*_inputs(32, h=4, p=8, g=2, n=16), chunk=16)
    assert _takes_the_kernels(*_inputs(32))
    assert _takes_the_kernels(*_inputs(32, h=8, p=32, g=2, n=256))
    assert not _takes_the_kernels(*_inputs(32), chunk=64)
    assert not _takes_the_kernels(*_inputs(32, h=2, g=2))
    assert not _takes_the_kernels(*_inputs(32, n=64))
    assert ssd_kernels.tiles(64, 64, 8, 128, 128)
    assert not ssd_kernels.tiles(64, 64, 8, 128, 256)
    assert ssm.ssd_kernel_chunks(2048, 64, 64, 8, 128) == 16
    assert ssm.ssd_kernel_chunks(2000, 64, 64, 8, 128) == 16
    assert ssm.ssd_kernel_chunks(2048, 4, 8, 2, 16, 16) == 0
    assert ssm.ssd_chunks(2048, 16) == 128


def _counted(name):
    series = metrics.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series)


@pytest.mark.parametrize("head_dim, kernel", [(8, 0), (64, 1)],
                         ids=["8_channels", "64_channels"])
def test_the_counter_counts_the_chunks_the_kernels_ran(head_dim, kernel):
    """A train step over one Mamba-2 mixer: ``mxnet_ssd_chunks_total`` counts
    sequences x heads x chunks; ``mxnet_ssd_kernel_chunks_total`` the same
    where the shapes tile and nothing where they do not."""
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = [_counted("mxnet_ssd_chunks_total"),
              _counted("mxnet_ssd_kernel_chunks_total")]
    step, losses = _steps(head_dim, 160)
    assert np.isfinite(losses).all()
    stats = step.step_stats()
    # two sequences x two heads x two chunks of 128, two steps
    assert int(stats["ssd/3"][0]) == 2 * 2 * 2 * 2
    assert int(stats["ssd_kernel/3"][0]) == kernel * 2 * 2 * 2 * 2
    assert _counted("mxnet_ssd_chunks_total") - before[0] == 16
    assert _counted("mxnet_ssd_kernel_chunks_total") - before[1] \
        == kernel * 16
