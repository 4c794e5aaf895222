"""GPipe pipeline parallelism (parallel/pipeline.py) on the virtual mesh.

No reference counterpart (SURVEY §2.4: pipeline parallel absent there);
correctness oracle is the sequential application of the same stages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.test_utils import assert_almost_equal


def _stage(params, x):
    w, b = params
    return jnp.tanh(x @ w + b)


def _sequential(stacked, x):
    w, b = stacked
    out = x
    for i in range(w.shape[0]):
        out = np.tanh(out @ w[i] + b[i])
    return out


@pytest.fixture(scope="module")
def mesh4():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    return parallel.make_mesh({"pp": 4})


def test_gpipe_matches_sequential(mesh4):
    rs = np.random.RandomState(0)
    n, d, m, mb = 4, 8, 6, 3
    w = rs.randn(n, d, d).astype(np.float32) * 0.5
    b = rs.randn(n, d).astype(np.float32) * 0.1
    x = rs.randn(m, mb, d).astype(np.float32)
    out = parallel.gpipe(_stage, (jnp.asarray(w), jnp.asarray(b)),
                         jnp.asarray(x), mesh4)
    expect = _sequential((w, b), x.reshape(m * mb, d)).reshape(m, mb, d)
    assert_almost_equal(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_gpipe_backward_matches_sequential(mesh4):
    rs = np.random.RandomState(1)
    n, d, m, mb = 4, 6, 5, 2
    w = jnp.asarray(rs.randn(n, d, d).astype(np.float32) * 0.5)
    b = jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)
    x = jnp.asarray(rs.randn(m, mb, d).astype(np.float32))
    y = jnp.asarray(rs.randn(m, mb, d).astype(np.float32))

    loss_pipe = parallel.gpipe_loss_fn(
        _stage, lambda o, t: jnp.mean((o - t) ** 2), mesh4)
    # one program: a bare shard_map differentiated eagerly runs its body
    # one operation at a time on every device
    gp = jax.jit(jax.grad(loss_pipe))((w, b), x, y)

    def loss_seq(params, x, y):
        wv, bv = params
        out = x
        for i in range(wv.shape[0]):
            out = jnp.tanh(out @ wv[i] + bv[i])
        return jnp.mean((out - y) ** 2)

    gs = jax.grad(loss_seq)((w, b), x, y)
    for a, e in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        assert_almost_equal(np.asarray(a), np.asarray(e),
                            rtol=1e-4, atol=1e-5)


def test_gpipe_training_converges(mesh4):
    rs = np.random.RandomState(2)
    n, d, m, mb = 4, 6, 4, 4
    w = jnp.asarray(rs.randn(n, d, d).astype(np.float32) * 0.4)
    b = jnp.zeros((n, d), jnp.float32)
    x = jnp.asarray(rs.randn(m, mb, d).astype(np.float32))
    y = jnp.asarray(np.tanh(rs.randn(m, mb, d)).astype(np.float32))
    loss_pipe = parallel.gpipe_loss_fn(
        _stage, lambda o, t: jnp.mean((o - t) ** 2), mesh4)
    vg = jax.jit(jax.value_and_grad(loss_pipe))
    params = (w, b)
    first = None
    for _ in range(30):
        loss, grads = vg(params, x, y)
        if first is None:
            first = float(loss)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.3 * g, params, grads)
    assert float(loss) < 0.5 * first, (first, float(loss))
