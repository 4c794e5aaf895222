"""Expert parallelism (MoE): routing, capacity, dense-vs-sharded parity
(SURVEY §2.4 'Expert parallel' row — new TPU-first design)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.parallel.moe import router_top1


def _inputs(s=32, d=16, e=4, h=32, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(s, d), jnp.float32),
            jnp.asarray(rs.randn(d, e) * 0.3, jnp.float32),
            jnp.asarray(rs.randn(e, d, h) * 0.2, jnp.float32),
            jnp.asarray(rs.randn(e, h, d) * 0.2, jnp.float32))


def test_router_top1_dispatch_properties():
    x, rw, _, _ = _inputs()
    dispatch, combine, aux = router_top1(x, rw, 4, capacity=16)
    d = np.asarray(dispatch)
    # each token goes to at most one (expert, slot)
    assert (d.sum(axis=(1, 2)) <= 1.0 + 1e-6).all()
    # no capacity slot is double-booked
    assert (d.sum(axis=0) <= 1.0 + 1e-6).all()
    # combine carries the gate prob exactly where dispatch is 1
    c = np.asarray(combine)
    assert ((c > 0) <= (d > 0)).all()
    assert float(aux) > 0


def test_capacity_drops_overflow_tokens():
    x, rw, wi, wo = _inputs(s=64)
    y_small, _ = parallel.moe_ffn(x, rw, wi, wo, capacity_factor=0.25)
    y_big, _ = parallel.moe_ffn(x, rw, wi, wo, capacity_factor=4.0)
    # tight capacity zeroes some tokens' outputs
    small_norms = np.linalg.norm(np.asarray(y_small), axis=1)
    big_norms = np.linalg.norm(np.asarray(y_big), axis=1)
    assert (small_norms < 1e-7).sum() > (big_norms < 1e-7).sum()


def test_dense_matches_manual_top1():
    """With generous capacity, each token's output equals gate * its
    chosen expert's MLP output."""
    x, rw, wi, wo = _inputs(s=8)
    y, _ = parallel.moe_ffn(x, rw, wi, wo, capacity_factor=8.0)
    probs = np.asarray(jax.nn.softmax(x @ rw, axis=-1))
    for t in range(8):
        e = int(np.argmax(probs[t]))
        h = np.asarray(jax.nn.gelu(np.asarray(x)[t] @ np.asarray(wi)[e]))
        expect = probs[t, e] * (h @ np.asarray(wo)[e])
        np.testing.assert_allclose(np.asarray(y)[t], expect,
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_sharded_parity_and_errors():
    x, rw, wi, wo = _inputs()
    y_ref, aux_ref = parallel.moe_ffn(x, rw, wi, wo)
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    # the sharded layer as one program: a bare shard_map called eagerly runs
    # its body one operation at a time on every device
    sharded = jax.jit(lambda *a: parallel.moe_ffn_sharded(*a, mesh))
    y_sh, aux_sh = sharded(x, rw, wi, wo)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)
    assert abs(float(aux_sh) - float(aux_ref)) < 1e-6
    # GRADIENT parity dense vs sharded (shard_map+psum transpose path)
    def loss_dense(wi_, wo_, rw_):
        y, aux = parallel.moe_ffn(x, rw_, wi_, wo_)
        return jnp.sum(y * y) + 0.01 * aux

    def loss_sharded(wi_, wo_, rw_):
        y, aux = sharded(x, rw_, wi_, wo_)
        return jnp.sum(y * y) + 0.01 * aux

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(wi, wo, rw)
    gs = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(wi, wo, rw)
    for a, b in zip(gd, gs):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)

    mesh3 = Mesh(np.array(jax.devices()[:3]), ("expert",))
    with pytest.raises(mx.MXNetError, match="divide"):
        parallel.moe_ffn_sharded(x, rw, wi, wo, mesh3)


def test_moe_gradients_flow_to_experts_and_router():
    x, rw, wi, wo = _inputs()

    def loss(rw_, wi_, wo_):
        y, aux = parallel.moe_ffn(x, rw_, wi_, wo_)
        return jnp.sum(y * y) + 0.01 * aux

    g_rw, g_wi, g_wo = jax.grad(loss, argnums=(0, 1, 2))(rw, wi, wo)
    for g in (g_rw, g_wi, g_wo):
        assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(g_wi).max()) > 0
    assert float(jnp.abs(g_rw).max()) > 0  # aux loss reaches the router
