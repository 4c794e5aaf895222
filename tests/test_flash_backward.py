"""Blocked flash-attention backward: gradient correctness + memory shape.

VERDICT r2 item 4: the backward must be the two-pass blocked kernel (dq
pass + dk/dv pass), differentiated against the plain-XLA reference at
several (T, D, causal) points, with no (T, T) buffer in the compiled HLO
at long T.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk


def _ref_grads(q, k, v, do, scale, causal):
    _, vjp = jax.vjp(
        lambda a, b, c: pk._attention_ref(a, b, c, scale, causal), q, k, v)
    return vjp(do)


def _flash_grads(q, k, v, do, scale, causal, bq, bk):
    _, vjp = jax.vjp(
        lambda a, b, c: pk._flash_attention(a, b, c, scale, causal, bq, bk),
        q, k, v)
    return vjp(do)


@pytest.mark.parametrize("t,d,causal,bq,bk", [
    (32, 16, False, 8, 8),
    (64, 32, True, 16, 16),
    (64, 8, True, 8, 32),
    (128, 64, False, 32, 16),
])
def test_flash_backward_matches_reference(t, d, causal, bq, bk):
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, t, d), jnp.float32) * 0.5
    k = jnp.asarray(rs.randn(2, t, d), jnp.float32) * 0.5
    v = jnp.asarray(rs.randn(2, t, d), jnp.float32)
    do = jnp.asarray(rs.randn(2, t, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    ref = _ref_grads(q, k, v, do, scale, causal)
    got = _flash_grads(q, k, v, do, scale, causal, bq, bk)
    for name, r, g in zip("qkv", ref, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-4,
            err_msg="d%s mismatch (t=%d d=%d causal=%s)" % (
                name, t, d, causal))


def test_flash_backward_finite_difference():
    """Independent FD check of the full custom_vjp chain on a tiny case."""
    rs = np.random.RandomState(1)
    t, d = 16, 8
    q0 = rs.randn(1, t, d).astype(np.float32) * 0.3
    k0 = rs.randn(1, t, d).astype(np.float32) * 0.3
    v0 = rs.randn(1, t, d).astype(np.float32)
    scale = 1.0 / np.sqrt(d)

    def f(q):
        out = pk._flash_attention(q, jnp.asarray(k0), jnp.asarray(v0),
                                  scale, True, 8, 8)
        return jnp.sum(out * out)

    g = np.asarray(jax.grad(f)(jnp.asarray(q0)))
    eps = 1e-3
    for idx in [(0, 0, 0), (0, 5, 3), (0, 15, 7), (0, 9, 1)]:
        qp, qm = q0.copy(), q0.copy()
        qp[idx] += eps
        qm[idx] -= eps
        fd = (float(f(jnp.asarray(qp))) - float(f(jnp.asarray(qm)))) \
            / (2 * eps)
        assert abs(fd - g[idx]) < 5e-2 * max(1.0, abs(fd)), (idx, fd, g[idx])


def test_flash_backward_no_quadratic_buffer():
    """The compiled train-direction HLO at T=4096 must not contain any
    (T, T) f32/bf16 buffer — the flash property, forward AND backward."""
    t, d = 4096, 64

    def loss(q, k, v):
        out = pk._flash_attention(q, k, v, 0.125, True, 128, 128)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    shapes = [jax.ShapeDtypeStruct((1, t, d), jnp.float32)] * 3
    txt = g.lower(*shapes).compile().as_text()
    assert "%dx%d" % (t, t) not in txt.replace(",", "x"), \
        "quadratic buffer found in compiled HLO"
    assert "4096,4096" not in txt, "quadratic buffer found in compiled HLO"


def test_flash_backward_bf16_inputs():
    rs = np.random.RandomState(2)
    t, d = 64, 32
    q = jnp.asarray(rs.randn(2, t, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(2, t, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(2, t, d), jnp.bfloat16)
    do = jnp.asarray(rs.randn(2, t, d), jnp.bfloat16)
    scale = 1.0 / np.sqrt(d)
    got = _flash_grads(q, k, v, do, scale, True, 16, 16)
    ref = _ref_grads(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), do.astype(jnp.float32),
                     scale, True)
    for name, r, g in zip("qkv", ref, got):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r), rtol=0.1, atol=0.15,
            err_msg="d%s bf16 mismatch" % name)


def test_flash_gluon_training_path():
    """nd.contrib.flash_attention backward flows through the tape."""
    from mxnet_tpu import autograd

    rs = np.random.RandomState(3)
    q = mx.nd.array(rs.randn(2, 2, 32, 16).astype(np.float32))
    q.attach_grad()
    with autograd.record():
        out = mx.nd.contrib.flash_attention(q, q, q, causal=True,
                                            block_q=8, block_k=8)
        loss = (out * out).sum()
    loss.backward()
    g = q.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_flash_per_shard_on_a_mesh_matches_one_device():
    """Traced under a context mesh (JitTrainStep with a mesh does that)
    the kernel runs per shard inside shard_map — batch over `data`, heads
    over `model` — and must give what the one-device kernel gives,
    forward and grads; an eager trace of the op made before must not be
    reused there."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    rs = np.random.RandomState(3)
    q, k, v, w = (jnp.asarray(rs.randn(2, 4, 32, 8), jnp.float32) * 0.5
                  for _ in range(4))

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True, block_q=8,
                                 block_k=16)
        return (out * w).sum()

    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want = both(q, k, v)
    sharded = NamedSharding(mesh, P("data", "model", None, None))
    with jax.set_mesh(mesh):
        got = both(*(jax.device_put(x, sharded) for x in (q, k, v)))
        text = both.lower(q, k, v).as_text()
    assert "shard_map" in text or "manual" in text
    assert got[1][0].sharding.is_equivalent_to(sharded, 4)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("manual", [("data", "model"), ("data",)],
                         ids=["all-axes-manual", "data-manual-model-auto"])
def test_flash_inside_a_callers_shard_map(manual):
    """Inside a caller's own shard_map (parallel.shard_map, a gpipe
    stage, moe) the mesh's axes are already manual: the op must not wrap
    itself over them a second time, only over what is left to GSPMD."""
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu import parallel

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    rs = np.random.RandomState(4)
    q, k, v, w = (jnp.asarray(rs.randn(2, 4, 32, 8), jnp.float32) * 0.5
                  for _ in range(4))

    def attend(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=8,
                                  block_k=16)

    spec = P("data", "model" if "model" in manual else None, None, None)
    if len(manual) == 2:
        mapped = parallel.shard_map(attend, mesh, (spec,) * 3, spec)
    else:
        mapped = jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, axis_names=set(manual),
                               check_vma=False)

    def loss(f, q, k, v):
        return (f(q, k, v) * w).sum()

    want = jax.jit(jax.value_and_grad(
        lambda *a: loss(attend, *a), argnums=(0, 1, 2)))(q, k, v)
    got = jax.jit(jax.value_and_grad(
        lambda *a: loss(mapped, *a), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_flash_per_shard_takes_the_steps_batch_axis():
    """The batch goes over `sharding.batch_axis` (JitTrainStep sets it to
    its data_axis), whatever the axis is called; an axis that neither
    batch nor heads divide over is warned about, not silently
    replicated."""
    import warnings
    from jax.sharding import Mesh
    from mxnet_tpu import sharding

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(2, 2, 32, 8), jnp.float32) * 0.5

    def attend(q):
        return pk.flash_attention(q, q, q, causal=True, block_q=8,
                                  block_k=16)

    want = attend(q)
    with jax.set_mesh(mesh), sharding.batch_axis("dp"), \
            warnings.catch_warnings():
        warnings.simplefilter("error")
        text = jax.jit(attend).lower(q).as_text()
        got = jax.jit(attend)(q)
    assert '{"dp"}, {"tp"}' in text, text[:2000]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # no axis called "data" here, and 2 heads do not divide over 4
    with jax.set_mesh(mesh), pytest.warns(UserWarning, match="replicated"):
        got = jax.jit(attend)(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
