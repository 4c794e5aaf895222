"""The GSPMD substrate (mxnet_tpu/sharding/): one mesh object, one
ambient stack, one placement story — ISSUE 10.

Contracts pinned here:

- every mesh spelling (framework ``Mesh``, raw jax mesh, axes dict,
  ambient context, ``mx.tpu(mesh=...)``) normalizes to the SAME jax
  mesh → identical ``NamedSharding``s → identical executables, so a
  dp=8 / megatron-tp train step built from the wrapper is bitwise-
  identical to one built from the raw mesh (the "substrate guarantee");
- ``nd.shard`` / ``arr.reshard`` flow through the engine as async
  pushes and shardings PROPAGATE through eager ops and bulk segments
  (jit specializes per input sharding — an 8-device matmul is ONE
  jitted computation, no per-device loop, no host gather);
- sharded and single-device executions never share a segment-cache
  entry, in memory or on disk (subprocess-verified like the exact/fused
  compile-cache split in test_compile_cache.py);
- ``MXNET_SHARDING_VERIFY`` turns async placement errors into
  synchronous MXNetErrors at the call site.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest
from jax.sharding import NamedSharding

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine as engine_mod, gluon, nd, parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.sharding import (Mesh, P, as_jax_mesh, canonicalize_spec,
                                current_mesh, named_sharding, spec_axes_label,
                                verify_spec)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (conftest sets XLA_FLAGS)")


@pytest.fixture
def eng():
    e = engine_mod.Engine.get()
    e.flush_bulk("test_setup")
    return e


# ---------------------------------------------------------------------------
# Mesh object + ambient stack
# ---------------------------------------------------------------------------


def test_mesh_constructions_all_normalize_to_one_jax_mesh(eight_devices):
    raw = jax.sharding.Mesh(
        np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    wrapped = Mesh(raw)
    from_dict = Mesh({"data": 4, "model": 2})
    rewrapped = Mesh(wrapped)
    assert wrapped == from_dict == rewrapped == raw
    assert hash(wrapped) == hash(from_dict) == hash(raw)
    assert as_jax_mesh(wrapped) is raw
    assert as_jax_mesh(raw) is raw
    assert as_jax_mesh({"data": 4, "model": 2}) == raw
    assert as_jax_mesh(None) is None
    with pytest.raises(TypeError):
        as_jax_mesh(42)


def test_mesh_dict_with_remainder_axis(eight_devices):
    m = Mesh({"data": 2, "model": -1})
    assert dict(m.shape) == {"data": 2, "model": 4}
    assert m.size == 8
    assert m.axis_names == ("data", "model")
    assert m.axis_size("model") == 4
    assert m.axis_size(("data", "model")) == 8
    assert Mesh(None).axis_size("data") == len(jax.devices())


def test_mesh_too_large_raises():
    with pytest.raises(ValueError):
        Mesh({"data": len(jax.devices()) * 2})


def test_ambient_mesh_stack_nests():
    assert current_mesh() is None
    outer, inner = Mesh({"data": 2}), Mesh({"data": 4})
    with outer:
        assert current_mesh() is outer
        with inner:
            assert current_mesh() is inner
        assert current_mesh() is outer
    assert current_mesh() is None


def test_tpu_context_sets_ambient_mesh(eight_devices):
    """mx.tpu(mesh=...) IS a mesh scope — the ISSUE's headline API."""
    ctx = mx.tpu(mesh={"data": 8})
    assert isinstance(ctx.mesh, Mesh)
    with ctx:
        assert current_mesh() == ctx.mesh
        sh = named_sharding(None, P("data"))      # ambient pickup
        assert sh.mesh == ctx.mesh.jax_mesh
    assert current_mesh() is None
    # mesh participates in context identity
    assert ctx != mx.tpu()
    assert ctx == mx.tpu(mesh={"data": 8})
    assert hash(ctx) == hash(mx.tpu(mesh={"data": 8}))
    assert "mesh" in repr(ctx)


def test_named_sharding_requires_some_mesh():
    with pytest.raises(ValueError, match="no mesh"):
        named_sharding(None, P("data"))


def test_canonicalize_spec_forms():
    assert canonicalize_spec(None) == P()
    assert canonicalize_spec("data") == P("data")
    assert canonicalize_spec(("data", None)) == P("data", None)
    assert canonicalize_spec(P("x")) == P("x")
    with pytest.raises(TypeError):
        canonicalize_spec(3.14)


def test_spec_axes_label():
    assert spec_axes_label(P()) == "replicated"
    assert spec_axes_label(None) == "replicated"
    assert spec_axes_label(P("data", None)) == "data"
    assert spec_axes_label(P(("data", "model"), None)) == "data,model"


# ---------------------------------------------------------------------------
# NDArray surface: .sharding / nd.shard / reshard / constraints
# ---------------------------------------------------------------------------


def test_shard_places_and_preserves_values(eight_devices):
    mesh = Mesh({"data": 8})
    x = nd.array(np.arange(64, dtype=np.float32).reshape(8, 8))
    xs = nd.shard(x, P("data"), mesh=mesh)
    assert isinstance(xs.sharding, NamedSharding)
    assert xs.sharding.spec == P("data")
    assert len(xs.sharding.device_set) == 8
    np.testing.assert_array_equal(xs.asnumpy(), x.asnumpy())
    # the source array is untouched (shard copies; reshard mutates)
    assert not isinstance(x.sharding, NamedSharding)


def test_reshard_mutates_in_place(eight_devices):
    mesh = Mesh({"data": 4, "model": 2})
    a = nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    ref = a.asnumpy()
    out = a.reshard(P("data", "model"), mesh=mesh)
    assert out is a
    assert a.sharding.spec == P("data", "model")
    np.testing.assert_array_equal(a.asnumpy(), ref)
    with mesh:
        a.reshard(P(None, "model"))               # ambient mesh pickup
    assert a.sharding.spec == P(None, "model")


def test_reshard_on_taped_array_raises(eight_devices):
    mesh = Mesh({"data": 8})
    a = nd.ones((8, 4))
    a.attach_grad()
    with autograd.record():
        b = a * 2.0
        with pytest.raises(MXNetError, match="taped"):
            b.reshard(P("data"), mesh=mesh)


def test_shard_is_differentiable_under_record(eight_devices):
    mesh = Mesh({"data": 8})
    a = nd.ones((8, 4))
    a.attach_grad()
    with autograd.record():
        b = nd.shard(a * 3.0, P("data"), mesh=mesh)
        loss = (b * b).sum()
    loss.backward()
    np.testing.assert_allclose(a.grad.asnumpy(), np.full((8, 4), 18.0))


def test_with_sharding_constraint(eight_devices):
    """A constraint is an annotation, not a placement op: it applies to
    arrays already resident on the mesh (typically inside a traced
    body), pinning the layout GSPMD must produce at that point."""
    mesh = Mesh({"data": 8})
    a = nd.shard(nd.array(np.random.RandomState(1).rand(8, 4)
                          .astype(np.float32)), P("data"), mesh=mesh)
    ref = a.asnumpy()
    with mesh:
        b = a.with_sharding_constraint(P("data"))
    assert b.sharding.spec == P("data")
    assert len(b.sharding.device_set) == 8
    np.testing.assert_array_equal(b.asnumpy(), ref)


# ---------------------------------------------------------------------------
# propagation: eager ops and bulk segments inherit input shardings
# ---------------------------------------------------------------------------

_PROPAGATION_CASES = [
    ("elementwise_chain", lambda xs, w: xs * 2.0 + 1.0, P("data", None)),
    ("matmul_row_sharded", lambda xs, w: nd.dot(xs, w), P("data", None)),
    ("reduce_keeps_batch_axis", lambda xs, w: xs.sum(axis=1), P("data")),
    ("relu_activation", lambda xs, w: nd.relu(xs - 0.5), P("data", None)),
]


@pytest.mark.parametrize("name,fn,expect_spec", _PROPAGATION_CASES,
                         ids=[c[0] for c in _PROPAGATION_CASES])
def test_sharding_propagates_through_ops(eight_devices, name, fn,
                                         expect_spec):
    """GSPMD propagation is free: jit specializes per input sharding, so
    the sharded result of op N feeds op N+1 without any framework code."""
    mesh = Mesh({"data": 8})
    rs = np.random.RandomState(3)
    x = rs.rand(8, 16).astype(np.float32)
    w = rs.rand(16, 4).astype(np.float32)
    ref = fn(nd.array(x), nd.array(w)).asnumpy()

    # every operand lives on the mesh (replicated counts) — the same
    # "one context per op" contract as the reference; docs/sharding.md
    xs = nd.shard(nd.array(x), P("data", None), mesh=mesh)
    ws = nd.shard(nd.array(w), P(), mesh=mesh)
    out = fn(xs, ws)
    assert isinstance(out.sharding, NamedSharding), name
    assert out.sharding.spec == expect_spec
    assert len(out.sharding.device_set) == 8
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-6, atol=1e-6)


def test_propagation_through_bulk_segment(eight_devices, eng):
    """A 12-op bulked chain on sharded input flushes as ONE push and its
    output keeps the NamedSharding."""
    mesh = Mesh({"data": 8})
    x = nd.shard(nd.ones((8, 8)), P("data"), mesh=mesh)
    nd.waitall()
    p0 = eng.stats.ops_pushed
    with engine_mod.bulk(16):
        y = x
        for i in range(12):
            y = (y + 1.0) if i % 2 else (y * 0.5)
    out = y.asnumpy()
    assert eng.stats.ops_pushed == p0 + 1
    assert y.sharding.spec == P("data")
    assert len(y.sharding.device_set) == 8
    ref = np.ones((8, 8))
    for i in range(12):
        ref = (ref + 1.0) if i % 2 else (ref * 0.5)
    np.testing.assert_allclose(out, ref)


def test_sharded_matmul_is_one_jitted_computation(eight_devices, eng):
    """The ISSUE acceptance line: an 8-device sharded matmul dispatches
    as exactly one engine push whose output lives sharded across all 8
    devices — no gather, no per-device loop."""
    mesh = Mesh({"data": 8})
    a = nd.shard(nd.array(np.random.RandomState(5).rand(8, 64)
                          .astype(np.float32)), P("data", None), mesh=mesh)
    b = nd.shard(nd.array(np.random.RandomState(6).rand(64, 32)
                          .astype(np.float32)), P(), mesh=mesh)
    nd.waitall()
    p0 = eng.stats.ops_pushed
    c = nd.dot(a, b)
    c.wait_to_read()
    assert eng.stats.ops_pushed == p0 + 1
    assert len(c.sharding.device_set) == 8
    np.testing.assert_allclose(
        c.asnumpy(), a.asnumpy() @ b.asnumpy(), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# segment cache: sharded and single-device traces never cross-hit
# ---------------------------------------------------------------------------


def _cache_chain(x, n=10):
    y = x
    for i in range(n):
        y = (y + 1.0) if i % 2 else (y * 1.5)
    return y


def test_segment_cache_keys_on_placement(eight_devices, eng):
    """Same op structure, different placement → different in-memory
    segment-cache entries (the PR's engine fix: placements ride in the
    flush key unconditionally)."""
    mesh = Mesh({"data": 8})
    stats = engine_mod._seg_cache_stats

    def run(sharded):
        x = nd.ones((8, 8))
        if sharded:
            x = nd.shard(x, P("data"), mesh=mesh)
        nd.waitall()
        h0, m0 = stats["hits"], stats["misses"]
        with engine_mod.bulk(16):
            y = _cache_chain(x)
        y.wait_to_read()
        return stats["hits"] - h0, stats["misses"] - m0

    assert run(sharded=False) == (0, 1)     # cold: traced
    assert run(sharded=False) == (1, 0)     # identical placement: hit
    assert run(sharded=True) == (0, 1)      # sharded: MUST NOT hit
    assert run(sharded=True) == (1, 0)      # sharded steady state: hit
    assert run(sharded=False) == (1, 0)     # original entry still live


_TAPED_CHAIN = r"""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.sharding import Mesh, P

sharded = %r
x = nd.array(np.ones((32, 32), np.float32))
if sharded:
    x = nd.shard(x, P("data"), mesh=Mesh({"data": 8}))
x.attach_grad()
with autograd.record():
    a = x
    for i in range(8):
        a = (a + 1.0) if i %% 2 else (a * 0.5)
    loss = a.sum()
loss.backward()
x.grad.wait_to_read()
print("DONE")
"""


def test_sharded_and_unsharded_artifacts_never_cross_hit(tmp_path):
    """The taped/exact path pins its lowering at build time: an
    unsharded disk artifact served to a sharded run would silently
    compute on the wrong placement.  Subprocess-verified exactly like
    the exact/fused split (test_compile_cache.py)."""
    cache = str(tmp_path / "sh_cache")

    def run(sharded):
        env = dict(os.environ)
        env.update({"MXNET_COMPILE_CACHE": "1",
                    "MXNET_COMPILE_CACHE_DIR": cache,
                    "MXNET_COMPILE_CACHE_MIN_SECS": "0",
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS":
                        "--xla_force_host_platform_device_count=8"})
        r = subprocess.run([sys.executable, "-c", _TAPED_CHAIN % sharded],
                           cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr

    run(sharded=False)                     # single-device entries
    after_plain = set(os.listdir(cache))
    assert after_plain
    run(sharded=True)                      # sharded run: new entries
    after_sharded = set(os.listdir(cache))
    assert after_sharded - after_plain, \
        "sharded chain wrote no new entries — it was served the " \
        "single-device artifact"
    run(sharded=True)                      # steady state: pure cache hit
    assert set(os.listdir(cache)) == after_sharded, \
        "third process re-wrote entries instead of hitting the cache"


# ---------------------------------------------------------------------------
# bitwise parity: substrate spellings vs the legacy raw-mesh path
# ---------------------------------------------------------------------------


def _mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _train_losses(mesh, param_rule=None, steps=3, seed=11):
    """Fresh net + JitTrainStep under ``mesh``; returns per-step losses
    and the final flat parameter vector (both exact float64 copies)."""
    rs = np.random.RandomState(4)
    X = rs.rand(16, 8).astype(np.float32)
    Y = rs.randint(0, 4, 16).astype(np.float32)
    mx.random.seed(seed)
    net = _mlp()
    mx.random.seed(seed)          # pin the step RNG stream too
    step = parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, param_rule=param_rule)
    losses = [float(step.step(nd.array(X), nd.array(Y)))
              for _ in range(steps)]
    step.sync_params()
    flat = np.concatenate([p.data().asnumpy().ravel()
                           for p in net.collect_params().values()])
    return np.asarray(losses), flat


def test_dp8_bitwise_parity_wrapper_vs_raw_mesh(eight_devices):
    """dp=8 via the framework Mesh — explicit, and ambient via
    mx.tpu(mesh=...) — is BITWISE identical to the legacy raw jax mesh:
    every spelling normalizes to one mesh, one set of NamedShardings,
    one executable."""
    raw = jax.sharding.Mesh(np.asarray(jax.devices()[:8]), ("data",))
    legacy_l, legacy_p = _train_losses(raw)
    wrapper_l, wrapper_p = _train_losses(Mesh(raw))
    assert np.array_equal(legacy_l, wrapper_l)
    assert np.array_equal(legacy_p, wrapper_p)
    with mx.tpu(mesh={"data": 8}):
        ambient_l, ambient_p = _train_losses(mesh=None)   # ambient pickup
    assert np.array_equal(legacy_l, ambient_l)
    assert np.array_equal(legacy_p, ambient_p)


def _tp_net():
    net = nn.HybridSequential(prefix="blk_")
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=16,
                         prefix="attn_q_"),
                nn.Dense(16, in_units=32, prefix="attn_o_"),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def test_megatron_tp_bitwise_parity_wrapper_vs_raw_mesh(eight_devices):
    """megatron column/row rules on a 4x2 dp×tp mesh: rule-set built
    from the wrapper == rule-set built from the raw mesh, bitwise."""
    rs = np.random.RandomState(7)
    X = rs.rand(8, 16).astype(np.float32)
    Y = rs.randint(0, 4, 8).astype(np.float32)

    def run(mesh):
        mx.random.seed(13)
        net = _tp_net()
        mx.random.seed(13)
        step = parallel.JitTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh,
            param_rule=parallel.megatron_rule(axis="model", mesh=mesh))
        losses = [float(step.step(nd.array(X), nd.array(Y)))
                  for _ in range(3)]
        step.sync_params()
        flat = np.concatenate([p.data().asnumpy().ravel()
                               for p in net.collect_params().values()])
        return np.asarray(losses), flat

    raw = jax.sharding.Mesh(
        np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    legacy_l, legacy_p = run(raw)
    wrapper_l, wrapper_p = run(Mesh({"data": 4, "model": 2}))
    assert np.array_equal(legacy_l, wrapper_l)
    assert np.array_equal(legacy_p, wrapper_p)
    # sanity: the rule actually sharded the paired projections
    rule = parallel.megatron_rule(axis="model",
                                  mesh=Mesh({"data": 4, "model": 2}))
    assert rule("blk_attn_q_weight", (32, 16)) == P("model", None)
    assert rule("blk_attn_o_weight", (16, 32)) == P(None, "model")


def test_mesh_step_compiler_options_follow_the_devices(eight_devices,
                                                        monkeypatch):
    """The overlap options (PR 28) are the TPU compiler's: a CPU mesh and
    the one-device path hand ``jax.jit`` none, and compile what they
    always compiled (the bitwise parity tests above run through here)."""
    from mxnet_tpu.parallel import train_step as ts

    cpu_mesh = as_jax_mesh(Mesh({"data": 4, "model": 2}))
    assert ts.mesh_compiler_options(None) is None
    assert ts.mesh_compiler_options(cpu_mesh) is None
    seen = []
    real_jit = jax.jit

    def spy(fn, **kwargs):
        seen.append(kwargs)
        return real_jit(fn, **kwargs)

    monkeypatch.setattr(ts.jax, "jit", spy)
    _train_losses(cpu_mesh, steps=1)
    _train_losses(None, steps=1)
    steps = [kw for kw in seen if kw.get("donate_argnums") == (2, 3)]
    assert len(steps) == 2
    assert steps[0]["compiler_options"] is None
    assert "out_shardings" in steps[0]
    assert set(steps[1]) == {"donate_argnums"}


def test_mesh_trained_parameter_keeps_no_gradient_buffer(eight_devices):
    """A mesh step has its gradients inside the step program only, so
    placing a parameter's shards releases gluon's zero-gradient buffer on
    the context device (PR 28: the room the overlapped all-reduces need).
    The parameter still answers ``grad()`` with zeros, ``data()`` still
    reads and ``sync_params()`` still writes; one device keeps it all."""
    X = np.random.RandomState(5).rand(16, 8).astype(np.float32)
    Y = np.zeros(16, np.float32)

    def run(mesh):
        net = _mlp()
        params = list(net.collect_params().values())
        before = [p.data().asnumpy() for p in params]
        step = parallel.JitTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh)
        step.step(nd.array(X), nd.array(Y))
        return step, params, before

    step, params, before = run(Mesh({"data": 8}))
    for p, was in zip(params, before):
        assert p._data._grad is None
        g = p.grad()
        assert g.shape == p.shape and not g.asnumpy().any()
        assert np.array_equal(p.data().asnumpy(), was)     # not yet synced
    # an imperative backward over the same parameters still has where to
    # put its gradients
    with autograd.record():
        loss = step._net(nd.array(X)).sum()
    loss.backward()
    assert all(p.grad().asnumpy().any() for p in params)
    step.sync_params()
    assert all((p.data().asnumpy() != was).any()
               for p, was in zip(params, before))

    _, params, _ = run(None)
    assert all(p._data._grad is not None for p in params)


# ---------------------------------------------------------------------------
# MXNET_SHARDING_VERIFY
# ---------------------------------------------------------------------------


def test_verify_spec_unit():
    mesh = Mesh({"data": 4, "model": 2})
    verify_spec(mesh, P("data", "model"), shape=(8, 8))
    with pytest.raises(MXNetError, match="not an axis"):
        verify_spec(mesh, P("modle"), shape=(8,))
    with pytest.raises(MXNetError, match="rank"):
        verify_spec(mesh, P("data", None, None), shape=(8, 8))
    with pytest.raises(MXNetError, match="not divisible"):
        verify_spec(mesh, P(("data", "model")), shape=(6, 8))
    # shape-free call still validates axis names
    verify_spec(mesh, P(None, "model"))


def test_verify_spec_error_paths_on_dict_mesh():
    """Every verify_spec error path against a raw axes dict — no Mesh
    object, no placement, resolved through as_jax_mesh the same as the
    planner's abstract-mesh spelling."""
    axes = {"data": 4, "model": 2}
    with pytest.raises(MXNetError, match="axis 'expert' .*not an axis"):
        verify_spec(axes, P("expert"))
    with pytest.raises(MXNetError, match="rank"):
        verify_spec(axes, P("data", None), shape=(8,))
    with pytest.raises(MXNetError, match="dim 0 .*not divisible"):
        verify_spec(axes, P(("data", "model")), shape=(12, 4))
    # error message names the failing dim, not just the spec
    with pytest.raises(MXNetError, match="dim 1"):
        verify_spec(axes, P(None, "model"), shape=(8, 7))
    verify_spec(axes, P("data", "model"), shape=(8, 8))   # clean


def test_verify_spec_nested_ambient_meshes(eight_devices, monkeypatch):
    """verify resolves against the INNERMOST ambient mesh; popping the
    context restores the outer mesh's axis vocabulary."""
    monkeypatch.setenv("MXNET_SHARDING_VERIFY", "1")
    with Mesh({"data": 8}):
        with Mesh({"data": 4, "model": 2}):
            verify_spec(current_mesh(), P(None, "model"))
            nd.shard(nd.ones((4, 2)), P("data", "model")).wait_to_read()
            with pytest.raises(MXNetError, match="not divisible"):
                nd.shard(nd.ones((6, 4)), P("data"))
        # inner mesh popped: 'model' is no longer an axis out here
        with pytest.raises(MXNetError, match="not an axis"):
            verify_spec(current_mesh(), P("model"))
        with pytest.raises(MXNetError, match="not divisible"):
            nd.shard(nd.ones((6, 2)), P("data"))
        nd.shard(nd.ones((8, 2)), P("data")).wait_to_read()   # clean


def test_verify_env_gates_shard_calls(eight_devices, monkeypatch):
    mesh = Mesh({"data": 8})
    # off (default): the bad placement is jax's generic ValueError from
    # deep inside device_put dispatch...
    monkeypatch.delenv("MXNET_SHARDING_VERIFY", raising=False)
    with pytest.raises(Exception) as err:
        nd.shard(nd.ones((6, 4)), P("data"), mesh=mesh).wait_to_read()
    assert not isinstance(err.value, MXNetError)
    # ...on: a synchronous MXNetError naming the dim at the call site
    monkeypatch.setenv("MXNET_SHARDING_VERIFY", "1")
    with pytest.raises(MXNetError, match="not divisible"):
        nd.shard(nd.ones((6, 4)), P("data"), mesh=mesh)
    with pytest.raises(MXNetError, match="not divisible"):
        nd.ones((6, 4)).reshard(P("data"), mesh=mesh)
    # clean calls pass with the flag on
    nd.shard(nd.ones((8, 4)), P("data"), mesh=mesh).wait_to_read()


# ---------------------------------------------------------------------------
# serve: KV arena placement
# ---------------------------------------------------------------------------


def test_kv_arena_shards_on_mesh(eight_devices, monkeypatch):
    from mxnet_tpu.serve.arena import PagedKVArena
    from mxnet_tpu.serve.model import KVGeometry

    def geom(**over):
        kw = dict(num_layers=1, num_heads=8, num_kv_heads=8, head_dim=4,
                  units=32, hidden_size=64, vocab_size=32, page_size=4,
                  num_pages=9, max_pages_per_seq=4, max_batch=2,
                  prefill_buckets=(4, 8))
        kw.update(over)
        return KVGeometry(**kw)

    mesh = Mesh({"model": 2})
    spec = P(None, "model", None, None)   # a layer's KV heads on tp axis
    arena = PagedKVArena(geom(), mesh=mesh, kv_spec=spec)
    for (k, _), (v, _) in arena.buffers():
        for buf in (k, v):
            assert isinstance(buf.sharding, NamedSharding)
            assert buf.sharding.spec == spec
            assert len(buf.sharding.device_set) == 2
    # default stays single-device (the AOT executables expect it)
    plain = PagedKVArena(geom())
    assert not isinstance(plain.buffers()[0][0][0].sharding, NamedSharding)
    # MXNET_SHARDING_VERIFY covers the arena too
    monkeypatch.setenv("MXNET_SHARDING_VERIFY", "1")
    with pytest.raises(MXNetError, match="not divisible"):
        PagedKVArena(geom(num_kv_heads=3, num_heads=3),
                     mesh=mesh, kv_spec=spec)


# ---------------------------------------------------------------------------
# telemetry: reshard counters + flight events
# ---------------------------------------------------------------------------


def test_reshard_telemetry_and_flight(eight_devices):
    from mxnet_tpu import telemetry

    mesh = Mesh({"data": 8})
    a = nd.ones((8, 16))
    a.reshard(P("data"), mesh=mesh)
    nd.shard(a, P(), mesh=mesh).wait_to_read()
    text = telemetry.prometheus_text()
    assert 'mxnet_reshard_total{axis="data"}' in text
    assert 'mxnet_reshard_total{axis="replicated"}' in text
    assert "mxnet_reshard_bytes_total" in text
    kinds = [e for e in telemetry.flight.events() if e["kind"] == "reshard"]
    assert kinds, "no reshard flight events recorded"
    last = kinds[-1]
    assert last["origin"] == "shard"
    assert last["bytes"] == 8 * 16 * 4
    assert any(e["axis"] == "data" and e["origin"] == "reshard"
               for e in kinds)
