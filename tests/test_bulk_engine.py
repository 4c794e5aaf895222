"""Op bulking (BulkEngine / engine.bulk): semantics pinned by ISSUE 4/6.

The contract under test: consecutive deferrable imperative ops collect
into ONE engine push (a jitted, XLA-fused segment), lazy outputs carry
eval_shape avals until a sync point flushes them, numerics and version
bumps are indistinguishable from the eager engine, failed segments poison
their outputs through ``Var.set_exception`` (async rethrow), and repeated
identical streams hit the segment cache without retracing.

ISSUE 6 extensions: BulkEngine is the DEFAULT engine (cap 64),
``autograd.record()`` no longer flushes at the boundary (taped ops defer
and the tape resolves promises at backward time, with grads
bitwise-identical to eager), dead input buffers are donated to XLA, and
the segment cache is size-tiered with per-tier LRU budgets.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu import engine as engine_mod
from mxnet_tpu.base import MXNetError
from mxnet_tpu.engine import Engine


@pytest.fixture
def eng():
    e = Engine.get()
    e.flush_bulk("test_setup")
    return e


def _chain(x, n=20):
    y = x
    for i in range(n):
        y = (y + 1.0) if i % 2 else (y * 1.5)
    return y


def test_20_op_chain_is_one_push_bit_identical(eng):
    x = nd.ones((8, 8))
    ref = _chain(x).asnumpy()  # eager
    p0, b0, s0 = (eng.stats.ops_pushed, eng.stats.bulk_ops,
                  eng.stats.bulk_segments)
    with engine_mod.bulk(32):
        y = _chain(x)
        # nothing dispatched yet: the whole chain is deferred
        assert eng.stats.ops_pushed == p0
        assert y._pending is not None
    out = y.asnumpy()  # scope exit flushed; read resolves the promise
    assert eng.stats.ops_pushed == p0 + 1
    assert eng.stats.bulk_ops == b0 + 20
    assert eng.stats.bulk_segments == s0 + 1
    assert np.array_equal(out, ref), "bulked numerics differ from eager"


def test_lazy_ndarray_carries_aval_without_flushing(eng):
    with engine_mod.bulk(16):
        y = nd.ones((3, 4), dtype="float32") + 1.0
        p0 = eng.stats.ops_pushed
        # shape/dtype/size/ndim come from jax.eval_shape, not a flush
        assert y.shape == (3, 4)
        assert str(y.dtype) == "float32"
        assert y.size == 12 and y.ndim == 2 and len(y) == 3
        assert eng.stats.ops_pushed == p0


@pytest.mark.parametrize("sync", [
    "asnumpy", "wait_to_read", "waitall", "float", "bool", "getitem",
    "setitem", "repr", "array",
])
def test_segment_flushes_at_every_sync_point(eng, sync):
    with engine_mod.bulk(64):
        y = (nd.ones((2, 2)) + 1.0) * 2.0
        p0 = eng.stats.ops_pushed
        if sync == "asnumpy":
            y.asnumpy()
        elif sync == "wait_to_read":
            y.wait_to_read()
        elif sync == "waitall":
            mx.nd.waitall()
        elif sync == "float":
            float(y.sum())
        elif sync == "bool":
            bool(y.sum() > 0)
        elif sync == "getitem":
            y[0, 0].asnumpy()
        elif sync == "setitem":
            y[0, 0] = 7.0
        elif sync == "repr":
            repr(y)
        elif sync == "array":
            np.asarray(y)
        assert eng.stats.ops_pushed > p0, "%s did not flush" % sync
        assert np.asarray(y.data()).flat[-1] == 4.0


def test_autograd_recording_does_not_flush(eng):
    # ISSUE 6: the record() boundary is NOT a segment boundary — taped ops
    # defer too, and backward resolves the promises by flushing on demand
    w = nd.ones((3,))
    w.attach_grad()
    with engine_mod.bulk(64):
        c = nd.ones((3,)) * 2.0 + 1.0
        p0 = eng.stats.ops_pushed
        with autograd.record():
            assert eng.stats.ops_pushed == p0, \
                "entering record() must not flush the pending segment"
            loss = (w * c).sum()
        assert eng.stats.ops_pushed == p0, \
            "leaving record() must not flush either"
        s0 = eng.stats.bulk_segments
        loss.backward()  # backward-triggered flush: ONE fused push
        assert eng.stats.bulk_segments == s0 + 1
    np.testing.assert_allclose(w.grad.asnumpy(), 3.0)


def _recorded_chain_grads(x_np, bulk_cap, n=20):
    x = nd.array(x_np)
    x.attach_grad()
    with engine_mod.bulk(bulk_cap):
        with autograd.record():
            y = x
            for i in range(n):
                y = y * 1.25 if i % 2 == 0 else y + 0.5
            loss = (y * y).sum()
        loss.backward()
    return x.grad.asnumpy(), y.asnumpy()


def test_recorded_20op_chain_one_segment_bitwise_grads(eng):
    xv = np.random.RandomState(11).randn(8, 8).astype(np.float32)
    g_eager, y_eager = _recorded_chain_grads(xv, 0)
    s0 = eng.stats.bulk_segments
    g_bulk, y_bulk = _recorded_chain_grads(xv, 64)
    # chain + loss deferred into ONE segment, flushed by backward
    # (array/grad-buffer creation pushes eagerly and forms no segment)
    assert eng.stats.bulk_segments == s0 + 1
    assert np.array_equal(y_bulk, y_eager), \
        "bulked recorded forward differs bitwise from eager"
    assert np.array_equal(g_bulk, g_eager), \
        "grads through a bulked forward differ bitwise from eager"


def test_recorded_mixed_ops_bitwise_grads(eng):
    # matmul + tanh + broadcast: the exact-compile path must pin every
    # op's rounding, not just elementwise chains.  tanh is the one that
    # tells: XLA:CPU emits it as a polynomial whose multiply-adds only an
    # optimizing backend contracts, so a segment compiled at backend
    # level 0 read 1-2 ulp off the op's own executable, forward first
    rs = np.random.RandomState(3)
    xv, wv = (rs.randn(8, 8).astype(np.float32) for _ in range(2))

    def run(cap):
        x, w = nd.array(xv), nd.array(wv)
        x.attach_grad()
        w.attach_grad()
        with engine_mod.bulk(cap):
            with autograd.record():
                h = nd.tanh(nd.dot(x, w)) * 1.25 + 0.5
                loss = (h * h).sum()
            loss.backward()
        return h.asnumpy(), x.grad.asnumpy(), w.grad.asnumpy()

    for eager, bulked in zip(run(0), run(64)):
        assert np.array_equal(eager, bulked)


def test_higher_order_grads_through_segment_smoke(eng):
    xv = np.random.RandomState(5).randn(4, 4).astype(np.float32)

    def run(cap):
        x = nd.array(xv)
        x.attach_grad()
        with engine_mod.bulk(cap):
            with autograd.record():
                y = x * x * x
                loss = y.sum()
            g = autograd.grad(loss, [x], create_graph=True)[0]
            with autograd.record():
                g2 = (g * g).sum()
            g2.backward()
        return x.grad.asnumpy()

    assert np.array_equal(run(0), run(64))


def test_var_version_bumps_match_eager(eng):
    a = nd.ones((2, 2))
    v0 = a._var.version
    with engine_mod.bulk(16):
        a += 1.0  # deferred, but the write is visible NOW
        assert a._var.version == v0 + 1
        a *= 2.0
        assert a._var.version == v0 + 2
        # out= bumps the destination at call time too
        dst = nd.zeros((2, 2))
        d0 = dst._var.version
        nd.broadcast_add(a, a, out=dst)
        assert dst._var.version == d0 + 1
    np.testing.assert_allclose(a.asnumpy(), 4.0)
    np.testing.assert_allclose(dst.asnumpy(), 8.0)


def test_failed_segment_poisons_all_outputs(eng, monkeypatch):
    orig = Engine.push

    def failing(self, fn, *args, **kwargs):
        if (kwargs.get("op_name") or "").startswith("bulk_segment["):
            raise RuntimeError("segment boom")
        return orig(self, fn, *args, **kwargs)

    monkeypatch.setattr(Engine, "push", failing)
    with engine_mod.bulk(16):
        a = nd.ones((2,)) + 1.0
        b = a * 3.0
        with pytest.raises(RuntimeError, match="segment boom"):
            b.asnumpy()
        # the sibling output's var was poisoned: async rethrow at ITS read
        with pytest.raises(RuntimeError, match="segment boom"):
            a.asnumpy()
        # after the rethrow the value is permanently gone
        with pytest.raises(MXNetError, match="deferred NDArray lost"):
            a.asnumpy()


def test_segment_cache_no_retrace_on_repeat(eng):
    def step(x):
        with engine_mod.bulk(16):
            y = x
            for _ in range(5):
                y = y * 2.0 + 1.0
        return y.asnumpy()

    x = nd.ones((4, 4))
    r1 = step(x)
    t1 = engine_mod.bulk_trace_count()
    r2 = step(x)
    assert engine_mod.bulk_trace_count() == t1, \
        "identical op stream retraced its segment"
    assert np.array_equal(r1, r2)
    # a different shape is a cache hit at the python level but a fresh
    # XLA trace underneath (jax.jit's aval-level cache)
    step(nd.ones((2, 2)))
    assert engine_mod.bulk_trace_count() == t1 + 1


def test_max_node_cap_splits_segments(eng):
    p0 = eng.stats.ops_pushed
    with engine_mod.bulk(4):
        z = _chain(nd.ones((4,)), n=10)
    z.wait_to_read()
    # 10 ops at cap 4 -> segments of 4, 4, 2
    assert eng.stats.ops_pushed - p0 == 3


def test_bulk_engine_env_selection(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "BulkEngine")
    monkeypatch.setenv("MXNET_EXEC_BULK_EXEC_MAX_NODE", "15")
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        assert e.kind == "BulkEngine"
        p0 = e.stats.ops_pushed
        y = _chain(nd.ones((3, 3)), n=10)
        assert e.stats.ops_pushed == p0, "BulkEngine should defer by default"
        y.asnumpy()
        assert e.stats.ops_pushed == p0 + 1
    finally:
        Engine._instance = old


def test_bulk_engine_inference_knob_disables(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "BulkEngine")
    monkeypatch.setenv("MXNET_EXEC_BULK_EXEC_INFERENCE", "0")
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        p0 = e.stats.ops_pushed
        _chain(nd.ones((3,)), n=4).asnumpy()
        assert e.stats.ops_pushed == p0 + 4, \
            "MXNET_EXEC_BULK_EXEC_INFERENCE=0 must fall back to eager"
    finally:
        Engine._instance = old


def test_bulk_scope_zero_disables_under_bulk_engine(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "BulkEngine")
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        p0 = e.stats.ops_pushed
        with engine_mod.bulk(0):
            _chain(nd.ones((3,)), n=4).asnumpy()
        assert e.stats.ops_pushed == p0 + 4
    finally:
        Engine._instance = old


def test_rng_ops_flush_and_run_eagerly(eng):
    mx.random.seed(7)
    with engine_mod.bulk(16):
        a = nd.ones((4,)) + 1.0
        p0 = eng.stats.ops_pushed
        r = mx.nd.random.uniform(shape=(4,))  # RNG-keyed: can't defer
        # the pending segment flushed first, then the rng op pushed eagerly
        assert eng.stats.ops_pushed == p0 + 2
        assert a._pending is None or a._pending.value is not None
    assert r.asnumpy().shape == (4,)


OPWAVE_CASES = [
    ("elemwise", lambda x: ((x + 1.5) * 2.0 - 0.25) / 3.0),
    ("unary", lambda x: x.abs().sqrt().exp().tanh()),
    ("reduce", lambda x: x.sum(axis=1, keepdims=True) + x.mean()),
    ("matmul", lambda x: x.dot(x.T) * 0.1),
    ("softmax", lambda x: x.softmax(axis=-1).log_softmax(axis=-1)),
    ("shape", lambda x: (x.reshape(-1).expand_dims(0).squeeze(0)
                         .reshape(4, 6).transpose())),
    ("compare", lambda x: (x > 0.2) * x + x.clip(-0.5, 0.5)),
    ("mixed", lambda x: (x.relu() + x.sigmoid()).sum(axis=0).square()),
]


@pytest.mark.parametrize("name,fn", OPWAVE_CASES, ids=[c[0] for c in OPWAVE_CASES])
def test_bulked_numerics_identical_to_eager(eng, name, fn):
    x = nd.array(np.random.RandomState(42).randn(4, 6).astype(np.float32))
    ref = fn(x).asnumpy()
    with engine_mod.bulk(64):
        lazy = fn(x)
    out = lazy.asnumpy()
    assert np.array_equal(out, ref), \
        "op wave %r: bulked result differs bitwise from eager" % name


def test_prep_drops_none_attrs_from_cache_key(eng):
    """Satellite regression: the old filter (`if v is not None or True`)
    kept None attrs, so {axis: None} and {} fragmented the _jitted cache."""
    from mxnet_tpu.ops import registry as reg

    x = nd.ones((3, 3))
    a = reg.invoke("sum", [x], {"axis": None, "keepdims": False})
    b = reg.invoke("sum", [x], {"keepdims": False})
    assert np.array_equal(a.asnumpy(), b.asnumpy())
    fn_a = reg._jitted("sum", ("data",), reg._freeze({"keepdims": False}))
    info = reg._jitted.cache_info()
    # the explicit-None spelling must resolve to the SAME cached callable
    reg.invoke("sum", [x], {"axis": None, "keepdims": False})
    assert reg._jitted.cache_info().misses == info.misses
    assert fn_a is reg._jitted("sum", ("data",),
                               reg._freeze({"keepdims": False}))


def test_inflight_ring_is_deque_and_skips_ready_buffers(monkeypatch):
    """Satellite: the overflow path only blocks on buffers still in
    flight; already-ready (or foreign) objects are dropped without a sync."""
    import collections

    monkeypatch.setenv("MXNET_ENGINE_INFLIGHT_CAP", "8")
    e = Engine()
    assert isinstance(e._inflight, collections.deque)

    class Probe:
        def __init__(self, ready):
            self.ready = ready
            self.blocked = False

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.blocked = True

    ready = [Probe(True) for _ in range(4)]
    pending = [Probe(False) for _ in range(4)]
    for p in ready + pending:
        e.track(p)
    e.track(object())  # overflow: retires the oldest half (the ready ones)
    assert not any(p.blocked for p in ready), \
        "ready buffers must not be blocked on"
    assert len(e._inflight) == 5


def test_deferred_value_survives_source_overwrite(eng):
    # snapshot semantics: an op reads its input's value AT CALL TIME,
    # even if the input is overwritten before the segment flushes
    a = nd.ones((3,))
    with engine_mod.bulk(16):
        b = a + 1.0        # reads a == 1
        a[:] = 100.0       # setitem is a sync for a, but b's promise holds
        c = b * 2.0
    np.testing.assert_allclose(c.asnumpy(), 4.0)
    np.testing.assert_allclose(a.asnumpy(), 100.0)


def test_dead_rebind_buffers_are_donated(eng):
    d0 = eng.stats.bulk_donated
    with engine_mod.bulk(16):
        a = nd.ones((16, 16))
        a.wait_to_read()
        for _ in range(4):
            a = a + 1.0  # each rebind kills the previous supplier
        a.wait_to_read()
    assert eng.stats.bulk_donated > d0
    np.testing.assert_allclose(a.asnumpy(), 5.0)


def test_donation_never_aliases_live_buffer(eng):
    # a foreign handle to the input buffer (detach/copy view, another
    # tape's primal, ...) must veto donation: read-after-donate would
    # observe XLA reusing the storage for an output
    with engine_mod.bulk(16):
        z = nd.ones((8, 8)) + 1.0
        z.wait_to_read()
        raw = z.data()              # foreign reference to the same buffer
        expect = np.asarray(raw).copy()
        z = z + 1.0                 # supplier moves on: donation candidate
        z = z + 1.0
        z.wait_to_read()
    assert np.array_equal(np.asarray(raw), expect), \
        "donated a buffer that was still externally referenced"


def test_live_ndarray_input_is_never_donated(eng):
    with engine_mod.bulk(16):
        z = nd.ones((8, 8)) * 2.0
        z.wait_to_read()
        # (the ones-temporary above WAS legitimately donated; snapshot now)
        d0 = eng.stats.bulk_donated
        w = z + 1.0                 # z stays live: supplier not dead
        w = w + 1.0
        w.wait_to_read()
    np.testing.assert_allclose(z.asnumpy(), 2.0)
    assert eng.stats.bulk_donated == d0


def test_default_engine_is_bulk_with_64_cap(monkeypatch):
    monkeypatch.delenv("MXNET_ENGINE_TYPE", raising=False)
    monkeypatch.delenv("MXNET_EXEC_BULK_EXEC_MAX_NODE", raising=False)
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        assert e.kind == "BulkEngine", "BulkEngine must be the default"
        assert e._bulk_max == 64
        x = nd.ones((3,))
        x.wait_to_read()
        p0 = e.stats.ops_pushed
        y = _chain(x, n=70)
        y.wait_to_read()
        # 70 ops at the 64 cap -> segments of 64 + 6
        assert e.stats.ops_pushed - p0 == 2
    finally:
        Engine._instance = old


def test_segment_cache_tier_eviction(eng, monkeypatch):
    import collections

    monkeypatch.setattr(engine_mod, "_SEG_TIER_BUDGETS", (1, 1, 1, 1))
    monkeypatch.setattr(engine_mod, "_SEG_TIERS",
                        tuple(collections.OrderedDict() for _ in range(4)))
    stats = tuple({"hits": 0, "misses": 0, "evictions": 0}
                  for _ in range(4))
    monkeypatch.setattr(engine_mod, "_seg_tier_stats", stats)

    def run(mult):
        with engine_mod.bulk(8):
            y = nd.ones((4,)) * mult + 1.0
        y.wait_to_read()

    run(2.0)
    run(2.0)   # same structure: cache hit in the le8 tier
    assert stats[0]["hits"] == 1 and stats[0]["misses"] == 1
    run(3.0)   # different attrs: new key evicts the old (budget 1)
    assert stats[0]["evictions"] == 1
    run(2.0)   # the evicted structure misses again
    assert stats[0]["misses"] == 3
    assert len(engine_mod._SEG_TIERS[0]) == 1


def test_tier_budget_env_knob(monkeypatch):
    monkeypatch.setenv("MXNET_EXEC_BULK_SEG_CACHE_BUDGETS", "2,3,4,5")
    assert engine_mod._parse_tier_budgets() == (2, 3, 4, 5)
    monkeypatch.delenv("MXNET_EXEC_BULK_SEG_CACHE_BUDGETS")
    assert engine_mod._parse_tier_budgets() == (128, 64, 32, 32)


def test_nested_bulk_zero_flushes_pending(eng):
    # ISSUE 6 bugfix: bulk(0) must flush the PENDING segment on entry,
    # not merely stop new deferrals
    with engine_mod.bulk(16):
        a = nd.ones((3,)) + 1.0    # ones pushes eagerly; +1.0 defers
        p1 = eng.stats.ops_pushed
        with engine_mod.bulk(0):
            assert eng.stats.ops_pushed == p1 + 1, \
                "entering bulk(0) must flush the pending segment"
            b = a * 2.0            # dispatches eagerly inside the scope
            assert eng.stats.ops_pushed == p1 + 2
        c = b + 1.0                # outer scope resumes deferral
        assert eng.stats.ops_pushed == p1 + 2
    np.testing.assert_allclose(c.asnumpy(), 5.0)


def test_set_bulk_size_zero_flushes_pending(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "BulkEngine")
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        x = nd.ones((3,))
        x.wait_to_read()
        p0 = e.stats.ops_pushed
        y = x + 1.0                # deferred under the default
        assert e.stats.ops_pushed == p0
        prev = engine_mod.set_bulk_size(0)
        assert e.stats.ops_pushed == p0 + 1, \
            "set_bulk_size(0) must flush the pending segment"
        z = y * 2.0                # eager from here on
        assert e.stats.ops_pushed == p0 + 2
        engine_mod.set_bulk_size(prev)
        np.testing.assert_allclose(z.asnumpy(), 4.0)
    finally:
        Engine._instance = old


def test_profile_bulk_env_keeps_segments_fused(monkeypatch):
    # MXNET_PROFILE_BULK=1: the profiler hook no longer disables implicit
    # bulking; the trace gets ONE cat="bulk" span with the op count
    from mxnet_tpu import profiler

    monkeypatch.setenv("MXNET_ENGINE_TYPE", "BulkEngine")
    monkeypatch.setenv("MXNET_PROFILE_BULK", "1")
    old = Engine._instance
    Engine._instance = None
    try:
        e = Engine.get()
        x = nd.ones((4,))
        x.wait_to_read()
        profiler.set_state("run")
        try:
            s0 = e.stats.bulk_segments
            y = _chain(x, n=6)
            y.wait_to_read()
            assert e.stats.bulk_segments == s0 + 1
        finally:
            profiler.set_state("stop")
        import json

        events = json.loads(profiler.dumps(aggregate=False))
        assert any(ev["cat"] == "bulk" and ev["name"] == "bulk_segment[6]"
                   and ev.get("args", {}).get("ops") == 6
                   for ev in events)
    finally:
        Engine._instance = old


def test_profiler_sees_one_named_segment_op(eng, tmp_path):
    from mxnet_tpu import profiler

    fname = str(tmp_path / "bulk_profile.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    try:
        with engine_mod.bulk(16):
            _chain(nd.ones((4, 4)), n=6).wait_to_read()
        import json

        table = profiler.dumps(aggregate=False)
        events = json.loads(table)
    finally:
        profiler.set_state("stop")
    segs = [ev for ev in events if ev["name"].startswith("bulk_segment[")]
    assert any(ev["name"] == "bulk_segment[6]" and ev["cat"] == "bulk"
               for ev in segs)


def test_trainer_donation_drains_pending_segment(eng):
    # Trainer.step's fused update DONATES old weight/state buffers to
    # XLA.  A recorded forward whose output is never read leaves its
    # segment pending while holding the old weight as an ext input —
    # the step must drain that segment (flush_if_referencing) or the
    # segment's eventual flush reads a deleted array.
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(mx.init.Constant(0.5))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array([[1.0, 2.0]])
    with autograd.record():
        y = net(x)          # y is never read: segment stays pending
    y.backward()            # vjp inputs are concrete — still no flush
    trainer.step(1)         # donates the old weight buffer
    y.wait_to_read()        # flushes the segment: must not hit a dead array
    np.testing.assert_allclose(net.weight.data().asnumpy(), [[0.4, 0.3]],
                               rtol=1e-5)


def test_cross_segment_rebind_chain_donates(eng):
    # ISSUE 8: a segment's output fed to the NEXT segment as a dead ext
    # input must be donatable — segments release their pinned output
    # refs at resolve time, so only the consumer's own handle remains.
    # This is the steady-state shape of a serving decode loop (cache
    # out of segment N = cache into segment N+1).
    d0 = eng.stats.bulk_donated
    with engine_mod.bulk(2):
        a = nd.ones((16, 16))
        a.wait_to_read()
        for _ in range(8):      # 2 ops/segment -> 4 cross-segment handoffs
            a = a + 1.0
        a.wait_to_read()
    assert eng.stats.bulk_donated >= d0 + 3, \
        "cross-segment dead inputs must be donated"
    np.testing.assert_allclose(a.asnumpy(), 9.0)


def test_cross_segment_inplace_update_donates(eng):
    # in-place out= updates bump the var version past supply time, so
    # the superseded buffer donates even though the NDArray persists
    d0 = eng.stats.bulk_donated
    with engine_mod.bulk(2):
        cache = nd.ones((16, 16))
        one = nd.ones((16, 16))
        cache.wait_to_read()
        one.wait_to_read()
        for _ in range(8):
            nd.elemwise_add(cache, one, out=cache)
        cache.wait_to_read()
    assert eng.stats.bulk_donated >= d0 + 3
    np.testing.assert_allclose(cache.asnumpy(), 9.0)


def test_pending_reads_tracks_open_segment_ext_inputs(eng):
    # Engine.pending_reads is the serving arena's liveness query: it
    # must name exactly the buffers the open segment still reads, and
    # go empty once that segment flushes.
    a = nd.ones((4, 4))
    a.wait_to_read()
    buf = a.data()
    assert eng.pending_reads((buf,)) == ()
    with engine_mod.bulk(16):
        b = a * 2.0                       # defers; captures buf as ext
        assert eng.pending_reads((buf,)) == (buf,)
        other = nd.ones((4, 4))
        other.wait_to_read()
        assert eng.pending_reads((other.data(),)) == ()
        eng.flush_if_referencing((buf,), "test_pending_reads")
        assert eng.pending_reads((buf,)) == ()
    np.testing.assert_allclose(b.asnumpy(), 2.0)
