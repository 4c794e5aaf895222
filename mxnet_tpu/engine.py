"""Dependency engine, TPU-native.

Reference: ``src/engine/`` — an async scheduler with versioned variables
(``include/mxnet/engine.h:44``), per-device worker threads, and
read/write-dependency queues (``src/engine/threaded_engine.h:71-150``).

On TPU the heavy machinery collapses by design: PJRT dispatch is already
asynchronous (every jax op returns a future-backed buffer and executes in
enqueue order on the device stream), so RAW/WAR ordering within a device is
guaranteed by the runtime and there is nothing for a worker thread to do.
What survives from the reference engine, and what this module provides:

* ``Var`` — versioned variables (one per NDArray chunk).  Version bumps on
  every write; this is what makes MXNet-style "mutation" observable and is
  used by the executable caches to invalidate.
* ``push``/``push_async`` — an explicit hand-off point kept so engine-level
  instrumentation (profiler hooks, op bulking stats) has a single choke
  point, and so an alternate threaded implementation can be slotted in via
  ``MXNET_ENGINE_TYPE`` exactly like the reference (``src/engine/engine.cc:32``).
* ``wait_for_var`` / ``wait_for_all`` — blocking sync, incl. async exception
  rethrow (parity: ``src/engine/threaded_engine.cc:383-436``).
* op bulking (``BulkEngine`` / ``bulk(size)``) — the reference's imperative
  segment fusion (``MXNET_EXEC_BULK_EXEC_*``, imperative_utils.h
  ``CreateEngineOpSeg``): consecutive deferrable ops collect into a
  ``BulkSegment`` and flush as ONE jitted, XLA-fused executable at the
  first sync point, so N python/PJRT dispatches collapse into ~1.
"""
from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
import weakref

import jax

from . import compile_cache as _ccache
from .telemetry import flight as _flight
from .telemetry import memdump as _memdump
from .telemetry import metrics as _metrics
from .testing.faults import maybe_inject as _inject

# itertools.count holds the GIL for the whole increment, so ids stay
# unique across threads without a lock on the NDArray hot path
_var_ids = itertools.count(1)

# bound on first use by Engine.bulk_size (importing at module scope would
# cycle: autograd lazily imports the engine for segment flushes)
_autograd = None


class Var:
    """Versioned variable (parity: engine::Var, include/mxnet/engine.h:44)."""

    __slots__ = ("vid", "version", "_exc")

    def __init__(self):
        self.vid = next(_var_ids)
        self.version = 0
        self._exc = None

    def on_write(self):
        self.version += 1

    def set_exception(self, exc):
        self._exc = exc

    def rethrow(self):
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc


class _Stats:
    __slots__ = ("ops_pushed", "bulk_ops", "bulk_segments", "bulk_donated",
                 "sync_origins", "flush_origins")

    def __init__(self):
        self.ops_pushed = 0
        self.bulk_ops = 0       # ops that executed inside a bulk segment
        self.bulk_segments = 0  # segments flushed (each = one push)
        self.bulk_donated = 0   # dead input buffers donated to XLA
        self.sync_origins = {}   # device->host syncs by origin
        self.flush_origins = {}  # segment flushes by origin kind


# ----------------------------------------------------------------------------
# op bulking (reference: MXNET_EXEC_BULK_EXEC_* segments,
# src/imperative/imperative_utils.h CreateEngineOpSeg)
# ----------------------------------------------------------------------------

# jitted segment executables keyed by the op-sequence structure
# (op name, static attrs, argument wiring, donated-input set) — the
# engine-level analogue of CachedOp's executable cache for code that never
# calls hybridize().  jax.jit adds the per-(shape, dtype) level underneath,
# so re-running the same imperative stream with the same avals re-traces
# nothing.  Segments are bucketed into size tiers, each with its own LRU
# budget: short interactive chains (<=8 ops) and long fused training steps
# (<=64) churn at very different rates, and one flat LRU lets a burst of
# small segments evict the expensive long-segment executables.
_SEG_TIER_BOUNDS = (8, 16, 32, 64)
_SEG_TIER_LABELS = ("le8", "le16", "le32", "le64")


def _parse_tier_budgets():
    vals = [128, 64, 32, 32]  # sums to the old flat cap of 256
    raw = os.environ.get("MXNET_EXEC_BULK_SEG_CACHE_BUDGETS", "").strip()
    if raw:
        try:
            parts = [int(p) for p in raw.split(",")]
        except ValueError:
            parts = []
        for i, p in enumerate(parts[: len(vals)]):
            if p > 0:
                vals[i] = p
    return tuple(vals)


_SEG_TIER_BUDGETS = _parse_tier_budgets()
_SEG_TIERS = tuple(collections.OrderedDict() for _ in _SEG_TIER_BOUNDS)
_seg_tier_stats = tuple({"hits": 0, "misses": 0, "evictions": 0}
                        for _ in _SEG_TIER_BOUNDS)
_seg_cache_stats = {"hits": 0, "misses": 0,  # all-tier totals (collector)
                    "disk_hits": 0}  # persistent-cache warm starts
_trace_count = [0]
_SEGMENT_OPS_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def _tier_index(n_ops):
    for i, bound in enumerate(_SEG_TIER_BOUNDS):
        if n_ops <= bound:
            return i
    # explicit bulk(size) scopes may exceed the largest bound; they share
    # the top tier rather than getting an unbounded one
    return len(_SEG_TIER_BOUNDS) - 1


def bulk_trace_count():
    """How many times a bulk segment has been (re)traced by XLA — the
    probe tests use to assert segment-cache hits (no retrace)."""
    return _trace_count[0]


def _build_segment_fn(steps, donate=(), exact=False, example_args=None):
    """One traceable callable running every deferred step in push order.

    ``steps`` is a sequence of ``(run_fn, slots, n_out)``; each slot is
    ``('v', i)`` — the i-th value produced inside the segment — or
    ``('x', i)`` — the i-th external (concrete) input.  All produced
    values are returned so the signature depends only on the op sequence,
    never on which outputs happen to still be referenced at flush time
    (liveness-dependent signatures would make cache hits GC-timing flaky).
    ``donate`` are ext indices whose buffers are dead at flush time; XLA
    may reuse them for outputs (donated inputs are deleted after the call).

    ``exact=True`` compiles ahead-of-time with XLA's fusion pass off
    (``xla_disable_hlo_passes=fusion``), so each op of the segment stays
    a kernel of its own and keeps the rounding of its standalone eager
    executable.  The default pipeline fuses across op boundaries (FMA
    contraction, output rematerialization) and drifts intermediates off
    eager by ulps — it even strips ``optimization_barrier`` before
    fusing, so barriers can't pin the numerics.  Backend optimization
    level 0 is no substitute: it stops the contraction inside an op too,
    and XLA:CPU emits ``tanh`` as a polynomial whose multiply-adds the
    op's own executable contracts, so a level-0 segment reads 1-2 ulp
    off eager wherever it holds one.  Not pinned: an op of several HLO
    instructions that its own executable fuses (``LayerNorm``'s scale
    and shift) — compare those with allclose.  Recorded segments need
    the exact path because the tape re-linearizes against segment
    intermediates and bulked grads must be BIT-identical to eager;
    unrecorded segments keep the fast fused path (the forward values the
    user sees are checked against eager by tier-1 at default opts).  The
    dispatch win (one push per N ops) is identical either way.  A rule
    about XLA:CPU: the TPU compiler's program is the same text with or
    without the option, and bitwise agreement on the chip is not
    measured.
    """
    steps = tuple(steps)

    def _body(ext):
        _trace_count[0] += 1  # python body → runs only while tracing
        vals = []
        for run_fn, slots, _n_out in steps:
            args = [vals[i] if kind == "v" else ext[i] for kind, i in slots]
            vals.extend(run_fn(*args))
        return tuple(vals)

    if not exact:
        def seg_run(*ext):
            return _body(ext)

        return jax.jit(seg_run, donate_argnums=donate)

    # distinct traced-function NAME for the exact path: the HLO module name
    # enters jax's persistent-cache key, so exact (taped) and fused
    # artifacts for the same op sequence can never cross-hit on disk even
    # if a jax version ever drops compiler_options from the key
    def seg_run_exact(*ext):
        return _body(ext)

    jitted = jax.jit(seg_run_exact, donate_argnums=donate)
    return jitted.lower(*example_args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"})


class _BulkRef:
    """A promised output of a not-yet-flushed segment (lazy NDArray chunk)."""

    __slots__ = ("segment", "index", "aval", "value", "failed")

    def __init__(self, segment, index, aval):
        self.segment = segment
        self.index = index
        self.aval = aval      # jax.ShapeDtypeStruct from eval_shape
        self.value = None     # concrete jax.Array after flush
        self.failed = False   # the flush raised; value will never arrive


class BulkSegment:
    """A deferred run of consecutive imperative ops, flushed as ONE push.

    Built by ``ops.registry`` (which owns op semantics: attrs, fields,
    eval_shape) and executed here (which owns scheduling: the single
    ``Engine.push``, var poisoning, stats, inflight tracking).
    """

    __slots__ = ("engine", "cap", "steps", "key_parts", "ext", "_ext_ids",
                 "ext_src", "refs", "write_vars", "flushed", "n_ops", "taped")

    def __init__(self, engine, cap):
        self.engine = engine
        self.cap = cap            # flush after this many ops (0 = unbounded)
        self.steps = []           # (run_fn, slots, n_out)
        self.key_parts = []       # hashable mirror of steps → cache key
        self.ext = []             # external concrete inputs, dedup by id
        self._ext_ids = {}
        self.ext_src = []         # per ext: [(weakref(NDArray), Var, version)]
        self.refs = []            # _BulkRef per produced value, in order
        self.write_vars = []      # Vars of every NDArray built on a ref
        self.flushed = False
        self.n_ops = 0
        self.taped = False        # any op recorded into the autograd tape

    def defer(self, step_key, run_fn, handles, out_avals):
        """Append one op; ``handles`` are ``('v', _BulkRef)`` for values
        produced earlier in this segment or ``('x', jax.Array, NDArray)``
        for concrete inputs (the NDArray that supplied the buffer, or
        ``None`` — supplier identity drives input-buffer donation).
        Returns one ``_BulkRef`` per output."""
        slots = []
        for h in handles:
            if h[0] == "v":
                slots.append(("v", h[1].index))
            else:
                v = h[1]
                i = self._ext_ids.get(id(v))
                if i is None:
                    i = len(self.ext)
                    self.ext.append(v)
                    self._ext_ids[id(v)] = i
                    self.ext_src.append([])
                owner = h[2] if len(h) > 2 else None
                src = self.ext_src[i]
                if owner is None:
                    self.ext_src[i] = None  # unknown supplier: never donate
                elif src is not None:
                    try:
                        src.append((weakref.ref(owner), owner._var,
                                    owner._var.version))
                    except TypeError:  # unweakrefable supplier: never donate
                        self.ext_src[i] = None
                slots.append(("x", i))
        slots = tuple(slots)
        base = len(self.refs)
        refs = [_BulkRef(self, base + j, aval)
                for j, aval in enumerate(out_avals)]
        self.refs.extend(refs)
        self.steps.append((run_fn, slots, len(out_avals)))
        self.key_parts.append((step_key, slots, len(out_avals)))
        self.n_ops += 1
        return refs

    def add_write_vars(self, new_vars):
        self.write_vars.extend(new_vars)

    def _donation(self, eng):
        """Ext indices whose buffers are provably dead → XLA donation.

        An ext buffer is donatable iff (a) every NDArray that ever
        supplied it has moved on (collected, or its engine var version
        bumped past the supply-time version — in-place ``out=`` adoption
        and rebinding both land here), (b) its aval matches some segment
        output (XLA can only reuse matching buffers; anything else would
        warn and donate for nothing), and (c) a refcount audit shows no
        OTHER owner: exactly the ext list, the local probe, and tracked
        in-flight occurrences hold it.  (c) is the safety net — buffer
        shares the suppliers can't see (detach/copy views, autograd tape
        primals, another thread's segment) all show up as extra refs and
        veto the donation, so a donated buffer can never be read again.
        """
        if not eng._bulk_donate:
            return ()
        donate = []
        out_avals = None
        inflight = None
        for i, srcs in enumerate(self.ext_src):
            if not srcs:  # None (opted out) or no recorded supplier
                continue
            dead = True
            for wref, var, ver in srcs:
                nd = wref()
                if nd is not None and var.version == ver:
                    dead = False
                    break
            if not dead:
                continue
            b = self.ext[i]
            if out_avals is None:
                out_avals = {(tuple(r.aval.shape), r.aval.dtype)
                             for r in self.refs}
            if (tuple(b.shape), b.dtype) not in out_avals:
                continue
            if inflight is None:
                inflight = collections.Counter(map(id, eng._inflight))
            # refs: ext list + local ``b`` + getrefcount's argument,
            # plus tracked in-flight entries
            if sys.getrefcount(b) <= 3 + inflight[id(b)]:
                donate.append(i)
        if donate:
            # the donated buffers are deleted by the call; purge them from
            # the in-flight ring so waitall() never blocks on a dead buffer
            donated_ids = {id(self.ext[i]) for i in donate}
            eng._inflight = collections.deque(
                d for d in eng._inflight if id(d) not in donated_ids)
        return tuple(donate)

    def flush(self, origin="flush"):
        """Execute the whole segment as one engine push. Idempotent.

        On failure every unresolved ref is marked dead and every output
        var poisoned via ``Var.set_exception`` — the same async-rethrow
        contract the eager path gives a single failing op.
        """
        if self.flushed:
            return
        self.flushed = True
        eng = self.engine
        st = eng._bulk_state()
        if st.seg is self:
            st.seg = None
        if not self.steps:
            return
        donate = self._donation(eng)
        # taped segments compile ahead-of-time (see _build_segment_fn), so
        # their cache key must pin the concrete ext avals jit would have
        # re-traced on; untaped segments let jit handle shape polymorphism.
        # Placement is ALWAYS part of the key: a jax sharding object
        # (SingleDeviceSharding or NamedSharding) encodes platform, device
        # and partition spec, so a segment traced against cpu:0 inputs can
        # never serve sharded (or other-device) inputs — the exact path
        # pins its lowering at build time and would silently compute on
        # the wrong placement otherwise.
        exact = self.taped
        placements = tuple(getattr(a, "sharding", None) for a in self.ext)
        key = (tuple(self.key_parts), donate, placements, exact and tuple(
            (tuple(a.shape), str(a.dtype)) for a in self.ext))
        ti = _tier_index(self.n_ops)
        tier = _SEG_TIERS[ti]
        tstats = _seg_tier_stats[ti]
        # snapshot BEFORE the cache lookup: exact (taped) segments trace
        # at build time inside _build_segment_fn, not at first call
        n_traces0 = _trace_count[0]
        n_disk0 = _ccache.persistent_hits()
        t_flush0 = time.perf_counter()
        fn = tier.get(key)
        cached = fn is not None
        if fn is None:
            tstats["misses"] += 1
            _seg_cache_stats["misses"] += 1
            fn = _build_segment_fn(self.steps, donate, exact=exact,
                                   example_args=self.ext)
            tier[key] = fn
            budget = _SEG_TIER_BUDGETS[ti]
            while len(tier) > budget:
                tier.popitem(last=False)
                tstats["evictions"] += 1
        else:
            tstats["hits"] += 1
            _seg_cache_stats["hits"] += 1
            tier.move_to_end(key)
        _flight.record("engine.flush", origin=origin, ops=self.n_ops,
                       tier=_SEG_TIER_LABELS[ti], cached=cached,
                       donated=len(donate))
        ext = self.ext
        try:
            # one push for the whole op stream; write-var versions were
            # already bumped at defer time (exactly as eager would have),
            # so the push declares none — it only publishes values.
            vals = eng.push(lambda: fn(*ext),
                            op_name="bulk_segment[%d]" % self.n_ops)
        except Exception as e:
            # push() already recorded engine.poison + crash-dumped; this
            # event names the segment-level blast radius
            _flight.record("engine.flush_failed", origin=origin,
                           ops=self.n_ops, error=type(e).__name__,
                           writes=len(self.write_vars))
            for r in self.refs:
                if r.value is None:
                    r.failed = True
            for v in self.write_vars:
                v.set_exception(e)
            self._release()
            raise
        eng.stats.bulk_segments += 1
        eng.stats.bulk_donated += len(donate)
        if _metrics.enabled():
            # origins like "rng:<op>" truncate to "rng" so the metric
            # label set stays bounded (docs/observability.md)
            kind = origin.split(":", 1)[0]
            fo = eng.stats.flush_origins
            fo[kind] = fo.get(kind, 0) + 1
            _metrics.histogram(
                "mxnet_engine_bulk_segment_ops",
                help="ops fused per flushed bulk segment",
                buckets=_SEGMENT_OPS_BUCKETS).observe(self.n_ops)
            retraces = _trace_count[0] - n_traces0
            if retraces:
                if _ccache.persistent_hits() - n_disk0 >= retraces:
                    # the executable came off the persistent disk cache: a
                    # warm start, not a retrace.  Count it as a cache hit
                    # (the disk tier below the in-memory _SEG_TIERS) and
                    # keep it out of mxnet_compile_seconds AND the
                    # MXNET_RETRACE_WARN_THRESHOLD watchdog — a restarted
                    # fleet re-tracing every segment once is healthy.
                    _seg_cache_stats["disk_hits"] += retraces
                else:
                    # first run of a (structure, avals) pair: the push wall
                    # time is trace+compile dominated — record it per retrace
                    _metrics.record_compile(
                        "bulk_segment", ("bulk_segment", key),
                        time.perf_counter() - t_flush0, n=retraces)
        for r, val in zip(self.refs, vals):
            r.value = val
        eng.track_many(vals)
        self._release()

    def _release(self):
        """Drop input/step references once flushed: lazy tape nodes and
        lazy NDArrays can pin _BulkRefs (→ this segment) long after the
        flush, and holding every ext buffer alive through them would keep
        whole training steps' worth of inputs resident.

        ``refs`` is dropped too: each _BulkRef.value pins one output
        buffer, and live consumers (lazy NDArrays, tape nodes) hold their
        OWN _BulkRef — the segment's list is a pure duplicate.  Keeping it
        would add one refcount to every output for the segment cache's
        lifetime, so a segment-N output fed to segment N+1 as a dead ext
        input could never pass the _donation refcount audit — exactly the
        steady-state KV-update shape of a decode loop."""
        self.steps = ()
        self.key_parts = ()
        self.ext = ()
        self._ext_ids = None
        self.ext_src = ()
        self.write_vars = ()
        self.refs = ()


class Engine:
    """Engine façade. ``NaiveEngine`` semantics: push == run-on-device-stream.

    The device stream itself is async (PJRT), so even the "naive" engine gives
    compute/host overlap — the property the reference needed worker threads
    for.  Tracked arrays register their backing buffers so ``wait_for_all``
    can block on everything in flight.
    """

    _instance = None

    def __init__(self):
        self.stats = _Stats()
        self._hooks = []  # profiler hooks: fn(op_name, t_start, t_end)
        self._sync_hooks = []  # sync hooks: fn(origin) per device->host sync
        self.kind = os.environ.get("MXNET_ENGINE_TYPE", "BulkEngine")
        self._inflight = collections.deque()  # recent output buffers (ring)
        self._inflight_cap = int(os.environ.get("MXNET_ENGINE_INFLIGHT_CAP", "512"))
        # op bulking knobs (reference: MXNET_EXEC_BULK_EXEC_*,
        # docs/env_vars.md) — segments are per-thread.
        #
        # Concurrency contract (CD11xx / docs/static_analysis.md): the
        # engine owns NO locks by design.  Mutable state is either
        # per-thread (this threading.local), append-only counters read
        # for monitoring, or var version/pending maps whose cross-thread
        # discipline EngineAudit (MXNET_ENGINE_AUDIT=1) checks at every
        # push — serialization is the caller's (stream's) job, exactly
        # like the reference engine's per-var queues.  Keep it that way:
        # a lock on the push path would serialize dispatch against the
        # device and show up directly in mxnet_lock_hold_seconds.
        self._bulk_tls = threading.local()
        self._bulk_train = os.environ.get(
            "MXNET_EXEC_BULK_EXEC_TRAIN", "1") not in ("", "0")
        self._bulk_infer = os.environ.get(
            "MXNET_EXEC_BULK_EXEC_INFERENCE", "1") not in ("", "0")
        self._bulk_max = int(os.environ.get(
            "MXNET_EXEC_BULK_EXEC_MAX_NODE", "64"))
        self._bulk_donate = os.environ.get(
            "MXNET_EXEC_BULK_DONATE", "1") not in ("", "0")
        # profiling normally disables implicit bulking (per-op spans,
        # reference parity); MXNET_PROFILE_BULK=1 keeps segments fused so
        # the profiler sees the execution mode it is actually measuring
        self._profile_bulk = os.environ.get(
            "MXNET_PROFILE_BULK", "0") not in ("", "0")
        self._audit = None  # EA4xx dependency auditor (docs/static_analysis.md)
        if os.environ.get("MXNET_ENGINE_AUDIT", "0") not in ("", "0"):
            from .analysis.engine_audit import EngineAudit
            self._audit = EngineAudit()

    @staticmethod
    def get():
        if Engine._instance is None:
            Engine._instance = Engine()
        return Engine._instance

    # -- push -------------------------------------------------------------
    def push(self, fn, read_vars=(), write_vars=(), op_name=None):
        """Run ``fn`` now; device-side it is async.  Bumps write-var versions."""
        for v in read_vars:
            v.rethrow()
        audit = self._audit
        if audit is not None:
            audit.before_push(read_vars, write_vars, op_name)
        self.stats.ops_pushed += 1
        _flight.record("engine.push", op=op_name or "op")
        t0 = time.perf_counter() if self._hooks else 0.0
        try:
            # chaos hook: an injected op failure takes the same
            # set_exception path a real one would (tests assert the
            # async rethrow at the next read of a poisoned var)
            _inject("engine_push", op=op_name)
            out = fn()
        except Exception as e:
            # black box first: the poisoned vars will rethrow far from
            # here, so the ring must already hold the story
            _flight.record("engine.poison", op=op_name or "op",
                           error=type(e).__name__, writes=len(write_vars))
            _memdump.maybe_oom_report(e)
            for v in write_vars:
                v.set_exception(e)
            if audit is not None:
                audit.after_push(read_vars, write_vars, op_name)
            _flight.crash_dump("poison")
            raise
        for v in write_vars:
            v.on_write()
        if audit is not None:
            audit.after_push(read_vars, write_vars, op_name)
        if self._hooks:
            t1 = time.perf_counter()
            for h in self._hooks:
                h(op_name or getattr(fn, "__name__", "op"), t0, t1)
        return out

    def track_many(self, vals):
        """Track a batch of buffers (segment flush) in one extend."""
        self._inflight.extend(vals)
        if len(self._inflight) > self._inflight_cap:
            self._retire_inflight()

    def track(self, data):
        """Remember a dispatched buffer so wait_for_all() can sync on it."""
        self._inflight.append(data)
        if len(self._inflight) > self._inflight_cap:
            self._retire_inflight()

    def _retire_inflight(self):
        # ring full: retire the oldest half before dropping it, so
        # waitall() semantics stay exact (Engine::WaitForAll blocks on
        # every outstanding op; silently forgetting buffers could let
        # waitall() return with work — and async errors — in flight).
        # Only buffers still in flight cost a block; anything PJRT has
        # already finished (is_ready) is dropped without stalling.
        for _ in range(self._inflight_cap // 2):
            if not self._inflight:
                break
            d = self._inflight.popleft()
            try:
                ready = d.is_ready()
            except AttributeError:
                ready = False  # unknown state: assume still in flight
            except RuntimeError:
                continue  # donated-and-deleted buffer: nothing to wait on
            if not ready:
                try:
                    d.block_until_ready()  # mxlint: allow-host-sync
                except (AttributeError, RuntimeError):
                    pass

    # -- bulking ----------------------------------------------------------
    def _bulk_state(self):
        tls = self._bulk_tls
        if not hasattr(tls, "seg"):
            tls.seg = None     # this thread's open BulkSegment
            tls.scopes = []    # explicit bulk(size) scope stack
        return tls

    def bulk_size(self):
        """Segment cap for the next deferred op; 0 = dispatch eagerly.

        An explicit ``bulk(size)`` scope wins; otherwise ``BulkEngine``
        bulks up to ``MXNET_EXEC_BULK_EXEC_MAX_NODE`` when the mode knob
        (TRAIN/INFERENCE) allows.  Recording does NOT disable bulking:
        taped ops defer too, and the tape re-linearizes through the
        segment's promised values at backward time.  Implicit bulking
        still steps aside while an op profiler hook is attached (per-op
        spans, reference parity — unless MXNET_PROFILE_BULK=1 keeps
        segments fused under the profiler) and under the EA4xx auditor
        (it validates the eager push stream).
        """
        global _autograd
        st = self._bulk_state()
        if st.scopes:
            size = st.scopes[-1]
            return size if size > 0 else 0
        if self.kind != "BulkEngine":
            return 0
        if self._audit is not None or (self._hooks and not self._profile_bulk):
            return 0
        size = self._bulk_max
        if size <= 0:
            return 0
        if _autograd is None:
            from . import autograd as _autograd  # noqa: F811 (bind once)
        knob = self._bulk_train if _autograd.is_training() \
            else self._bulk_infer
        return size if knob else 0

    def current_segment(self, size=None):
        """This thread's open segment, creating one if needed."""
        st = self._bulk_state()
        seg = st.seg
        if seg is None or seg.flushed:
            seg = BulkSegment(self, size if size is not None
                              else self.bulk_size())
            st.seg = seg
        return seg

    def flush_bulk(self, origin="flush"):
        """Flush this thread's open segment, if any (cheap when none)."""
        st = self._bulk_state()
        seg = st.seg
        st.seg = None
        if seg is not None and not seg.flushed:
            seg.flush(origin)

    def flush_if_referencing(self, buffers, origin="donation_guard"):
        """Flush this thread's open segment if it captured any of
        ``buffers`` as an external input.

        Callers that donate buffers to XLA outside the bulk machinery
        (``gluon.Trainer``'s fused optimizer update) must drain pending
        deferred work first: XLA deletes a donated buffer even while a
        pending segment still holds it as an ext input, and the
        segment's later flush would read a dead array.  Cheap when the
        segment doesn't touch the buffers — bulking continues across
        the donating call.
        """
        st = self._bulk_state()
        seg = st.seg
        if seg is None or seg.flushed or not seg.ext:
            return
        if {id(b) for b in buffers} & seg._ext_ids.keys():
            self.flush_bulk(origin)

    def pending_reads(self, buffers):
        """Which of ``buffers`` this thread's open segment still reads.

        The page-liveness query behind ``serve.PagedKVArena``: a KV page
        buffer that appears as an ext input of an unflushed segment must
        not be overwritten or donated until that segment runs, so the
        arena asks here before recycling pages and flushes (via
        ``flush_if_referencing``) when the answer is non-empty.  Returns
        the subset of ``buffers`` captured as ext inputs — empty tuple
        when nothing pends, which is the cheap common case.
        """
        st = self._bulk_state()
        seg = st.seg
        if seg is None or seg.flushed or not seg.ext:
            return ()
        ids = seg._ext_ids
        return tuple(b for b in buffers if id(b) in ids)

    # -- sync -------------------------------------------------------------
    def wait_for_var(self, var):
        var.rethrow()

    def wait_for_all(self):
        self.flush_bulk("waitall")
        self.notify_sync("waitall")
        pending, self._inflight = self._inflight, collections.deque()
        for d in pending:
            try:
                d.block_until_ready()  # mxlint: allow-host-sync
            except (AttributeError, RuntimeError):
                # RuntimeError: buffer was donated to a segment and
                # deleted — by definition nothing can still be computing it
                pass

    # -- instrumentation --------------------------------------------------
    def add_hook(self, fn, kind="op"):
        """Register an instrumentation hook, idempotently.

        ``kind='op'``: ``fn(op_name, t_start, t_end)`` after every push.
        ``kind='sync'``: ``fn(origin)`` on every device->host sync
        (``asnumpy``/``wait_to_read``/``waitall`` report through
        ``notify_sync``) — the surface ``analysis.SyncCounter`` builds on.
        Registering the same hook twice is a no-op, so callers wrapped in
        retry/setup code can't double-count.
        """
        hooks = self._hooks_of(kind)
        if fn not in hooks:
            hooks.append(fn)

    def remove_hook(self, fn, kind="op"):
        hooks = self._hooks_of(kind)
        if fn in hooks:
            hooks.remove(fn)

    def _hooks_of(self, kind):
        if kind == "op":
            return self._hooks
        if kind == "sync":
            return self._sync_hooks
        raise ValueError("unknown hook kind %r (want 'op' or 'sync')" % kind)

    def notify_sync(self, origin):
        """Report one device->host sync to the sync hooks (cheap when none
        are registered — a single truthiness check on the hot path)."""
        _flight.record("engine.sync", origin=origin)
        if _metrics.enabled():
            so = self.stats.sync_origins
            so[origin] = so.get(origin, 0) + 1
        if self._sync_hooks:
            for h in self._sync_hooks:
                h(origin)


def _telemetry_collector():
    """Export engine aggregates at snapshot time (docs/observability.md).

    ``Engine.stats`` and the segment cache already count on the hot
    path; mirroring them here instead of inc'ing registry counters per
    push keeps telemetry's per-op cost at zero for these families.
    """
    eng = Engine._instance
    if eng is None:
        return
    st = eng.stats
    _metrics.counter("mxnet_engine_ops_pushed_total",
                     help="ops dispatched through Engine.push"
                     ).set(st.ops_pushed)
    _metrics.counter("mxnet_engine_bulk_ops_total",
                     help="ops that executed inside a bulk segment"
                     ).set(st.bulk_ops)
    _metrics.gauge("mxnet_engine_inflight_depth",
                   help="buffers tracked for waitall"
                   ).set(len(eng._inflight))
    for origin, n in list(st.sync_origins.items()):
        _metrics.counter("mxnet_engine_sync_total",
                         help="device->host syncs by origin",
                         origin=origin).set(n)
    for origin, n in list(st.flush_origins.items()):
        _metrics.counter("mxnet_engine_bulk_segments_total",
                         help="bulk segments flushed, by flush origin",
                         origin=origin).set(n)
    _metrics.counter("mxnet_engine_segment_cache_hits_total",
                     help="bulk segment executable cache hits"
                     ).set(_seg_cache_stats["hits"])
    _metrics.counter("mxnet_engine_segment_cache_disk_hits_total",
                     "bulk segments whose executable loaded from the "
                     "persistent compile cache (warm start, not a retrace)"
                     ).set(_seg_cache_stats["disk_hits"])
    _metrics.counter("mxnet_engine_segment_cache_misses_total",
                     help="bulk segment executable cache misses"
                     ).set(_seg_cache_stats["misses"])
    _metrics.counter("mxnet_engine_bulk_donated_total",
                     help="dead segment inputs donated to XLA"
                     ).set(st.bulk_donated)
    for label, tstats, tier in zip(_SEG_TIER_LABELS, _seg_tier_stats,
                                   _SEG_TIERS):
        _metrics.counter("mxnet_engine_segment_cache_tier_hits_total",
                         help="segment cache hits by size tier",
                         tier=label).set(tstats["hits"])
        _metrics.counter("mxnet_engine_segment_cache_tier_misses_total",
                         help="segment cache misses by size tier",
                         tier=label).set(tstats["misses"])
        _metrics.counter("mxnet_engine_segment_cache_tier_evictions_total",
                         help="segment cache LRU evictions by size tier",
                         tier=label).set(tstats["evictions"])
        _metrics.gauge("mxnet_engine_segment_cache_tier_size",
                       help="segment executables held by size tier",
                       tier=label).set(len(tier))


_metrics.register_collector(_telemetry_collector)


def waitall():
    Engine.get().wait_for_all()


def set_bulk_size(size):
    """Set the default segment cap (parity: mxnet.engine.set_bulk_size).
    Returns the previous cap.  Only takes effect under ``BulkEngine`` or
    inside an explicit :class:`bulk` scope."""
    eng = Engine.get()
    size = int(size)
    if size <= 0:
        # disabling bulking must fully disable deferral, not just cap new
        # segments: any already-deferred ops flush NOW so everything after
        # this call observes concrete program order
        eng.flush_bulk("bulk_size_zero")
    prev, eng._bulk_max = eng._bulk_max, size
    return prev


class bulk:
    """Scope bulking consecutive imperative ops (parity: mxnet.engine.bulk).

    ::

        with mx.engine.bulk(16):
            for _ in range(100):
                x = x + 1          # deferred; flushes every 16 ops
        x.asnumpy()                # sync point: flushes the tail

    Works under any engine kind — the scope overrides the engine default,
    so ``bulk(0)`` also force-disables bulking under ``BulkEngine``.
    Entering and leaving the scope are segment boundaries.
    """

    def __init__(self, size):
        self.size = int(size)

    def __enter__(self):
        eng = Engine.get()
        eng.flush_bulk("bulk_scope_enter")
        eng._bulk_state().scopes.append(self.size)
        return self

    def __exit__(self, *exc):
        eng = Engine.get()
        try:
            eng.flush_bulk("bulk_scope_exit")
        finally:
            eng._bulk_state().scopes.pop()
