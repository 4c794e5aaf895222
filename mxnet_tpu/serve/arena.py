"""Paged KV-cache arena: fixed-size pages, block tables, liveness-safe
reuse.

The cache is one ``(P, KV, page, D)`` device buffer a layer and side
(K, V), each with a ``(P,)`` float32 scale row beside it when the arena
is int8 (``model.state_avals``): every buffer is a donated argument of
its own, so a program appends to a layer's pages where they lie.
Sequences own pages through host-side block tables —
int32 rows mapping ``token_position // page_size`` to a page index — so
admission never copies or reshapes cache memory: allocating a sequence
is popping page ids off a free list, finishing one is pushing them back.
Page 0 is reserved as the **null page**: inactive decode slots point
their block-table row at it and scribble there harmlessly.

The arena is **loop-thread-only and lock-free by contract** (CD11xx):
every mutator — allocate, append, finish, defrag — runs on the serve
loop thread (or the caller's thread before ``start()``), never
concurrently.  Cross-thread visibility goes through the scheduler,
whose lock (``serve.sched`` under ``MXNET_LOCKCHECK=1``) is dropped
before any arena call.  Do not add locks here; add state to the
scheduler if another thread ever needs it.

Reuse safety rides on the engine's var-dependency tracking.  The decode
/prefill executables *donate* the KV buffers (XLA deletes them), and a
freed page may be handed to a new sequence while imperative NDArray ops
— a debug checksum, an eviction scorer — sit deferred in an open bulk
segment that captured the old buffer as an ext input.  Before any
donating call or page reuse the arena asks ``Engine.pending_reads`` and
drains via ``flush_if_referencing``, so a pending segment always reads
the pre-reuse snapshot (tests/test_serve.py stress-tests this).
"""
from __future__ import annotations

import collections

import numpy as np

from ..base import MXNetError
from ..engine import Engine
from ..telemetry import memdump as _memdump
from ..telemetry import metrics as _metrics
from ..testing import rescheck as _rescheck


class PagedKVArena:
    """Block-table allocator over the per-layer K and V page buffers."""

    def __init__(self, geometry, mesh=None, kv_spec=None):
        self.geometry = geometry
        self.quantized = geometry.quantized
        # With mesh=/kv_spec= the page buffers live sharded on the mesh
        # — KV heads (dim 1 of a layer's buffer) on the tp axis is the
        # canonical spec; the serving executables' kv arguments then
        # inherit the placement.  Scales are tiny and stay on one device.
        self._placement = None
        if mesh is not None or kv_spec is not None:
            from .. import sharding as _sharding

            self._placement = _sharding.named_sharding(mesh, kv_spec)
            _sharding.maybe_verify(self._placement.mesh,
                                   self._placement.spec,
                                   shape=geometry.kv_shape(),
                                   what="kv_arena")
        self._state = self._zeros()
        self.tag()
        # page 0 is the null page — never allocated
        self._free = collections.deque(range(1, geometry.num_pages))
        # page id -> LIST of owner tags.  One entry per reference: the
        # allocating request, plus (ISSUE 19) the prefix cache and any
        # session or spliced request sharing the page.  refcount ==
        # len(list); the page recycles only when the list empties, so
        # ``free`` under sharing decrements instead of recycling.
        self._owner = {}
        # MXNET_RESCHECK: one token per live allocation, keyed by its
        # first page (plain dict — loop-thread-only like _owner)
        self.res_scope = "arena:%x" % id(self)
        self._res = {}
        self.liveness_flushes = 0  # times a pending segment forced a flush

    # -- capacity ---------------------------------------------------------
    @property
    def free_pages(self):
        return len(self._free)

    @property
    def total_pages(self):
        """Allocatable pages (the null page is not part of the budget)."""
        return self.geometry.num_pages - 1

    def pages_needed(self, total_tokens):
        """Pages a sequence of ``total_tokens`` (prompt + budget) needs."""
        return -(-int(total_tokens) // self.geometry.page_size)

    def utilization(self):
        used = self.total_pages - len(self._free)
        return used / float(self.total_pages)

    # -- alloc/free -------------------------------------------------------
    def alloc(self, n_pages, owner):
        """Claim ``n_pages`` for ``owner``; None when the arena is full.

        Handing a previously-freed page to a new owner is the reuse
        moment: drain any bulk segment still reading the arena buffers
        first, so deferred imperative work observes the pre-reuse
        snapshot before the next executable call overwrites the page.
        """
        n_pages = int(n_pages)
        if n_pages <= 0:
            raise MXNetError("alloc wants a positive page count")
        if n_pages > self.geometry.max_pages_per_seq:
            raise MXNetError(
                "sequence needs %d pages but max_pages_per_seq is %d "
                "(max context %d tokens)"
                % (n_pages, self.geometry.max_pages_per_seq,
                   self.geometry.max_context))
        if n_pages > len(self._free):
            return None
        self.drain_pending_readers("serve_arena_alloc")
        pages = [self._free.popleft() for _ in range(n_pages)]
        for p in pages:
            self._owner[p] = [owner]
        if _rescheck.enabled():
            self._res[pages[0]] = _rescheck.acquire(
                "arena", owner, scope=self.res_scope)
        self._gauges()
        return pages

    def retain(self, pages, owner):
        """Add one reference per page for ``owner`` (prefix-cache splice,
        session pin).  The pages must already be allocated — retaining a
        free or null page is block-table corruption, not a cache miss."""
        for p in pages:
            owners = self._owner.get(p)
            if owners is None or p == 0:
                raise MXNetError("retaining page %d that is not allocated"
                                 % p)
            owners.append(owner)
        self._gauges()

    def free(self, pages, owner=None):
        """Drop one reference per page; recycle pages whose count hits 0.

        Double frees stay guarded under sharing: the ``owner`` tag must
        hold a reference on every page it frees, and a page recycles
        exactly once — when its last reference goes (refcounted free
        must not confuse the RL12xx page tracking, so the rescheck token
        for an allocation group releases only when its first page truly
        returns to the free list).
        """
        for p in pages:
            owners = self._owner.get(p)
            if owners is None or p == 0:
                raise MXNetError("freeing page %d that is not allocated"
                                 % p)
            if owner is not None:
                if owner not in owners:
                    raise MXNetError(
                        "page %d is owned by %r, not %r — double free or "
                        "block-table corruption" % (p, owners, owner))
                owners.remove(owner)
            else:
                owners.pop()
            if not owners:
                del self._owner[p]
                self._free.append(p)
                _rescheck.release(self._res.pop(p, None))
        self._gauges()

    def owner_of(self, page):
        owners = self._owner.get(page)
        return owners[0] if owners else None

    def refcount(self, page):
        """Live references on ``page`` (0 when free / null)."""
        return len(self._owner.get(page, ()))

    def shared_pages(self):
        """Pages currently referenced by more than one owner."""
        return sum(1 for o in self._owner.values() if len(o) > 1)

    def assert_quiescent(self):
        """Leak check: every allocatable page is back on the free list
        and nothing but the null page is live.  Raises ``MXNetError``
        naming the leaked pages and their owners — the serve
        chaos/expiry/cancel/drain tests call this after every scenario
        (ISSUE 15: a robustness path that loses pages is a slow death).
        """
        problems = []
        if self._owner:
            by_owner = {}
            for p, owners in sorted(self._owner.items()):
                for o in owners:
                    by_owner.setdefault(o, []).append(p)
            problems.append("%d live page(s): %s" % (
                len(self._owner),
                ", ".join("owner %r holds %s" % (o, pages)
                          for o, pages in sorted(by_owner.items(),
                                                 key=lambda kv: str(kv[0])))))
        free = list(self._free)
        expect = set(range(1, self.geometry.num_pages))
        if len(free) != len(set(free)):
            problems.append("free list has duplicates")
        if set(free) - expect:
            problems.append("free list holds invalid pages %s"
                            % sorted(set(free) - expect))
        missing = expect - set(free) - set(self._owner)
        if missing:
            problems.append("page(s) %s neither free nor owned (leaked)"
                            % sorted(missing))
        if problems:
            raise MXNetError("arena not quiescent: "
                             + "; ".join(problems))

    def reset(self):
        """Hard reset after loop-crash containment: rebuild the free
        list and re-zero the buffers with plain ``device_put`` (no ops —
        zero live compiles holds even through a crash).  Only legal once
        every request was failed (``Scheduler.fail_all``): resetting
        under a live sequence would be silent KV corruption."""
        if self._owner:
            raise MXNetError(
                "arena reset with %d live page(s) — fail the in-flight "
                "requests first" % len(self._owner))
        self._free = collections.deque(range(1, self.geometry.num_pages))
        for tok in self._res.values():
            _rescheck.release(tok)
        self._res.clear()
        self._state = self._zeros()
        self.tag()
        self._gauges()

    def block_row(self, pages):
        """Block-table row (maxp,) int32 for a page list; unused entries
        point at the null page."""
        row = np.zeros(self.geometry.max_pages_per_seq, dtype=np.int32)
        row[: len(pages)] = pages
        return row

    # -- engine liveness --------------------------------------------------
    def _zeros(self):
        """A zeroed cache state placed with plain ``device_put`` — NOT
        nd.zeros: a serving process must not push ops (zero live
        compiles, the AOT warm start's claim, even through a crash)."""
        import jax

        from .model import state_avals

        def zero(aval, where=None):
            return aval and jax.device_put(
                np.zeros(aval.shape, aval.dtype), where)

        # the pages where the caller placed them, the scales where the
        # programs run
        return tuple(
            ((zero(k, self._placement), zero(k_sc)),
             (zero(v, self._placement), zero(v_sc)))
            for (k, k_sc), (v, v_sc) in state_avals(self.geometry))

    def tag(self):
        """Attribute every buffer of the current state to ``kv_page``
        (an untagged buffer sweeps as "temp").  A program hands back new
        array objects every step, two to four a layer, so the tags are
        renewed where the accounting is read (``LlamaServer.healthz``,
        ``/metrics``) and not in :meth:`adopt`.  Any thread may call it:
        it reads one reference, and a buffer donated meanwhile is no
        longer live and counts nowhere."""
        for (k, k_sc), (v, v_sc) in self._state:
            _memdump.tag(k, origin="kv_page", label="arena.k")
            _memdump.tag(v, origin="kv_page", label="arena.v")
            _memdump.tag(k_sc, origin="kv_page", label="arena.k_scale")
            _memdump.tag(v_sc, origin="kv_page", label="arena.v_scale")

    def buffers(self):
        """The cache state as the programs take it (argument 0): one
        ``((k_pages, k_scale), (v_pages, v_scale))`` a layer, the scales
        None unless the arena is quantized."""
        return self._state

    def drain_pending_readers(self, origin):
        """Flush this thread's bulk segment if it still reads the arena.

        Called before page reuse and before every donating executable
        call: XLA deletes donated buffers even while a pending segment
        holds them as ext inputs, and a recycled page must not be
        overwritten under a deferred read.  Cheap no-op when nothing
        pends (the steady-state serving case — no imperative ops at all).
        """
        import jax

        eng = Engine.get()
        bufs = jax.tree_util.tree_leaves(self._state)
        if eng.pending_reads(bufs):
            eng.flush_if_referencing(bufs, origin)
            self.liveness_flushes += 1
            if _metrics.enabled():
                _metrics.counter(
                    "mxnet_serve_arena_liveness_flushes_total",
                    help="bulk-segment flushes forced because a pending "
                         "segment still read the KV arena").inc()

    def adopt(self, state):
        """Swap in the post-call cache state (the donating executables
        deleted the old buffers, so this is the only live reference
        handoff)."""
        self._state = state

    def _gauges(self):
        if _metrics.enabled():
            _metrics.gauge(
                "mxnet_serve_arena_utilization",
                help="fraction of allocatable KV pages in use",
            ).set(self.utilization())
            _metrics.gauge(
                "mxnet_serve_arena_pages_in_use",
                help="allocated KV pages (null page excluded)",
            ).set(self.total_pages - len(self._free))
            _metrics.gauge(
                "mxnet_serve_prefix_shared_pages",
                help="arena pages held by more than one reference "
                     "(prefix-cache hits, pinned sessions)",
            ).set(self.shared_pages())
