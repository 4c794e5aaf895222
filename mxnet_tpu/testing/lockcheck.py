"""Runtime lock sanitizer: instrumented proxies for framework locks.

The static CD11xx pass (``mxnet_tpu/analysis/concurrency_check.py``)
reasons about lock *source*; this module watches lock *behaviour*.  With
``MXNET_LOCKCHECK=1`` (or :func:`install`), every framework lock created
through :func:`named_lock` / :func:`named_condition` is wrapped in a
proxy that, per acquisition:

* maintains the calling thread's **held-set** (a stack of lock names),
* adds an edge ``held -> acquiring`` to the process-global
  **acquisition-order graph** and raises :class:`LockCycleError` the
  moment an edge closes a cycle — deadlock *potential* is an error even
  on runs where the interleaving never actually deadlocks,
* counts contention (``mxnet_lock_contention_total{lock}`` — the probe
  acquire failed and the thread had to block) and records a
  ``lock.blocked`` flight event naming the holder,
* observes the hold time into ``mxnet_lock_hold_seconds{lock}`` on
  release.

Cycles additionally record a ``lock.cycle`` flight event before
raising, so a crash dump from a chaos run carries the full cycle path —
the serve-chaos and elastic-chaos CI matrices run under
``MXNET_LOCKCHECK=1`` and assert zero such events in the uploaded dumps.

Design constraints:

* **Zero cost when off.**  Disabled, :func:`named_lock` returns a plain
  ``threading.Lock`` — framework hot paths pay nothing.
* **Import-light** (stdlib + telemetry, like ``faults``): this package
  is imported from ``engine.py`` and ``dist_kvstore.py`` hot paths.
* **Graph nodes are lock NAMES**, not instances: two instances sharing
  a name (e.g. per-key kvstore locks) share one node, so an A→B order
  between *classes* of locks is enforced across all instances.  The
  flip side: same-name edges are skipped (they would be instant false
  cycles), so ordering between two locks of one class is out of scope —
  give locks distinct names where that ordering matters.
* **Proxy transparency**: the proxy supports ``with``, ``acquire`` /
  ``release`` (including ``blocking=False`` and ``timeout=``),
  ``locked()``, and the ``_is_owned`` hook ``threading.Condition``
  probes — ``threading.Condition(named_lock("x"))`` behaves exactly
  like one over a bare lock, with ``wait()`` correctly popping and
  re-pushing the held-set around its internal release/re-acquire.

Enabling mid-process (:func:`install`) affects locks created *after*
the call; module-level framework singletons created at import keep
their bare locks: build the objects to be watched after ``install()``.
"""
from __future__ import annotations

import threading
import time

from ..base import env_flag
from ..telemetry import flight as _flight
from ..telemetry import metrics as _metrics

__all__ = [
    "LockCycleError", "enabled", "install", "uninstall", "named_lock",
    "named_rlock", "named_condition", "held", "order_edges", "reset",
]

_ENABLED = env_flag("MXNET_LOCKCHECK", False)

# hold times are expected to be tiny (locks guarding dict/deque state);
# the top buckets exist to make a lock held across a blocking call glow
_HOLD_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5,
                 1.0, 5.0)

_tls = threading.local()            # .stack: [(proxy, t_acquired), ...]

# the sanitizer's own state is guarded by a BARE lock (never proxied,
# never part of the order graph) and nothing blocking runs under it
_state_lock = threading.Lock()
_edges = {}     # src name -> {dst name: "first seen" description}


class LockCycleError(RuntimeError):
    """A lock acquisition closed a cycle in the acquisition-order graph:
    some interleaving of the participating threads can deadlock, even if
    this run didn't."""


def enabled():
    return _ENABLED


def install():
    """Turn the sanitizer on for locks created from now on."""
    global _ENABLED
    _ENABLED = True


def uninstall():
    """Stop wrapping newly-created locks (existing proxies keep working
    so already-built objects stay consistent)."""
    global _ENABLED
    _ENABLED = False


def reset():
    """Test hook: clear the acquisition-order graph."""
    with _state_lock:
        _edges.clear()


def held():
    """Names of the locks the CURRENT thread holds, outermost first."""
    return [p._name for p, _t in getattr(_tls, "stack", [])]


def order_edges():
    """Snapshot of the acquisition-order graph: ``{src: {dst, ...}}``."""
    with _state_lock:
        return {src: set(dsts) for src, dsts in _edges.items()}


def _find_path(src, dst):
    """BFS over ``_edges`` (caller holds ``_state_lock``); returns the
    name path ``[src, ..., dst]`` or ``None``."""
    frontier = [[src]]
    seen = {src}
    while frontier:
        path = frontier.pop(0)
        for nxt in _edges.get(path[-1], ()):
            if nxt == dst:
                return path + [nxt]
            if nxt not in seen:
                seen.add(nxt)
                frontier.append(path + [nxt])
    return None


def _describe(path):
    return " -> ".join(path)


def _note_order(proxy):
    """Record ``held -> proxy`` edges; raise on a fresh cycle."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return
    me = threading.current_thread().name
    new = proxy._name
    for heldp, _t in stack:
        src = heldp._name
        if src == new:
            continue  # same-name nesting: out of scope (see module doc)
        with _state_lock:
            dsts = _edges.setdefault(src, {})
            if new in dsts:
                continue
            back = _find_path(new, src)
            if back is not None:
                fwd = [src, new]
                where = "; ".join(
                    "%s->%s first seen %s" % (a, b, _edges[a][b])
                    for a, b in zip(back, back[1:]))
                _flight.record("lock.cycle", name=new,
                               path=_describe(fwd),
                               conflicts=_describe(back), thread=me)
                raise LockCycleError(
                    "lock-order cycle: thread %r acquires %s while "
                    "holding %s (order %s), but the reverse order %s "
                    "already exists (%s) — some interleaving deadlocks"
                    % (me, new, src, _describe(fwd), _describe(back),
                       where))
            dsts[new] = "thread %s" % me


def _push(proxy):
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append((proxy, time.monotonic()))


def _pop(proxy):
    stack = getattr(_tls, "stack", None)
    if not stack:
        return
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] is proxy:
            _p, t0 = stack.pop(i)
            _metrics.histogram(
                "mxnet_lock_hold_seconds",
                help="instrumented-lock hold time (MXNET_LOCKCHECK=1)",
                buckets=_HOLD_BUCKETS,
                lock=proxy._name).observe(time.monotonic() - t0)
            return


class _LockProxy:
    """Instrumented ``threading.Lock`` stand-in (see module docstring)."""

    _reentrant = False

    def __init__(self, name):
        self._name = name
        self._inner = threading.Lock()
        self._owner = None          # thread ident while held
        self._owner_name = None
        self._count = 0

    def acquire(self, blocking=True, timeout=-1):
        me = threading.get_ident()
        if self._reentrant and self._owner == me:
            self._count += 1
            return True
        _note_order(self)
        # this IS the lock implementation: release pairs in release(),
        # driven by the caller's with/try-finally  # mxlint: disable=CD1104
        got = self._inner.acquire(False)
        if not got:
            if not blocking:
                return False
            _metrics.counter(
                "mxnet_lock_contention_total",
                help="instrumented-lock acquisitions that had to block "
                     "(MXNET_LOCKCHECK=1)",
                lock=self._name).inc()
            _flight.record("lock.blocked", name=self._name,
                           holder=self._owner_name or "?",
                           thread=threading.current_thread().name)
            got = self._inner.acquire(True, timeout) if timeout != -1 \
                else self._inner.acquire(True)
            if not got:
                return False
        self._owner = me
        self._owner_name = threading.current_thread().name
        self._count = 1
        _push(self)
        return True

    def release(self):
        if self._reentrant and self._owner == threading.get_ident() \
                and self._count > 1:
            self._count -= 1
            return
        _pop(self)
        self._owner = None
        self._owner_name = None
        self._count = 0
        self._inner.release()

    def locked(self):
        return self._inner.locked()

    # threading.Condition probes this instead of its acquire(0) fallback
    # — without it every wait()/notify() would count spurious contention
    def _is_owned(self):
        return self._owner == threading.get_ident()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return "<%s %r held=%s>" % (type(self).__name__, self._name,
                                    self._owner is not None)


class _RLockProxy(_LockProxy):
    """Reentrant variant: nested acquires by the owner are counted, only
    the outermost acquisition/release touches the held-set and graph."""

    _reentrant = True


def named_lock(name):
    """A ``threading.Lock`` — instrumented under ``MXNET_LOCKCHECK=1``
    (``name`` labels its telemetry and names its order-graph node)."""
    if not _ENABLED:
        return threading.Lock()
    return _LockProxy(name)


def named_rlock(name):
    if not _ENABLED:
        return threading.RLock()
    return _RLockProxy(name)


def named_condition(name, lock=None):
    """A ``threading.Condition`` over :func:`named_lock` (or over a
    caller-supplied lock/proxy, for conditions sharing one lock)."""
    return threading.Condition(lock if lock is not None
                               else named_lock(name))
