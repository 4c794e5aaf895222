"""Runtime resource-leak sanitizer: a tracked-handle registry.

The static RL12xx pass (``mxnet_tpu/analysis/lifecycle_check.py``)
proves lifecycle properties about handles it can *see* in one function
body; this module watches the handles whose ownership crosses threads
and components — exactly the ones static analysis hands off and stops
tracking.  With ``MXNET_RESCHECK=1`` (or :func:`install`), the
framework's acquisition sites register every expensive handle here:

* arena page lists (``serve/arena.py`` ``alloc``/``free``),
* scheduler request futures (queued ``Request`` objects — resolved,
  failed, or cancelled),
* kvstore client sockets (``parallel/dist_kvstore.py``),
* serve loop threads (``serve/server.py``),
* temp files/dirs (``base.atomic_path``),
* armed flight-dump registrations (``telemetry/flight.arm`` — tracked
  for double-disarm detection but *exempt* from quiescence: a dump
  hook legitimately outlives every drain).

Each registration records kind, owner, a creation-site stack and the
flight sequence number at acquisition.  :func:`release` on an
already-released token raises :class:`ResourceLeakError` (and records
a ``res.double_free`` flight event); :func:`assert_quiescent` — called
from ``LlamaServer.stop()``/``drain()`` and usable from any test —
reports every live handle with its creation stack, generalizing
``PagedKVArena.assert_quiescent()`` from pages-only to every handle
kind.  An atexit hook reports stragglers to stderr (never raising at
interpreter exit).  Telemetry: ``mxnet_resource_live{kind}`` gauge,
``mxnet_resource_leaks_total{kind}`` counter, ``res.leak`` /
``res.double_free`` flight events (the chaos CI matrices run under
``MXNET_RESCHECK=1`` and assert zero ``res.leak`` events in the
uploaded dumps).

Design constraints (same contract as ``lockcheck``):

* **Zero cost when off.**  Disabled, :func:`acquire` returns ``None``
  and :func:`release`/:func:`assert_quiescent` are no-ops on ``None``
  — instrumented hot paths pay one truthiness check.
* **Import-light** (stdlib + telemetry): imported from the serve loop
  and kvstore hot paths.
* **Own state under a BARE lock** (never a framework ``named_lock``;
  nothing blocking runs under it) so the sanitizer can never deadlock
  the code it watches.

Enabling mid-process (:func:`install`) affects handles acquired
*after* the call: build the objects to be watched after ``install()``.
"""
from __future__ import annotations

import atexit
import itertools
import sys
import threading
import time
import traceback

from ..base import env_flag
from ..telemetry import flight as _flight
from ..telemetry import metrics as _metrics

__all__ = [
    "ResourceLeakError", "enabled", "install", "uninstall", "reset",
    "acquire", "release", "live", "assert_quiescent",
]

_ENABLED = env_flag("MXNET_RESCHECK", False)

_seq = itertools.count()

# registry of live handles, keyed by token; bare lock per module doc
_state_lock = threading.Lock()
_live = {}          # _Handle -> None (insertion-ordered set)
_leaked_total = 0   # handles ever reported leaked (test/debug aid)


class ResourceLeakError(RuntimeError):
    """A tracked handle was leaked (still live at a quiescence point)
    or released twice.  ``leaks`` carries the offending handles."""

    def __init__(self, message, leaks=()):
        super().__init__(message)
        self.leaks = tuple(leaks)


class _Handle:
    """One live acquisition.  Opaque to callers — hold it, pass it back
    to :func:`release`."""

    __slots__ = ("kind", "owner", "scope", "exempt", "seq", "stack",
                 "released")

    def __init__(self, kind, owner, scope, exempt):
        self.kind = kind
        self.owner = owner
        self.scope = scope
        self.exempt = exempt
        self.seq = next(_seq)
        # skip the two innermost frames (this ctor + acquire)
        self.stack = traceback.extract_stack(sys._getframe(2), limit=6)
        self.released = False

    @property
    def site(self):
        if self.stack:
            f = self.stack[-1]
            return "%s:%d in %s" % (f.filename, f.lineno, f.name)
        return "?"

    def describe(self):
        head = "%s %r (scope=%s, seq=%d) acquired at:" % (
            self.kind, self.owner, self.scope or "-", self.seq)
        frames = "".join("    %s" % line
                         for line in traceback.format_list(self.stack))
        return head + "\n" + frames.rstrip("\n")

    def __repr__(self):
        return "<tracked %s %r live=%s>" % (self.kind, self.owner,
                                            not self.released)


def enabled():
    return _ENABLED


def install():
    """Turn the sanitizer on for handles acquired from now on."""
    global _ENABLED
    _ENABLED = True


def uninstall():
    """Stop tracking newly-acquired handles (handles already tracked
    stay tracked so their release() calls pair up)."""
    global _ENABLED
    _ENABLED = False


def reset():
    """Test hook: forget every tracked handle."""
    with _state_lock:
        for h in _live:
            _gauge(h.kind).dec()
        _live.clear()


def _gauge(kind):
    return _metrics.gauge(
        "mxnet_resource_live",
        help="tracked handles currently live (MXNET_RESCHECK=1)",
        kind=kind)


def _leak_counter(kind):
    return _metrics.counter(
        "mxnet_resource_leaks_total",
        help="tracked handles reported leaked at a quiescence point "
             "(MXNET_RESCHECK=1)",
        kind=kind)


def acquire(kind, owner, scope=None, exempt=False):
    """Register a live handle; returns the token to :func:`release`
    later, or ``None`` when the sanitizer is off.

    ``kind`` buckets the handle for telemetry and filtering (``arena``,
    ``socket``, ``future``, ``thread``, ``tempfile``, ``flight``);
    ``owner`` names the owning entity (request id, server shard, path);
    ``scope`` groups handles torn down together (one server instance,
    one kvstore client) so :func:`assert_quiescent` can check one
    component without tripping over another's live handles.  Exempt
    handles skip quiescence/atexit reporting but keep double-free
    detection.
    """
    if not _ENABLED:
        return None
    h = _Handle(str(kind), str(owner), scope, exempt)
    with _state_lock:
        _live[h] = None
    _gauge(h.kind).inc()
    return h


def release(token):
    """Mark a tracked handle released.  ``None``-tolerant (the token is
    ``None`` whenever the acquire ran with the sanitizer off).  Raises
    :class:`ResourceLeakError` on a second release of the same token —
    the runtime twin of static RL1204."""
    if token is None:
        return
    with _state_lock:
        if token.released:
            double = True
        else:
            double = False
            token.released = True
            _live.pop(token, None)
    if double:
        _flight.record("res.double_free", resource=token.kind,
                       owner=token.owner, site=token.site)
        raise ResourceLeakError(
            "double release of tracked %s %r (first acquired at %s)"
            % (token.kind, token.owner, token.site), leaks=[token])
    _gauge(token.kind).dec()


def live(kind=None, scope=None):
    """Snapshot of live (non-exempt) handles, oldest first."""
    with _state_lock:
        out = [h for h in _live if not h.exempt]
    if kind is not None:
        out = [h for h in out if h.kind == kind]
    if scope is not None:
        out = [h for h in out if h.scope == scope]
    return out


def assert_quiescent(scope=None, kind=None, grace_s=0.25):
    """Raise :class:`ResourceLeakError` naming every live handle (in
    ``scope``/of ``kind``, when given) with its creation stack — the
    every-handle-kind generalization of
    ``PagedKVArena.assert_quiescent``.  Each leak records a
    ``res.leak`` flight event and bumps
    ``mxnet_resource_leaks_total{kind}``.

    ``grace_s`` re-polls briefly before declaring a leak: a resolving
    thread may sit between handing the resource back and releasing its
    token (e.g. the serve loop finishing a slot while ``drain()``
    checks) — a leak is a handle that *stays* live, not one caught
    mid-release."""
    deadline = time.monotonic() + float(grace_s)
    while True:
        leaks = live(kind=kind, scope=scope)
        if not leaks:
            return
        if time.monotonic() >= deadline:
            break
        time.sleep(0.005)
    _report(leaks)
    raise ResourceLeakError(
        "%d tracked handle(s) still live at quiescence point%s:\n%s"
        % (len(leaks),
           " (scope=%s)" % scope if scope is not None else "",
           "\n".join(h.describe() for h in leaks)),
        leaks=leaks)


def _report(leaks):
    global _leaked_total
    for h in leaks:
        _flight.record("res.leak", resource=h.kind, owner=h.owner,
                       scope=h.scope or "-", site=h.site, seq=h.seq)
        _leak_counter(h.kind).inc()
    with _state_lock:
        _leaked_total += len(leaks)


def _atexit_report():
    leaks = live()
    if not leaks:
        return
    _report(leaks)
    # never raise at interpreter exit: leave the evidence on stderr
    # (and in the flight dump, which arms its own atexit/excepthook)
    print("mxnet_tpu: MXNET_RESCHECK: %d tracked handle(s) leaked at "
          "exit:\n%s" % (len(leaks),
                         "\n".join(h.describe() for h in leaks)),
          file=sys.stderr)


atexit.register(_atexit_report)
