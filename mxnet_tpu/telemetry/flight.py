"""Flight recorder: a lock-free per-process event ring for black-box
forensics.

The async engine means the Python stack trace at crash time describes
almost nothing about what the framework was executing — the op that
failed was pushed long before the exception surfaces, and a serving
request's life spans queue, prefill and dozens of decode flushes.  The
flight recorder keeps the last N framework events (engine
push/flush/sync, kvstore RPCs, fault injections, serve scheduler
transitions, memory tags, elastic-membership transitions —
``membership.evict`` / ``membership.join`` / ``membership.epoch`` /
``membership.resync``, each eviction naming the lost rank's last RPC)
in a preallocated ring and dumps them to disk when the process dies, so
a post-mortem can read what *actually* happened instead of where the
exception happened to surface.

Design constraints:

- **Lock-free recording.**  ``record()`` is called from the engine hot
  path and from every HTTP/scheduler thread; it must never contend.
  Sequence numbers come from :func:`itertools.count` (atomic under the
  GIL) and each event writes exactly one ring slot — two racing events
  can at worst overwrite each other's slot near the wrap boundary,
  never corrupt the structure.
- **Bounded memory.**  The ring is a preallocated list (capacity
  rounded up to a power of two so the slot index is a mask, default
  4096 via ``MXNET_FLIGHT_RECORDER_SIZE``); old events are overwritten,
  the ``dropped`` count in :func:`status` says how many.
- **Timeline-compatible anchors.**  Events carry a monotonic timestamp
  relative to module import plus a wall anchor (``wall_t0_us``, the
  wall time of local ``ts == 0`` — the same convention as the profiler
  dumps), so ``tools/mxflight.py merge`` can overlay multi-rank flight
  dumps onto the PR 5 trace timeline via
  :func:`telemetry.merge_traces`.

Crash dumps are **armed** by ``MXNET_FLIGHT_DUMP=<path>`` (``{pid}`` /
``{rank}`` substitute), or programmatically via :func:`arm`.  Arming
installs an ``excepthook`` chain and a chained SIGTERM handler; the
engine additionally calls :func:`crash_dump` when it poisons a var.
Nothing is installed when unarmed — SIGTERM disposition stays whatever
the application set (``CheckpointHandler`` relies on ``SIG_DFL``).
"""
from __future__ import annotations

import itertools
import json
import os
import signal
import sys
import threading
import time

from ..base import atomic_path, env_flag

__all__ = [
    "record", "events", "status", "dump", "load", "enabled", "enable",
    "disable", "arm", "armed", "crash_dump", "reset", "to_trace",
]

_FORMAT_VERSION = 1

_ENABLED = env_flag("MXNET_FLIGHT_RECORDER", True)


def _pow2(n):
    c = 1
    while c < n:
        c <<= 1
    return c


def _capacity_from_env():
    raw = os.environ.get("MXNET_FLIGHT_RECORDER_SIZE") or "4096"
    try:
        n = int(raw)
    except ValueError:
        n = 4096
    return _pow2(max(64, n))


_CAPACITY = _capacity_from_env()
_MASK = _CAPACITY - 1
_ring = [None] * _CAPACITY
_seq = itertools.count()

# wall time of local ts==0 (module import) — same anchor convention as
# profiler dumps, so flight timelines merge with profiler timelines
_WALL_T0 = time.time()
_MONO_T0 = time.monotonic()

_armed_path = os.environ.get("MXNET_FLIGHT_DUMP") or None
_res_token = None   # rescheck handle for the armed dump registration
_hooks_installed = False
_crash_lock = threading.Lock()
_in_crash = False


def enabled():
    return _ENABLED


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    """Stop recording (the ring keeps its contents)."""
    global _ENABLED
    _ENABLED = False


def record(kind, **fields):
    """Append one event; returns its sequence number (-1 when disabled).

    ``kind`` is a dotted family name (``engine.push``, ``kv.send``,
    ``serve.admit``, ``fault``, ...); ``fields`` must be JSON-scalar
    values.  Lock-free: one counter increment + one slot store.
    """
    if not _ENABLED:
        return -1
    i = next(_seq)
    _ring[i & _MASK] = (i, time.monotonic() - _MONO_T0, kind, fields)
    return i


def events(kind=None, last=None):
    """Snapshot the ring as a seq-ordered list of event dicts.

    ``kind`` filters by exact name or dotted prefix (``"kv"`` matches
    ``kv.send``/``kv.recv``/...); ``last`` keeps only the N most recent
    after filtering.
    """
    evs = [e for e in list(_ring) if e is not None]
    evs.sort(key=lambda e: e[0])
    out = []
    for seq, ts, k, fields in evs:
        if kind is not None and k != kind and not k.startswith(kind + "."):
            continue
        d = {"seq": seq, "ts": round(ts, 6), "kind": k}
        d.update(fields)
        out.append(d)
    if last is not None:
        out = out[-int(last):]
    return out


def _recorded():
    live = [e[0] for e in list(_ring) if e is not None]
    return (max(live) + 1) if live else 0


def status():
    """Health summary for ``/healthz`` and dump metadata."""
    n = _recorded()
    return {
        "enabled": _ENABLED,
        "capacity": _CAPACITY,
        "recorded": n,
        "dropped": max(0, n - _CAPACITY),
        "armed": _armed_path is not None,
    }


def _rank():
    try:
        return int(os.environ.get("DMLC_RANK", "0") or 0)
    except ValueError:
        return 0


def _expand(path):
    return (path.replace("{pid}", str(os.getpid()))
                .replace("{rank}", str(_rank())))


def dump(path=None, reason="explicit"):
    """Write the ring to ``path`` (default: the armed ``MXNET_FLIGHT_DUMP``
    target) as JSON via ``base.atomic_path``.  Returns the path written."""
    if path is None:
        if _armed_path is None:
            raise ValueError(
                "flight.dump() needs a path (or set MXNET_FLIGHT_DUMP)")
        path = _armed_path
    path = _expand(os.fspath(path))
    st = status()
    doc = {
        "meta": {
            "version": _FORMAT_VERSION,
            "pid": os.getpid(),
            "rank": _rank(),
            "role": os.environ.get("DMLC_ROLE"),
            "reason": reason,
            "wall_t0_us": _WALL_T0 * 1e6,
            "capacity": st["capacity"],
            "recorded": st["recorded"],
            "dropped": st["dropped"],
        },
        "events": events(),
    }
    with atomic_path(path) as tmp:
        with open(tmp, "w") as f:
            json.dump(doc, f)
    return path


def load(path):
    """Parse a flight dump; raises on files that are not flight dumps."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "meta" not in doc or "events" not in doc:
        raise ValueError("%s: not a flight-recorder dump" % (path,))
    return doc


def to_trace(doc, pid=0):
    """Convert a loaded dump into a chrome-trace dict (µs timestamps)
    carrying the dump's wall anchor — directly mergeable with profiler
    dumps via :func:`telemetry.merge_traces`.

    Events are instants by default; an event carrying ``dur_s`` (the
    fleet router's attempt/request spans) renders as a complete "X"
    span ending at its record time.  Span events land on one thread row
    per ``replica`` field (row 0 = the router itself), so a hedged
    request is visible as overlapping spans on two replica rows."""
    evs = []
    tids = {"": 0}   # replica name -> chrome tid (row per replica)
    for e in doc.get("events", []):
        args = {k: v for k, v in e.items() if k not in ("ts", "kind")}
        ts_us = float(e.get("ts", 0.0)) * 1e6
        dur_s = e.get("dur_s")
        if isinstance(dur_s, (int, float)) and dur_s > 0:
            tid = tids.setdefault(str(e.get("replica", "")), len(tids))
            evs.append({"name": e.get("kind", "?"), "ph": "X",
                        "ts": ts_us - float(dur_s) * 1e6,
                        "dur": float(dur_s) * 1e6,
                        "pid": pid, "tid": tid, "args": args})
        else:
            evs.append({"name": e.get("kind", "?"), "ph": "i", "s": "p",
                        "ts": ts_us, "pid": pid, "tid": 0, "args": args})
    other = {}
    anchor = doc.get("meta", {}).get("wall_t0_us")
    if anchor is not None:
        other["wall_t0_us"] = anchor
    return {"traceEvents": evs, "displayTimeUnit": "ms", "otherData": other}


# ----------------------------------------------------------------------
# crash dumps
# ----------------------------------------------------------------------
def armed():
    return _armed_path


def arm(path):
    """Arm crash dumps to ``path`` and install the exception/SIGTERM
    hooks (idempotent).  ``MXNET_FLIGHT_DUMP`` does this at import."""
    global _armed_path, _res_token
    _armed_path = os.fspath(path)
    _install_crash_hooks()
    if _res_token is None:
        try:
            # lazy (testing imports this module); exempt from quiescence
            # — a dump hook legitimately outlives every drain, but a
            # second registration still trips double-free detection
            from ..testing import rescheck as _rescheck
            _res_token = _rescheck.acquire("flight", _armed_path,
                                           exempt=True)
        except ImportError:  # mid-bootstrap arm during circular import
            pass
    return _armed_path


def crash_dump(reason):
    """Best-effort dump to the armed path; no-op (returns None) when
    unarmed.  Called from the excepthook/SIGTERM chains and from the
    engine when a var is poisoned — must never raise or re-enter."""
    global _in_crash
    if _armed_path is None:
        return None
    with _crash_lock:
        if _in_crash:
            return None
        _in_crash = True
    try:
        return dump(reason=reason)
    except Exception:
        return None
    finally:
        _in_crash = False


def _install_crash_hooks():
    global _hooks_installed
    if _hooks_installed:
        return
    _hooks_installed = True

    prev_hook = sys.excepthook

    def _flight_excepthook(tp, val, tb):
        crash_dump("exception:%s" % getattr(tp, "__name__", tp))
        prev_hook(tp, val, tb)

    sys.excepthook = _flight_excepthook

    try:
        prev_term = signal.getsignal(signal.SIGTERM)

        def _flight_sigterm(signum, frame):
            crash_dump("sigterm")
            if callable(prev_term):
                prev_term(signum, frame)
            elif prev_term != signal.SIG_IGN:
                # re-raise with default disposition so exit status stays
                # "killed by SIGTERM" for the parent
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _flight_sigterm)
    except (ValueError, OSError):
        pass  # not the main thread / restricted env: excepthook still works


if _armed_path is not None:
    _install_crash_hooks()


def reset():
    """Test hook: clear the ring and restart sequence numbering."""
    global _ring, _seq
    _ring = [None] * _CAPACITY
    _seq = itertools.count()
