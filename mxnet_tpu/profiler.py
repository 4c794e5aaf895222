"""``mx.profiler`` — profiling API with chrome-trace export.

Capability parity with the reference profiler
(``python/mxnet/profiler.py:33-224`` API; ``src/profiler/profiler.h:251``
engine-hooked op stats; ``DumpProfile:299`` chrome://tracing JSON;
``aggregate_stats.cc`` summary tables; Domain/Task/Frame/Counter/Marker
primitives ``profiler.h:768-910``).

TPU-native mechanism: eager-mode op timings come from the engine's push
hook (each dispatched executable reports wall time); device-side detail
comes from the XLA/PJRT profiler — ``set_config(xla_trace_dir=...)``
arms ``jax.profiler`` so a ``run``→``stop`` window also captures an
xplane trace (viewable in TensorBoard/Perfetto, the TPU analogue of the
reference's NVTX/VTune emitters).  ``dump()`` writes standard
chrome://tracing JSON.
"""
from __future__ import annotations

import json
import threading
import time

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_imperative": True,
    "profile_symbolic": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
    "continuous_dump": False,
    "xla_trace_dir": None,
}
_state = {"running": False, "paused": False, "hook": None,
          "xla_active": False}
_events = []  # chrome trace event dicts
_t0 = time.perf_counter()
# wall-clock time of local ts==0: lets telemetry.merge_traces align
# dumps from different processes (each has its own perf_counter epoch)
# onto one timeline.  Embedded in every dump as otherData.wall_t0_us.
_wall0 = time.time()


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def _recording():
    return _state["running"] and not _state["paused"]


def set_config(**kwargs):
    """Configure the profiler (parity: profiler.py:33).

    Accepted keys: ``filename``, ``profile_all``, ``profile_symbolic``,
    ``profile_imperative``, ``profile_memory``, ``profile_api``,
    ``aggregate_stats``, ``continuous_dump`` and the TPU-specific
    ``xla_trace_dir`` (directory for the PJRT xplane trace).
    """
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise ValueError("invalid profiler options: %s" % sorted(unknown))
    _config.update(kwargs)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Deprecated alias (parity: profiler.py:70)."""
    set_config(filename=filename)


def _engine_hook(op_name, t_start, t_end):
    # a flushed bulk segment arrives as ONE push named bulk_segment[N];
    # give it its own category (with the fused op count in args) so
    # traces distinguish fused segments from single-op dispatches and
    # tooling can sum ops without parsing names (engine.BulkSegment.flush)
    args = None
    if op_name.startswith("bulk_segment["):
        cat = "bulk"
        try:
            args = {"ops": int(op_name[len("bulk_segment["):-1])}
        except ValueError:
            pass
    else:
        cat = "operator"
    add_span(op_name, (t_start - _t0) * 1e6, (t_end - _t0) * 1e6, cat=cat,
             args=args)


def add_span(name, t_start_us, t_end_us, cat="operator", tid=None,
             pid=0, args=None):
    """Record one complete duration event; timestamps are ``_now_us()``
    values (server request handlers and other non-engine
    instrumentation report through this).  ``tid`` defaults to the
    calling thread so concurrent handlers land on distinct trace
    tracks instead of overlapping on one.  ``pid`` is the trace
    process track — dist servers record at ``rank + 1`` so merged
    traces keep worker/server timelines apart; ``args`` carries
    correlation ids (e.g. the kvstore wire span id)."""
    if not _recording():
        return
    if tid is None:
        tid = threading.get_ident() & 0xFFFF
    ev = {
        "name": name, "ph": "X", "cat": cat,
        "ts": t_start_us, "dur": t_end_us - t_start_us,
        "pid": pid, "tid": tid,
    }
    if args:
        ev["args"] = dict(args)
    with _lock:
        _events.append(ev)


def set_state(state="stop", profile_process="worker"):
    """Start ('run') or stop ('stop') profiling (parity: profiler.py:89).

    ``profile_process='server'`` routes the command over the dist
    KVStore wire to every server (parity: the reference's
    kSetProfilerParams server command, include/mxnet/kvstore.h:49) —
    call ``set_kvstore_handle(kv)`` first.
    """
    from .engine import Engine

    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if profile_process == "server":
        _require_kv_handle().set_server_profiler_state(state)
        return
    eng = Engine.get()
    if state == "run" and not _state["running"]:
        _state["running"] = True
        _state["paused"] = False
        if _state["hook"] is None:
            _state["hook"] = _engine_hook
            eng.add_hook(_engine_hook)
        if _config["xla_trace_dir"]:
            try:
                import jax

                jax.profiler.start_trace(_config["xla_trace_dir"])
                _state["xla_active"] = True
            except Exception:  # device-side tracing is best-effort
                _state["xla_active"] = False
    elif state == "stop" and _state["running"]:
        _state["running"] = False
        if _state["hook"] is not None:
            eng.remove_hook(_state["hook"])
            _state["hook"] = None
        if _state["xla_active"]:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            _state["xla_active"] = False


def profiler_set_state(state="stop"):
    """Deprecated alias (parity: profiler.py:109)."""
    set_state(state)


def pause(profile_process="worker"):
    """Suspend event collection without tearing down (parity: :193).
    ``profile_process='server'`` pauses every dist server's profiler
    over the kvstore wire, same routing as ``set_state``/``dump``."""
    if profile_process == "server":
        _require_kv_handle().server_profiler_pause()
        return
    _state["paused"] = True


def resume(profile_process="worker"):
    if profile_process == "server":
        _require_kv_handle().server_profiler_resume()
        return
    _state["paused"] = False


def dump(finished=True, profile_process="worker"):
    """Write collected events as chrome://tracing JSON (parity: :122).
    ``profile_process='server'`` makes every dist server write ITS OWN
    trace file server-side (reference server profiling contract)."""
    if profile_process == "server":
        _require_kv_handle().server_profiler_dump(finished=finished)
        return
    if finished and _state["running"]:
        set_state("stop")
    trace = get_trace()
    with open(_config["filename"], "w") as f:
        json.dump(trace, f)
    if not _config["continuous_dump"]:
        with _lock:
            _events.clear()


def get_trace():
    """The collected events as a chrome-trace dict (what ``dump`` would
    write), without touching disk or profiler state.  Includes the
    wall-clock anchor ``telemetry.merge_traces`` aligns timelines by."""
    with _lock:
        return {"traceEvents": list(_events), "displayTimeUnit": "ms",
                "otherData": {"wall_t0_us": _wall0 * 1e6}}


def dump_profile():
    """Deprecated alias (parity: :143)."""
    dump(finished=False)


def dumps(reset=False, format="table", sort_by="total", ascending=False,
          aggregate=True):
    """Aggregate per-op summary (parity: :151, aggregate_stats.cc —
    count/total/avg/min/max per op name).  ``aggregate=False`` returns
    the raw event list as JSON instead of the table."""
    if not aggregate:
        with _lock:
            out = json.dumps(list(_events))
            if reset:
                _events.clear()
        return out
    with _lock:
        stats = {}
        for e in _events:
            if e["ph"] != "X":
                continue
            s = stats.setdefault(e["name"],
                                 {"count": 0, "total": 0.0,
                                  "min": float("inf"), "max": 0.0})
            s["count"] += 1
            s["total"] += e["dur"]
            s["min"] = min(s["min"], e["dur"])
            s["max"] = max(s["max"], e["dur"])
        if reset:
            _events.clear()
    for s in stats.values():
        s["avg"] = s["total"] / max(s["count"], 1)
    if format == "json":
        return json.dumps(stats)
    key = {"total": "total", "avg": "avg", "min": "min", "max": "max",
           "count": "count"}.get(sort_by, "total")
    rows = sorted(stats.items(), key=lambda kv: kv[1][key],
                  reverse=not ascending)
    lines = ["%-40s %8s %12s %12s %12s %12s"
             % ("Name", "Calls", "Total(us)", "Avg(us)", "Min(us)",
                "Max(us)")]
    for name, s in rows:
        lines.append("%-40s %8d %12.1f %12.1f %12.1f %12.1f"
                     % (name[:40], s["count"], s["total"], s["avg"],
                        s["min"], s["max"]))
    return "\n".join(lines)


class Domain:
    """Named grouping for custom profiling objects (parity: :225)."""

    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Annotation(TraceAnnotation):
    """The one span primitive.  Entering and leaving it is entering and
    leaving a ``jax.profiler.TraceAnnotation``: inside any ``jax.profiler``
    trace the span lies on the host plane of the same file, and the same
    clock, as the device's operations.  Where ``mx.profiler`` itself is
    running, leaving it also records the chrome event (``add_span``), so
    ``profile.json`` shows the same spans.  With neither running nothing
    is stored."""

    def __init__(self, name, event, cat, tid=None):
        super().__init__(name)
        self._event, self._cat, self._tid = event, cat, tid
        self._start = None

    def __enter__(self):
        self._start = _now_us() if _state["running"] else None
        super().__enter__()
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        start, self._start = self._start, None
        if start is not None:
            add_span(self._event, start, _now_us(), cat=self._cat,
                     tid=self._tid)


def span(name):
    """Context manager for one named stretch of the program's own work,
    ``mx:<name>`` in a device trace (``set_config(xla_trace_dir=...)``, or
    any ``jax.profiler`` trace around the program) and in ``dump()``'s
    chrome trace.  It takes a name and nothing else: what would be a
    keyword goes into the name, and order in time says which step."""
    name = "mx:" + name
    return _Annotation(name, name, "span")


_stages = threading.local()     # .open: this thread's open set-up stages


def setup_stage():
    """The innermost :func:`setup_span` open on this thread (its name
    without ``mx:``), or None: what ``compile_cache``'s ledger files a
    program ``under``."""
    stack = getattr(_stages, "open", None)
    return stack[-1] if stack else None


def _add_setup_seconds(stage, seconds):
    from .telemetry import metrics

    metrics.counter("mxnet_setup_seconds_total",
                    "Seconds in the program's own set-up stages "
                    "(profiler.setup_span)", stage=stage).inc(seconds)


class _SetupSpan(_Annotation):
    def __init__(self, stage):
        super().__init__("mx:" + stage, "mx:" + stage, "span")
        self._stage = stage

    def __enter__(self):
        if not hasattr(_stages, "open"):
            _stages.open = []
        _stages.open.append(self._stage)
        self._t0 = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _stages.open.pop()
        _add_setup_seconds(self._stage, time.perf_counter() - self._t0)


def setup_span(name):
    """:func:`span` for a stage that runs once a process or once an object,
    never in a steady step: the same ``mx:<name>`` annotation, and on
    leaving the stage's seconds are added to
    ``mxnet_setup_seconds_total{stage=<name>}``, whether or not a trace
    runs.  Programs built inside it carry ``<name>`` as ``under`` in
    ``compile_cache.programs()``."""
    return _SetupSpan(name)


class _Span:
    _tid = 1

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._open = None
        cls = _Span
        self._tid_id = cls._tid
        cls._tid = cls._tid + 1

    def start(self):
        self._open = _Annotation("mx:" + self.name, self.name,
                                 str(self.domain), self._tid_id)
        self._open.__enter__()

    def stop(self):
        if self._open is None:
            return
        ann, self._open = self._open, None
        ann.__exit__(None, None, None)

    def __str__(self):
        return self.name


class Task(_Span):
    """Nestable named span (parity: :284)."""


class Frame(_Span):
    """Per-iteration span, e.g. one training step (parity: :326)."""


class Counter:
    """Numeric time-series value (parity: :368); chrome 'C' events."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value  # value tracks even while not recording
        if not _recording():  # same gate as add_span
            return
        with _lock:
            _events.append({"name": self.name, "ph": "C",
                            "ts": _now_us(), "pid": 0,
                            "args": {self.name: value}})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self

    def __str__(self):
        return self.name


class Marker:
    """Instant event (parity: :430); chrome 'i' events."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if not _recording():  # same gate as add_span
            return
        with _lock:
            _events.append({"name": self.name, "ph": "i",
                            "ts": _now_us(), "pid": 0, "tid": 0,
                            "s": {"process": "p", "thread": "t",
                                  "global": "g"}.get(scope, "p")})

    def __str__(self):
        return self.name


_kv_handle = [None]


def set_kvstore_handle(handle):
    """Attach a dist KVStore so ``profile_process='server'`` commands
    reach the servers (parity: profiler.py set_kvstore_handle)."""
    _kv_handle[0] = handle


def _require_kv_handle():
    h = _kv_handle[0]
    if h is None or not hasattr(h, "set_server_profiler_state"):
        raise RuntimeError(
            "profile_process='server' needs a dist kvstore: call "
            "mx.profiler.set_kvstore_handle(kv) with a dist_* store first")
    return h


# parity: MXNET_PROFILER_AUTOSTART (env_var.md) — begin collecting as
# soon as the process imports the framework
import os as _os  # noqa: E402

if _os.environ.get("MXNET_PROFILER_AUTOSTART", "0") in ("1", "true"):
    set_state("run")
