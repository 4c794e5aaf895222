"""Process-global metrics registry: counters, gauges, histograms.

The observability counterpart of ``mx.profiler`` (docs/observability.md):
the profiler records *individual events* while it is explicitly running;
metrics collect *aggregates* all the time, cheaply enough to stay on in
production — every update is a plain int/dict mutation behind one
``_ENABLED`` branch (``MXNET_TELEMETRY=0`` turns the branch off).

Three primitives, Prometheus-shaped:

* :func:`counter` — monotonically increasing count (``_total`` names).
* :func:`gauge` — point-in-time value (queue depth, samples/sec).
* :func:`histogram` — bucketed distribution with ``sum``/``count``
  (latencies, compile wall-times).

All three take ``**labels``; one (name, labels) pair maps to one metric
object forever, so hot paths resolve their handle once and call
``.inc()``/``.set()``/``.observe()`` directly.

Sources that already aggregate (``Engine.stats``, the ``_jitted`` lru
cache) export through *collectors* — callbacks run at snapshot time that
copy the aggregate into the registry, so the hot path pays nothing.

Export surfaces: :func:`snapshot` (JSON-able dict), :func:`prometheus_text`
(text exposition format), :func:`dump` (atomic file write; also armed at
interpreter exit when ``MXNET_TELEMETRY_DUMP`` is set).
"""
from __future__ import annotations

import atexit
import bisect
import json
import os
import threading
import warnings

from ..base import atomic_path, env_flag

_ENABLED = env_flag("MXNET_TELEMETRY", True)

_lock = threading.Lock()          # guards registration (updates take
                                  # the per-metric lock instead)
_METRICS = {}                     # (name, labels_tuple) -> metric object
_FAMILIES = {}                    # name -> (kind, help)
_COLLECTORS = []                  # snapshot-time exporters

# Histogram default: latency-shaped seconds buckets, 100us..60s
_DEFAULT_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def enabled():
    """Is metric collection on? (``MXNET_TELEMETRY``, default on)."""
    return _ENABLED


def enable():
    """Turn collection on at runtime (e.g. after a disabled baseline)."""
    global _ENABLED
    _ENABLED = True


def disable():
    """Turn collection off at runtime; handles stay valid but updates
    become one dead branch."""
    global _ENABLED
    _ENABLED = False


class Counter:
    """Monotonic count.  ``set()`` exists for collectors that mirror an
    externally-maintained total (e.g. ``Engine.stats.ops_pushed``).

    Updates take a per-metric lock: ``value += n`` is three bytecodes and
    the serving tier mutates handles from the scheduler loop and every
    HTTP thread at once — without the lock, concurrent increments lose
    counts.  Engine hot-path families are collector-backed (one ``set``
    at snapshot time), so the lock never sits on the dispatch path."""

    __slots__ = ("value", "_lk")

    def __init__(self):
        self.value = 0
        self._lk = threading.Lock()

    def inc(self, n=1):
        if _ENABLED:
            with self._lk:
                self.value += n

    def set(self, value):
        if _ENABLED:
            self.value = value


class Gauge:
    __slots__ = ("value", "_lk")

    def __init__(self):
        self.value = 0
        self._lk = threading.Lock()

    def set(self, value):
        if _ENABLED:
            self.value = value

    def inc(self, n=1):
        if _ENABLED:
            with self._lk:
                self.value += n

    def dec(self, n=1):
        if _ENABLED:
            with self._lk:
                self.value -= n


class Histogram:
    """Prometheus-style histogram: per-bucket counts (cumulated at export
    time), plus ``sum`` and ``count``.  ``observe`` locks so concurrent
    observers can't lose bucket increments (see Counter)."""

    __slots__ = ("bounds", "counts", "sum", "count", "_lk")

    def __init__(self, bounds):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0
        self._lk = threading.Lock()

    def observe(self, value):
        if _ENABLED:
            with self._lk:
                self.counts[bisect.bisect_left(self.bounds, value)] += 1
                self.sum += value
                self.count += 1


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


def _labels_key(labels):
    if not labels:
        return ()
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _check_kind(name, kind):
    fam = _FAMILIES.get(name)
    if fam is not None and fam[0] != kind:
        raise ValueError(
            "metric %r already registered as a %s (requested %s)"
            % (name, fam[0], kind))
    return fam


def _get(kind, name, help, buckets, labels):
    key = (name, _labels_key(labels))
    _check_kind(name, kind)  # before the fast path: a same-key lookup
    m = _METRICS.get(key)    # of the wrong kind must not hand back the
    if m is not None:        # existing series
        return m
    with _lock:
        m = _METRICS.get(key)
        if m is not None:
            return m
        fam = _check_kind(name, kind)
        if fam is None:
            _FAMILIES[name] = (kind, help or "")
        if kind == "histogram":
            m = Histogram(buckets or _DEFAULT_BUCKETS)
        else:
            m = _KINDS[kind]()
        _METRICS[key] = m
        return m


def counter(name, help="", **labels):
    """Resolve (creating if needed) the counter for (name, labels)."""
    return _get("counter", name, help, None, labels)


def gauge(name, help="", **labels):
    return _get("gauge", name, help, None, labels)


def histogram(name, help="", buckets=None, **labels):
    """``buckets`` are upper bounds (exclusive of the implicit +Inf);
    only the first registration of a family sets them."""
    return _get("histogram", name, help, buckets, labels)


def register_collector(fn):
    """Run ``fn()`` before every snapshot/export so sources that already
    aggregate (engine stats, lru caches) publish without hot-path cost."""
    with _lock:
        if fn not in _COLLECTORS:
            _COLLECTORS.append(fn)


def _run_collectors():
    for fn in list(_COLLECTORS):
        try:
            fn()
        except Exception:  # an exporter bug must never break a snapshot
            pass


# -- compile tracking (shared by ops.registry and engine.BulkSegment) -------

_COMPILE_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0, 30.0, 60.0, 120.0, 300.0)
_compile_counts = {}   # signature key -> compiles seen
_retrace_warned = set()


def record_compile(op, key, seconds, n=1):
    """One XLA (re)trace of ``op`` (an op name or ``bulk_segment``) took
    ``seconds``; ``key`` identifies the op *signature* (name + static
    attrs + input fields) the retrace watchdog counts per.

    Warns ONCE per signature when its compile count exceeds
    ``MXNET_RETRACE_WARN_THRESHOLD`` (default 8) — the silent-retrace
    storm (shape/attr churn re-tracing the same op every step) that is
    otherwise invisible until a job is mysteriously slow.
    """
    if not _ENABLED:
        return
    histogram("mxnet_compile_seconds",
              help="XLA compile (trace-to-executable) wall time",
              buckets=_COMPILE_BUCKETS, op=op).observe(seconds)
    counter("mxnet_compiles_total", help="XLA compiles", op=op).inc(n)
    seen = _compile_counts.get(key, 0) + n
    _compile_counts[key] = seen
    threshold = int(os.environ.get("MXNET_RETRACE_WARN_THRESHOLD", "8"))
    if seen > threshold and key not in _retrace_warned:
        _retrace_warned.add(key)
        warnings.warn(
            "op signature %r has compiled %d times "
            "(MXNET_RETRACE_WARN_THRESHOLD=%d): inputs keep changing "
            "shape/dtype or attrs churn, so XLA re-traces instead of "
            "reusing the cached executable — pad/bucket input shapes or "
            "hoist varying attrs; see docs/observability.md"
            % (op, seen, threshold), stacklevel=2)


# -- export -----------------------------------------------------------------

def snapshot():
    """All metrics as one JSON-able dict:
    ``{family: {"type", "help", "series": [{"labels", ...values}]}}``.
    Histogram buckets are cumulative, keyed by upper bound, with the
    implicit ``+Inf`` bucket equal to ``count`` (Prometheus semantics).
    """
    _run_collectors()
    with _lock:
        items = sorted(_METRICS.items())
        fams = dict(_FAMILIES)
    out = {}
    for (name, labels), m in items:
        kind, help_ = fams.get(name, ("counter", ""))
        fam = out.setdefault(name, {"type": kind, "help": help_,
                                    "series": []})
        entry = {"labels": dict(labels)}
        if isinstance(m, Histogram):
            acc, buckets = 0, {}
            for bound, c in zip(m.bounds, m.counts):
                acc += c
                buckets["%g" % bound] = acc
            buckets["+Inf"] = m.count
            entry.update(buckets=buckets, sum=m.sum, count=m.count)
        else:
            entry["value"] = m.value
        fam["series"].append(entry)
    return out


def histogram_quantile(series, q):
    """Estimate the ``q``-quantile (0..1) from one snapshot histogram
    series (Prometheus ``histogram_quantile`` semantics: cumulative
    buckets, linear interpolation within the winning bucket, +Inf
    clamped to the largest finite bound).

    ``series`` is one entry of ``snapshot()[family]["series"]`` — the
    shape the telemetry dump stores, so serving dashboards and the
    serve-smoke CI job can read p50/p99 TTFT straight off a dump without
    the process that produced it.  Returns 0.0 for an empty histogram.
    """
    total = series.get("count", 0)
    if not total:
        return 0.0
    rank = q * total
    prev_bound, prev_acc = 0.0, 0
    finite = [(float(b), c) for b, c in series["buckets"].items()
              if b != "+Inf"]
    finite.sort()
    for bound, acc in finite:
        if acc >= rank:
            span = acc - prev_acc
            frac = (rank - prev_acc) / span if span else 1.0
            return prev_bound + (bound - prev_bound) * frac
        prev_bound, prev_acc = bound, acc
    return finite[-1][0] if finite else 0.0


def _escape_label_value(v):
    """Exposition-format label-value escaping: exactly backslash, double
    quote and newline get escape sequences; every other byte (tabs,
    non-ASCII UTF-8) passes through raw.  ``json.dumps`` is NOT a valid
    substitute — it emits ``\\t``/``\\uXXXX`` sequences the Prometheus
    parser rejects."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                  .replace("\n", "\\n"))


def _fmt_labels(labels, extra=None):
    parts = ['%s="%s"' % (k, _escape_label_value(v))
             for k, v in labels.items()]
    if extra:
        parts.append("%s=%s" % extra)
    return "{%s}" % ",".join(parts) if parts else ""


def render_text(snap):
    """Render one :func:`snapshot`-shaped dict (this registry's or a
    fleet-merged one from ``telemetry.aggregate``) as Prometheus text."""
    lines = []
    for name, fam in snap.items():
        if fam["help"]:
            lines.append("# HELP %s %s" % (name, fam["help"]))
        lines.append("# TYPE %s %s" % (name, fam["type"]))
        for s in fam["series"]:
            labels = s["labels"]
            if fam["type"] == "histogram":
                for bound, c in s["buckets"].items():
                    lines.append("%s_bucket%s %d" % (
                        name, _fmt_labels(labels, ("le", '"%s"' % bound)),
                        c))
                lines.append("%s_sum%s %g"
                             % (name, _fmt_labels(labels), s["sum"]))
                lines.append("%s_count%s %d"
                             % (name, _fmt_labels(labels), s["count"]))
            else:
                lines.append("%s%s %g"
                             % (name, _fmt_labels(labels), s["value"]))
    return "\n".join(lines) + "\n"


def prometheus_text():
    """Prometheus text exposition format (scrape-able / pushgateway-able)."""
    return render_text(snapshot())


def dump(path=None):
    """Atomically write the snapshot to ``path`` (default:
    ``MXNET_TELEMETRY_DUMP`` or ``telemetry.json``).  A ``.prom``/
    ``.txt`` suffix writes Prometheus text; anything else JSON."""
    path = path or os.environ.get("MXNET_TELEMETRY_DUMP") \
        or "telemetry.json"
    if path.endswith((".prom", ".txt")):
        payload = prometheus_text()
    else:
        payload = json.dumps(snapshot(), indent=1, sort_keys=True)
    with atomic_path(path) as tmp:
        with open(tmp, "w") as f:
            f.write(payload)
    return path


def reset():
    """Zero every metric IN PLACE (handles cached by hot paths stay
    valid) and clear the retrace watchdog.  Test isolation helper."""
    with _lock:
        for m in _METRICS.values():
            if isinstance(m, Histogram):
                m.counts = [0] * (len(m.bounds) + 1)
                m.sum = 0.0
                m.count = 0
            else:
                m.value = 0
        _compile_counts.clear()
        _retrace_warned.clear()


def _atexit_dump():
    try:
        dump(os.environ["MXNET_TELEMETRY_DUMP"])
    except Exception as exc:
        # never turn interpreter exit into a traceback — but a silently
        # missing dump file costs hours; leave one line of evidence
        import sys
        print("mxnet_tpu: telemetry dump failed: %s" % (exc,),
              file=sys.stderr)


if os.environ.get("MXNET_TELEMETRY_DUMP"):
    atexit.register(_atexit_dump)
