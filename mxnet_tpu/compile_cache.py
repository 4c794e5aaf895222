"""Persistent cross-process compilation cache + AOT warm start.

TPU-native answer to the reference's deployment story (the C predict API and
``amalgamation/``: load an artifact, run immediately, no frontend).  Under
XLA every process pays trace+compile for every executable it touches — a
cold llama train step is ~2 minutes of compile — so this module provides
two escape hatches, wired under every compile site the framework has
(``_jitted`` eager ops, bulk segments, ``hybridize()``'d blocks,
``JitTrainStep``, ``deploy.export_model``):

1. **Persistent compilation cache** — JAX's disk cache, enabled and managed
   here.  ``MXNET_COMPILE_CACHE`` controls it: ``0`` disables, ``1`` forces
   on, a *path* forces on with that directory, and the default ``auto``
   enables it unless the process was told to run on CPU (XLA:CPU cache
   entries are AOT objects keyed without host machine features — an entry
   compiled elsewhere can SIGILL a pure-CPU process that loads it).
   ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory and jax
   applies it itself; else ``MXNET_COMPILE_CACHE_DIR``, else
   ``<checkout>/.jax_cache``.  ``MXNET_COMPILE_CACHE_MIN_SECS`` is
   the minimum compile time worth persisting, and
   ``MXNET_COMPILE_CACHE_BUDGET_MB`` an LRU size budget enforced here (not
   via jax's own ``jax_compilation_cache_max_size``) so evictions are
   *countable*.  Hit/miss/write/evict counters and a size gauge export
   through telemetry as ``mxnet_compile_cache_*``.  The same listener keeps
   a ledger of every program the process builds, cache on or off
   (:func:`programs`, :func:`report`): what each cost to trace, to lower and
   to load or compile, and under which set-up stage it was built.

2. **AOT executable serialization** — ``serialize_compiled`` /
   ``deserialize_compiled`` wrap PJRT executable pickling
   (``jax.experimental.serialize_executable``) and ``save_bundle`` /
   ``load_bundle`` give ``hybridize(aot=...)``, ``JitTrainStep
   .save_executable`` and ``Predictor.warm()`` a common signed artifact
   format, so a fleet restart compiles *nothing*.

Keying notes: bulk segments are structurally keyed by op sequence in
``engine.py``; the exact taped path compiles through
``lower().compile(compiler_options=...)`` under a *differently named*
traced callable, so exact and fused artifacts can never collide in the
disk cache (the HLO module name and the compiler options both enter
jax's cache key).
"""
from __future__ import annotations

import atexit
import collections
import os
import pickle
import threading
import time

from .base import MXNetError, atomic_path
from .testing import lockcheck as _lockcheck

_AOT_MAGIC = b"MXAOT1\n"

_lock = _lockcheck.named_lock("compile.cache")
# raw monitoring-event tallies; "misses" is derived (requests - hits)
_stats = {"hits": 0, "writes": 0, "requests": 0, "evictions": 0,
          "aot_loads": 0, "aot_saves": 0}
_state = {"enabled": False, "dir": None, "budget_mb": 0.0,
          "listener": False, "collector": False, "atexit": False}

# The ledger of programs built: one entry a program, closed by jax's backend
# event.  The oldest entries fall out; ``seq`` counts from 0, so the first
# kept entry's ``seq`` is the number fallen.  ``_built`` keeps the totals
# over every entry ever closed, for the collector.
_PROGRAMS_KEPT = 4096
_programs = collections.deque(maxlen=_PROGRAMS_KEPT)
_built = {"closed": 0, "trace": 0.0, "lower": 0.0, "load": 0.0,
          "compile": 0.0, "hit": 0, "miss": 0, "off": 0}
_pending = threading.local()        # a thread's build that is still open

_EVENTS = "/jax/core/compile/"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def default_cache_dir():
    """``<checkout>/.jax_cache``: a fixed path beside the package, because
    the path is part of jax's cache key — a home, temporary, pid- or
    time-named directory never hits from the next checkout or run."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def enabled():
    """True when the persistent disk cache was activated by configure()."""
    return _state["enabled"]


def cache_dir():
    """The active cache directory, or None when disabled."""
    return _state["dir"] if _state["enabled"] else None


def persistent_hits():
    """Monotonic count of executables loaded from the disk cache.

    Cheap enough for the dispatch hot path: engine/registry snapshot it
    around a push to tell a disk hit (fast, warm start) from a true
    retrace, so warm processes neither pollute ``mxnet_compile_seconds``
    nor trip the MXNET_RETRACE_WARN_THRESHOLD watchdog.
    """
    return _stats["hits"]


def stats():
    with _lock:
        out = dict(_stats)
    out["misses"] = max(0, out["requests"] - out["hits"])
    return out


def cache_size_bytes():
    d = _state["dir"]
    if not d or not os.path.isdir(d):
        return 0
    total = 0
    try:
        for ent in os.scandir(d):
            try:
                if ent.is_file():
                    total += ent.stat().st_size
            except OSError:
                continue
    except OSError:
        return 0
    return total


def _open_build():
    """This thread's pending build: what jax has reported since the thread's
    last closed entry."""
    build = _pending.__dict__
    if not build:
        build.update(traces=[], lower_s=0.0, cache="off", written=False,
                     retrieval_s=None)
    return build


def _listener(event, **kwargs):
    # jax emits these from compiler.py/compilation_cache.py:
    #   cache_hits                 -> executable deserialized from disk
    #   cache_misses               -> entry WRITTEN to disk (fired on put)
    #   compile_requests_use_cache -> any compile request with cache on
    # all three between a build's lowering event and its backend event
    if not event.startswith("/jax/compilation_cache/"):
        return
    build = _open_build()
    with _lock:
        if event.endswith("/cache_hits"):
            _stats["hits"] += 1
            build["cache"] = "hit"
        elif event.endswith("/cache_misses"):
            _stats["writes"] += 1
            build["written"] = True
        elif event.endswith("/compile_requests_use_cache"):
            _stats["requests"] += 1
            build["cache"] = "miss"         # until a hit says otherwise


def _duration_listener(event, duration, **kwargs):
    """One build on one thread arrives as: trace events (a function traced
    inside another ends, and so arrives, before it), one lowering event
    ``jit(<name>)``, the cache's events, and one backend event
    ``jit(<name>)`` that closes the entry (jax 0.9.0)."""
    if event == _RETRIEVAL_EVENT:
        _open_build()["retrieval_s"] = duration
        return
    if not event.startswith(_EVENTS):
        return
    build = _open_build()
    if event.endswith("/jaxpr_trace_duration"):
        # a trace's duration holds those of the functions traced inside it:
        # keep the outermost, so that every moment counts once
        start = time.perf_counter() - duration
        traces = build["traces"]
        while traces and traces[-1][0] >= start:
            traces.pop()
        traces.append((start, duration))
    elif event.endswith("/jaxpr_to_mlir_module_duration"):
        build["lower_s"] += duration
    elif event.endswith("/backend_compile_duration"):
        from . import profiler

        entry = {"name": kwargs.get("fun_name"),
                 "trace_s": sum(d for _, d in build["traces"]),
                 "lower_s": build["lower_s"], "backend_s": duration,
                 "cache": build["cache"], "written": build["written"],
                 "retrieval_s": build["retrieval_s"],
                 "under": profiler.setup_stage(),
                 "t_end_ns": time.time_ns()}
        build.clear()
        backend = "load" if entry["cache"] == "hit" else "compile"
        with _lock:
            entry["seq"] = _built["closed"]
            entry["requests"] = _stats["requests"]
            _built["closed"] += 1
            _built["trace"] += entry["trace_s"]
            _built["lower"] += entry["lower_s"]
            _built[backend] += duration
            _built[entry["cache"]] += 1
            _programs.append(entry)


def _total_s(entry):
    return entry["trace_s"] + entry["lower_s"] + entry["backend_s"]


def programs():
    """The ledger: one dict for every program this process built, oldest
    first (the last ``_PROGRAMS_KEPT``; the first entry's ``seq`` is the
    number that fell out).  Keys: ``seq`` (order of closing), ``requests``
    (``stats()["requests"]`` when the entry closed, so a snapshot of
    ``stats()`` cuts the ledger at its moment), ``name`` (``jit(step)``),
    ``trace_s``, ``lower_s``, ``backend_s`` (the load on a hit, XLA's compile
    otherwise), ``cache`` (``hit``; ``miss``: compiled, and ``written`` says
    whether the entry went to the disk; ``off``: the build made no request
    of the cache), ``retrieval_s`` on a hit, ``under`` (the innermost
    ``profiler.setup_span`` open on the building thread, or None) and
    ``t_end_ns`` (``time.time_ns()``, the clock of a ``jax.profiler``
    trace's host plane).  Trace and lowering time that no backend event
    followed (``jit(f).lower()`` alone) goes to the thread's next entry."""
    with _lock:
        return [dict(e) for e in _programs]


def report():
    """The ledger as a text table, the costliest program first, and a line
    of totals: why a process took as long as it did to start."""
    rows = sorted(programs(), key=_total_s, reverse=True)
    with _lock:
        built = dict(_built)
    lines = ["%5s  %-36s %-17s %-5s %9s %9s %9s %9s"
             % ("seq", "name", "under", "cache", "trace_s", "lower_s",
                "backend_s", "total_s")]
    for e in rows:
        lines.append("%5d  %-36s %-17s %-5s %9.3f %9.3f %9.3f %9.3f" % (
            e["seq"], (e["name"] or "?")[:36], e["under"] or "-", e["cache"],
            e["trace_s"], e["lower_s"], e["backend_s"], _total_s(e)))
    lines.append(
        "total: %d programs (%d hit, %d miss, %d off): trace %.3f s, lower "
        "%.3f s, load %.3f s, compile %.3f s; %d older entries fell out"
        % (built["closed"], built["hit"], built["miss"], built["off"],
           built["trace"], built["lower"], built["load"], built["compile"],
           built["closed"] - len(rows)))
    return "\n".join(lines)


def _collector():
    from .telemetry import metrics as _m

    snap = stats()
    _m.counter("mxnet_compile_cache_hits_total",
               "Executables loaded from the persistent compile cache"
               ).set(snap["hits"])
    _m.counter("mxnet_compile_cache_misses_total",
               "Compile requests the persistent cache could not serve"
               ).set(snap["misses"])
    _m.counter("mxnet_compile_cache_writes_total",
               "Executables written to the persistent compile cache"
               ).set(snap["writes"])
    _m.counter("mxnet_compile_cache_evictions_total",
               "Cache entries evicted by MXNET_COMPILE_CACHE_BUDGET_MB"
               ).set(snap["evictions"])
    _m.counter("mxnet_compile_cache_aot_loads_total",
               "AOT executables deserialized from bundles"
               ).set(snap["aot_loads"])
    _m.counter("mxnet_compile_cache_aot_saves_total",
               "AOT executables serialized into bundles"
               ).set(snap["aot_saves"])
    if _state["enabled"]:
        _m.gauge("mxnet_compile_cache_size_bytes",
                 "Total bytes in the persistent compile cache directory"
                 ).set(cache_size_bytes())
    with _lock:
        built = dict(_built)
    for stage in ("trace", "lower", "load", "compile"):
        _m.counter("mxnet_program_build_seconds_total",
                   "Seconds spent building programs, by stage (load: the "
                   "backend on a persistent-cache hit; compile: otherwise)",
                   stage=stage).set(built[stage])
    for cache in ("hit", "miss", "off"):
        _m.counter("mxnet_programs_built_total",
                   "Programs built, by what the persistent cache did",
                   cache=cache).set(built[cache])


def _ensure_observability():
    if not _state["listener"]:
        try:
            from jax._src import monitoring

            monitoring.register_event_listener(_listener)
            monitoring.register_event_duration_secs_listener(
                _duration_listener)
            _state["listener"] = True
        except Exception:
            pass
    if not _state["collector"]:
        try:
            from .telemetry import metrics as _m

            _m.register_collector(_collector)
            _state["collector"] = True
        except Exception:
            pass


def enforce_budget(budget_mb=None):
    """Evict oldest-mtime cache entries until the directory fits the budget.

    Deliberately NOT delegated to jax's ``jax_compilation_cache_max_size``:
    jax evicts silently, and the whole point of owning eviction is the
    ``mxnet_compile_cache_evictions_total`` counter.  Returns the number of
    entries evicted.
    """
    if budget_mb is None:
        budget_mb = _state["budget_mb"]
    d = _state["dir"]
    if not budget_mb or budget_mb <= 0 or not d or not os.path.isdir(d):
        return 0
    budget = float(budget_mb) * 1024 * 1024
    # jax stores each executable as "<key>-cache" plus a tiny "<key>-atime"
    # companion it touches on every read; group the pair into one logical
    # entry, use the freshest mtime of the pair as its LRU recency, and
    # evict both files together so no orphans accumulate
    groups = {}
    try:
        for ent in os.scandir(d):
            try:
                if not ent.is_file():
                    continue
                st = ent.stat()
            except OSError:
                continue
            key = ent.name
            for suffix in ("-atime", "-cache"):
                if key.endswith(suffix):
                    key = key[: -len(suffix)]
                    break
            mtime, size, paths = groups.get(key, (0.0, 0, []))
            groups[key] = (max(mtime, st.st_mtime), size + st.st_size,
                           paths + [ent.path])
    except OSError:
        return 0
    total = sum(sz for _, sz, _ in groups.values())
    if total <= budget:
        return 0
    evicted = 0
    for _, sz, paths in sorted(groups.values()):  # least recently used first
        if total <= budget:
            break
        removed = False
        for path in paths:
            try:
                os.remove(path)
                removed = True
            except OSError:
                continue
        if removed:
            total -= sz
            evicted += 1
    if evicted:
        with _lock:
            _stats["evictions"] += evicted
    return evicted


def _looks_like_path(raw):
    return (os.sep in raw or raw.startswith(("~", ".", "$"))
            or (os.altsep and os.altsep in raw))


def configure(env=None):
    """The one cache rule: resolve the env contract and apply it to jax.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax keeps its cache
    there and this code sets no directory — it only records the path for
    its counters and budget.  Where it is not, the cache goes to
    ``MXNET_COMPILE_CACHE_DIR`` / a path-valued ``MXNET_COMPILE_CACHE``,
    else :func:`default_cache_dir`.  On by default unless the process was
    told to run on CPU (``JAX_PLATFORMS`` names cpu first): an unset
    ``jax_platforms`` means jax picks the TPU, and asking the backend
    would initialise it at import.  A CPU process opts in with
    ``MXNET_COMPILE_CACHE=1``, a path, or either ``*_DIR`` variable.

    Called once at ``import mxnet_tpu`` (before any compile can happen).
    Never raises: a cache is an optimization and must not break import.
    Returns True when the persistent cache ended up enabled.  The listeners
    are attached whatever the answer: the ledger of programs built
    (:func:`programs`) is kept with the cache off too.
    """
    if env is None:
        env = os.environ
    _ensure_observability()
    raw = env.get("MXNET_COMPILE_CACHE", "auto")
    mode = raw.lower()
    jax_dir = env.get("JAX_COMPILATION_CACHE_DIR") or None
    try:
        import jax

        if mode in ("0", "false", "off", "no"):
            if jax_dir:
                jax.config.update("jax_enable_compilation_cache", False)
            return False
        dir_from_mode = None
        if mode not in ("1", "true", "on", "yes", "auto") \
                and _looks_like_path(raw):
            dir_from_mode = os.path.expandvars(os.path.expanduser(raw))
        own_dir = env.get("MXNET_COMPILE_CACHE_DIR") or dir_from_mode
        forced = mode in ("1", "true", "on", "yes") or bool(own_dir)
        if not (forced or jax_dir):
            # auto is off for CPU processes: XLA:CPU cache entries are
            # AOT objects keyed without host machine features, and an
            # entry compiled elsewhere can SIGILL the process that loads
            # it.  CPU compiles are cheap; TPU compiles are the
            # minutes-long ones worth persisting.
            from .context import _told_cpu

            if _told_cpu():
                return False
        cache_dir_ = jax_dir or own_dir or default_cache_dir()
        os.makedirs(cache_dir_, exist_ok=True)
        min_secs = float(env.get("MXNET_COMPILE_CACHE_MIN_SECS", "1.0"))
        if not jax_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir_)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _state["enabled"] = True
        _state["dir"] = cache_dir_
        try:
            _state["budget_mb"] = float(
                env.get("MXNET_COMPILE_CACHE_BUDGET_MB", "0") or "0")
        except ValueError:
            _state["budget_mb"] = 0.0
        enforce_budget()
        if not _state["atexit"]:
            atexit.register(enforce_budget)
            _state["atexit"] = True
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# AOT executable serialization (PJRT pickling + bundle format)
# ---------------------------------------------------------------------------

def serialize_compiled(compiled):
    """``jax.stages.Compiled`` -> opaque bytes.

    The ids of the devices it was compiled for go with it: an executable
    built for one device loads onto that one device, also on a host that
    has four or eight."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = _se.serialize(compiled)
    devices = compiled._executable._unloaded_executable.device_list
    return pickle.dumps(
        {"payload": payload, "in_tree": in_tree, "out_tree": out_tree,
         "device_ids": [d.id for d in devices]},
        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob, backend=None):
    """Inverse of :func:`serialize_compiled`; returns a callable Compiled."""
    import jax
    from jax.experimental import serialize_executable as _se

    try:
        doc = pickle.loads(blob)
        by_id = {d.id: d for d in jax.devices(backend)}
        out = _se.deserialize_and_load(
            doc["payload"], doc["in_tree"], doc["out_tree"], backend=backend,
            execution_devices=[by_id[i] for i in doc["device_ids"]])
    except MXNetError:
        raise
    except Exception as e:
        raise MXNetError(
            "failed to deserialize AOT executable (%s: %s) — bundles are "
            "only loadable on the jax version/backend that produced them"
            % (type(e).__name__, e))
    with _lock:
        _stats["aot_loads"] += 1
    # the aot_loads counter must be published even in processes where the
    # disk cache is off (CPU serving procs): configure() never ran
    # _ensure_observability there, so register the collector here too
    _ensure_observability()
    return out


def save_bundle(path, entries, meta=None):
    """Write an AOT bundle: ``{key: serialized-executable-bytes}`` + meta.

    Atomic (tmp + rename) so an interrupted save never corrupts a bundle a
    serving fleet is about to load.
    """
    import jax

    doc = {
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "meta": dict(meta or {}),
        "entries": dict(entries),
    }
    with atomic_path(path) as tmp:
        with open(tmp, "wb") as f:
            f.write(_AOT_MAGIC)
            pickle.dump(doc, f, protocol=pickle.HIGHEST_PROTOCOL)
    with _lock:
        _stats["aot_saves"] += len(doc["entries"])
    _ensure_observability()


def load_bundle(path):
    """Read an AOT bundle; validates magic + platform before any load."""
    import jax

    try:
        with open(path, "rb") as f:
            magic = f.read(len(_AOT_MAGIC))
            if magic != _AOT_MAGIC:
                raise MXNetError(
                    "%s is not an mxnet_tpu AOT bundle (bad magic)" % path)
            doc = pickle.load(f)
    except MXNetError:
        raise
    except Exception as e:
        raise MXNetError("failed to read AOT bundle %s (%s: %s)"
                         % (path, type(e).__name__, e))
    plat = doc.get("platform")
    if plat and plat != jax.default_backend():
        raise MXNetError(
            "AOT bundle %s was compiled for platform %r but this process "
            "runs %r — recompile or re-export on the target platform"
            % (path, plat, jax.default_backend()))
    ver = doc.get("jax_version")
    if ver and ver != jax.__version__:
        import warnings

        warnings.warn(
            "AOT bundle %s was produced under jax %s (running %s); "
            "deserialization may fail across versions"
            % (path, ver, jax.__version__))
    return doc
