#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process.  It fails at once, and prints no result, where jax finds no
TPU, a ``device_kind`` that ``peaks.json`` does not list, or fewer chips
than the cell asks for.  Then: the program's compile-cache rule
(``compile_cache.configure()``: ``JAX_COMPILATION_CACHE_DIR`` where set,
else ``<checkout>/.jax_cache``), the cell's driver builds and warms the
cell's own shapes (set-up), the window, the peak memory, the comparison
with the plain reference that decides ``correct``, the metric readers, and
the contract's one JSON line last on standard output.

Nothing about a cell, a configuration, an architecture or a metric is in
this file:

- ``BENCHMARK.json`` (root) names the cells and which metrics each reports;
- ``configs/<config>.json`` holds a configuration's sizes and its
  ``model_type``;
- ``archs/<model_type>.py`` holds what is that architecture's own: the
  plain reference's equations, the count of parameters and operations, and
  the builder of the program's network (``archs/__init__.py``);
- ``workloads/<cell>.json`` holds a traffic mix, the name of its driver
  (``drivers/<driver>.py``) and the limits of its comparison;
- ``metrics/<metric>.py`` holds one reader, ``read(run)``, which returns a
  number or, where it finds nothing to read, ``None`` (left out of the
  line).

``--rehearse`` runs the same control flow on the CPU at the ``tiny`` sizes
of those files (virtual devices for a mesh cell).  It prints counts, never
a rate and never the contract's line.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is counted from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the benchmark's own modules (flops, trace_reduce, compare, ...) by their
# plain names, for this file, the drivers and the metric readers; and the
# program
sys.path[:0] = [HERE, ROOT]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(kind, name):
    """``<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit("no %s file %s" % (kind, path))
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(doc):
    """``doc`` with its ``tiny`` block laid over it."""
    out = dict(doc)
    out.update(out.pop("tiny", {}))
    return out


def load_cell(name, rehearse=False):
    """The cell's entry, configuration and traffic mix, and the names of
    the metrics it reports: ``(cell, cfg, workload, end_to_end,
    per_layer)``."""
    bench = _json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (there are: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg = _json(ROOT, files[cell["config"]])
    workload = _json(HERE, "workloads", name + ".json")
    if workload["config"] != cell["config"] \
            or workload["traffic"] != cell["traffic"]:
        raise SystemExit("workloads/%s.json and BENCHMARK.json disagree on "
                         "the cell's config or traffic" % name)
    if rehearse:
        cfg, workload = _tiny(cfg), _tiny(workload)

    def reported(metrics):
        return [m for m in metrics
                if name in m.get("workloads", cells)]
    return (cell, cfg, workload, reported(bench["end_to_end"]),
            reported(bench["per_layer"]))


def find_devices(cell, rehearse):
    """The devices, as jax reports them; no chip, no run."""
    import jax

    import flops

    devs = jax.devices()
    dev = devs[0]
    if rehearse:
        peak = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0, "hbm_bytes": 1.0}
    else:
        if dev.platform != "tpu":
            raise SystemExit("no TPU: jax.devices() = %s" % (devs,))
        peak = flops.peaks(dev.device_kind)     # unknown kind: KeyError
    if len(devs) < cell["chips"]:
        raise SystemExit("the cell needs %d chip(s), jax reports %d"
                         % (cell["chips"], len(devs)))
    return devs[:cell["chips"]], peak


def _memory_peak(devs):
    peaks, limits = [], []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        limits.append(int(stats.get("bytes_limit", 0)))
    return max(peaks), max(limits)


TRACE_DIR = os.path.join(HERE, ".cache", "trace")     # emptied after reading


def _start_trace():
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the interpreter's calls: not wanted
    opts.host_tracer_level = 2          # TraceAnnotation spans
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def _stop_trace(run, dump=None):
    """Stop, reduce, delete the files.  On the chip a trace without a
    device operation is an error; a rehearsal's CPU trace has none."""
    import jax

    import trace_reduce

    jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run["trace"] = trace
    span = trace_reduce.window_of(trace)
    if dump:        # for the look by hand, and to cut a fixture
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, "summary.json"), "w") as f:
            json.dump(trace_reduce.summary(trace), f, indent=1)
        if span:
            mid = (span[0] + span[1]) // 2
            trace_reduce.write_xspace(
                trace_reduce.cut(trace, mid, mid + int(0.25e9),
                                 (r"^/device:TPU", r"^/host:CPU$")),
                os.path.join(dump, "cut.xplane.pb"))
    if span is None:
        raise SystemExit("the trace holds no bench:window annotation")
    run["trace_window"] = span
    busy, n = trace_reduce.busy_seconds(trace, *span)
    if (n == 0 or busy <= 0) and not run["rehearse"]:
        raise SystemExit("the trace shows no operation on a device")
    run["busy_s"], run["traced_devices"] = busy, n
    run["trace_window_s"] = (span[1] - span[0]) / 1e9
    run["breakdown"] = {
        "device_ops": trace_reduce.top_ops(trace, 10, *span),
        "idle_gaps": trace_reduce.idle_gaps(trace, 10, *span)}


def read_metrics(run, metrics):
    out = {}
    for m in metrics:
        value = _module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="control flow on the CPU at the tiny sizes; prints "
                         "no rate and never the result line")
    ap.add_argument("--dump-trace", default=None, help=argparse.SUPPRESS)
    a = ap.parse_args(argv)

    cell, cfg, workload, end_to_end, per_layer = load_cell(
        a.workload, a.rehearse)
    if a.rehearse:
        # before jax is imported: the CPU, and a device for each chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                "platform_device_count=%d" % cell["chips"]).strip()
    # every program goes to the persistent cache, the small ones too: after
    # a checkout's first run nothing compiles in set-up either
    os.environ.setdefault("MXNET_COMPILE_CACHE_MIN_SECS", "0")
    devs, peak = find_devices(cell, a.rehearse)
    t_devices = time.perf_counter() - _T_START
    from mxnet_tpu import compile_cache

    compile_cache.configure()
    t_program = time.perf_counter() - _T_START
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print("[chipbench] %s seed %d on %s; %.1fs to the devices, %.1fs to the "
          "program imported" % (a.workload, a.seed, device, t_devices,
                                t_program), file=sys.stderr, flush=True)

    run = {"cell": cell, "cfg": cfg, "workload": workload, "seed": a.seed,
           "chips": cell["chips"], "peak": peak, "rehearse": a.rehearse,
           "trace": None}
    driver = _module("drivers", workload["driver"]).Driver(run)
    driver.setup()
    run["cache_at_window"] = compile_cache.stats()
    run["setup_s"] = time.perf_counter() - _T_START
    if a.trace:
        _start_trace()
    seconds = a.seconds
    if a.trace:        # a traced window may be cut short: traces are large
        seconds = min(seconds, workload.get("trace_seconds", seconds))
    driver.window(seconds)
    if a.trace:
        _stop_trace(run, a.dump_trace)
    run["memory_peak_bytes"], run["memory_limit_bytes"] = _memory_peak(devs)
    driver.release()
    t0 = time.perf_counter()
    numbers = driver.check()
    run["check_s"] = time.perf_counter() - t0
    import compare

    correct, table = compare.judge(numbers, workload["limits"])
    metrics = read_metrics(run, per_layer if a.trace else end_to_end)
    print("[chipbench] set-up %.1fs, window %.2fs, %d attempted, reference "
          "and comparison %.1fs" % (run["setup_s"], run["window_s"],
                                    run["attempted"], run["check_s"]),
          file=sys.stderr)
    for k in sorted(set(numbers) - set(table)):
        print("[chipbench] read  %s = %.6g (not compared)" % (k, numbers[k]),
              file=sys.stderr)
    for k, (v, lim) in table.items():
        print("[chipbench] check %s = %.6g (limit %.6g) %s"
              % (k, v, lim, "ok" if v <= lim else "FAILED"), file=sys.stderr)
    sys.stderr.flush()
    if a.rehearse:
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "attempted": run["attempted"],
                          "failed": run["failed"],
                          "metrics_read": sorted(metrics),
                          "device": device, "check": table}))
        return 0
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if a.trace:
        device["busy_s"] = run["busy_s"]
        device["window_s"] = run["trace_window_s"]
        line["breakdown"] = run["breakdown"]
    line["check"] = table
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
