"""The driver records bench.py's stdout verbatim; this pins the JSON
contract (the platform provenance field + the multi-metric array)
without running the heavy benchmarks.

Round-3 lesson: a CPU-fallback number with no machine-readable platform
field was indistinguishable from a 300x chip regression in the recorded
artifact.  There is no fallback any more (ISSUE 22): no TPU, or a metric
that fails, fails the run — and every record still names its platform.
"""
import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stub(mod, monkeypatch, values):
    monkeypatch.setattr(mod, "_init_backend", lambda: "cpu")
    # the default mode runs each metric as `python bench.py <name>`; the
    # stubs live in this process, so measure here
    monkeypatch.setattr(mod, "_measure_in_child", mod._measure)
    specs = {}
    for name, (_, metric, unit, baseline) in mod._SPECS.items():
        specs[name] = (lambda platform, v=values[name]: v,
                       metric, unit, baseline)
    monkeypatch.setattr(mod, "_SPECS", specs)


_STUB_VALUES = {"train": 100.0, "infer": 200.0, "bert": 300.0,
                "llama": 400.0, "dispatch_eager": 500.0,
                "dispatch_eager_notelemetry": 550.0,
                "dispatch_bulked": 600.0,
                "dispatch_bulked_train": 650.0,
                "dispatch_bulked_long": 700.0,
                # serving runner (ISSUE 8): continuous tok/s as value,
                # static baseline + latency percentiles as extras
                "serve": {"value": 1000.0, "static_tok_s": 500.0,
                          "continuous_vs_static": 2.0,
                          "ttft_p50_ms": 10.0, "ttft_p99_ms": 50.0,
                          "tpot_p50_ms": 2.0, "completed": 64,
                          "n_requests": 64, "live_compiles": 0,
                          "lockcheck_tok_s": 980.0,
                          "lockcheck_overhead_pct": 2.0,
                          "rescheck_tok_s": 985.0,
                          "rescheck_overhead_pct": 1.5},
                # speculative serving runner (ISSUE 13): spec-on tok/s
                # as value, spec-off baseline + acceptance + int8 kv
                # byte ratio as extras (parity asserted in the probe)
                "serve_spec": {"value": 1500.0, "spec_off_tok_s": 1000.0,
                               "spec_vs_off": 1.5, "accept_rate": 0.3,
                               "spec_accepted_tokens": 400,
                               "parity_checked": 64,
                               "kv_bytes_int8": 1000, "kv_bytes_fp32": 4000,
                               "kv_bytes_ratio": 0.25, "completed": 64,
                               "n_requests": 64, "live_compiles": 0},
                # paged-attention serving runner (ISSUE 14): kernel-on
                # tok/s as value, kernel-off baseline + memdump peak
                # byte ratio as extras (parity asserted in the probe)
                "serve_paged": {"value": 1200.0,
                                "paged_off_tok_s": 1000.0,
                                "paged_vs_off": 1.2,
                                "parity_checked": 64,
                                "paged_peak_bytes": 3000,
                                "ref_peak_bytes": 5000,
                                "paged_attn_hbm_bytes_ratio": 0.6,
                                "completed": 64, "n_requests": 64,
                                "live_compiles": 0},
                # prefix-cache runner (ISSUE 19): cache-on tok/s as
                # value, the cache-off baseline + hit rate + the
                # cached-vs-cold TTFT p50 split as extras (parity
                # asserted in the probe)
                "prefix": {"value": 1800.0, "prefix_off_tok_s": 1000.0,
                           "prefix_vs_off": 1.8, "hit_rate": 0.78,
                           "cached_tokens": 100000,
                           "ttft_cached_p50_ms": 12.0,
                           "ttft_cold_p50_ms": 48.0,
                           "ttft_cached_vs_cold": 4.0,
                           "parity_checked": 64, "completed": 64,
                           "n_requests": 64, "live_compiles": 0},
                # fleet runner (ISSUE 18): aggregate 3-replica tok/s as
                # value, the N=1 router-vs-direct routing overhead,
                # fleet TTFT p99 and (ISSUE 20) the telemetry-off
                # observability overhead as extras
                "fleet": {"value": 2800.0, "n_replicas": 3,
                          "ttft_p99_ms": 60.0, "completed": 64,
                          "n_requests": 64, "retried": 0,
                          "ejections": 0, "dropped": 0,
                          "direct_tok_s": 1000.0,
                          "router1_tok_s": 980.0,
                          "routing_overhead_pct": 2.0,
                          "fleet_notelemetry_tok_s": 2850.0,
                          "obs_overhead_pct": 1.75,
                          "live_compiles": 0},
                # planner runner (ISSUE 11): median plan seconds as
                # value, the ms-precision figure rides along
                "planner": {"value": 0.0, "planner_ms": 0.9,
                            "n_params": 21},
                # cold-start runners return value + extra record fields
                "cold_resnet50": {"value": 30.0, "warm_seconds": 2.0,
                                  "cold_warm_speedup": 15.0},
                "cold_bert": {"value": 20.0, "warm_seconds": 2.0,
                              "cold_warm_speedup": 10.0},
                "cold_llama": {"value": 10.0, "warm_seconds": 2.0,
                               "cold_warm_speedup": 5.0}}


def test_single_metric_line(monkeypatch, capsys):
    mod = _load_bench()
    _stub(mod, monkeypatch, _STUB_VALUES)
    monkeypatch.setattr(sys, "argv", ["bench.py", "bert"])
    mod.main()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "bert_base_train_throughput"
    assert rec["value"] == 300.0
    assert rec["platform"] == "cpu"
    assert "fallback" not in rec
    # ISSUE 9: every record carries the device-memory high-water mark
    assert isinstance(rec["peak_device_bytes"], int)
    assert rec["peak_device_bytes"] >= 0


def test_default_mode_emits_all_metrics_in_one_line(monkeypatch, capsys):
    mod = _load_bench()
    _stub(mod, monkeypatch, _STUB_VALUES)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    mod.main()
    out_lines = [ln for ln in capsys.readouterr().out.strip().splitlines()
                 if ln.startswith("{")]
    assert len(out_lines) == 1, "driver contract: exactly ONE JSON line"
    rec = json.loads(out_lines[0])
    # headline at top level
    assert rec["metric"] == "resnet50_train_throughput"
    assert rec["value"] == 100.0
    assert rec["vs_baseline"] > 0
    assert rec["platform"] == "cpu" and "fallback" not in rec
    # every metric in the array, each with provenance
    names = [m["metric"] for m in rec["metrics"]]
    assert names == ["resnet50_train_throughput",
                     "resnet50_infer_throughput",
                     "bert_base_train_throughput",
                     "llama_decoder_train_throughput",
                     "imperative_dispatch_eager",
                     "imperative_dispatch_eager_notelemetry",
                     "imperative_dispatch_bulked",
                     "imperative_dispatch_bulked_train",
                     "imperative_dispatch_bulked_long",
                     "llama_serve_tok_s",
                     "llama_serve_spec_tok_s",
                     "llama_serve_paged_tok_s",
                     "llama_serve_prefix_tok_s",
                     "fleet_serve_tok_s",
                     "planner_seconds",
                     "resnet50_cold_start_seconds",
                     "bert_cold_start_seconds",
                     "llama_cold_start_seconds"]
    assert all(m["platform"] == "cpu" for m in rec["metrics"])
    # ISSUE 9: memory provenance in every row, headline included
    assert isinstance(rec["peak_device_bytes"], int)
    assert all(isinstance(m["peak_device_bytes"], int)
               and m["peak_device_bytes"] >= 0 for m in rec["metrics"])
    # the op-bulking microbench rides in the metrics array (ISSUE 4);
    # the recorded-chain and 64-op variants joined in ISSUE 6
    by_name = {m["metric"]: m for m in rec["metrics"]}
    assert by_name["imperative_dispatch_eager"]["value"] == 500.0
    assert by_name["imperative_dispatch_bulked"]["value"] == 600.0
    assert by_name["imperative_dispatch_bulked_train"]["value"] == 650.0
    assert by_name["imperative_dispatch_bulked_long"]["value"] == 700.0
    # cold-start records (ISSUE 7): dict-returning runners surface the
    # cold number as "value" and the warm/speedup extras as fields
    cold = by_name["resnet50_cold_start_seconds"]
    assert cold["value"] == 30.0 and cold["unit"] == "seconds"
    assert cold["warm_seconds"] == 2.0
    assert cold["cold_warm_speedup"] == 15.0
    # serving record (ISSUE 8): continuous tok/s is the value; the
    # static baseline measured in the SAME run and the TTFT percentiles
    # ride along (the >=1.5x claim is checked against these two fields)
    srv = by_name["llama_serve_tok_s"]
    assert srv["value"] == 1000.0 and srv["unit"] == "tokens/sec"
    assert srv["static_tok_s"] == 500.0
    assert srv["continuous_vs_static"] == 2.0
    assert srv["ttft_p50_ms"] == 10.0 and srv["ttft_p99_ms"] == 50.0
    assert srv["live_compiles"] == 0
    # lockcheck sanitizer overhead (lint pass 11 runtime half): the
    # same workload replayed on a fresh proxied server; the <=3% claim
    # in docs/static_analysis.md is checked against these two fields
    assert srv["lockcheck_tok_s"] == 980.0
    assert srv["lockcheck_overhead_pct"] == 2.0
    # rescheck sanitizer overhead (lint pass 12 runtime half): a fresh
    # tracked server replays the same workload; <=3% is the acceptance
    # gate, checked against these two fields like lockcheck's
    assert srv["rescheck_tok_s"] == 985.0
    assert srv["rescheck_overhead_pct"] == 1.5
    # speculative serving record (ISSUE 13): spec-on tok/s is the
    # value; the spec-off baseline from the SAME bundle, the n-gram
    # acceptance rate, and the int8/fp32 kv_page byte ratio ride along
    # (the >=1.3x and <=0.55x claims are checked against these fields)
    sspec = by_name["llama_serve_spec_tok_s"]
    assert sspec["value"] == 1500.0 and sspec["unit"] == "tokens/sec"
    assert sspec["spec_off_tok_s"] == 1000.0
    assert sspec["spec_vs_off"] == 1.5
    assert sspec["accept_rate"] == 0.3
    assert sspec["kv_bytes_ratio"] == 0.25
    assert sspec["parity_checked"] == 64
    assert sspec["live_compiles"] == 0
    # paged-attention serving record (ISSUE 14): kernel-on tok/s is the
    # value; the kernel-off baseline from the SAME net and geometry and
    # the memdump peak-byte ratio ride along (parity asserted in-probe)
    spag = by_name["llama_serve_paged_tok_s"]
    assert spag["value"] == 1200.0 and spag["unit"] == "tokens/sec"
    assert spag["paged_off_tok_s"] == 1000.0
    assert spag["paged_vs_off"] == 1.2
    assert spag["paged_attn_hbm_bytes_ratio"] == 0.6
    assert spag["parity_checked"] == 64
    assert spag["live_compiles"] == 0
    # prefix-cache record (ISSUE 19): cache-on tok/s is the value; the
    # cache-off baseline from the SAME bundle, the hit rate, and the
    # cached-vs-cold TTFT p50 split ride along (the >=1.5x and >=3x
    # claims are checked against these fields; parity asserted in-probe)
    spfx = by_name["llama_serve_prefix_tok_s"]
    assert spfx["value"] == 1800.0 and spfx["unit"] == "tokens/sec"
    assert spfx["prefix_off_tok_s"] == 1000.0
    assert spfx["prefix_vs_off"] == 1.8
    assert spfx["hit_rate"] == 0.78
    assert spfx["ttft_cached_p50_ms"] == 12.0
    assert spfx["ttft_cold_p50_ms"] == 48.0
    assert spfx["ttft_cached_vs_cold"] == 4.0
    assert spfx["parity_checked"] == 64
    assert spfx["live_compiles"] == 0
    # fleet record (ISSUE 18): aggregate tok/s over 3 replicas is the
    # value; the N=1 router-vs-direct overhead (acceptance: within 5%)
    # and the zero-loss counters ride along
    fleet = by_name["fleet_serve_tok_s"]
    assert fleet["value"] == 2800.0 and fleet["unit"] == "tokens/sec"
    assert fleet["n_replicas"] == 3
    assert fleet["routing_overhead_pct"] == 2.0
    assert fleet["direct_tok_s"] == 1000.0
    assert fleet["router1_tok_s"] == 980.0
    assert fleet["dropped"] == 0 and fleet["ejections"] == 0
    # ISSUE 20: the observability tax rides along (<=3% standing gate)
    assert fleet["fleet_notelemetry_tok_s"] == 2850.0
    assert fleet["obs_overhead_pct"] == 1.75
    assert fleet["live_compiles"] == 0
    # planner record (ISSUE 11): static analysis latency, LOWER better;
    # the ms-precision figure survives the 2-decimal value rounding
    plan = by_name["planner_seconds"]
    assert plan["unit"] == "seconds"
    assert plan["planner_ms"] == 0.9
    assert plan["n_params"] == 21


def test_budget_exhaustion_marks_skipped(monkeypatch, capsys):
    mod = _load_bench()
    _stub(mod, monkeypatch, _STUB_VALUES)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setenv("MXNET_BENCH_BUDGET", "0")
    mod.main()
    rec = json.loads([ln for ln in capsys.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert rec["value"] == 100.0  # headline always measured
    skipped = [m for m in rec["metrics"] if m.get("skipped")]
    assert len(skipped) == 17
    assert all(m["value"] == 0.0 for m in skipped)


def test_failed_benchmark_fails_the_run(monkeypatch, capsys):
    mod = _load_bench()

    def boom(platform):
        raise RuntimeError("synthetic failure")

    values = dict(_STUB_VALUES)
    _stub(mod, monkeypatch, values)
    specs = dict(mod._SPECS)
    specs["bert"] = (boom,) + specs["bert"][1:]
    monkeypatch.setattr(mod, "_SPECS", specs)
    # no retry, no value 0: the metric's exception ends the run, in
    # single-metric mode and from the middle of the default mode alike
    for argv in (["bench.py", "bert"], ["bench.py"]):
        monkeypatch.setattr(sys, "argv", argv)
        with pytest.raises(RuntimeError, match="synthetic failure"):
            mod.main()
        assert not [ln for ln in capsys.readouterr().out.splitlines()
                    if ln.startswith("{")]


def test_spawning_metric_initialises_no_backend_before_its_probes(
        monkeypatch, capsys):
    mod = _load_bench()
    order = []
    _stub(mod, monkeypatch, _STUB_VALUES)
    monkeypatch.setattr(mod, "_init_backend",
                        lambda: order.append("backend") or "cpu")
    specs = dict(mod._SPECS)
    for name in ("serve", "llama"):
        specs[name] = (lambda platform, n=name: order.append(n) or 1.0,) \
            + specs[name][1:]
    monkeypatch.setattr(mod, "_SPECS", specs)
    for name in ("serve", "llama"):
        monkeypatch.setattr(sys, "argv", ["bench.py", name])
        mod.main()
    # a chip belongs to one process: the parent of probe processes that
    # need it touches jax only after they are done
    assert order == ["serve", "backend", "backend", "llama"]


def test_no_tpu_is_an_error_unless_told_cpu(monkeypatch, capsys):
    from mxnet_tpu import context
    from mxnet_tpu.base import MXNetError

    mod = _load_bench()
    # this process was told JAX_PLATFORMS=cpu; take that away (in
    # process: a child with the variable unset would load libtpu)
    monkeypatch.setattr(context, "_told_cpu", lambda: False)
    monkeypatch.setattr(sys, "argv", ["bench.py", "llama"])
    with pytest.raises(MXNetError, match="no accelerator"):
        mod.main()
    assert not capsys.readouterr().out.strip()


def test_cold_start_probes_never_see_the_machines_cache(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins over MXNET_COMPILE_CACHE_DIR; a
    machine that exports it would hand the 'cold' probe a warm cache."""
    mod = _load_bench()
    envs = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/shared/warm/cache")
    monkeypatch.setattr(
        mod, "_probe_subprocess",
        lambda argv, env, marker, what: envs.append(dict(env)) or "1.0")
    out = mod._run_cold_start("llama")
    assert out["value"] == 1.0 and len(envs) == 2
    for env in envs:
        assert "JAX_COMPILATION_CACHE_DIR" not in env
        assert env["MXNET_COMPILE_CACHE_DIR"] == \
            envs[0]["MXNET_COMPILE_CACHE_DIR"]
    assert not os.path.exists(envs[0]["MXNET_COMPILE_CACHE_DIR"])
