"""The Nemotron-H configuration, its cell, the kept Mistral cell at 2048
tokens, and the readers they brought (PR 30).  CPU only: nothing here gives
a time or a rate of a device.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import archs  # noqa: E402
import compare  # noqa: E402
import flops  # noqa: E402
import mixer_reduce  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

NEMOTRON = "train_nemotronh_p7_b2s2048"
KEPT = "train_mistral7b_d2_b1s2048"


def _run(script, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- the configuration ------------------------------------------------------------

def test_param_count_of_the_published_configuration():
    _, cfg, _, _, _ = harness.load_cell(NEMOTRON)
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items() if k != "parameters"})
    assert flops.param_count(whole) == 31_577_940_288 == pub["parameters"]
    assert flops.param_count(cfg) == 528_093_120
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["nemotron_h_30b_p7_e8"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        k for k in whole if whole[k] != cfg[k])
    assert entry["source"] == cfg["source"]
    # no width among the cuts
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank",
                                                         "_size"))
                and k != "vocab_size"]


# -- both new cells, rehearsed -----------------------------------------------------

@pytest.mark.parametrize("cell,trace,want", [
    (NEMOTRON, "0", {"setup_s", "train_tokens_per_s"}),
    (NEMOTRON, "1", {"moe_dropped_assignments", "moe_load_max_over_mean",
                     "moe_rows_per_held_expert", "train_compiles_in_window"}),
    (KEPT, "0", {"setup_s", "train_tokens_per_s"}),
    (KEPT, "1", {"train_compiles_in_window", "cache_hits_setup"})])
def test_the_new_cells_rehearse_end_to_end(cell, trace, want):
    doc = _run(os.path.join(CHIP, "run.py"), "--workload", cell, "--seed",
               str(2 ** 31 + 9), "--seconds", "1", "--trace", trace,
               "--rehearse")
    assert doc["rehearsal"] is True and doc["correct"] is True
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert want <= set(doc["metrics_read"])
    # a CPU trace has no device operation: no share of a device is read
    assert not [m for m in doc["metrics_read"] if "roofline" in m
                or m.startswith(("mixer_ms", "flash_step"))]


# -- the readers, on a written trace with scopes ---------------------------------------

def _trace_with_scopes():
    ms = 1_000_000
    ops = [("fusion.1", 0, 4 * ms), ("fusion.2", 4 * ms, 2 * ms),
           ("fusion.3", 6 * ms, 3 * ms), ("ragged-dot-none.4", 9 * ms, 2 * ms),
           ("ragged-dot-metadata.1", 11 * ms, 1 * ms),
           ("flash.5", 12 * ms, 1 * ms), ("fusion.6", 13 * ms, 2 * ms),
           ("fusion.7", 15 * ms, 1 * ms)]
    scope = {
        "fusion.1": "jit(step)/jvp(mamba2)/jit(FullyConnected)/dot_general:",
        "fusion.2": "jit(step)/transpose(jvp(mamba2))/jit(contrib_ssd_scan)/"
                    "ssd_scan/checkpoint/mul:",
        "fusion.3": "jit(step)/jvp(moe)/jit(contrib_moe_grouped_ffn)/"
                    "moe_experts/checkpoint/gather:",
        "ragged-dot-none.4": "jit(step)/transpose(jvp(moe))/"
                             "jit(contrib_moe_grouped_ffn)/ragged-dot-none:",
        "ragged-dot-metadata.1": "jit(step)/jvp(moe)/"
                                 "jit(contrib_moe_grouped_ffn)/"
                                 "ragged-dot-metadata:",
        "flash.5": "jit(step)/transpose(jvp(attention))/"
                   "jit(contrib_flash_attention)/mx_flash_bwd_dq/pallas_call:",
        "fusion.6": "jit(step)/jvp(attention)/jit(FullyConnected)/"
                    "dot_general:",
        "fusion.7": "jit(step)/jvp(jit(moelike))/mul:"}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 16 * ms)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 0, 20 * ms)]}]}
    return {"planes": [dev, host], "scope": scope}


def test_the_new_readers_on_a_written_trace(tmp_path, monkeypatch):
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(_trace_with_scopes(), path)
    trace = trace_reduce.load(path)
    _, cfg, workload, _, _ = harness.load_cell(NEMOTRON)
    peak = flops.peaks("TPU v5 lite")
    run = {"trace": trace, "trace_window": trace_reduce.window_of(trace),
           "steps": 2, "cfg": cfg, "workload": workload, "peak": peak,
           "busy_s": 16e-3}
    read = lambda name: harness._module("metrics", name).read(run)  # noqa
    assert read("mixer_ms_per_step.mamba2") == pytest.approx((4 + 2) / 2)
    assert read("mixer_ms_per_step.attention") == pytest.approx((1 + 2) / 2)
    # the grouped kernels carry their caller's scope and count once; a
    # scope that only begins with "moe" does not
    assert read("mixer_ms_per_step.moe") == pytest.approx((3 + 2 + 1) / 2)
    assert read("flash_step_share_pct") == pytest.approx(100 * 1 / 16)
    arch = archs.of(cfg)
    least, bound = flops.least_seconds(arch.ssd_calls(cfg, 2, 2048), peak)
    assert bound == "bytes"
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * least * 3 * 2 / 2e-3)
    # the counters: 5 steps of 3 layers counted, 200 rows a held expert and
    # layer-step but for one that took 400
    held = {(str(layer), str(e)): 5 * 200 for layer in (1, 3, 6)
            for e in range(8)}
    held[("3", "2")] = 5 * 400
    counts = {"total": 5 * 3 * 4096 * 6, "dropped": 0, "held": held}
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: counts)
    assert mixer_reduce.layer_steps(run, counts) == 15
    rows = sum(held.values()) / 15
    assert read("moe_rows_per_held_expert") == pytest.approx(rows / 8)
    assert read("moe_load_max_over_mean") == pytest.approx(
        2000 / (sum(held.values()) / 24))
    assert read("moe_dropped_assignments") == 0
    least, _ = flops.least_seconds(arch.grouped_calls(cfg, rows), peak)
    assert read("moe_grouped_roofline") == pytest.approx(
        100 * least * 3 * 2 / 3e-3)
    # nothing to read: no trace, no scope, no counter -> None, never a raise
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: None)
    for name in ("moe_grouped_roofline", "moe_rows_per_held_expert",
                 "moe_load_max_over_mean", "moe_dropped_assignments"):
        assert read(name) is None
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    bare = dict(run, cfg=d2, workload=d2_wl, trace={
        "planes": trace["planes"], "scope": {}, "detail": {}})
    for name in ("ssd_scan_roofline", "mixer_ms_per_step.mamba2",
                 "mixer_ms_per_step.moe", "flash_step_share_pct"):
        assert harness._module("metrics", name).read(bare) is None
        assert harness._module("metrics", name).read(
            dict(run, trace=None)) is None


def test_a_program_without_the_counters_reads_none():
    """The parent's program has no ``mxnet_moe_*`` family: the readers
    leave their metric out of the line."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import mixer_reduce; "
         "print(mixer_reduce.moe_counts({}))" % CHIP],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"


# -- correct has been shown to fail on the Nemotron cell ------------------------------------

@pytest.mark.parametrize("fault", ["no_carry", "seventh_expert",
                                   "not_normalised", "dropped"])
def test_a_planted_fault_comes_out_not_correct(fault):
    doc = _run(os.path.join(HERE, "faults_nemotron_h.py"), fault,
               "--workload", NEMOTRON, "--seed", "11", "--seconds", "1",
               "--trace", "0", "--rehearse")
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < compare.NEVER for v, _ in doc["check"].values())


def test_the_fp8_control_fails_the_nemotron_cells_tiny_limits(tmp_path):
    out = str(tmp_path / "probe.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "probe.py"), "--workload",
         NEMOTRON, "--seeds", "31,32", "--what", "control,unchanged", "--out",
         out, "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    _, _, workload, _, _ = harness.load_cell(NEMOTRON, rehearse=True)
    docs = [json.loads(ln) for ln in open(out)]
    assert len(docs) == 4
    for d in docs:
        ok, table = compare.judge(d["numbers"], workload["limits"])
        assert not ok, (d["kind"], d["seed"], table)
