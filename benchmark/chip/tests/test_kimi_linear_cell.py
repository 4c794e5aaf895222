"""The Kimi Linear configuration, its cell and the readers it brought
(PR 34).  CPU only: nothing here gives a time or a rate of a device.
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
import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "train_kimilinear_p5_b1s4096"
FAULTS = ["no_carry", "decay_per_head", "no_beta", "ninth_expert",
          "not_normalised"]      # ``rotary``: see faults_kimi_linear.py


def _run(script, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- the configuration and the entries ---------------------------------------------

def test_param_count_and_the_benchmarks_entries():
    cell, cfg, workload, end_to_end, per_layer = harness.load_cell(CELL)
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items() if k != "parameters"})
    assert flops.param_count(whole) == 49_122_681_728 == pub["parameters"]
    assert flops.param_count(cfg) == 602_434_432
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["kimi_linear_48b_p5_e8"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        k for k in whole if whole[k] != cfg[k])
    assert entry["source"] == cfg["source"]
    # no width among the cuts, and none changed inside the listed group
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank",
                                                         "_size"))
                and k != "vocab_size"]
    lin, pub_lin = cfg["linear_attn_config"], pub["linear_attn_config"]
    assert {k: lin[k] for k in lin if not k.endswith("_layers")} == \
        {k: pub_lin[k] for k in pub_lin if not k.endswith("_layers")}
    assert (cell["chips"], cell["traffic"]) == (1, "fresh_b1s4096")
    assert (workload["batch"], workload["seq"]) == (1, 4096)
    assert {m["name"] for m in end_to_end} == {"train_tokens_per_s",
                                               "setup_s"}
    names = {m["name"] for m in per_layer}
    assert {"kda_scan_roofline", "mla_flash_roofline",
            "mixer_ms_per_step.kda", "mixer_ms_per_step.mla",
            "mixer_ms_per_step.mlp", "mixer_ms_per_step.moe",
            "moe_gmm_calls_per_step", "flash_step_share_pct",
            "train_mfu_pct"} <= names
    # the readers that read Nemotron's keys are not asked of this cell
    assert not names & {"flash_roofline", "moe_gmm_roofline",
                        "ssd_scan_roofline", "moe_load_max_over_mean",
                        "moe_rows_per_held_expert", "moe_grouped_roofline"}
    # each limit between the program's largest and a fault's smallest
    for key in ("limits_from",):
        assert workload[key] and workload.get("limits")


# -- the cell, rehearsed ---------------------------------------------------------------

@pytest.mark.parametrize("trace,want", [
    ("0", {"setup_s", "train_tokens_per_s"}),
    ("1", {"moe_dropped_assignments", "moe_sorted_rows_walked_pct",
           "train_compiles_in_window"})])
def test_the_cell_rehearses_end_to_end(trace, want):
    doc = _run(os.path.join(CHIP, "run.py"), "--workload", CELL, "--seed",
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
    ops = [("fusion.1", 0, 4 * ms), ("fusion.2", 4 * ms, 6 * ms),
           ("fusion.3", 10 * ms, 2 * ms), ("flash.4", 12 * ms, 3 * ms),
           ("flash.5", 15 * ms, 1 * ms), ("fusion.6", 16 * ms, 2 * ms),
           ("fusion.7", 18 * ms, 1 * ms), ("fusion.8", 19 * ms, 1 * ms)]
    step = "jit(step)/"
    scope = {
        "fusion.1": step + "jvp(kda)/jit(FullyConnected)/dot_general:",
        "fusion.2": step + "transpose(jvp(kda))/jit(contrib_kda_attention)/"
                    "kda_attention/checkpoint/kda_scan/dot_general:",
        "fusion.3": step + "jvp(kda)/jit(contrib_kda_attention)/"
                    "kda_attention/checkpoint/causal_conv/mul:",
        "flash.4": step + "transpose(jvp(mla))/jit(contrib_flash_attention)/"
                   "mx_flash_bwd_dkv/pallas_call:",
        "flash.5": step + "jvp(mla)/jit(contrib_flash_attention)/"
                   "mx_flash_fwd/pallas_call:",
        "fusion.6": step + "jvp(mla)/jit(FullyConnected)/dot_general:",
        "fusion.7": step + "jvp(mlp)/jit(FullyConnected)/dot_general:",
        "fusion.8": step + "jvp(jit(kdalike))/mul:"}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 20 * ms)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 0, 25 * ms)]}]}
    return {"planes": [dev, host], "scope": scope}


def test_the_new_readers_on_a_written_trace(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(_trace_with_scopes(), path)
    trace = trace_reduce.load(path)
    _, cfg, workload, _, _ = harness.load_cell(CELL)
    peak = flops.peaks("TPU v5 lite")
    run = {"trace": trace, "trace_window": trace_reduce.window_of(trace),
           "steps": 2, "cfg": cfg, "workload": workload, "peak": peak,
           "busy_s": 20e-3}
    read = lambda name: harness._module("metrics", name).read(run)  # noqa
    # a scope that only begins with "kda" does not count
    assert read("mixer_ms_per_step.kda") == pytest.approx((4 + 6 + 2) / 2)
    assert read("mixer_ms_per_step.mla") == pytest.approx((3 + 1 + 2) / 2)
    assert read("mixer_ms_per_step.mlp") == pytest.approx(1 / 2)
    assert read("flash_step_share_pct") == pytest.approx(100 * 4 / 20)
    arch = archs.of(cfg)
    least, bound = flops.least_seconds(arch.kda_calls(cfg, 1, 4096), peak)
    assert bound == "bytes"
    # four KDA layers, two steps, 6 ms under ``kda_scan``
    assert read("kda_scan_roofline") == pytest.approx(
        100 * least * 4 * 2 / 6e-3)
    least, bound = flops.least_seconds(
        arch.mla_flash_calls(cfg, 1, 4096), peak)
    assert bound == "flops"
    assert read("mla_flash_roofline") == pytest.approx(
        100 * least * 1 * 2 / 4e-3)
    # nothing to read: no trace, no scope, another architecture -> None
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    bare = dict(run, cfg=d2, workload=d2_wl, trace={
        "planes": trace["planes"], "scope": {}, "detail": {}})
    for name in ("kda_scan_roofline", "mla_flash_roofline",
                 "mixer_ms_per_step.kda", "mixer_ms_per_step.mla",
                 "mixer_ms_per_step.mlp"):
        assert harness._module("metrics", name).read(bare) is None
        assert harness._module("metrics", name).read(
            dict(run, trace=None)) is None
    # the flash kernels of another architecture are not this reader's
    assert harness._module("metrics", "mla_flash_roofline").read(
        dict(run, cfg=d2, workload=d2_wl)) is None


# -- correct has been shown to fail on the cell ------------------------------------------

@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    doc = _run(os.path.join(HERE, "faults_kimi_linear.py"), fault,
               "--workload", CELL, "--seed", "11", "--seconds", "1",
               "--trace", "0", "--rehearse")
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < compare.NEVER for v, _ in doc["check"].values())


def test_the_fp8_control_fails_the_cells_tiny_limits(tmp_path):
    out = str(tmp_path / "probe.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "probe.py"), "--workload",
         CELL, "--seeds", "31,32", "--what", "control,unchanged", "--out",
         out, "--rehearse"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    _, _, workload, _, _ = harness.load_cell(CELL, rehearse=True)
    docs = [json.loads(ln) for ln in open(out)]
    assert len(docs) == 4
    for d in docs:
        ok, table = compare.judge(d["numbers"], workload["limits"])
        assert not ok, (d["kind"], d["seed"], table)
