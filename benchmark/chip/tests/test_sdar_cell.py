"""The SDAR configuration, its block-diffusion cell and its readers.  CPU
only: nothing here gives a time or a rate of a device.
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

CELL = "train_sdar_bd4_b1s4096"
FAULTS = ["noised_causal", "noised_prefix", "noised_positions", "no_qk_norm",
          "sigmoid", "seventeenth_expert"]
NEW_READERS = ["bd_flash_roofline", "bd_flash_tiles_pct",
               "mixer_ms_per_step.bd_attention"]


def _run(script, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, script, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- the configuration and the entries ---------------------------------------------

def test_param_count_work_and_the_benchmarks_entries():
    cell, cfg, workload, end_to_end, per_layer = harness.load_cell(CELL)
    pub = cfg["published"]
    whole = dict(cfg, **{k: v for k, v in pub.items() if k != "parameters"})
    whole["router_num_experts"] = whole["num_experts"]
    assert flops.param_count(whole) == 30_532_122_624 == pub["parameters"]
    assert flops.param_count(cfg) == 456_346_624
    assert flops.param_count(dict(cfg, num_hidden_layers=5)) == 550_984_960
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["sdar_30b_a3b_e16"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        k for k in pub if k != "parameters" and pub[k] != cfg[k])
    assert entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["router_num_experts"]) == (
        2048, 128, 32, 4, 768, 6144, 8, 1000000, 1e-6, 128)
    # the work of a step at depth 4: 8.95 TFLOP, 36.9% of it the mask's
    # live pairs (16,793,600 a head and layer)
    arch = archs.of(cfg)
    assert arch.live_pairs(4096, 4) == 16_793_600
    per_step = flops.train_flops_per_token(cfg, 4096) * 4096
    assert per_step == pytest.approx(8.9478e12, rel=1e-4)
    assert flops.train_flops_per_token(dict(cfg, num_hidden_layers=5),
                                       4096) * 4096 == pytest.approx(
        10.9457e12, rel=1e-4)
    assert (cell["chips"], cell["traffic"]) == (1, "bd4_fresh_b1s4096")
    assert (workload["batch"], workload["seq"]) == (1, 4096)
    assert {m["name"] for m in end_to_end} == {"train_tokens_per_s",
                                               "setup_s"}
    names = {m["name"] for m in per_layer}
    assert set(NEW_READERS) | {"train_mfu_pct", "peak_hbm_pct.train",
                               "device_idle_pct.train"} <= names
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
        else:           # no standing metric's list was appended to
            assert CELL not in m.get("workloads", [])
    assert workload["limits_from"] and workload.get("limits")


# -- the cell, rehearsed ---------------------------------------------------------------

@pytest.mark.parametrize("trace,want", [
    ("0", {"setup_s", "train_tokens_per_s"}),
    ("1", {"bd_flash_tiles_pct", "train_compiles_in_window"})])
def test_the_cell_rehearses_end_to_end(trace, want):
    doc = _run(os.path.join(CHIP, "run.py"), "--workload", CELL, "--seed",
               str(2 ** 31 + 11), "--seconds", "1", "--trace", trace,
               "--rehearse")
    assert doc["rehearsal"] is True and doc["correct"] is True
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert want <= set(doc["metrics_read"])
    # a CPU trace has no device operation: no share of a device is read
    assert not [m for m in doc["metrics_read"] if "roofline" in m
                or m.startswith("mixer_ms")]


# -- the readers, on a written trace and a snapshot ---------------------------------------

def _trace_with_scopes():
    ms = 1_000_000
    ops = [("fusion.1", 0, 4 * ms), ("flash.2", 4 * ms, 6 * ms),
           ("fusion.3", 10 * ms, 2 * ms), ("flash.4", 12 * ms, 3 * ms),
           ("flash.5", 15 * ms, 1 * ms), ("fusion.6", 16 * ms, 2 * ms)]
    step = "jit(step)/"
    op = "jit(contrib_flash_attention)/"
    scope = {
        "fusion.1": step + "jvp(bd_attention)/jit(FullyConnected)/dot:",
        "flash.2": step + "transpose(jvp(bd_attention))/" + op
                   + "mx_flash_bwd_dq_bd/pallas_call:",
        "fusion.3": step + "jvp(moe)/jit(FullyConnected)/dot:",
        "flash.4": step + "transpose(jvp(bd_attention))/" + op
                   + "mx_flash_bwd_dkv_bd/pallas_call:",
        "flash.5": step + "jvp(bd_attention)/" + op
                   + "mx_flash_fwd_bd/pallas_call:",
        "fusion.6": step + "jvp(bd_attention_like)/mul:"}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 18 * ms)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 0, 20 * ms)]}]}
    return {"planes": [dev, host], "scope": scope}


def test_the_new_readers_on_a_written_trace(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(_trace_with_scopes(), path)
    trace = trace_reduce.load(path)
    _, cfg, workload, _, _ = harness.load_cell(CELL)
    peak = flops.peaks("TPU v5 lite")
    run = {"trace": trace, "trace_window": trace_reduce.window_of(trace),
           "steps": 2, "cfg": cfg, "workload": workload, "peak": peak,
           "busy_s": 18e-3}
    read = lambda name: harness._module("metrics", name).read(run)  # noqa
    # the scope, and not one that only begins with its name
    assert read("mixer_ms_per_step.bd_attention") == pytest.approx(
        (4 + 6 + 3 + 1) / 2)
    arch = archs.of(cfg)
    least, bound = flops.least_seconds(arch.bd_flash_calls(cfg, 1, 4096),
                                       peak)
    assert bound == "flops"
    # four layers attend, two steps, 10 ms under ``mx_flash_*_bd``
    assert read("bd_flash_roofline") == pytest.approx(
        100 * least * 4 * 2 / 10e-3)
    # the standing kernels' names are not the mask's: nothing to read
    causal = {k: v.replace("_bd/", "/") for k, v in trace["scope"].items()}
    assert harness._module("metrics", "bd_flash_roofline").read(
        dict(run, trace=dict(trace, scope=causal))) is None
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    for name in ("bd_flash_roofline", "mixer_ms_per_step.bd_attention"):
        assert harness._module("metrics", name).read(
            dict(run, trace=None)) is None
        assert harness._module("metrics", name).read(dict(
            run, cfg=d2, workload=d2_wl, trace=dict(trace, scope={}))) \
            is None


def test_the_tile_share_reads_the_two_counters(monkeypatch):
    from mxnet_tpu.telemetry import metrics

    reader = harness._module("metrics", "bd_flash_tiles_pct")
    snaps = [{}, {"mxnet_flash_tiles_total": {"series": [{"value": 0}]},
                  "mxnet_flash_tiles_computed_total": {"series": [
                      {"value": 0}]}},
             {"mxnet_flash_tiles_total": {"series": [
                 {"value": 256 * 32 * 4}, {"value": 256 * 32 * 4}]},
              "mxnet_flash_tiles_computed_total": {"series": [
                  {"value": 80 * 32 * 8}]}}]
    got = []
    for snap in snaps:
        monkeypatch.setattr(metrics, "snapshot", lambda snap=snap: snap)
        got.append(reader.read({}))
    assert got == [None, None, pytest.approx(31.25)]


# -- correct has been shown to fail on the cell ------------------------------------------

@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    doc = _run(os.path.join(HERE, "faults_sdar_moe.py"), fault,
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
