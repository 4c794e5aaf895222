"""The JoyAI-LLM Flash configuration, its cell and the readers it brought
(PR 36).  CPU only: nothing here gives a time or a rate of a device.
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

CELL = "train_joyai_p5_b1s8192"
FAULTS = ["no_query_norm", "no_kv_norm", "key_a_head", "ninth_expert",
          "not_normalised", "scale_nope"]
NEW_READERS = ["mla_flash_roofline_by_arch", "mla_around_flash_ms_per_step",
               "mla_rope_ms_per_step"]


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
    whole = dict(cfg, **{k: v for k, v in pub.items()
                         if not k.startswith("parameters")})
    whole["router_num_experts"] = whole["n_routed_experts"]
    assert flops.param_count(whole) == 50_190_491_648
    assert flops.param_count(dict(whole, num_nextn_predict_layers=0)) \
        == 48_942_542_592 == pub["parameters"]
    assert flops.param_count(cfg) == 413_959_168
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["joyai_llm_flash_p5_e8"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        k for k in pub if not k.startswith("parameters")
        and pub[k] != cfg[k])
    assert entry["source"] == cfg["source"]
    # no width among the cuts
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank",
                                                         "_size"))
                and k != "vocab_size"]
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 1536, 512, 128, 64, 128, 768, 7168, 8, 32000000, 1e-6)
    assert (cell["chips"], cell["traffic"]) == (1, "fresh_b1s8192")
    assert (workload["batch"], workload["seq"]) == (1, 8192)
    assert {m["name"] for m in end_to_end} == {"train_tokens_per_s",
                                               "setup_s"}
    names = {m["name"] for m in per_layer}
    assert set(NEW_READERS) | {
        "mixer_ms_per_step.mla", "mixer_ms_per_step.mlp",
        "mixer_ms_per_step.moe", "moe_gmm_calls_per_step",
        "moe_around_gmm_ms_per_step", "moe_rows_per_held_expert",
        "moe_dropped_assignments", "moe_sorted_rows_walked_pct",
        "flash_step_share_pct", "programs_per_step", "train_mfu_pct"} <= names
    # the readers that read another model's keys are not asked of this cell
    assert not names & {"flash_roofline", "mla_flash_roofline",
                        "moe_gmm_roofline", "ssd_scan_roofline",
                        "moe_load_max_over_mean", "moe_grouped_roofline",
                        "kda_scan_roofline", "mixer_ms_per_step.kda"}
    # the new readers are asked of this cell alone
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
    assert workload["limits_from"] and workload.get("limits")


# -- the cell, rehearsed ---------------------------------------------------------------

@pytest.mark.parametrize("trace,want", [
    ("0", {"setup_s", "train_tokens_per_s"}),
    ("1", {"moe_dropped_assignments", "moe_sorted_rows_walked_pct",
           "moe_rows_per_held_expert", "train_compiles_in_window"})])
def test_the_cell_rehearses_end_to_end(trace, want):
    doc = _run(os.path.join(CHIP, "run.py"), "--workload", CELL, "--seed",
               str(2 ** 31 + 9), "--seconds", "1", "--trace", trace,
               "--rehearse")
    assert doc["rehearsal"] is True and doc["correct"] is True
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert want <= set(doc["metrics_read"])
    # a CPU trace has no device operation: no share of a device is read
    assert not [m for m in doc["metrics_read"] if "roofline" in m
                or m.startswith(("mixer_ms", "flash_step", "mla_"))]


# -- the readers, on a written trace with scopes ---------------------------------------

def _trace_with_scopes():
    ms = 1_000_000
    ops = [("fusion.1", 0, 4 * ms), ("flash.2", 4 * ms, 6 * ms),
           ("fusion.3", 10 * ms, 2 * ms), ("flash.4", 12 * ms, 3 * ms),
           ("flash.5", 15 * ms, 1 * ms), ("fusion.6", 16 * ms, 2 * ms),
           ("fusion.7", 18 * ms, 1 * ms), ("fusion.8", 19 * ms, 1 * ms)]
    step = "jit(step)/"
    op = "jit(contrib_flash_attention)/"
    rope = "mla_rope/jit(contrib_rotary_embedding)/"
    scope = {
        "fusion.1": step + "jvp(mla)/jit(FullyConnected)/dot_general:",
        "flash.2": step + "transpose(jvp(mla))/" + op
                   + "mx_flash_bwd_dq/pallas_call:",
        "fusion.3": step + "transpose(jvp(mla))/" + rope + "mul:",
        "flash.4": step + "transpose(jvp(mla))/" + op
                   + "mx_flash_bwd_dkv/pallas_call:",
        "flash.5": step + "jvp(mla)/" + op + "mx_flash_fwd/pallas_call:",
        "fusion.6": step + "jvp(mla)/mla_rope/jit(concat)/concatenate:",
        "fusion.7": step + "jvp(mlp)/jit(FullyConnected)/dot_general:",
        "fusion.8": step + "jvp(jit(mla_ropelike))/mul:"}
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
    assert read("mixer_ms_per_step.mla") == pytest.approx(
        (4 + 6 + 2 + 3 + 1 + 2) / 2)
    # the scope less the kernels inside it
    assert read("mla_around_flash_ms_per_step") == pytest.approx(
        (4 + 2 + 2) / 2)
    # a scope that only begins with "mla_rope" does not count
    assert read("mla_rope_ms_per_step") == pytest.approx((2 + 2) / 2)
    assert read("mixer_ms_per_step.mlp") == pytest.approx(1 / 2)
    assert read("flash_step_share_pct") == pytest.approx(100 * 10 / 20)
    arch = archs.of(cfg)
    least, bound = flops.least_seconds(
        arch.mla_flash_calls(cfg, 1, 8192), peak)
    assert bound == "flops"
    # five layers attend, two steps, 10 ms under ``mx_flash_*``
    assert read("mla_flash_roofline_by_arch") == pytest.approx(
        100 * least * 5 * 2 / 10e-3)
    # nothing to read: no trace, no scope, an architecture that does not
    # say how many of its layers attend -> None, and nothing raised
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    bare = dict(run, cfg=d2, workload=d2_wl, trace={
        "planes": trace["planes"], "scope": {}, "detail": {}})
    for name in NEW_READERS:
        assert harness._module("metrics", name).read(bare) is None
        assert harness._module("metrics", name).read(
            dict(run, trace=None)) is None
    _, kimi, kimi_wl, _, _ = harness.load_cell("train_kimilinear_p5_b1s4096")
    assert harness._module("metrics", "mla_flash_roofline_by_arch").read(
        dict(run, cfg=kimi, workload=kimi_wl)) is None


# -- correct has been shown to fail on the cell ------------------------------------------

@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    doc = _run(os.path.join(HERE, "faults_joyai_llm_flash.py"), fault,
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
