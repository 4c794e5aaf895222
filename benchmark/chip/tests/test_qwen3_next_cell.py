"""The Qwen3-Next configuration, its cell and its readers.  CPU only: nothing
here gives a time or a rate of a device.
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

CELL = "train_qwen3next_p4_b1s8192"
FAULTS = ["key_head", "sigmoid_z", "no_attention_gate", "last_channels",
          "ungated_shared", "eleventh_expert"]
NEW_READERS = ["mixer_ms_per_step.gdn", "mixer_ms_per_step.gated_attention",
               "gdn_scan_roofline", "gated_flash_roofline",
               "gdn_kernel_layers_pct"]


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
    assert flops.param_count(whole) == 79_674_391_296 == pub["parameters"]
    assert flops.param_count(cfg) == 424_340_544
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["qwen3_next_80b_p4_e16"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        k for k in pub if k != "parameters" and pub[k] != cfg[k])
    assert entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_num_experts"], cfg["partial_rotary_factor"]) == (
        2048, 256, 16, 2, 16, 32, 128, 512, 10, 512, 0.25)
    arch = archs.of(cfg)
    assert arch.kinds(cfg) == ["gdn", "gdn", "gdn", "attn"]
    # 11.12 TFLOP a step at 8192 tokens: the causal core 67.1 MFLOP a token
    assert flops.train_flops_per_token(cfg, 8192) * 8192 == pytest.approx(
        11.1233e12, rel=1e-4)
    assert arch._attention_flops_per_token(cfg, 8192) == 67_108_864
    assert (cell["chips"], cell["traffic"]) == (1, "fresh_b1s8192")
    assert (workload["batch"], workload["seq"]) == (1, 8192)
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
    # the worst leaf is read; the median leaf is what fp8 fails
    assert workload["driver"] == "train_median_leaf"
    assert set(workload["limits"]) == {"grad_norm_gap_median",
                                       "delta_norm_gap"}


# -- the cell, rehearsed ---------------------------------------------------------------

@pytest.mark.parametrize("trace,want", [
    ("0", {"setup_s", "train_tokens_per_s"}),
    ("1", {"gdn_kernel_layers_pct", "train_compiles_in_window"})])
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
    ops = [("fusion.1", 0, 4 * ms), ("kda.2", 4 * ms, 6 * ms),
           ("flash.3", 10 * ms, 2 * ms), ("flash.4", 12 * ms, 3 * ms),
           ("flash.5", 15 * ms, 1 * ms), ("fusion.6", 16 * ms, 2 * ms),
           ("flash.7", 18 * ms, 1 * ms)]
    step = "jit(step)/"
    op = "jit(contrib_flash_attention)/"
    scope = {
        "fusion.1": step + "jvp(gdn)/jit(FullyConnected)/dot:",
        "kda.2": step + "transpose(jvp(gdn))/jit(contrib_gdn_attention)/"
                 "gdn_attention/gdn_scan/mx_kda_bwd/pallas_call:",
        "flash.3": step + "transpose(jvp(gated_attention))/" + op
                   + "mx_flash_bwd_dq/pallas_call:",
        "flash.4": step + "transpose(jvp(gated_attention))/" + op
                   + "mx_flash_bwd_dkv/pallas_call:",
        "flash.5": step + "jvp(gated_attention)/" + op
                   + "mx_flash_fwd/pallas_call:",
        "fusion.6": step + "jvp(gdn_like)/mul:",
        "flash.7": step + "jvp(bd_attention)/" + op
                   + "mx_flash_fwd_bd/pallas_call:"}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [("jit_step(1)", 0, 19 * ms)]}]}
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
           "busy_s": 19e-3}

    def read(name, **kw):
        return harness._module("metrics", name).read(dict(run, **kw))
    # the scope, and not one that only begins with its name
    assert read("mixer_ms_per_step.gdn") == pytest.approx((4 + 6) / 2)
    assert read("mixer_ms_per_step.gated_attention") == pytest.approx(
        (2 + 3 + 1) / 2)
    arch = archs.of(cfg)
    least, bound = flops.least_seconds(arch.gdn_calls(cfg, 1, 8192), peak)
    assert bound == "bytes"
    # three GDN layers, two steps, 6 ms under ``gdn_scan``
    assert read("gdn_scan_roofline") == pytest.approx(
        100 * least * 3 * 2 / 6e-3)
    least, bound = flops.least_seconds(arch.attention_calls(cfg, 1, 8192),
                                       peak)
    assert bound == "flops"
    # one attention layer, two steps, 6 ms of the generic kernels inside
    # ``gated_attention`` (not the block-diffusion kernel beside them)
    assert read("gated_flash_roofline") == pytest.approx(
        100 * least * 1 * 2 / 6e-3)
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    for name in NEW_READERS[:4]:
        assert read(name, trace=None) is None
        assert read(name, cfg=d2, workload=d2_wl,
                    trace=dict(trace, scope={})) is None


def test_the_kernel_share_reads_the_two_counters(monkeypatch):
    from mxnet_tpu.telemetry import metrics

    reader = harness._module("metrics", "gdn_kernel_layers_pct")
    snaps = [{}, {"mxnet_gdn_layers_total": {"series": [{"value": 0}]},
                  "mxnet_gdn_kernel_layers_total": {"series": [
                      {"value": 0}]}},
             {"mxnet_gdn_layers_total": {"series": [{"value": 30}]},
              "mxnet_gdn_kernel_layers_total": {"series": [{"value": 30}]}}]
    got = []
    for snap in snaps:
        monkeypatch.setattr(metrics, "snapshot", lambda snap=snap: snap)
        got.append(reader.read({}))
    assert got == [None, None, 100.0]


# -- correct has been shown to fail on the cell ------------------------------------------

@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    doc = _run(os.path.join(HERE, "faults_qwen3_next.py"), fault,
               "--workload", CELL, "--seed", "11", "--seconds", "1",
               "--trace", "0", "--rehearse")
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < compare.NEVER for v, _ in doc["check"].values())


# the control and a state left unchanged, read by the cell's own driver
_CONTROL = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import run as harness
_, cfg, wl, _, _ = harness.load_cell(sys.argv[2], rehearse=True)
import reference, traffic
drv = harness._module("drivers", wl["driver"])
for seed in (31, 32):
    feed = traffic.token_batches(seed, wl["batch"], wl["seq"],
                                 cfg["vocab_size"])
    batches = [(t, lab.astype("int32")) for t, lab in
               (next(feed) for _ in range(wl["follow_steps"]))]

    def readings(**kw):
        return reference.train_readings(cfg, cfg["optimizer"], seed,
                                        batches, **kw)
    ref = readings()
    for kind, kw in (("control", {"precision": "fp8"}),
                     ("unchanged", {"skip_update": True})):
        print(json.dumps({"seed": seed, "kind": kind,
                          "numbers": drv.numbers(readings(**kw), ref)}))
"""


def test_the_fp8_control_fails_the_cells_tiny_limits():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _CONTROL, CHIP, CELL],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    _, _, workload, _, _ = harness.load_cell(CELL, rehearse=True)
    docs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert len(docs) == 4
    for d in docs:
        ok, table = compare.judge(d["numbers"], workload["limits"])
        assert not ok, (d["kind"], d["seed"], table)
        # each fails on the number its fault moves, a real reading
        name = {"control": "grad_norm_gap_median",
                "unchanged": "delta_norm_gap"}[d["kind"]]
        value, limit = table[name]
        assert limit < value < compare.NEVER, (d["kind"], table)


def test_the_median_leaf_reads_past_one_wild_leaf():
    drv = harness._module("drivers", "train_median_leaf")
    ref = {"losses": [2.0], "grad_norms": [1.0, 2.0, 3.0, 4.0, 5.0],
           "delta_norms": [1.0] * 5}
    near = dict(ref, grad_norms=[1.0, 2.0, 3.0, 4.0, 5.0 * 1.5])
    far = dict(ref, grad_norms=[g * 1.1 for g in ref["grad_norms"]])
    # one leaf off by half: the worst leaf reads it, the median does not
    assert drv.numbers(near, ref)["grad_norm_gap"] == pytest.approx(0.5)
    assert drv.numbers(near, ref)["grad_norm_gap_median"] == 0.0
    # every leaf off by a tenth: against max(leaf, median leaf 3.0)
    assert drv.numbers(far, ref)["grad_norm_gap_median"] == pytest.approx(
        0.1)
    # the changes: over the leaves whose reference gradient counts
    moved = dict(ref, delta_norms=[1.0, 1.0, 1.0, 2.0, 2.0])
    ref_small = dict(ref, grad_norms=[1e-9, 2.0, 3.0, 4.0, 5.0])
    assert drv.numbers(moved, ref)["delta_norm_gap_median"] == 0.0
    assert drv.numbers(moved, ref_small)["delta_norm_gap_median"] \
        == pytest.approx(0.5)
    for bad in ({}, dict(ref, grad_norms=[1.0])):
        assert set(drv.median_gaps(bad, ref).values()) == {compare.NEVER}
    nan = dict(ref, grad_norms=[float("nan")] * 5)
    assert drv.median_gaps(nan, ref)["grad_norm_gap_median"] == compare.NEVER
