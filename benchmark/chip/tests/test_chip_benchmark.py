"""Tests of the chip benchmark's own code (the yardstick), all in one file.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.
Nothing here touches a chip, and nothing here gives a time or a rate.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import compare  # noqa: E402
import flops  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cfg(name):
    return json.load(open(os.path.join(CHIP, "configs", name + ".json")))


# -- flops.py and peaks.json ----------------------------------------------------

def test_configurations_reproduce_the_published_totals():
    for name in ("mistral_7b_d2", "mistral_7b_d3_mesh4"):
        cfg = _cfg(name)
        assert flops.layer_params(cfg) == 218_112_000
        assert flops.param_count(cfg, 32) == 7_241_732_096
        assert cfg["published"]["parameters"] == 7_241_732_096
    assert flops.param_count(_cfg("mistral_7b_d2")) == 698_372_096
    assert flops.param_count(_cfg("mistral_7b_d3_mesh4")) == 916_484_096
    smol = dict(hidden_size=960, num_hidden_layers=32, num_attention_heads=15,
                num_key_value_heads=5, intermediate_size=2560,
                vocab_size=49152, tie_word_embeddings=True)
    assert flops.layer_params(smol) == 9_832_320
    assert flops.param_count(smol) == 361_821_120


def test_flops_and_bytes_follow_from_shapes():
    cfg = _cfg("mistral_7b_d2")
    per_token = flops.train_flops_per_token(cfg, 512)
    matmul = 2 * (218_112_000 - 8192) + 32000 * 4096
    assert per_token == 6 * matmul + 3 * (4 * 512 * 4096 * 2 // 2)
    fwd, dq, dkv = flops.flash_calls(4, 32, 512, 128)
    unit = 2 * 128 * 512 * 512 * 128 // 2        # one causal product
    assert (fwd["flops"], dq["flops"], dkv["flops"]) == \
        (2 * unit, 3 * unit, 4 * unit)
    tile, stat = 128 * 512 * 128 * 2, 128 * 512 * 4
    assert fwd["bytes"] == 4 * tile + stat
    assert dkv["bytes"] == 6 * tile + 2 * stat
    double = flops.flash_calls(8, 32, 512, 128)
    assert all(b["flops"] == 2 * a["flops"] and b["bytes"] == 2 * a["bytes"]
               for a, b in zip((fwd, dq, dkv), double))
    peak = flops.peaks("TPU v5 lite")
    secs, bound = flops.least_seconds([fwd, dq, dkv], peak)
    assert bound == "bytes" and secs == pytest.approx(
        sum(c["bytes"] for c in (fwd, dq, dkv)) / 819e9)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


# -- traffic.py ---------------------------------------------------------------------

def test_traffic_is_a_function_of_the_seed():
    big = 2 ** 31 + 12345
    a = traffic.token_batches(big, 4, 16, 256)
    b = traffic.token_batches(big, 4, 16, 256)
    for _ in range(3):
        (ta, la), (tb, lb) = next(a), next(b)
        assert (ta == tb).all() and (la == lb).all()
        assert ta.dtype == np.int32 and la.dtype == np.float32
        assert len({tuple(r) for r in ta}) == 4          # rows all differ
    other = next(traffic.token_batches(big + 1, 4, 16, 256))[0]
    assert not (other == ta).all()
    r1 = traffic.poisson_requests(big, 10, 8, (16, 512), (16, 192), 1000)
    r2 = traffic.poisson_requests(big, 10, 8, (16, 512), (16, 192), 1000)
    r3 = traffic.poisson_requests(7, 10, 8, (16, 512), (16, 192), 1000)
    assert r1 == r2 and len(r1) == len(r3) == 80
    assert [r["due_s"] for r in r1] == sorted(r["due_s"] for r in r1)
    assert 0 < r1[0]["due_s"] and r1[-1]["due_s"] < 10
    # every seed: the same set of sizes, in another order
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"])  # noqa
                              for r in rs)
    assert sizes(r1) == sizes(r3)
    assert [len(r["prompt"]) for r in r1] != [len(r["prompt"]) for r in r3]
    assert all(16 <= len(r["prompt"]) <= 512
               and 16 <= r["max_new_tokens"] <= 192 for r in r1)


# -- compare.py ---------------------------------------------------------------------

def test_comparison_numbers_and_limits():
    ref = {"losses": [10.0, 9.0, 8.0], "grad_norms": [1.0, 2.0, 1e-6],
           "delta_norms": [0.5, 0.5, 0.5]}
    same = compare.train_numbers(ref, ref)
    assert same == {"loss_gap": 0, "grad_norm_gap": 0, "delta_norm_gap": 0}
    ours = {"losses": [10.1, 9.0, 8.0], "grad_norms": [1.1, 2.0, 2e-6],
            "delta_norms": [0.5, 0.25, 5.0]}
    n = compare.train_numbers(ours, ref)
    assert n["loss_gap"] == pytest.approx(0.01)
    # leaf 2's gradient is all but zero: measured against the median leaf,
    # and left out of the change
    assert n["grad_norm_gap"] == pytest.approx(0.1)
    assert n["delta_norm_gap"] == pytest.approx(0.5)
    ok, table = compare.judge(n, {"loss_gap": 0.02, "grad_norm_gap": 0.05})
    assert not ok and table["grad_norm_gap"] == [pytest.approx(0.1), 0.05]
    assert compare.judge(n, {"loss_gap": 0.02})[0]
    broken = compare.train_numbers({"losses": [float("nan")] * 3}, ref)
    assert not compare.judge(broken, {"loss_gap": 1.0})[0]
    assert not compare.judge({}, {"loss_gap": 1.0})[0]       # missing number


# -- trace_reduce.py ----------------------------------------------------------------

def _synthetic():
    ms = 1_000_000
    dev = lambda ops, mods: {"lines": [  # noqa: E731
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    kernel = '%flash.2 = bf16[8,512,128]{2,1,0} custom-call(bf16[8,512,128] ' \
             '%q), custom_call_target="tpu_custom_call"'
    fusion = "%fusion.1 = (f32[4,8]{1,0}, f32[4]{0}) fusion(f32[4] %p), " \
             "kind=kLoop"
    d0 = dev([(fusion, 10 * ms, 4 * ms), (kernel, 14 * ms, 2 * ms),
              ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)",
               16 * ms, 3 * ms), ("fusion.4", 17 * ms, 1 * ms),
              ("while.5", 30 * ms, 6 * ms), ("fusion.6", 31 * ms, 2 * ms)],
             [("jit_step(1)", 10 * ms, 9 * ms), ("jit_step(1)", 30 * ms, 6 * ms)])
    d0["name"] = "/device:TPU:0"
    d1 = dev([(fusion, 10 * ms, 10 * ms)], [])
    d1["name"] = "/device:TPU:1"
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 10 * ms, 30 * ms),
        ("bench:loss_fetch", 18 * ms, 10 * ms),
        ("bench:step.dispatch", 36 * ms, 1 * ms), ("other", 0, 5 * ms)]}]}
    return {"planes": [d0, d1, host]}


def _check_reductions(trace):
    ms = 1e-3
    t0, t1 = trace_reduce.window_of(trace)
    assert (t0, t1) == (10_000_000, 40_000_000)
    busy, n = trace_reduce.busy_seconds(trace, t0, t1)
    assert n == 2 and busy == pytest.approx((15 * ms + 10 * ms) / 2)
    top = dict(trace_reduce.top_ops(trace, 10, t0, t1))
    assert top["fusion.1 f32[4,8]"] == pytest.approx((4 + 10) * ms / 2)
    assert top["while.5"] == pytest.approx(4 * ms / 2)      # less its body
    kinds = dict(trace_reduce.top_ops(trace, 10, t0, t1,
                                      by=trace_reduce._family))
    assert kinds["fusion"] == pytest.approx((4 + 1 + 2 + 10) * ms / 2)
    secs, count = trace_reduce.matching_seconds(
        trace, r'custom_call_target="tpu_custom_call"', t0, t1, detail=True)
    assert count == 1 and secs == pytest.approx(2 * ms / 2)
    # the all-reduce runs 16..19 ms, a fusion covers 17..18 ms of it
    assert trace_reduce.collective_exposed_seconds(trace, t0, t1) == \
        pytest.approx(2 * ms / 2)
    assert trace_reduce.module_durations(trace, "jit_step") == \
        {"jit_step": [pytest.approx(9 * ms), pytest.approx(6 * ms)]}
    gaps = dict(trace_reduce.idle_gaps(trace, 10, t0, t1))
    assert gaps["loss_fetch"] == pytest.approx(11 * ms)       # 19..30 ms
    assert gaps["step.dispatch"] == pytest.approx(4 * ms)     # 36..40 ms


def test_trace_reductions_on_a_written_trace(tmp_path):
    trace = _synthetic()
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(trace, path)
    _check_reductions(trace_reduce.load(path))     # through ProfileData


def test_trace_reductions_on_the_recorded_fixture():
    """A quarter second cut from a traced chip run of
    train_mistral7b_d2_b4s512 (PR 26); the numbers asserted are that
    cut's own, worked out by hand from ``fixtures/recorded.json``."""
    path = os.path.join(CHIP, "fixtures", "recorded.xplane.pb")
    want = json.load(open(os.path.join(CHIP, "fixtures", "recorded.json")))
    trace = trace_reduce.load(path)
    t0, t1 = want["window_ns"]
    busy, n = trace_reduce.busy_seconds(trace, t0, t1)
    assert n == want["devices"]
    assert busy == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < busy <= (t1 - t0) / 1e9
    secs, count = trace_reduce.matching_seconds(trace, want["kernel"], t0, t1,
                                                detail=True)
    assert count == want["kernel_events"]
    assert secs == pytest.approx(want["kernel_s"], rel=1e-6)
    top = trace_reduce.top_ops(trace, 3, t0, t1)
    assert [n for n, _ in top] == want["top3"]
    assert trace_reduce.marks(trace)


# -- the harness, rehearsed -------------------------------------------------------------

def _run(script, *args, env=None, cwd=ROOT, timeout=900):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


def _last_json(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_every_cell_end_to_end(cell, trace):
    doc = _last_json(_run(os.path.join(CHIP, "run.py"), "--workload", cell,
                          "--seed", str(2 ** 31 + 7), "--seconds", "1",
                          "--trace", trace, "--rehearse"))
    assert doc["rehearsal"] is True and doc["correct"] is True
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert "metrics" not in doc            # never the contract's line
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}[cell]
    assert doc["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": chips}
    want = "train_compiles_in_window" if trace == "1" else "setup_s"
    assert want in doc["metrics_read"]
    assert all(v <= lim for v, lim in doc["check"].values())


def test_no_tpu_and_no_rehearse_fails_and_prints_no_result():
    proc = _run(os.path.join(CHIP, "run.py"), "--workload", CELLS[0],
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "metrics" not in proc.stdout and "tokens" not in proc.stdout


def test_a_cell_a_configuration_and_a_metric_are_added_by_files_alone(
        tmp_path):
    """A later PR adds files and entries and edits none: a copy of the
    benchmark with one more configuration, cell and per-layer metric runs
    through the unchanged harness."""
    chip = tmp_path / "benchmark" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    cfg = _cfg("mistral_7b_d2")
    cfg["tiny"]["num_hidden_layers"] = 1
    (chip / "configs" / "added_cfg.json").write_text(json.dumps(cfg))
    wl = json.load(open(os.path.join(
        CHIP, "workloads", "train_mistral7b_d2_b4s512.json")))
    # what this test shows is that the files are found, so its limits are
    # wide: a 2 x 8-token batch is noisier than any cell's rehearsal
    wl.update(config="added_cfg", traffic="fresh_b2s8",
              tiny={"batch": 2, "seq": 8, "limits": {
                  "grad_norm_gap": 0.05, "delta_norm_gap": 0.05}})
    (chip / "workloads" / "added_cell.json").write_text(json.dumps(wl))
    (chip / "metrics" / "added.steps.py").write_text(
        "def read(run):\n    return run['steps']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "added_cfg", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/chip/configs/added_cfg.json"})
    bench["workloads"].append({"name": "added_cell", "config": "added_cfg",
                               "traffic": "fresh_b2s8", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "added.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["added_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    doc = _last_json(_run(str(chip / "run.py"), "--workload", "added_cell",
                          "--seed", "3", "--seconds", "1", "--trace", "1",
                          "--rehearse", env={"PYTHONPATH": ROOT},
                          cwd=str(tmp_path)))
    assert doc["correct"] is True and "added.steps" in doc["metrics_read"]
    # and a cell that was there does not report the added metric
    doc = _last_json(_run(str(chip / "run.py"), "--workload", CELLS[0],
                          "--seed", "3", "--seconds", "1", "--trace", "1",
                          "--rehearse", env={"PYTHONPATH": ROOT},
                          cwd=str(tmp_path)))
    assert "added.steps" not in doc["metrics_read"]


# -- correct has been shown to fail ---------------------------------------------------------

FAULTS = [("unchanged", CELLS[0]), ("half", CELLS[0])] + \
    [("no_exchange", c) for c in CELLS if "mesh" in c]


@pytest.mark.parametrize("fault,cell", FAULTS)
def test_a_fault_in_the_timed_path_comes_out_not_correct(fault, cell):
    doc = _last_json(_run(os.path.join(HERE, "faults.py"), fault,
                          "--workload", cell, "--seed", "11", "--seconds",
                          "1", "--trace", "0", "--rehearse"))
    assert doc["correct"] is False
    failed = [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert failed, doc["check"]
    if fault == "unchanged":
        # nothing moved: the change reads 1 by the measure, the gradient
        # the optimizer kept reads 0 against the reference's
        assert doc["check"]["delta_norm_gap"][0] == pytest.approx(1.0)
    else:
        assert "grad_norm_gap" in failed


@pytest.mark.parametrize("cell", CELLS)
def test_the_fp8_control_comes_out_not_correct(cell):
    """The reference in fp8, put in the program's place at the tiny size,
    fails the cell's own limits on three seeds; the float32 reference
    against itself passes them."""
    out = os.path.join(CHIP, ".cache", "probe_test_%s.jsonl" % cell)
    if os.path.exists(out):
        os.remove(out)
    proc = _run(os.path.join(CHIP, "probe.py"), "--workload", cell,
                "--seeds", "21,22,23", "--what", "control,unchanged",
                "--out", out, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    limits = json.load(open(os.path.join(
        CHIP, "workloads", cell + ".json")))["limits"]
    docs = [json.loads(ln) for ln in open(out)]
    os.remove(out)
    assert len(docs) == 6
    for d in docs:
        ok, table = compare.judge(d["numbers"], limits)
        assert not ok, (d["kind"], d["seed"], table)
