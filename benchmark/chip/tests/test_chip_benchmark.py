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

import archs  # noqa: E402
import compare  # noqa: E402
import flops  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cfg(name):
    return json.load(open(os.path.join(CHIP, "configs", name + ".json")))


# -- flops.py, archs/ and peaks.json ---------------------------------------------

def test_configurations_reproduce_the_published_totals():
    """Through the look-up by ``model_type``, as every reader counts."""
    for name in ("mistral_7b_d2", "mistral_7b_d3_mesh4"):
        cfg = _cfg(name)
        assert archs.of(cfg) is archs.load("mistral")
        assert archs.of(cfg).layer_params(cfg) == 218_112_000
        whole = dict(cfg, num_hidden_layers=cfg["published"][
            "num_hidden_layers"])
        assert flops.param_count(whole) == 7_241_732_096
        assert cfg["published"]["parameters"] == 7_241_732_096
        # the leaves the reference is made of are the parameters counted
        assert sum(int(np.prod(shape)) for _, shape in
                   archs.of(cfg).leaf_specs(cfg)) == flops.param_count(cfg)
    assert flops.param_count(_cfg("mistral_7b_d2")) == 698_372_096
    assert flops.param_count(_cfg("mistral_7b_d3_mesh4")) == 916_484_096
    smol = dict(model_type="mistral", hidden_size=960, num_hidden_layers=32,
                num_attention_heads=15, num_key_value_heads=5,
                intermediate_size=2560, vocab_size=49152,
                tie_word_embeddings=True)
    assert archs.of(smol).layer_params(smol) == 9_832_320
    assert flops.param_count(smol) == 361_821_120


def test_a_model_type_without_a_module_is_an_error_that_names_the_file():
    cfg = dict(_cfg("mistral_7b_d2"), model_type="imaginary")
    looked_for = os.path.join(CHIP, "archs", "imaginary.py")
    for ask in (flops.param_count, lambda c: flops.train_flops_per_token(
            c, 512), archs.of):
        with pytest.raises(LookupError) as err:
            ask(cfg)
        assert looked_for in str(err.value)
    del cfg["model_type"]                    # and never a default
    with pytest.raises(LookupError):
        flops.param_count(cfg)


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
    # an asynchronous all-reduce as the TPU compiler writes it: the start
    # runs under fusion.6, the done waits with nothing beside it
    start = "%async-collective-start.7 = (f32[8]{0}, f32[8]{0}) " \
            "fusion(f32[8]{0} %g), kind=kCustom"
    done = "%async-collective-done = f32[8]{0} fusion(%get-tuple-element.1" \
           "), kind=kCustom"
    d0 = dev([(fusion, 10 * ms, 4 * ms), (kernel, 14 * ms, 2 * ms),
              ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)",
               16 * ms, 3 * ms), ("fusion.4", 17 * ms, 1 * ms),
              ("while.5", 30 * ms, 6 * ms), ("fusion.6", 31 * ms, 2 * ms),
              (start, 31 * ms + ms // 2, ms // 2), (done, 37 * ms, 2 * ms)],
             [("jit_step(1)", 10 * ms, 9 * ms), ("jit_step(1)", 30 * ms, 6 * ms)])
    d0["name"] = "/device:TPU:0"
    d1 = dev([(fusion, 10 * ms, 10 * ms)], [])
    d1["name"] = "/device:TPU:1"
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 10 * ms, 30 * ms),
        ("bench:loss_fetch", 18 * ms, 10 * ms),
        ("bench:step.dispatch", 36 * ms, 1 * ms), ("other", 0, 5 * ms)]}]}
    # the paths the operations were traced under; fusion.6 and the
    # compiler's own operations have none
    scope = {fusion: "jit(step)/jvp(jit(FullyConnected))/dot_general:",
             kernel: "jit(step)/transpose(jvp(jit(attn)))/mx_flash_bwd_dq/"
                     "pallas_call:",
             "fusion.4": "jit(step)/sub:"}
    return {"planes": [d0, d1, host], "scope": scope}


def _check_reductions(trace):
    ms = 1e-3
    t0, t1 = trace_reduce.window_of(trace)
    assert (t0, t1) == (10_000_000, 40_000_000)
    busy, n = trace_reduce.busy_seconds(trace, t0, t1)
    assert n == 2 and busy == pytest.approx((17 * ms + 10 * ms) / 2)
    top = dict(trace_reduce.top_ops(trace, 10, t0, t1))
    assert top["fusion.1 f32[4,8]"] == pytest.approx((4 + 10) * ms / 2)
    assert top["while.5"] == pytest.approx(4 * ms / 2)      # less its body
    kinds = dict(trace_reduce.top_ops(trace, 10, t0, t1,
                                      by=trace_reduce._family))
    assert kinds["fusion"] == pytest.approx((4 + 1 + 1.5 + 10) * ms / 2)
    secs, count = trace_reduce.matching_seconds(
        trace, r'custom_call_target="tpu_custom_call"', t0, t1, detail=True)
    assert count == 1 and secs == pytest.approx(2 * ms / 2)
    # the all-reduce runs 16..19 ms, a fusion covers 17..18 ms of it; the
    # asynchronous one's start runs under fusion.6 and its done, 37..39 ms,
    # waits alone
    assert trace_reduce.collective_exposed_seconds(trace, t0, t1) == \
        pytest.approx((2 + 2) * ms / 2)
    # each operation's scope came through the file; its self time by scope
    assert trace["scope"] == {
        "fusion.1 f32[4,8]": "jit(step)/jvp(jit(FullyConnected))/"
                             "dot_general:",
        "flash.2 bf16[8,512,128]": "jit(step)/transpose(jvp(jit(attn)))/"
                                   "mx_flash_bwd_dq/pallas_call:",
        "fusion.4": "jit(step)/sub:"}
    backward = r"transpose\(jvp\("
    assert trace_reduce.scope_seconds(trace, backward, t0, t1) == \
        (pytest.approx(2 * ms / 2), 1)
    assert trace_reduce.scope_seconds(
        trace, r"^(?!.*%s).*jvp\(" % backward, t0, t1) == \
        (pytest.approx((4 + 10) * ms / 2), 1)
    assert trace_reduce.scope_seconds(trace, r"/sub:$", t0, t1) == \
        (pytest.approx(1 * ms / 2), 1)
    everything, _ = trace_reduce.scope_seconds(trace, "", t0, t1)
    assert everything == pytest.approx(busy)    # no scope: the empty path
    assert trace_reduce.module_durations(trace, "jit_step") == \
        {"jit_step": [pytest.approx(9 * ms), pytest.approx(6 * ms)]}
    gaps = dict(trace_reduce.idle_gaps(trace, 10, t0, t1))
    assert gaps["loss_fetch"] == pytest.approx(11 * ms)       # 19..30 ms
    assert gaps["step.dispatch"] == pytest.approx(1 * ms)     # 36..37 ms
    assert gaps["host (unattributed)"] == pytest.approx(1 * ms)   # 39..40


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


def test_exposed_collectives_on_the_recorded_mesh_fixture():
    """One step period cut from a traced chip run of
    train_mistral7b_d3_mesh4, whose gradient all-reduces are asynchronous
    since PR 28.  On an ``XLA Ops`` line no two operations overlap, so what
    a collective exposes is its duration: ``fixtures/recorded_mesh_async.
    json`` holds the sums by hand, per device and kind, and the step split
    by the scope the profiler recorded for each operation."""
    want = json.load(open(os.path.join(
        CHIP, "fixtures", "recorded_mesh_async.json")))
    trace = trace_reduce.load(os.path.join(
        CHIP, "fixtures", "recorded_mesh_async.xplane.pb"))
    t0, t1 = want["window_ns"]
    ops = trace_reduce.device_ops(trace)
    assert sorted(ops) == sorted(want["by_hand"])
    by_hand = 0
    for name, hand in want["by_hand"].items():
        assert hand["events"] == {"async_done": 32, "async_start": 32,
                                  "sync": 9}
        by_hand += sum(hand["ns"].values())
        # the -done halves are where the device waits: 29 of 35 ms
        assert hand["ns"]["async_done"] > 4 * hand["ns"]["sync"]
    by_hand /= 1e9 * len(ops)
    assert by_hand == pytest.approx(want["collective_exposed_s"], rel=1e-9)
    got = trace_reduce.collective_exposed_seconds(trace, t0, t1)
    assert got == pytest.approx(by_hand, rel=1e-9)
    assert 100 * got / ((t1 - t0) / 1e9) == pytest.approx(
        want["collective_exposed_pct"], rel=1e-9)
    # what the pattern of before PR 29 saw: the synchronous ones alone
    assert want["synchronous_only_s"] < 0.2 * got
    # the scopes came through write_xspace and load; forward, backward
    # and the rest are the whole of what ran
    assert len(trace["scope"]) == want["scopes"]
    backward = r"transpose\(jvp\("
    split = {
        "backward": trace_reduce.scope_seconds(trace, backward, t0, t1)[0],
        "forward": trace_reduce.scope_seconds(
            trace, r"^(?!.*%s).*jvp\(" % backward, t0, t1)[0]}
    busy, _ = trace_reduce.busy_seconds(trace, t0, t1)
    split["rest"] = busy - split["backward"] - split["forward"]
    assert split == {k: pytest.approx(v, rel=1e-6)
                     for k, v in want["scope_s"].items()}


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


def _copy_of_the_benchmark(tmp_path):
    """The benchmark as a later PR finds it, copied to where files can be
    added: ``(its directory, BENCHMARK.json as a dict)``."""
    chip = tmp_path / "benchmark" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    return chip, json.loads(json.dumps(BENCH))


def _add_cell(chip, bench, name, cfg, workload):
    """One more configuration ``<name>_cfg`` and cell ``<name>_cell``, as
    files and entries."""
    (chip / "configs" / (name + "_cfg.json")).write_text(json.dumps(cfg))
    workload = dict(workload, config=name + "_cfg")
    (chip / "workloads" / (name + "_cell.json")).write_text(
        json.dumps(workload))
    bench["configs"].append({
        "name": name + "_cfg", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/chip/configs/%s_cfg.json" % name})
    bench["workloads"].append({
        "name": name + "_cell", "config": name + "_cfg",
        "traffic": workload["traffic"], "chips": 1, "why": "test"})
    (chip.parent.parent / "BENCHMARK.json").write_text(json.dumps(bench))


RUN_ARGS = ("--seed", "3", "--seconds", "1", "--trace", "1")


def _rehearse(chip, cell, script="run.py", args=RUN_ARGS):
    """``script`` of the copy on ``cell``, from the copy's root."""
    return _run(str(chip / script), "--workload", cell, *args, "--rehearse",
                env={"PYTHONPATH": ROOT}, cwd=str(chip.parent.parent))


def _d2_workload(**over):
    wl = json.load(open(os.path.join(
        CHIP, "workloads", "train_mistral7b_d2_b4s512.json")))
    # what these tests show is that the files are found, so their limits
    # are wide: a 2 x 8-token batch is noisier than any cell's rehearsal
    wl.update(traffic="fresh_b2s8", tiny={"batch": 2, "seq": 8, "limits": {
        "grad_norm_gap": 0.05, "delta_norm_gap": 0.05}})
    wl.update(over)
    return wl


def test_a_cell_a_configuration_and_a_metric_are_added_by_files_alone(
        tmp_path):
    """A later PR adds files and entries and edits none: a copy of the
    benchmark with one more configuration, cell and per-layer metric runs
    through the unchanged harness."""
    chip, bench = _copy_of_the_benchmark(tmp_path)
    cfg = _cfg("mistral_7b_d2")
    cfg["tiny"]["num_hidden_layers"] = 1
    (chip / "metrics" / "added.steps.py").write_text(
        "def read(run):\n    return run['steps']\n")
    bench["per_layer"].append({
        "name": "added.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["added_cell"]})
    _add_cell(chip, bench, "added", cfg, _d2_workload())
    doc = _last_json(_rehearse(chip, "added_cell"))
    assert doc["correct"] is True and "added.steps" in doc["metrics_read"]
    # and a cell that was there does not report the added metric
    doc = _last_json(_rehearse(chip, CELLS[0]))
    assert "added.steps" not in doc["metrics_read"]


# an architecture that is visibly not Llama's: embedding, one relu-squared
# MLP layer (no gate, no norm, no attention) with a residual, head; its
# program from gluon.nn.  Everything a later PR writes for a new model_type.
ADDED_ARCH = '''
import jax
import jax.numpy as jnp


def _sizes(cfg):
    return cfg["hidden_size"], cfg["ffn_size"], cfg["vocab_size"]


def leaf_specs(cfg):
    h, f, v = _sizes(cfg)
    return [("embed", (v, h)), ("up", (f, h)), ("down", (h, f)),
            ("head", (v, h))]


def forward(cfg, leaves, tokens, ein):
    embed, up, down, head = leaves
    x = embed[tokens]
    a = jnp.square(jax.nn.relu(ein("bti,fi->btf", x, up)))
    return ein("bti,vi->btv", x + ein("btf,if->bti", a, down), head)


def param_count(cfg):
    h, f, v = _sizes(cfg)
    return 2 * v * h + 2 * h * f


def train_flops_per_token(cfg, seq):
    h, f, v = _sizes(cfg)
    return 6 * (2 * h * f + v * h)


def build(cfg, ctx):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    h, f, v = _sizes(cfg)

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.embed = nn.Embedding(v, h)
                self.up = nn.Dense(f, flatten=False, use_bias=False)
                self.down = nn.Dense(h, flatten=False, use_bias=False)
                self.head = nn.Dense(v, flatten=False, use_bias=False)

        def hybrid_forward(self, F, tokens):
            x = self.embed(tokens)
            return self.head(x + self.down(F.square(F.relu(self.up(x)))))

    net = Net()
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
'''


def test_an_architecture_is_added_by_files_alone(tmp_path):
    """A later ``model_config`` PR brings ``archs/<model_type>.py`` beside
    its configuration and cell and edits nothing: the copy rehearses
    ``correct`` against the added reference, counts the added module's
    operations and not Llama's, and runs the fp8 control and the unchanged
    state on the added cell; a ``model_type`` with no module fails with the
    name of the file looked for."""
    chip, bench = _copy_of_the_benchmark(tmp_path)
    (chip / "archs" / "added.py").write_text(ADDED_ARCH)
    d2 = _cfg("mistral_7b_d2")
    cfg = {"model_type": "added", "hidden_size": 64, "ffn_size": 96,
           "vocab_size": 256, "dtype": d2["dtype"],
           "optimizer": d2["optimizer"]}
    _add_cell(chip, bench, "arch", cfg, _d2_workload())
    _add_cell(chip, bench, "nowhere", dict(cfg, model_type="nowhere"),
              _d2_workload(traffic="fresh_b2s8_nowhere"))
    doc = _last_json(_rehearse(chip, "arch_cell"))
    assert doc["correct"] is True and doc["attempted"] > 0
    ours = doc["check"]["grad_norm_gap"][0]
    # the count every reader gets is the added module's, from the copy's
    # own flops.py
    count = _run("-c", "import json, sys; sys.path.insert(0, %r); "
                 "import flops; cfg = json.load(open(%r)); "
                 "print(flops.param_count(cfg), "
                 "flops.train_flops_per_token(cfg, 8))"
                 % (str(chip), str(chip / "configs" / "arch_cfg.json")))
    assert count.returncode == 0, count.stderr[-2000:]
    assert count.stdout.split() == [
        str(2 * 256 * 64 + 2 * 64 * 96), str(6 * (2 * 64 * 96 + 256 * 64))]
    out = str(tmp_path / "probe.jsonl")
    proc = _rehearse(chip, "arch_cell", "probe.py", (
        "--seeds", "3", "--what", "control,unchanged", "--out", out))
    assert proc.returncode == 0, proc.stderr[-3000:]
    kinds = {d["kind"]: d["numbers"] for d in map(json.loads, open(out))}
    assert kinds["unchanged"]["delta_norm_gap"] == pytest.approx(1.0)
    # fp8 in the program's place reads wider than the program's bfloat16
    assert ours < kinds["control"]["grad_norm_gap"] < compare.NEVER
    proc = _rehearse(chip, "nowhere_cell")
    assert proc.returncode != 0 and "rehearsal" not in proc.stdout
    assert str(chip / "archs" / "nowhere.py") in proc.stderr


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
