"""Tests of the readers PR 31 brought for the grouped-matmul kernels
(``moe_gmm_roofline``, ``moe_gmm_calls_per_step``), on traces written by
hand, and of ``mixer_ms_per_step.moe`` counting those kernels by scope.  Run
with ``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.
Nothing here touches a chip."""
import os

import pytest

# the helpers, and the benchmark's directory on sys.path, from its sibling
from test_span_metrics import CHIP, MS, _reader, _synthetic, harness, \
    trace_reduce

import archs  # noqa: E402
import flops  # noqa: E402
import mixer_reduce  # noqa: E402

NEMOTRON = "train_nemotronh_p7_b2s2048"
EXPERTS = "jit(contrib_moe_grouped_ffn)/moe_experts/checkpoint/"
# as the TPU profiler names a device operation: the whole instruction
GMM = ("%%mx_gmm.%d = bf16[24576,1856]{1,0:T(8,128)(2,1)} custom-call("
       "%%bitcast.4, %%fusion.9), custom_call_target=\"tpu_custom_call\"")
GMM_DW = ("%%mx_gmm_dw.%d = bf16[8,1856,2688]{2,1,0:T(8,128)(2,1)} "
          "custom-call(%%bitcast.5), custom_call_target=\"tpu_custom_call\"")


def _with_kernels():
    """``_synthetic``'s three steps in the window and a fourth after it;
    in each, on the first device, a forward ``mx_gmm`` of 2 ms, a backward
    one of 3 ms and an ``mx_gmm_dw`` of 4 ms under the scope ``moe``, the
    operation that makes their visit lists (1 ms, under ``moe`` and outside
    the kernels), and a fusion of the router's (2 ms).  The second device
    runs one kernel that nobody counts."""
    trace = _synthetic()
    trace["planes"][0]["lines"][0]["events"] = ops = []
    scope = {}
    for k in range(4):
        base = 100 * k * MS
        for i, (text, at, dur, path) in enumerate((
                (GMM, 10, 2, "jit(step)/jvp(moe)/" + EXPERTS
                 + "custom_vjp_call/jit(_gmm_pallas)/mx_gmm/pallas_call:"),
                (GMM, 20, 3, "jit(step)/transpose(jvp(moe))/" + EXPERTS
                 + "transpose(jvp(jit(_gmm_pallas)))/mx_gmm/pallas_call:"),
                (GMM_DW, 30, 4, "jit(step)/transpose(jvp(moe))/" + EXPERTS
                 + "jit(_gmm_dw_pallas)/mx_gmm_dw/pallas_call:"),
                ("%%fusion.%d = s32[199]{0} fusion(%%p)", 40, 1,
                 "jit(step)/jvp(moe)/" + EXPERTS + "jit(_gmm_pallas)/cumsum:"),
                ("%%fusion.%d = f32[4096,128]{1,0} fusion(%%p)", 50, 2,
                 "jit(step)/jvp(moe)/jit(contrib_moe_router_topk)/"
                 "moe_router/dot_general:"))):
            name = text % (10 * k + i)
            ops.append((name, base + at * MS, dur * MS))
            scope[name] = path
    other = GMM % 99
    trace["planes"][1]["lines"][0]["events"].append((other, MS, MS))
    scope[other] = scope[GMM % 0]
    trace["scope"] = scope
    return trace


def _loaded(tmp_path, trace):
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(trace, path)
    return trace_reduce.load(path)             # through ProfileData


def _run(trace, steps=3):
    _, cfg, workload, _, _ = harness.load_cell(NEMOTRON)
    window = trace_reduce.window_of(trace) if trace else None
    return {"trace": trace, "trace_window": window, "steps": steps,
            "cfg": cfg, "workload": workload,
            "peak": flops.peaks("TPU v5 lite"), "busy_s": 0.1}


def test_the_readers_find_the_kernels_by_their_scope(tmp_path, monkeypatch):
    trace = _loaded(tmp_path, _with_kernels())
    run = _run(trace)
    # three launches a step on the first device; the fourth step begins
    # after the window, and the second device's kernel is not counted
    assert _reader("moe_gmm_calls_per_step")(run) == 3.0
    # 200 rows a held expert and layer-step
    held = {(str(layer), str(e)): 5 * 200 for layer in (1, 3, 6)
            for e in range(8)}
    counts = {"total": 5 * 3 * 4096 * 6, "dropped": 0, "held": held}
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: counts)
    cfg = run["cfg"]
    least, bound = flops.least_seconds(
        archs.of(cfg).grouped_calls(cfg, 1600), run["peak"])
    assert bound == "bytes"
    # the first device's 9 ms a step and the second's 1 ms, per device
    seconds = (3 * (2 + 3 + 4) + 1) * 1e-3 / 2
    assert _reader("moe_gmm_roofline")(run) == pytest.approx(
        100 * least * 3 * 3 / seconds)
    # the compiler's kernels are gone, and their reader with them
    assert _reader("moe_grouped_roofline")(run) is None
    # the block's time counts the kernels and what stands around them
    assert _reader("mixer_ms_per_step.moe")(run) == pytest.approx(
        (3 * (2 + 3 + 4 + 1 + 2) + 1) / 2 / 3)


def test_silent_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A run that was not traced; a program whose grouped products are the
    compiler's (the parent: left out, not 0), written and recorded; a
    program without the counters; a cell without expert layers."""
    counts = {"total": 3 * 4096 * 6, "dropped": 0,
              "held": {("1", "0"): 600}}
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: counts)
    readers = [_reader("moe_gmm_roofline"), _reader("moe_gmm_calls_per_step")]
    for read in readers:
        assert read(_run(None)) is None
    parent = _with_kernels()
    parent["scope"] = {
        name: path.replace("mx_gmm_dw/pallas_call", "ragged-dot-none")
        .replace("mx_gmm/pallas_call", "ragged-dot-none")
        for name, path in parent["scope"].items()}
    parent = _loaded(tmp_path, parent)
    assert trace_reduce.device_ops(parent)
    for read in readers:
        assert read(_run(parent)) is None
    for name in ("recorded.xplane.pb", "recorded_spans.xplane.pb"):
        old = trace_reduce.load(os.path.join(CHIP, "fixtures", name))
        for read in readers:
            assert read(_run(old)) is None
    trace = _loaded(tmp_path, _with_kernels())
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: None)
    assert _reader("moe_gmm_roofline")(_run(trace)) is None
    _, d2, d2_wl, _, _ = harness.load_cell("train_mistral7b_d2_b4s512")
    assert _reader("moe_gmm_roofline")(
        dict(_run(trace), cfg=d2, workload=d2_wl)) is None
    cpu = _with_kernels()
    cpu["planes"] = cpu["planes"][2:]
    for read in readers:
        assert read(_run(cpu)) is None


def test_the_benchmark_lists_both_for_the_nemotron_cell_alone():
    _, _, _, _, per_layer = harness.load_cell(NEMOTRON)
    mine = {m["name"]: m for m in per_layer
            if m["name"].startswith("moe_gmm_")}
    assert sorted(mine) == ["moe_gmm_calls_per_step", "moe_gmm_roofline"]
    for m in mine.values():
        assert m["layer"] == "expert kernels" and m["better"] == "higher"
        assert m["moves"] == "train_tokens_per_s"
        assert m["workloads"] == [NEMOTRON]
    for cell in ("train_mistral7b_d2_b4s512", "train_mistral7b_d3_mesh4",
                 "train_mistral7b_d2_b1s2048"):
        assert not [m for m in harness.load_cell(cell)[4]
                    if m["name"].startswith("moe_gmm_")]
