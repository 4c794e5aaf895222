"""Tests of the readers PR 33 brought for what stands around the grouped
products (``moe_around_gmm_ms_per_step``, ``moe_sorted_rows_walked_pct``), on
traces written by hand and on counter snapshots.  Run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.  Nothing here
touches a chip."""
import pytest

# the helpers, and the benchmark's directory on sys.path, from the siblings
from test_gmm_metrics import EXPERTS, NEMOTRON, _loaded, _run, _with_kernels
from test_span_metrics import MS, _reader, harness

import mixer_reduce  # noqa: E402

AROUND = "moe_around_gmm_ms_per_step"
WALKED = "moe_sorted_rows_walked_pct"
TAKE = ("%%mx_rows_take.%d = bf16[24576,2688]{1,0:T(8,128)(2,1)} custom-call("
        "%%select_n.96), custom_call_target=\"tpu_custom_call\"")


def _change_shaped():
    """``_with_kernels`` (9 ms of grouped products a step and 1 ms of their
    visit lists under ``moe_experts``, 2 ms of the router outside it, on the
    first device; one product of 1 ms on the second), and in each step a
    forward ``mx_rows_take`` of 0.5 ms and a backward one of 1.5 ms."""
    trace = _with_kernels()
    ops = trace["planes"][0]["lines"][0]["events"]
    for k in range(4):
        for i, (at, dur, path) in enumerate((
                (60, 0.5, "jit(step)/jvp(moe)/" + EXPERTS
                 + "jit(<unknown>)/cond/branch_0_fun/mx_rows_take/"
                 "pallas_call:"),
                (70, 1.5, "jit(step)/transpose(jvp(moe))/" + EXPERTS
                 + "jit(<unknown>)/cond/branch_0_fun/mx_rows_take/"
                 "pallas_call:"))):
            name = TAKE % (100 + 10 * k + i)
            ops.append((name, (100 * k + at) * MS, int(dur * MS)))
            trace["scope"][name] = path
    ops.sort(key=lambda e: e[1])
    return trace


def test_the_remainder_of_the_scope_is_read(tmp_path):
    # a step of the first device: 10 ms under moe_experts of which 9 are
    # the products'; the second device runs one product of 1 ms and nothing
    # around it; three steps in the window, per device
    run = _run(_loaded(tmp_path, _with_kernels()))
    assert _reader(AROUND)(run) == pytest.approx(3 * 1 / 2 / 3)
    run = _run(_loaded(tmp_path, _change_shaped()))
    assert _reader(AROUND)(run) == pytest.approx(3 * (1 + 0.5 + 1.5) / 2 / 3)
    # and the block's time holds it: one number seen twice
    moe = _reader("mixer_ms_per_step.moe")(run)
    assert moe == pytest.approx((3 * (9 + 1 + 2 + 0.5 + 1.5) + 1) / 2 / 3)


def test_a_parent_shaped_trace_reads_the_whole_scope(tmp_path):
    """Grouped products that are not ``mx_gmm*`` (the compiler's, before
    PR 31) are not taken off: the whole of ``moe_experts``."""
    parent = _with_kernels()
    parent["scope"] = {
        name: path.replace("mx_gmm_dw/pallas_call", "ragged-dot-none")
        .replace("mx_gmm/pallas_call", "ragged-dot-none")
        for name, path in parent["scope"].items()}
    run = _run(_loaded(tmp_path, parent))
    assert _reader(AROUND)(run) == pytest.approx((3 * 10 + 1) / 2 / 3)


def _snapshot(monkeypatch, walked):
    from mxnet_tpu.telemetry import metrics

    families = {"mxnet_moe_assignments_total": {
        "series": [{"labels": {}, "value": 5 * 3 * 4096 * 6}]}}
    if walked is not None:
        families["mxnet_moe_sorted_rows_walked_total"] = {
            "series": [{"labels": {}, "value": walked}]}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)


def test_the_share_of_the_sorted_rows_walked(monkeypatch):
    # five steps of three layers, 13 tiles of 128 of 24,576 rows each
    _snapshot(monkeypatch, 5 * 3 * 13 * 128)
    assert _reader(WALKED)(_run(None)) == pytest.approx(
        100 * 13 * 128 / 24576)
    _snapshot(monkeypatch, 5 * 3 * 24576)
    assert _reader(WALKED)(_run(None)) == pytest.approx(100.0)


def test_silent_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counter (the parent); one that routes nothing;
    a run that was not traced; a trace without the scope."""
    _snapshot(monkeypatch, None)
    assert _reader(WALKED)(_run(None)) is None
    monkeypatch.setattr(mixer_reduce, "moe_counts", lambda run: None)
    assert _reader(WALKED)(_run(None)) is None
    assert _reader(AROUND)(_run(None)) is None
    bare = _with_kernels()
    bare["scope"] = {name: path.replace("moe_experts/", "")
                     for name, path in bare["scope"].items()}
    assert _reader(AROUND)(_run(_loaded(tmp_path, bare))) is None


def test_the_benchmark_lists_both_for_the_nemotron_cell_alone():
    _, _, _, _, per_layer = harness.load_cell(NEMOTRON)
    mine = {m["name"]: m for m in per_layer if m["name"] in (AROUND, WALKED)}
    assert sorted(mine) == [AROUND, WALKED]
    assert mine[AROUND]["source"] == "device_trace"
    assert mine[WALKED]["source"] == "program_counter"
    for m in mine.values():
        assert m["layer"] == "expert kernels" and m["better"] == "lower"
        assert m["moves"] == "train_tokens_per_s"
        assert m["workloads"] == [NEMOTRON]
    for cell in ("train_mistral7b_d2_b4s512", "train_mistral7b_d3_mesh4",
                 "train_mistral7b_d2_b1s2048"):
        assert not [m for m in harness.load_cell(cell)[4]
                    if m["name"] in (AROUND, WALKED)]
