"""Tests of the reader ``bd_dkv_live_steps_pct``
(``mxnet_flash_tiles_computed_total`` over ``mxnet_flash_dkv_steps_total``),
on counter snapshots, and of the SDAR cell's rehearsal reading it.  Run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.  Nothing
here touches a chip."""
import os

import pytest

# the helpers, and the benchmark's directory on sys.path, from the siblings
from test_gmm_metrics import _run
from test_sdar_cell import CHIP, _run as _rehearse
from test_span_metrics import _reader, harness

CELL = "train_sdar_bd4_b1s4096"
SHARE = "bd_dkv_live_steps_pct"
COMPUTED = 80 * 32 * 4 * 50     # live tiles x heads, four layers, 50 steps


def _snapshot(monkeypatch, steps, computed=COMPUTED):
    from mxnet_tpu.telemetry import metrics

    families = {}
    if computed is not None:
        families["mxnet_flash_tiles_computed_total"] = {
            "series": [{"labels": {}, "value": computed}]}
    if steps is not None:
        families["mxnet_flash_dkv_steps_total"] = {
            "series": [{"labels": {}, "value": steps}]}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)


def test_the_live_visits_alone_read_100(monkeypatch):
    # 640 visits a key head, 4 key heads
    _snapshot(monkeypatch, 640 * 4 * 4 * 50)
    assert _reader(SHARE)(_run(None)) == pytest.approx(100.0)


def test_the_rectangle_reads_the_live_tiles_share(monkeypatch):
    # the composition's grid: 256 tiles a head, 32 heads
    _snapshot(monkeypatch, 256 * 32 * 4 * 50)
    assert _reader(SHARE)(_run(None)) == pytest.approx(31.25)


def test_silent_where_there_is_nothing_to_read(monkeypatch):
    """A program without the counter (the parent); one with no
    block-diffusion layer at all."""
    _snapshot(monkeypatch, None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, None, computed=None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, 0, computed=0)
    assert _reader(SHARE)(_run(None)) is None


def test_the_benchmark_lists_it_for_the_sdar_cell():
    _, _, _, _, per_layer = harness.load_cell(CELL)
    mine = [m for m in per_layer if m["name"] == SHARE]
    assert len(mine) == 1
    assert mine[0]["source"] == "program_counter"
    assert mine[0]["layer"] == "attention kernels"
    assert mine[0]["better"] == "higher" and mine[0]["unit"] == "%"
    assert mine[0]["moves"] == "train_tokens_per_s"
    assert mine[0]["workloads"] == [CELL]


def test_the_cells_traced_rehearsal_reads_it_with_the_counters():
    # the tiny rehearsal's heads of 32 channels take the composition: the
    # reader is a program counter and reads on a CPU, where no share of a
    # device is read
    doc = _rehearse(os.path.join(CHIP, "run.py"), "--workload", CELL,
                    "--seed", str(2 ** 31 + 44), "--seconds", "1", "--trace",
                    "1", "--rehearse")
    assert doc["correct"] is True
    assert {SHARE, "bd_flash_tiles_pct", "bd_kernel_layers_pct"} <= set(
        doc["metrics_read"])
    assert not [m for m in doc["metrics_read"] if "roofline" in m
                or m.startswith("mixer_ms")]
