"""Tests of the reader PR 37 brought, ``ssd_kernel_chunks_pct``
(``mxnet_ssd_kernel_chunks_total`` over ``mxnet_ssd_chunks_total``), on
counter snapshots.  Run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/chip/tests -q``.  Nothing here touches a chip."""
import pytest

# the helpers, and the benchmark's directory on sys.path, from the siblings
from test_gmm_metrics import _run
from test_span_metrics import _reader, harness

NEMOTRON = "train_nemotronh_p7_b2s2048"
SHARE = "ssd_kernel_chunks_pct"
CHUNKS = 5 * 3 * 2 * 64 * 16    # five steps, three layers, 2 x 64 heads, 16


def _snapshot(monkeypatch, kernel, chunks=CHUNKS):
    from mxnet_tpu.telemetry import metrics

    families = {}
    if chunks is not None:
        families["mxnet_ssd_chunks_total"] = {
            "series": [{"labels": {}, "value": chunks}]}
    if kernel is not None:
        families["mxnet_ssd_kernel_chunks_total"] = {
            "series": [{"labels": {}, "value": kernel}]}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)


def test_every_chunk_in_the_kernels_reads_100(monkeypatch):
    _snapshot(monkeypatch, CHUNKS)
    assert _reader(SHARE)(_run(None)) == pytest.approx(100.0)


def test_a_share_where_the_counters_differ(monkeypatch):
    # one layer of the three at shapes that tile; and none
    _snapshot(monkeypatch, CHUNKS // 3)
    assert _reader(SHARE)(_run(None)) == pytest.approx(100.0 / 3)
    _snapshot(monkeypatch, 0)
    assert _reader(SHARE)(_run(None)) == 0.0


def test_silent_where_there_is_nothing_to_read(monkeypatch):
    """A program without the new counter (the parent); one that scans
    nothing at all."""
    _snapshot(monkeypatch, None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, None, chunks=None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, 0, chunks=0)
    assert _reader(SHARE)(_run(None)) is None


def test_the_benchmark_lists_it_for_the_nemotron_cell_alone():
    _, _, _, _, per_layer = harness.load_cell(NEMOTRON)
    mine = [m for m in per_layer if m["name"] == SHARE]
    assert len(mine) == 1
    assert mine[0]["source"] == "program_counter"
    assert mine[0]["layer"] == "state-space kernels"
    assert mine[0]["better"] == "higher" and mine[0]["unit"] == "%"
    assert mine[0]["moves"] == "train_tokens_per_s"
    assert mine[0]["workloads"] == [NEMOTRON]
