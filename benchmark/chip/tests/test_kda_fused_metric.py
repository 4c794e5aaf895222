"""Tests of the reader PR 41 brought, ``kda_fused_layers_pct``
(``mxnet_kda_fused_layers_total`` over ``mxnet_kda_layers_total``), on
counter snapshots.  Run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/chip/tests -q``.  Nothing here touches a chip."""
import pytest

# the helpers, and the benchmark's directory on sys.path, from the siblings
from test_gmm_metrics import _run
from test_span_metrics import _reader, harness

KIMI = "train_kimilinear_p5_b1s4096"
SHARE = "kda_fused_layers_pct"
LAYERS = 50 * 4         # fifty steps, four KDA layers


def _snapshot(monkeypatch, fused, layers=LAYERS):
    from mxnet_tpu.telemetry import metrics

    families = {}
    if layers is not None:
        families["mxnet_kda_layers_total"] = {
            "series": [{"labels": {}, "value": layers}]}
    if fused is not None:
        families["mxnet_kda_fused_layers_total"] = {
            "series": [{"labels": {}, "value": fused}]}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)


def test_every_mixer_in_the_kernels_reads_100(monkeypatch):
    _snapshot(monkeypatch, LAYERS)
    assert _reader(SHARE)(_run(None)) == pytest.approx(100.0)


def test_a_share_where_the_counters_differ(monkeypatch):
    # one layer of the four at shapes that tile; and none (the composition)
    _snapshot(monkeypatch, LAYERS // 4)
    assert _reader(SHARE)(_run(None)) == pytest.approx(25.0)
    _snapshot(monkeypatch, 0)
    assert _reader(SHARE)(_run(None)) == 0.0


def test_silent_where_there_is_nothing_to_read(monkeypatch):
    """A program without the counters (the parent); one with no KDA layer
    at all."""
    _snapshot(monkeypatch, None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, None, layers=None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, 0, layers=0)
    assert _reader(SHARE)(_run(None)) is None


def test_the_benchmark_lists_it_for_the_kimi_cell_alone():
    _, _, _, _, per_layer = harness.load_cell(KIMI)
    mine = [m for m in per_layer if m["name"] == SHARE]
    assert len(mine) == 1
    assert mine[0]["source"] == "program_counter"
    assert mine[0]["layer"] == "linear-attention kernels"
    assert mine[0]["better"] == "higher" and mine[0]["unit"] == "%"
    assert mine[0]["moves"] == "train_tokens_per_s"
    assert mine[0]["workloads"] == [KIMI]
