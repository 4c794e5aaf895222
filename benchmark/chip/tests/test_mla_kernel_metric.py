"""Tests of the reader PR 39 brought, ``mla_kernel_layers_pct``
(``mxnet_mla_kernel_layers_total`` over ``mxnet_mla_layers_total``), on
counter snapshots.  Run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/chip/tests -q``.  Nothing here touches a chip."""
import pytest

# the helpers, and the benchmark's directory on sys.path, from the siblings
from test_gmm_metrics import _run
from test_span_metrics import _reader, harness

KIMI, JOYAI = "train_kimilinear_p5_b1s4096", "train_joyai_p5_b1s8192"
SHARE = "mla_kernel_layers_pct"
LAYERS = 40 * 5         # forty steps, five latent attention layers


def _snapshot(monkeypatch, kernel, layers=LAYERS):
    from mxnet_tpu.telemetry import metrics

    families = {}
    if layers is not None:
        families["mxnet_mla_layers_total"] = {
            "series": [{"labels": {}, "value": layers}]}
    if kernel is not None:
        families["mxnet_mla_kernel_layers_total"] = {
            "series": [{"labels": {}, "value": kernel}]}
    monkeypatch.setattr(metrics, "snapshot", lambda: families)


def test_every_layer_read_in_place_reads_100(monkeypatch):
    _snapshot(monkeypatch, LAYERS)
    assert _reader(SHARE)(_run(None)) == pytest.approx(100.0)


def test_a_share_where_the_counters_differ(monkeypatch):
    # one layer of the five at shapes that tile; and none (the composition)
    _snapshot(monkeypatch, LAYERS // 5)
    assert _reader(SHARE)(_run(None)) == pytest.approx(20.0)
    _snapshot(monkeypatch, 0)
    assert _reader(SHARE)(_run(None)) == 0.0


def test_silent_where_there_is_nothing_to_read(monkeypatch):
    """A program without the counter (the parent); one with no latent
    attention layer at all."""
    _snapshot(monkeypatch, None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, None, layers=None)
    assert _reader(SHARE)(_run(None)) is None
    _snapshot(monkeypatch, 0, layers=0)
    assert _reader(SHARE)(_run(None)) is None


@pytest.mark.parametrize("cell", [KIMI, JOYAI])
def test_the_benchmark_lists_it_for_the_two_latent_attention_cells(cell):
    _, _, _, _, per_layer = harness.load_cell(cell)
    mine = [m for m in per_layer if m["name"] == SHARE]
    assert len(mine) == 1
    assert mine[0]["source"] == "program_counter"
    assert mine[0]["layer"] == "model blocks"
    assert mine[0]["better"] == "higher" and mine[0]["unit"] == "%"
    assert mine[0]["moves"] == "train_tokens_per_s"
    assert mine[0]["workloads"] == [KIMI, JOYAI]
