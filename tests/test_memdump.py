"""Device-memory accounting: origin attribution, peak watermark,
reconciliation and the OOM interceptor (docs/observability.md
"Device-memory accounting")."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.telemetry import flight, memdump


@pytest.fixture(autouse=True)
def _fresh():
    memdump.reset()
    flight.reset()
    yield
    memdump.reset()
    flight.reset()


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_host_upload_tags_as_temp_by_default():
    x = nd.array(np.ones((64, 64), dtype=np.float32))
    by, total = memdump.refresh()
    assert total > 0
    assert by["temp"] >= x.data().nbytes


def test_origin_scope_attributes_uploads():
    with memdump.origin("activation"):
        a = nd.array(np.ones((32, 32), dtype=np.float32))
    by = memdump.device_bytes()
    assert by["activation"] >= a.data().nbytes
    top = memdump.topk()
    acts = [r for r in top if r["origin"] == "activation"]
    assert acts and acts[0]["nbytes"] == a.data().nbytes
    assert acts[0]["flight_seq"] >= 0  # tag left a mem.tag flight event
    assert any(e["kind"] == "mem.tag" and e["origin"] == "activation"
               for e in flight.events(kind="mem"))


def test_parameter_init_tags_as_param():
    from mxnet_tpu import gluon

    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    nd.waitall()
    by = memdump.device_bytes()
    assert by["param"] > 0
    labels = {r["label"] for r in memdump.topk() if r["origin"] == "param"}
    assert any("weight" in lb for lb in labels)


def test_attach_grad_tags_grad_buffer():
    x = nd.ones((8, 8))
    x.attach_grad()
    with autograd.record():
        y = (x * 2).sum()
    y.backward()
    by = memdump.device_bytes()
    assert by["grad"] >= x.data().nbytes


def test_kv_arena_tags_kv_pages():
    from test_serve import tiny_geometry
    from mxnet_tpu.serve import PagedKVArena

    arena = PagedKVArena(tiny_geometry())
    by = memdump.device_bytes()
    import jax

    expect = sum(b.nbytes for b in jax.tree_util.tree_leaves(arena.buffers()))
    assert by["kv_page"] >= expect


# ---------------------------------------------------------------------------
# watermark + gauges + reconciliation
# ---------------------------------------------------------------------------

def test_peak_watermark_is_monotonic():
    _, t0 = memdump.refresh()
    assert memdump.peak_bytes() >= t0
    big = nd.array(np.zeros((256, 256), dtype=np.float32))
    _, t1 = memdump.refresh()
    peak = memdump.peak_bytes()
    assert peak >= t1 > t0
    del big
    memdump.refresh()
    assert memdump.peak_bytes() >= peak  # never goes down


def test_refresh_publishes_gauges_via_snapshot():
    nd.array(np.ones((16, 16), dtype=np.float32))
    snap = telemetry.snapshot()  # collector runs memdump.refresh()
    fam = snap["mxnet_device_bytes"]
    origins = {s["labels"]["origin"] for s in fam["series"]}
    assert {"param", "temp", "grad", "kv_page", "activation"} <= origins
    assert snap["mxnet_device_peak_bytes"]["series"][0]["value"] > 0


def test_reconcile_reports_engine_cross_check():
    x = nd.ones((4, 4)) * 2
    x.asnumpy()
    rec = memdump.reconcile()
    for key in ("live_bytes", "live_by_origin", "live_tagged",
                "live_untagged", "finalized_frees", "finalized_bytes",
                "engine_donated", "engine_ops_pushed"):
        assert key in rec
    assert rec["live_bytes"] > 0
    assert rec["engine_ops_pushed"] > 0


def test_freed_buffers_leave_the_live_set():
    x = nd.array(np.ones((128, 128), dtype=np.float32))
    nbytes = x.data().nbytes
    _, before = memdump.refresh()
    del x
    _, after = memdump.refresh()
    assert after <= before - nbytes + 1  # the upload actually freed


# ---------------------------------------------------------------------------
# OOM interception
# ---------------------------------------------------------------------------

def test_is_oom_matches_backend_markers():
    assert memdump.is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"))
    assert memdump.is_oom(MemoryError("Allocator ran out of memory"))
    assert not memdump.is_oom(ValueError("shapes do not match"))


def test_oom_report_writes_attribution_json(tmp_path, monkeypatch,
                                            capsys):
    monkeypatch.setenv("MXNET_MEMDUMP_PATH", str(tmp_path / "oom.json"))
    with memdump.origin("activation"):
        keep = nd.array(np.ones((64, 64), dtype=np.float32))
    err = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    assert memdump.maybe_oom_report(err) is True
    assert keep is not None  # the buffer must be live at report time
    doc = json.load(open(tmp_path / "oom.json"))
    assert "RESOURCE_EXHAUSTED" in doc["error"]
    assert doc["total_bytes"] > 0
    assert doc["by_origin"]["activation"] > 0
    assert doc["topk"] and "flight_seq" in doc["topk"][0]
    assert "device OOM" in capsys.readouterr().err
    # the interceptor left a flight event for timeline correlation
    assert any(e["kind"] == "mem.oom" for e in flight.events(kind="mem"))


def test_non_oom_errors_do_not_report(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_MEMDUMP_PATH", str(tmp_path / "no.json"))
    assert memdump.maybe_oom_report(ValueError("not memory")) is False
    assert not (tmp_path / "no.json").exists()


def test_engine_push_failure_routes_through_oom_check():
    # a non-OOM op failure must NOT produce a mem.oom event
    a = nd.ones((2, 3))
    b = nd.ones((4, 5))
    with pytest.raises(Exception):
        (a + b).asnumpy()
    assert not any(e["kind"] == "mem.oom"
                   for e in flight.events(kind="mem"))
