"""Tests of ``setup_reduce.py`` and the nine readers built on it (PR 38).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.
Nothing here touches a chip.
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

import run as harness  # noqa: E402
import setup_reduce  # noqa: E402

PARTS = ["setup_part_s." + p for p in (
    "import", "step_init", "step_trace", "step_lower", "step_backend",
    "other_programs", "outside_program")]
READERS = PARTS + ["setup_programs_built", "cache_misses_setup"]


def _reader(name):
    return harness._module("metrics", name).read


def _entry(seq, requests, name, under, times, cache="hit"):
    trace_s, lower_s, backend_s = times
    return {"seq": seq, "requests": requests, "name": name, "under": under,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache": cache, "written": False, "retrieval_s": None,
            "t_end_ns": 0}


def _ledger():
    """A set-up of eight programs and three of the reference after it.  The
    step (seq 5) is the costliest entry under ``train_step.build``; seq 6
    was built beside it under the same stage; seq 2-3 under
    ``train_step.init``; seq 4 made no request of the cache."""
    init, build = setup_reduce.INIT, setup_reduce.BUILD
    return [
        _entry(0, 1, "jit(_uniform)", None, (0.01, 0.04, 0.25)),
        _entry(1, 2, "jit(convert_element_type)", None, (0.0, 0.01, 0.04)),
        _entry(2, 3, "jit(copy)", init, (0.0, 0.01, 0.09)),
        _entry(3, 4, "jit(zeros_like)", init, (0.0, 0.01, 0.04), "miss"),
        _entry(4, 4, "jit(callback)", None, (0.0, 0.02, 0.08), "off"),
        _entry(5, 5, "jit(step)", build, (2.0, 0.5, 1.5)),
        _entry(6, 6, "jit(_threefry_split)", build, (0.0, 0.01, 0.02)),
        _entry(7, 7, "jit(norms)", None, (0.02, 0.03, 0.15)),
        # after the window's start: the reference's, never set-up's
        _entry(8, 8, "jit(reference_step)", None, (3.0, 1.0, 9.0), "miss"),
        _entry(9, 8, "jit(callback)", None, (0.0, 0.0, 0.5), "off"),
        _entry(10, 9, "jit(step)", build, (9.0, 9.0, 9.0)),
    ]


def _families(stages):
    return {"mxnet_setup_seconds_total": {"type": "counter", "series": [
        {"labels": {"stage": k}, "value": v} for k, v in stages.items()]}}


STAGES = {"setup.import": 1.25, "train_step.init": 0.75,
          "train_step.build": 4.5}


AT_WINDOW = {"requests": 7, "hits": 6, "misses": 1}


def test_the_reduction_cuts_at_the_window_and_counts_nothing_twice():
    got = setup_reduce.reduce(
        _ledger(), _families(STAGES), AT_WINDOW, 20.0)
    assert got["setup_part_s.import"] == 1.25
    assert got["setup_part_s.step_init"] == 0.75
    # the costliest set-up entry under the build stage, not the later one
    assert (got["setup_part_s.step_trace"], got["setup_part_s.step_lower"],
            got["setup_part_s.step_backend"]) == (2.0, 0.5, 1.5)
    # seq 0, 1, 4, 7: under neither stage.  seq 2-3 lie inside step_init and
    # seq 6 inside the build stage: neither is counted a second time
    assert got["setup_part_s.other_programs"] == pytest.approx(
        0.30 + 0.05 + 0.10 + 0.20)
    assert got["setup_part_s.outside_program"] == pytest.approx(
        20.0 - 1.25 - 0.75 - 4.0 - 0.65)
    assert sum(got[p] for p in PARTS) == pytest.approx(20.0)
    assert got["setup_programs_built"] == 8
    # hits + misses + the entries that made no request
    assert got["setup_programs_built"] == \
        AT_WINDOW["hits"] + AT_WINDOW["misses"] + 1
    assert got["cache_misses_setup"] == 1


def test_a_step_that_was_never_built_leaves_its_three_parts_out():
    """A step loaded from an AOT bundle has no entry under the stage."""
    ledger = [e for e in _ledger() if e["under"] != setup_reduce.BUILD]
    got = setup_reduce.reduce(ledger, _families({"setup.import": 1.0}),
                              AT_WINDOW, 10.0)
    for part in ("step_trace", "step_lower", "step_backend", "step_init"):
        assert got["setup_part_s." + part] is None
    assert got["setup_part_s.outside_program"] == pytest.approx(
        10.0 - 1.0 - 0.65)


@pytest.fixture
def program(monkeypatch):
    """The program's two sources, replaced by the hand-made ones."""
    from mxnet_tpu import compile_cache
    from mxnet_tpu.telemetry import metrics

    monkeypatch.setattr(compile_cache, "programs", _ledger, raising=False)
    monkeypatch.setattr(metrics, "snapshot", lambda: _families(STAGES))
    return compile_cache


def test_every_reader_through_the_harness(program):
    run = {"cache_at_window": dict(AT_WINDOW), "setup_s": 20.0}
    got = {name: _reader(name)(run) for name in READERS}
    assert got == setup_reduce.reduce(
        _ledger(), _families(STAGES), AT_WINDOW, 20.0)
    assert all(v is not None for v in got.values())
    assert abs(sum(got[p] for p in PARTS) - run["setup_s"]) < 1e-9
    # one reduction for the nine
    program.programs = None
    assert _reader("setup_programs_built")(run) == 8


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_for_a_program_without_the_ledger(
        program, monkeypatch, name):
    """The parent, and any earlier tree: no ``compile_cache.programs``; and
    a run whose harness took no snapshot of the cache."""
    assert _reader(name)({"setup_s": 20.0}) is None
    monkeypatch.delattr(program, "programs")
    run = {"cache_at_window": dict(AT_WINDOW), "setup_s": 20.0}
    assert _reader(name)(run) is None


def test_the_benchmark_lists_the_nine_for_every_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == [
        "setup_part_s.import", "setup_part_s.step_init",
        "setup_part_s.step_trace", "setup_part_s.step_lower",
        "setup_part_s.step_backend", "setup_part_s.other_programs",
        "setup_programs_built", "cache_misses_setup",
        "setup_part_s.outside_program"]
    for m in mine:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["layer"] in ("compile cache", "train step")
        assert "workloads" not in m


def test_a_traced_rehearsal_of_the_d2_cell_reads_the_nine():
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "train_mistral7b_d2_b4s512", "--seed", "3800000001", "--seconds",
         "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(READERS) <= set(line["metrics_read"])
