"""Tests of ``span_reduce.py`` and the readers built on it (PR 27).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/chip/tests -q``.
Nothing here touches a chip.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import run as harness  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

PHASES = span_reduce.PHASES
READERS = ["step_host_ms_p50." + p for p in PHASES] \
    + ["step_idle_ms." + p for p in PHASES] + ["programs_per_step"]
MS = 1_000_000


def _reader(name):
    return harness._module("metrics", name).read


def _run(trace, window):
    return {"trace": trace, "trace_window": window}


def _synthetic():
    """Three steps in a 300 ms window.  Host: each ``mx:train_step`` lasts
    90 ms and holds place_batch 2, scalars 50/60/70, call 10, tag 4 ms.
    First device: idle 120..125 ms (under step 1's ``scalars``), 205..207
    (between steps) and 285..286 (under step 2's ``call``); five programs
    a step.  A second device never idles; a fourth step begins after the
    window."""
    host, mods = [("bench:window", 0, 300 * MS)], []
    for k, scalars in enumerate((50, 60, 70, 60)):
        s = (10 + 100 * k) * MS
        host += [("mx:train_step", s, 90 * MS),
                 ("mx:train_step.place_batch", s, 2 * MS),
                 ("mx:train_step.scalars", s + 3 * MS, scalars * MS),
                 ("mx:train_step.call", s + 74 * MS, 10 * MS),
                 ("mx:train_step.tag", s + 85 * MS, 4 * MS),
                 ("bench:step.dispatch", s - 1000, 90 * MS + 2000)]
        base = 100 * k * MS
        mods += [("jit__threefry_split(1)", base, 3000),
                 ("jit__unstack(2)", base + 4000, 900),
                 ("jit_convert_element_type(3)", base + 5000, 600),
                 ("jit_convert_element_type(3)", base + 6000, 600),
                 ("jit_step(4)", base + 7000, 99 * MS)]
    ops = [("fusion.1", 0, 120 * MS), ("fusion.2", 125 * MS, 80 * MS),
           ("fusion.3", 207 * MS, 78 * MS), ("fusion.4", 286 * MS, 30 * MS)]
    dev = lambda n, ops, mods: {"name": "/device:TPU:%d" % n, "lines": [  # noqa
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    return {"planes": [dev(0, ops, mods),
                       dev(1, [("fusion.1", 0, 320 * MS)], []),
                       {"name": "/host:CPU",
                        "lines": [{"name": "python3", "events": host}]}]}


def test_span_reductions_on_a_written_trace(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(_synthetic(), path)
    trace = trace_reduce.load(path)             # through ProfileData
    window = trace_reduce.window_of(trace)
    assert window == (0, 300 * MS)
    steps = span_reduce.steps(trace, *window)
    assert len(steps) == 3                       # the fourth begins later
    assert len(span_reduce.steps(trace)) == 4
    for st in steps:
        assert [p[0] for p in st["phases"]] == list(PHASES)
    assert span_reduce.coverage(steps[1]) == pytest.approx(76 / 90)
    want = {"place_batch": 2.0, "scalars": 60.0, "call": 10.0, "tag": 4.0}
    for phase, ms in want.items():
        assert span_reduce.phase_ms_p50(trace, window, phase) == \
            pytest.approx(ms)
    idle = span_reduce.idle_by_span(trace, *window)
    assert idle == {"train_step.scalars": pytest.approx(5e-3),
                    "host (unattributed)": pytest.approx(2e-3),
                    "train_step.call": pytest.approx(1e-3)}
    # what the harness puts under step.dispatch, the phases account for
    gaps = dict(trace_reduce.idle_gaps(trace, 10, *window))
    assert gaps["step.dispatch"] == pytest.approx(
        idle["train_step.scalars"] + idle["train_step.call"])
    run = _run(trace, window)
    got = {name: _reader(name)(run) for name in READERS}
    assert got == {
        "step_host_ms_p50.place_batch": pytest.approx(2.0),
        "step_host_ms_p50.scalars": pytest.approx(60.0),
        "step_host_ms_p50.call": pytest.approx(10.0),
        "step_host_ms_p50.tag": pytest.approx(4.0),
        "step_idle_ms.place_batch": 0.0,
        "step_idle_ms.scalars": pytest.approx(5.0 / 3),
        "step_idle_ms.call": pytest.approx(1.0 / 3),
        "step_idle_ms.tag": 0.0,
        "programs_per_step": 5.0}


def test_span_reductions_on_the_recorded_fixture():
    """A quarter second cut from a traced chip run of
    train_mistral7b_d2_b4s512 with this PR's spans in the program; the
    numbers asserted are that cut's own (``fixtures/recorded_spans.json``
    says where it is from)."""
    want = json.load(open(os.path.join(
        CHIP, "fixtures", "recorded_spans.json")))
    trace = trace_reduce.load(os.path.join(
        CHIP, "fixtures", "recorded_spans.xplane.pb"))
    window = tuple(want["window_ns"])
    steps = span_reduce.steps(trace, *window)
    assert len(steps) == want["steps"]
    for st in steps:
        assert [p[0] for p in st["phases"]] == list(PHASES)
        assert span_reduce.coverage(st) >= 0.95
    run = _run(trace, window)
    for name, value in want["readers"].items():
        assert _reader(name)(run) == pytest.approx(value, rel=1e-6), name
    # the three flash kernels, told apart by the names the program gave
    ops = [n for n, _, _ in trace_reduce.device_ops(trace)["/device:TPU:0"]]
    for kernel in want["kernels"]:
        mine = [n for n in ops if n.split(".")[0] == kernel]
        assert mine, kernel
        assert all('custom_call_target="tpu_custom_call"'
                   in trace["detail"][n] for n in mine)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_where_there_is_nothing_to_read(name):
    """A run that was not traced; a chip trace of a program without ``mx:``
    spans (PR 26's fixture); a trace with spans and no device operation."""
    read = _reader(name)
    assert read({"trace": None}) is None
    old = trace_reduce.load(os.path.join(CHIP, "fixtures",
                                         "recorded.xplane.pb"))
    assert trace_reduce.device_ops(old)
    assert read(_run(old, trace_reduce.window_of(old))) is None
    cpu = _synthetic()
    cpu["planes"] = cpu["planes"][2:]
    assert read(_run(cpu, (0, 300 * MS))) is None
