"""Set-up seen from inside the program: ``compile_cache``'s ledger of
programs built, ``profiler.setup_span`` and the two stages of
``JitTrainStep``'s own start.

The times here are a CPU's: they show that every build is one entry with its
stages apart, filed under the stage that built it; they give no time worth
reading.
"""
import collections
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import compile_cache, profiler
from mxnet_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmark", "chip")
sys.path.insert(0, CHIP)

import trace_reduce  # noqa: E402
from test_spans import _batch, _step, _traced  # noqa: E402

INIT, BUILD = "train_step.init", "train_step.build"


def _closed():
    """How many entries the ledger has closed so far."""
    return compile_cache._built["closed"]


def _since(seq):
    return [e for e in compile_cache.programs() if e["seq"] >= seq]


def _stage_seconds(stage):
    fam = metrics.snapshot().get("mxnet_setup_seconds_total", {})
    return sum(s["value"] for s in fam.get("series", [])
               if s["labels"]["stage"] == stage)


# -- the ledger ------------------------------------------------------------------

def test_a_nested_jit_is_one_entry_and_no_moment_of_its_trace_counts_twice():
    @jax.jit
    def ledger_inner(x):
        time.sleep(0.05)
        return x * 2 + 1

    @jax.jit
    def ledger_outer(x):
        time.sleep(0.05)
        return ledger_inner(x).sum()

    x = jnp.arange(11.0)
    x.block_until_ready()
    seq = _closed()
    ledger_outer(x).block_until_ready()
    (entry,) = _since(seq)
    assert entry["name"] == "jit(ledger_outer)"
    # jax reports the inner trace (0.05 s) and the outer one that holds it
    # (0.1 s): their sum would read 0.15
    assert 0.1 <= entry["trace_s"] < 0.145
    assert entry["lower_s"] > 0 and entry["backend_s"] > 0
    assert entry["under"] is None and entry["retrieval_s"] is None
    assert abs(entry["t_end_ns"] - time.time_ns()) < 5e9
    assert entry["requests"] == compile_cache.stats()["requests"]
    ledger_outer(x).block_until_ready()        # a call that builds nothing
    assert _closed() == seq + 1


_PROBE = r"""
import json
import jax
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import compile_cache

at_import = len(compile_cache.programs())

@jax.jit
def probe(x):
    return jnp.tanh(x) * 3 + 1

probe(jnp.arange(7.0)).block_until_ready()
stats = compile_cache.stats()
ledger = compile_cache.programs()
print("RESULT=" + json.dumps({
    "at_import": at_import, "stats": stats, "last": ledger[-1],
    "probe": [e for e in ledger if e["name"] == "jit(probe)"],
    "seqs": [e["seq"] for e in ledger],
    "report": compile_cache.report()}))
"""


@pytest.fixture(scope="module")
def three_processes(tmp_path_factory):
    """The same program built by a cold process, a warm one (they share a
    cache directory) and one with the cache disabled."""
    cache = str(tmp_path_factory.mktemp("ledger_cache"))

    def run(**extra):
        env = dict(os.environ)
        env.update({"MXNET_COMPILE_CACHE": "1",
                    "MXNET_COMPILE_CACHE_DIR": cache,
                    "MXNET_COMPILE_CACHE_MIN_SECS": "0",
                    "JAX_PLATFORMS": "cpu"})
        env.update(extra)
        r = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        return json.loads(r.stdout.rsplit("RESULT=", 1)[1])

    cold, warm = run(), run()
    off = run(MXNET_COMPILE_CACHE="0", JAX_COMPILATION_CACHE_DIR=cache)
    return {"cold": cold, "warm": warm, "off": off}


def test_a_cold_process_files_a_miss_that_was_written(three_processes):
    (entry,) = three_processes["cold"]["probe"]
    assert entry["cache"] == "miss" and entry["written"] is True
    assert entry["retrieval_s"] is None


def test_a_warm_process_files_a_hit_with_its_retrieval(three_processes):
    (entry,) = three_processes["warm"]["probe"]
    assert entry["cache"] == "hit" and entry["written"] is False
    assert 0 < entry["retrieval_s"] <= entry["backend_s"]
    stats = three_processes["warm"]["stats"]
    assert stats["hits"] == stats["requests"] > 0


def test_a_process_with_the_cache_disabled_files_off(three_processes):
    doc = three_processes["off"]
    (entry,) = doc["probe"]
    assert entry["cache"] == "off" and entry["retrieval_s"] is None
    assert entry["requests"] == 0 == doc["stats"]["requests"]


@pytest.mark.parametrize("which", ["cold", "warm", "off"])
def test_an_entry_carries_the_request_count_of_its_moment(three_processes,
                                                          which):
    """``stats()`` taken right after a build cuts the ledger at that build;
    the import itself builds nothing (so no program hides in
    ``setup.import``); ``report()`` ends in a line of totals."""
    doc = three_processes[which]
    assert doc["at_import"] == 0
    assert doc["last"]["requests"] == doc["stats"]["requests"]
    assert doc["seqs"] == list(range(len(doc["seqs"])))
    lines = doc["report"].splitlines()
    assert lines[0].split()[:4] == ["seq", "name", "under", "cache"]
    assert len(lines) == len(doc["seqs"]) + 2
    assert lines[-1].startswith("total: %d programs" % len(doc["seqs"]))
    assert "0 older entries fell out" in lines[-1]
    totals = [float(l.split()[-1]) for l in lines[1:-1]]
    assert totals == sorted(totals, reverse=True)


def test_the_list_is_bounded_and_counts_what_fell_out(monkeypatch):
    monkeypatch.setattr(compile_cache, "_programs",
                        collections.deque(maxlen=3))
    x = jnp.arange(13.0)
    x.block_until_ready()
    seq = _closed()
    for k in range(5):
        jax.jit(lambda x, k=k: x * (k + 2.5))(x).block_until_ready()
    kept = compile_cache.programs()
    assert [e["seq"] for e in kept] == [seq + 2, seq + 3, seq + 4]
    assert _closed() == seq + 5
    assert compile_cache.report().splitlines()[-1].endswith(
        "%d older entries fell out" % (seq + 2))


def test_two_threads_building_at_once_keep_their_pending_times_apart():
    a_has_traced, b_done = threading.Event(), threading.Event()

    @jax.jit
    def slow_inner(x):
        time.sleep(0.1)
        return x + 1

    @jax.jit
    def slow_outer(x):
        y = slow_inner(x)           # its trace event is pending on thread A
        a_has_traced.set()
        assert b_done.wait(60)      # B builds and closes in the meantime
        time.sleep(0.1)
        return y * 2

    @jax.jit
    def quick(x):
        return x - 3

    x = jnp.arange(17.0)
    x.block_until_ready()
    seq, errors = _closed(), []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:      # noqa: BLE001 (asserted below)
                errors.append(e)
                a_has_traced.set()
                b_done.set()
        return run

    def build_quick():
        assert a_has_traced.wait(60)
        quick(x).block_until_ready()
        b_done.set()

    threads = [threading.Thread(target=guarded(
        lambda: slow_outer(x).block_until_ready())),
        threading.Thread(target=guarded(build_quick))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    by_name = {e["name"]: e for e in _since(seq)}
    assert set(by_name) == {"jit(quick)", "jit(slow_outer)"}
    assert by_name["jit(quick)"]["trace_s"] < 0.09
    assert by_name["jit(slow_outer)"]["trace_s"] >= 0.2
    assert by_name["jit(quick)"]["seq"] < by_name["jit(slow_outer)"]["seq"]


def test_the_collector_exports_stage_seconds_and_counts():
    jax.jit(lambda x: x / 7.5)(jnp.arange(19.0)).block_until_ready()
    snap = metrics.snapshot()
    seconds = {s["labels"]["stage"]: s["value"] for s in
               snap["mxnet_program_build_seconds_total"]["series"]}
    built = {s["labels"]["cache"]: s["value"] for s in
             snap["mxnet_programs_built_total"]["series"]}
    assert set(seconds) == {"trace", "lower", "load", "compile"}
    assert set(built) == {"hit", "miss", "off"}
    assert sum(built.values()) == _closed() > 0
    assert seconds["lower"] > 0 and seconds["compile"] > 0
    assert seconds["load"] == 0         # this process has no cache to hit


# -- the span primitive ----------------------------------------------------------

def test_a_setup_span_names_the_stage_and_adds_its_seconds_once():
    before = _stage_seconds("unit.outer"), _stage_seconds("unit.inner")
    assert profiler.setup_stage() is None
    with profiler.setup_span("unit.outer"):
        assert profiler.setup_stage() == "unit.outer"
        with profiler.setup_span("unit.inner"):
            assert profiler.setup_stage() == "unit.inner"
            seq = _closed()
            jax.jit(lambda x: x * 0.125)(jnp.arange(23.0)).block_until_ready()
            time.sleep(0.02)
        assert profiler.setup_stage() == "unit.outer"
    assert profiler.setup_stage() is None
    assert {e["under"] for e in _since(seq)} == {"unit.inner"}
    outer = _stage_seconds("unit.outer") - before[0]
    inner = _stage_seconds("unit.inner") - before[1]
    assert 0.02 <= inner <= outer < 30
    # other threads see no stage of this one
    seen = []
    with profiler.setup_span("unit.outer"):
        t = threading.Thread(target=lambda: seen.append(
            profiler.setup_stage()))
        t.start()
        t.join(30)
    assert seen == [None]


def test_an_exception_inside_a_setup_span_closes_it():
    before = _stage_seconds("unit.broken")
    with pytest.raises(RuntimeError):
        with profiler.setup_span("unit.broken"):
            raise RuntimeError("boom")
    assert profiler.setup_stage() is None
    assert _stage_seconds("unit.broken") > before


def test_a_setup_span_under_mx_profiler_is_one_chrome_event():
    profiler.set_state("run")
    try:
        with profiler.setup_span("unit.recorded"):
            pass
    finally:
        profiler.set_state("stop")
    events = [e for e in profiler.get_trace()["traceEvents"]
              if e["name"] == "mx:unit.recorded"]
    profiler.dumps(reset=True)
    assert len(events) == 1 and events[0]["cat"] == "span"


# -- the sites in JitTrainStep ------------------------------------------------------

def _inside(inner, outer):
    return outer[1] <= inner[1] and \
        inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.mark.parametrize("mesh", [None, {"data": 2, "model": 2}],
                         ids=["one_device", "mesh_2x2"])
def test_the_first_step_is_two_stages_and_the_later_steps_add_nothing(
        tmp_path, mesh):
    step = _step(mesh)
    x, y = _batch()
    seconds = {s: _stage_seconds(s) for s in (INIT, BUILD)}
    seq, after_first = _closed(), {}

    def work():
        float(step.step(x, y))
        after_first.update(closed=_closed(), init=_stage_seconds(INIT),
                           build=_stage_seconds(BUILD))
        for _ in range(4):
            float(step.step(x, y))
    trace = _traced(tmp_path, work)

    assert after_first["init"] > seconds[INIT]
    assert after_first["build"] > seconds[BUILD]
    # steps 2-5: no program built, no second added to either stage
    assert _closed() == after_first["closed"]
    assert _stage_seconds(INIT) == after_first["init"]
    assert _stage_seconds(BUILD) == after_first["build"]

    entries = _since(seq)
    (own,) = [e for e in entries if e["under"] == BUILD]
    assert own["name"] == "jit(step)"
    assert own == max(entries, key=lambda e: e["trace_s"])
    assert {e["under"] for e in entries} <= {None, INIT, BUILD}
    built = after_first["build"] - seconds[BUILD]
    assert own["trace_s"] + own["lower_s"] + own["backend_s"] <= built

    marks = trace_reduce.marks(trace, "mx:")
    (init,) = [m for m in marks if m[0] == "mx:" + INIT]
    (build,) = [m for m in marks if m[0] == "mx:" + BUILD]
    place = [m for m in marks if m[0] == "mx:train_step.place_batch"]
    call = [m for m in marks if m[0] == "mx:train_step.call"]
    assert len(place) == len(call) == 5
    assert _inside(init, place[0]) and _inside(build, call[0])
    assert init[1] + init[2] <= build[1]


def test_step_n_first_builds_its_loop_under_the_same_stage():
    step = _step()
    x, y = _batch()
    seq, before = _closed(), _stage_seconds(BUILD)
    float(step.step_n(2, x, y))
    after = _closed(), _stage_seconds(BUILD)
    assert [e["name"] for e in _since(seq) if e["under"] == BUILD] \
        == ["jit(loop)"]
    assert after[1] > before
    float(step.step_n(2, x, y))
    float(step.step(x, y))          # the single step: built, but not a first
    assert _stage_seconds(BUILD) == after[1]
    assert [e["under"] for e in _since(after[0])
            if e["name"] == "jit(step)"] == [None]
