"""``profiler.span``: the program's own spans on the device trace's clock.

One primitive (a ``jax.profiler.TraceAnnotation`` named ``mx:<name>``),
the sites in ``JitTrainStep.step``/``step_n``, and the names on the Pallas
kernels.  The traces here are CPU traces: they show that the spans are
there, nested and in order; they give no time worth reading.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler
from mxnet_tpu.gluon import nn

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
sys.path.insert(0, CHIP)

import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

PHASES = list(span_reduce.PHASES)


def _step(mesh=None):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01},
        mesh=parallel.make_mesh(mesh) if mesh else None)


def _batch():
    rng = np.random.RandomState(0)
    return (rng.rand(8, 8).astype("float32"),
            rng.randint(0, 4, 8).astype("float32"))


def _traced(tmp_path, fn):
    """``fn()`` inside a ``jax.profiler`` trace, as plain data."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))


# -- the primitive ---------------------------------------------------------------

def test_a_span_outside_any_trace_stores_nothing():
    assert not profiler._state["running"]
    state, n = dict(profiler._state), len(profiler._events)
    with profiler.span("idle") as sp:
        assert sp._start is None
    assert profiler._state == state and len(profiler._events) == n


def _recorded(fn):
    profiler.set_state("run")
    try:
        fn()
    finally:
        profiler.set_state("stop")
    events = profiler.get_trace()["traceEvents"]
    profiler.dumps(reset=True)
    return events


def test_a_span_under_mx_profiler_is_one_chrome_event():
    def work():
        with profiler.span("unit.work"):
            pass
    events = [e for e in _recorded(work) if e["name"] == "mx:unit.work"]
    assert len(events) == 1
    ev = events[0]
    assert ev["ph"] == "X" and ev["cat"] == "span"
    assert ev["ts"] >= 0 and ev["dur"] >= 0


def test_task_start_stop_goes_through_the_same_primitive(tmp_path):
    task = profiler.Domain("dom").new_task("epoch")

    def work():
        task.start()
        assert isinstance(task._open, profiler._Annotation)
        task.stop()
        task.stop()             # a second stop is a no-op
    got = []
    trace = _traced(tmp_path, lambda: got.extend(_recorded(work)))
    mine = [e for e in got if e["name"] == "epoch"]
    assert len(mine) == 1 and mine[0]["cat"] == "dom"
    assert mine[0]["ph"] == "X" and mine[0]["dur"] >= 0
    # and the old API lands in the device trace too
    assert [m[0] for m in trace_reduce.marks(trace, "mx:")] == ["mx:epoch"]


def test_an_exception_inside_a_phase_closes_the_span(tmp_path):
    step = _step()
    x, y = _batch()
    step.step(x, y)
    boom = RuntimeError("boom")

    def broken(*a, **k):
        raise boom

    def work():
        step._step_fn, fn = broken, step._step_fn
        with pytest.raises(RuntimeError):
            step.step(x, y)
        step._step_fn = fn
        step.step(x, y)
    trace = _traced(tmp_path, work)
    failed, ok = span_reduce.steps(trace)
    # the failed call's spans are closed and nested like any other's;
    # it never reached .tag
    assert [p[0] for p in failed["phases"]] == PHASES[:3]
    assert [p[0] for p in ok["phases"]] == PHASES
    assert failed["start"] + failed["duration"] <= ok["start"]


# -- the sites in JitTrainStep ------------------------------------------------------

@pytest.mark.parametrize("mesh", [None, {"data": 2, "model": 2}],
                         ids=["one_device", "mesh_2x2"])
def test_three_steps_give_three_spans_of_four_phases(tmp_path, mesh):
    step = _step(mesh)
    x, y = _batch()
    float(step.step(x, y))       # compiled outside the trace

    def work():
        for _ in range(3):
            step.step(x, y)
        float(step._last_loss)
    trace = _traced(tmp_path, work)
    steps = span_reduce.steps(trace)
    assert len(steps) == 3
    for st in steps:
        assert [p[0] for p in st["phases"]] == PHASES     # once, in order
        ends = [s + d for _, s, d in st["phases"]]
        assert all(e <= p[1] for e, p in zip(ends, st["phases"][1:]))
        assert span_reduce.coverage(st) >= 0.95
    assert all(a["start"] + a["duration"] <= b["start"]
               for a, b in zip(steps, steps[1:]))
    # a CPU trace has no device operation: every reader stays silent
    window = (steps[0]["start"], steps[-1]["start"] + steps[-1]["duration"])
    assert span_reduce.phase_ms_p50(trace, window, "call") is None
    assert span_reduce.programs_per_step(trace, window) is None


def test_step_n_is_one_span_around_the_same_four_phases(tmp_path):
    step = _step()
    x, y = _batch()
    float(step.step_n(2, x, y))
    trace = _traced(tmp_path, lambda: float(step.step_n(2, x, y)))
    assert span_reduce.steps(trace) == []
    (loop,) = span_reduce.steps(trace, name="mx:train_step_n")
    assert [p[0] for p in loop["phases"]] == PHASES


# -- names on the device side ----------------------------------------------------------

def _lowered_for_tpu(fn, *avals):
    """StableHLO of ``fn`` lowered for the TPU platform (the kernels' TPU
    branch, with the Mosaic kernel serialized); needs no libtpu, so it
    does not contend for the lock ``tests/test_tpu_compile.py`` holds.
    Interpret mode makes no custom call and carries no name."""
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.fixture(scope="module")
def flash_text():
    from mxnet_tpu.ops.pallas_kernels import flash_attention

    def loss(q, k, v, w):
        return (flash_attention(q, k, v, causal=True) * w) \
            .astype(jnp.float32).sum()
    avals = [jax.ShapeDtypeStruct((1, 32, 512, 128), jnp.bfloat16)] * 4
    return _lowered_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), *avals)


@pytest.mark.parametrize("name", ["mx_flash_fwd", "mx_flash_bwd_dq",
                                  "mx_flash_bwd_dkv"])
def test_a_flash_kernel_carries_its_name(flash_text, name):
    assert flash_text.count("tpu_custom_call") == 3
    assert flash_text.count('kernel_name = "%s"' % name) == 1


def test_the_paged_kernel_carries_its_name():
    from mxnet_tpu.ops.paged_attention import paged_attention

    b, k1, h, d, pages, kv, page, maxp = 8, 1, 12, 64, 64, 4, 16, 8
    avals = [jax.ShapeDtypeStruct((b, k1, h, d), jnp.bfloat16),
             jax.ShapeDtypeStruct((pages, kv, page, d), jnp.bfloat16),
             jax.ShapeDtypeStruct((pages, kv, page, d), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, maxp), jnp.int32),
             jax.ShapeDtypeStruct((b,), jnp.int32)]
    text = _lowered_for_tpu(paged_attention, *avals)
    assert text.count('kernel_name = "mx_paged_attention"') == 1
