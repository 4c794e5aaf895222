"""Tests of the ``async_collectives_per_step`` reader (PR 28), on traces
written by hand.  Run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/chip/tests -q``.  Nothing here touches a chip."""
import os

# the helpers, and the benchmark's directory on sys.path, from its sibling
from test_span_metrics import CHIP, MS, _reader, _run, _synthetic, \
    trace_reduce

READ = _reader("async_collectives_per_step")
# as the TPU profiler names a device operation: the whole instruction
START = ("%%async-collective-start.%d = (f32[7168,4096]{1,0:T(8,128)}, "
         "bf16[7168,4096]{1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) "
         "fusion(%%convolution_bitcast_fusion.3), kind=kCustom")
DONE = ("%%async-collective-done.%d = bf16[7168,4096]{1,0:T(8,128)(2,1)} "
        "fusion(%%get-tuple-element.651), kind=kCustom")
SYNC = "%all-reduce.395 = f32[16000,4096]{1,0:T(8,128)} all-reduce(%x)"


def test_counts_starts_on_the_first_device_over_the_steps(tmp_path):
    """Two asynchronous all-reduces a step on the first device.  A
    synchronous all-reduce, the ``-done`` halves, the starts of the step
    that begins after the window and the second device's operations are
    not counted."""
    trace = _synthetic()             # three steps in the window, a fourth after
    ops = trace["planes"][0]["lines"][0]["events"]
    for k in range(4):
        base = (10 + 100 * k) * MS
        ops += [(START % 6, base + 1 * MS, 5000),
                (DONE % 6, base + 9 * MS, MS),
                (START % 14, base + 20 * MS, 5000),
                (DONE % 14, base + 30 * MS, MS),
                (SYNC, base + 40 * MS, MS)]
    trace["planes"][1]["lines"][0]["events"].append((START % 6, MS, 5000))
    path = str(tmp_path / "t.xplane.pb")
    trace_reduce.write_xspace(trace, path)
    loaded = trace_reduce.load(path)             # through ProfileData
    assert READ(_run(loaded, trace_reduce.window_of(loaded))) == 2.0


def test_silent_where_there_is_nothing_to_read():
    """A run that was not traced; a program compiled without asynchronous
    collectives (every one before PR 28: left out, not 0), on a written
    trace and on both recorded chip traces; spans and no device."""
    assert READ({"trace": None}) is None
    assert READ(_run(_synthetic(), (0, 300 * MS))) is None
    for name in ("recorded.xplane.pb", "recorded_spans.xplane.pb"):
        old = trace_reduce.load(os.path.join(CHIP, "fixtures", name))
        assert trace_reduce.device_ops(old)
        assert READ(_run(old, trace_reduce.window_of(old))) is None
    cpu = _synthetic()
    cpu["planes"] = cpu["planes"][2:]
    assert READ(_run(cpu, (0, 300 * MS))) is None
