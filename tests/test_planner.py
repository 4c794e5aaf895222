"""Static SPMD cost analyzer + auto-sharding planner (ISSUE 11).

Contracts pinned here:

- the cost maths (``analysis/spmd_cost.py``) are exact for the
  parameter term: per-device bytes = global bytes / partition factor,
  with ``pattern_rule``-style degradation on non-dividing dims;
- ``planner.plan`` is deterministic (same inputs → byte-identical
  ``as_dict``), needs NO devices (plans from an ``{axis: size}``
  dict), picks megatron for the Llama block tree on a 4x2 mesh and
  pure-dp for a small MLP (tie-break: dp wins when sharding buys
  nothing);
- ``JitTrainStep(rules="auto")`` is bitwise-identical (losses AND
  final params) to the hand-picked ``megatron_rule`` step, because the
  chosen specs ARE megatron's specs (the substrate guarantee);
- predicted per-device param bytes agree with memdump's measured
  ``param``-origin bytes within 10% on the dp=8 and megatron-TP
  dryruns (in practice: exactly);
- ``tools/mxplan.py`` plans abstract meshes from the CLI and its JSON
  output is byte-identical across runs (the CI determinism step).
"""
import gc
import json
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, planner
from mxnet_tpu.analysis import spmd_cost
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import llama
from mxnet_tpu.sharding import Mesh, P
from mxnet_tpu.telemetry import memdump

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AXES = {"data": 4, "model": 2}


@pytest.fixture
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (conftest sets XLA_FLAGS)")


def _llama_params():
    net = llama.llama_small()
    net.initialize()
    net(nd.array([[1, 2, 3, 4]], dtype="int32"))
    return [(p.name, tuple(p.shape), "float32")
            for p in net.collect_params().values()]


_MLP_PARAMS = [("dense0_weight", (16, 8)), ("dense0_bias", (16,)),
               ("dense1_weight", (4, 16)), ("dense1_bias", (4,))]


# ---------------------------------------------------------------------------
# spmd_cost: the byte maths
# ---------------------------------------------------------------------------
def test_partition_factor_and_per_device_bytes():
    assert spmd_cost.partition_factor((8, 4), P("model"), AXES) == 2
    assert spmd_cost.partition_factor((8, 4), P("data", "model"), AXES) == 8
    assert spmd_cost.partition_factor((8, 4), P(("data", "model")), AXES) \
        == 8
    # non-dividing dim degrades to replication (pattern_rule semantics)
    assert spmd_cost.partition_factor((7, 4), P("model"), AXES) == 1
    # spec longer than rank: extra entries ignored
    assert spmd_cost.partition_factor((8,), P("model", "data"), AXES) == 2
    assert spmd_cost.per_device_bytes((8, 4), "float32", P("model"),
                                      AXES) == 8 * 4 * 4 // 2
    assert spmd_cost.per_device_bytes((8, 4), "bfloat16", None, AXES) \
        == 8 * 4 * 2
    with pytest.raises(MXNetError, match="does not define"):
        spmd_cost.partition_factor((8,), P("expert"), AXES)


def test_mesh_axes_accepts_dicts_without_devices():
    assert spmd_cost.mesh_axes({"data": 64, "model": 8}) \
        == {"data": 64, "model": 8}
    with pytest.raises(MXNetError, match="positive static size"):
        spmd_cost.mesh_axes({"data": -1})
    with pytest.raises(MXNetError, match="needs a mesh"):
        spmd_cost.mesh_axes(None)


def test_analyze_params_dp_math_is_exact():
    # dp over 4: every param replicated; grads ring-all-reduce
    rep = spmd_cost.analyze_params(_MLP_PARAMS, {"data": 4},
                                   optimizer_slots=2)
    total = (16 * 8 + 16 + 4 * 16 + 4) * 4
    assert rep.param_bytes_per_device == total
    assert rep.grad_bytes_per_device == total
    assert rep.opt_bytes_per_device == 2 * total
    # ring all-reduce of each param's grad: 2*(k-1)/k * bytes, k=4
    expect_ar = sum(2 * 3 * (np.prod(s) * 4) // 4
                    for _, s in _MLP_PARAMS)
    assert rep.allreduce_bytes == expect_ar
    assert rep.reducescatter_bytes == 0
    assert rep.compile_signatures == 1


def test_analyze_params_tp_shards_and_fsdp_scatter():
    specs = {"dense0_weight": ("model",), "dense0_bias": (),
             "dense1_weight": (None, "model"), "dense1_bias": ()}
    rep = spmd_cost.analyze_params(_MLP_PARAMS, AXES, specs=specs)
    assert rep.param_bytes_per_device == \
        (16 * 8 // 2 + 16 + 4 * 16 // 2 + 4) * 4
    # fsdp: the data axis in a spec turns the grad sync into RS + AG
    fsdp = spmd_cost.analyze_params(
        [("w", (16, 8))], AXES, specs={"w": ("data",)})
    assert fsdp.reducescatter_bytes > 0
    assert fsdp.allgather_bytes > 0
    assert fsdp.allreduce_bytes == 0


def test_analyze_params_accepts_rule_and_gluon_params():
    mesh_rule = parallel.pattern_rule(
        [("*weight", P("model", None))], mesh=AXES)
    rep = spmd_cost.analyze_params(_MLP_PARAMS, AXES, rule=mesh_rule)
    by_name = {p.name: p for p in rep.params}
    assert by_name["dense0_weight"].factor == 2
    assert by_name["dense0_bias"].factor == 1
    net = nn.Dense(8, in_units=4)
    net.initialize()
    rep2 = spmd_cost.analyze_params(
        net.collect_params().values(), {"data": 2})
    assert {p.name for p in rep2.params} \
        == set(net.collect_params().keys())


def test_analyze_symbol_counts_activations_and_signatures():
    import mxnet_tpu.symbol as sym

    x = sym.Variable("x")
    y = sym.Variable("y")
    out = (x + y) * 2.0
    act, sigs = spmd_cost.analyze_symbol(
        out, arg_shapes={"x": (8, 4), "y": (8, 4)})
    assert act > 0
    assert sigs >= 2       # add + scalar-mul at least
    # a mesh divides activation bytes by the data-axis size
    act4, _ = spmd_cost.analyze_symbol(
        out, arg_shapes={"x": (8, 4), "y": (8, 4)}, mesh={"data": 4})
    assert act4 == act // 4


def test_calibration_from_telemetry_runs():
    cal = spmd_cost.Calibration.from_telemetry()
    assert cal.comm_weight == 1.0
    rep = spmd_cost.analyze_params(_MLP_PARAMS, {"data": 2})
    assert rep.comm_seconds(spmd_cost.Calibration(
        comm_bytes_per_second=1e9)) >= 0.0


# ---------------------------------------------------------------------------
# planner: enumeration, determinism, selection
# ---------------------------------------------------------------------------
def test_enumerate_candidates_fixed_order():
    names = [c.name for c in planner.enumerate_candidates(AXES)]
    assert names == ["dp", "megatron[model]",
                     "megatron[model]-replicated-embed", "embed[model]"]
    assert [c.name for c in planner.enumerate_candidates({"model": 2})] \
        == ["replicated", "megatron[model]",
            "megatron[model]-replicated-embed", "embed[model]"]


def test_plan_needs_no_devices_and_is_deterministic():
    params = _llama_params()
    a = planner.plan(params, {"data": 64, "model": 8}, step_tokens=4096)
    b = planner.plan(params, {"data": 64, "model": 8}, step_tokens=4096)
    assert json.dumps(a.as_dict(), sort_keys=True) \
        == json.dumps(b.as_dict(), sort_keys=True)


def test_plan_llama_picks_megatron_mlp_picks_dp():
    pl = planner.plan(_llama_params(), AXES, step_tokens=128)
    assert pl.candidate == "megatron[model]"
    assert pl.feasible
    # the chosen spec map IS megatron_rule's output (trailing-None
    # normalized) — the property that makes rules="auto" bitwise-equal
    # to the hand-picked rule-set
    mlp = planner.plan(_MLP_PARAMS, AXES, step_tokens=128)
    assert mlp.candidate == "dp"
    assert all(not e for e in mlp.specs.values())


def test_plan_spec_identity_with_megatron_rule(eight_devices):
    params = _llama_params()
    pl = planner.plan(params, AXES, step_tokens=128)
    rule = parallel.megatron_rule(axis="model", mesh=Mesh(AXES))

    def norm(spec):
        t = tuple(spec) if spec is not None else ()
        while t and t[-1] is None:
            t = t[:-1]
        return t

    for name, shape, _dt in params:
        assert norm(pl.param_rule(name, shape)) \
            == norm(rule(name, shape)), name


def test_plan_capacity_marks_infeasible():
    pl = planner.plan(_llama_params(), AXES, step_tokens=128,
                      capacity_bytes=1024)
    assert not pl.feasible
    assert "predicted per-device OOM" in pl.explain()
    # and the smallest-footprint candidate was still chosen
    assert pl.report.total_bytes_per_device == min(
        rep.total_bytes_per_device for _n, _s, _f, rep in pl.alternatives)


def test_plan_explain_lists_candidates_and_specs():
    pl = planner.plan(_llama_params(), AXES, step_tokens=128)
    text = pl.explain()
    assert "mxplan: mesh data=4xmodel=2" in text
    assert "chosen: megatron[model]" in text
    for cand in ("dp", "embed[model]"):
        assert cand in text
    assert "embed_weight" in text


def test_default_capacity_env(monkeypatch):
    monkeypatch.setenv(planner.ENV_CAPACITY, "12345")
    assert planner.default_capacity_bytes() == 12345
    monkeypatch.setenv(planner.ENV_CAPACITY, "lots")
    with pytest.raises(MXNetError, match="not an integer"):
        planner.default_capacity_bytes()


def test_plan_for_net_resolves_deferred_shapes():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    pl = planner.plan_for_net(net, {"data": 8},
                              sample=nd.ones((2, 8)))
    assert pl.candidate == "dp"
    assert all(0 not in p.shape for p in pl.report.params)


def test_plan_serving_suggests_kv_spec():
    from mxnet_tpu.serve.model import geometry_from_net

    net = llama.llama_small()
    net.initialize()
    net(nd.array([[1, 2, 3, 4]], dtype="int32"))
    g = geometry_from_net(net, num_pages=8, max_batch=2,
                          prefill_buckets=(4,), max_pages_per_seq=4)
    doc = planner.plan_serving(net, g, AXES)
    # llama_small has 2 KV heads: model=2 divides -> heads dim sharded
    assert doc["kv_spec"] == [None, "model", None, None]
    assert doc["candidate"] == "megatron[model]"
    json.dumps(doc)    # bundle-meta JSON-stable


# ---------------------------------------------------------------------------
# rules="auto": bitwise parity + memdump agreement (8 virtual devices)
# ---------------------------------------------------------------------------
def _llama_lm():
    vocab = 512

    class LM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            mx.random.seed(3)
            self.inner = llama.llama_small()

        def hybrid_forward(self, F, t):
            return F.reshape(self.inner(t), shape=(-1, vocab))

    net = LM()
    net.initialize()
    return net


def _llama_batch():
    rs = np.random.RandomState(1)
    toks = rs.randint(0, 512, (8, 16)).astype(np.int32)
    labels = rs.randint(0, 512, 8 * 16).astype(np.float32)
    return toks, labels


def _run_llama(mesh, steps=3, **step_kw):
    toks, labels = _llama_batch()
    mx.random.seed(5)
    net = _llama_lm()
    mx.random.seed(5)
    step = parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, **step_kw)
    losses = [float(step.step(nd.array(toks), nd.array(labels)))
              for _ in range(steps)]
    step.sync_params()
    flat = np.concatenate([p.data().asnumpy().ravel()
                           for p in net.collect_params().values()])
    return np.asarray(losses), flat, step


def test_rules_auto_bitwise_equals_handpicked_megatron(eight_devices):
    """The acceptance contract: on the 4x2 mesh rules="auto" picks the
    megatron-equivalent rule-set for the Llama tree, and the resulting
    step is BITWISE identical (losses and final params) to the
    hand-picked megatron_rule step — the chosen NamedShardings are the
    same, so the executable is the same."""
    mesh = Mesh(AXES)
    hand_l, hand_p, _ = _run_llama(
        mesh, param_rule=parallel.megatron_rule(axis="model", mesh=mesh))
    auto_l, auto_p, step = _run_llama(mesh, rules="auto")
    assert step.plan is not None
    assert step.plan.candidate == "megatron[model]"
    assert np.array_equal(hand_l, auto_l)
    assert np.array_equal(hand_p, auto_p)


def test_rules_dp_and_callable_spellings(eight_devices):
    mesh = Mesh({"data": 8})
    dp_l, dp_p, step = _run_llama(mesh, steps=1, rules="dp")
    assert step.plan is None
    none_l, none_p, _ = _run_llama(mesh, steps=1, param_rule=None)
    assert np.array_equal(dp_l, none_l)
    assert np.array_equal(dp_p, none_p)


def test_rules_param_rule_mutual_exclusion():
    net = _llama_lm()
    with pytest.raises(MXNetError, match="not both"):
        parallel.JitTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            rules="auto", param_rule=lambda n, s: None)


def test_rules_unknown_string_raises(eight_devices):
    toks, labels = _llama_batch()
    step = parallel.JitTrainStep(
        _llama_lm(), gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=Mesh({"data": 8}), rules="bogus")
    with pytest.raises(MXNetError, match="unknown rules"):
        step.step(nd.array(toks), nd.array(labels))


def _measured_param_bytes():
    gc.collect()       # free earlier steps' donated/replaced weights
    return memdump.per_device_bytes(label_prefix="train_step:")["param"]


def test_predicted_param_bytes_match_memdump_dp8(eight_devices):
    """Predicted per-device param bytes vs memdump's measured
    ``param``-origin bytes on the dp=8 dryrun: within 10% (exact in
    practice — dp replicates, so each device holds every full param)."""
    _l, _p, step = _run_llama(Mesh({"data": 8}), steps=1, rules="auto")
    predicted = step.plan.report.param_bytes_per_device
    measured = _measured_param_bytes()
    assert measured > 0
    assert abs(predicted - measured) <= 0.10 * measured, \
        (predicted, measured)


def test_predicted_param_bytes_match_memdump_megatron(eight_devices):
    """Same contract on the 4x2 megatron-TP dryrun: device 0 holds the
    column/row shards the cost model predicted."""
    _l, _p, step = _run_llama(Mesh(AXES), rules="auto")
    assert step.plan.candidate == "megatron[model]"
    predicted = step.plan.report.param_bytes_per_device
    measured = _measured_param_bytes()
    assert measured > 0
    # sharded params halve on device 0; a >10% gap means the placement
    # and the prediction disagree
    assert abs(predicted - measured) <= 0.10 * measured, \
        (predicted, measured)


def test_auto_dryrun_prints_explain(eight_devices, monkeypatch, capfd):
    monkeypatch.setenv(planner.ENV_DRYRUN, "1")
    _run_llama(Mesh(AXES), steps=1, rules="auto")
    err = capfd.readouterr().err
    assert "mxplan: mesh" in err
    assert "chosen: megatron[model]" in err


# ---------------------------------------------------------------------------
# tools/mxplan.py CLI
# ---------------------------------------------------------------------------
def _run_mxplan(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxplan.py")]
        + list(argv),
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_mxplan_cli_text_and_exit_codes(tmp_path):
    r = _run_mxplan("--mesh", "data=4,model=2", "--model", "mlp")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "chosen: dp" in r.stdout
    # capacity nothing fits -> exit 3 (predicted OOM, SP1001's twin)
    r = _run_mxplan("--mesh", "data=2", "--model", "mlp",
                    "--capacity", "1KiB")
    assert r.returncode == 3, r.stdout + r.stderr
    # usage errors -> exit 2
    assert _run_mxplan("--mesh", "bogus", "--model", "mlp").returncode == 2
    assert _run_mxplan("--mesh", "data=2").returncode == 2


def test_mxplan_cli_json_deterministic_abstract_mesh(tmp_path):
    """The CI determinism step: two runs over an abstract pod-sized mesh
    (no such devices exist here) produce byte-identical JSON."""
    args = ("--mesh", "data=64,model=8", "--model", "llama_small",
            "--tokens", "8192", "--slots", "2", "--format", "json")
    a, b = _run_mxplan(*args), _run_mxplan(*args)
    assert a.returncode == 0, a.stdout + a.stderr
    assert a.stdout == b.stdout
    doc = json.loads(a.stdout)
    assert doc["candidate"].startswith("megatron[model]")
    assert doc["mesh_axes"] == {"data": 64, "model": 8}


def test_mxplan_cli_params_json(tmp_path):
    p = tmp_path / "params.json"
    p.write_text(json.dumps([["w", [64, 64]], ["b", [64], "float32"]]))
    r = _run_mxplan("--mesh", "data=2,model=2", "--params", str(p))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "chosen:" in r.stdout
