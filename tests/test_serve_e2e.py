"""Serving tier end-to-end (ISSUE 8): real micro-Llama, real bundles.

Numerics: the paged-attention prefill/decode graphs must reproduce the
full-sequence gluon forward exactly (greedy token parity).  Ops: bundle
export/load round-trips, geometry validation refuses mismatches at load,
the serving process performs zero live jits (asserted from a fresh
subprocess's telemetry dump — the same check the serve-smoke CI job
runs), and the stdlib HTTP front speaks the documented endpoints.
"""
import json
import os
import re
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.llama import LlamaModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GEOM_KW = dict(page_size=4, num_pages=32, max_batch=2,
               prefill_buckets=(8, 16))


def micro_llama(seed=5, tie=False):
    mx.random.seed(seed)
    net = LlamaModel(vocab_size=64, units=16, hidden_size=32, num_layers=2,
                     num_heads=2, num_kv_heads=1, tie_embeddings=tie)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))  # resolve deferred shapes
    return net


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve") / "micro.mxaot")
    net = micro_llama()
    geometry = serve.export_serving_bundle(net, path, **GEOM_KW)
    return path, net, geometry


def greedy_reference(net, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = net(nd.array(np.asarray([seq], np.int32))).asnumpy()
        seq.append(int(logits[0, -1].argmax()))
    return seq[len(prompt):]


# -- numerics ------------------------------------------------------------

def test_paged_greedy_matches_full_forward(bundle):
    path, net, _ = bundle
    with serve.LlamaServer(path) as srv:
        for prompt in ([3, 1, 4, 1, 5], [2], list(range(12))):
            got = srv.generate(prompt, max_new_tokens=6)
            assert got == greedy_reference(net, prompt, 6), prompt


def test_tied_embeddings_bundle_parity(tmp_path):
    net = micro_llama(seed=9, tie=True)
    path = str(tmp_path / "tied.mxaot")
    serve.export_serving_bundle(net, path, **GEOM_KW)
    with serve.LlamaServer(path) as srv:
        got = srv.generate([7, 8, 9], max_new_tokens=5)
    assert got == greedy_reference(net, [7, 8, 9], 5)


def test_concurrent_mixed_lengths_all_complete_and_match(bundle):
    path, net, _ = bundle
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, size=int(rng.integers(1, 14))).tolist()
               for _ in range(12)]
    with serve.LlamaServer(path) as srv:
        reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        outs = [r.result(timeout=120) for r in reqs]
    for prompt, out in zip(prompts, outs):
        assert out == greedy_reference(net, prompt, 4), \
            "in-flight batching changed this sequence's tokens"


# -- bundle + geometry validation ---------------------------------------

def test_bundle_geometry_roundtrip(bundle):
    from mxnet_tpu.serve.model import read_bundle_geometry

    path, _, geometry = bundle
    got, doc = read_bundle_geometry(path)
    assert got.to_dict() == geometry.to_dict()
    assert doc["meta"]["kind"] == "serving"


def test_load_rejects_mismatched_geometry(bundle):
    path, _, geometry = bundle
    expect = dict(geometry.to_dict())
    expect["page_size"] = 8
    expect["num_pages"] = 64
    from mxnet_tpu.serve.model import KVGeometry

    with pytest.raises(MXNetError) as ei:
        serve.load_serving_executables(path,
                                       expect=KVGeometry(**expect))
    msg = str(ei.value)
    assert "page_size" in msg and "num_pages" in msg
    assert "refusing to serve" in msg


def test_load_rejects_non_serving_bundle(tmp_path):
    from mxnet_tpu import compile_cache

    path = str(tmp_path / "other.aot")
    compile_cache.save_bundle(path, {"k": b"x"}, meta={"kind": "other"})
    with pytest.raises(MXNetError, match="serving"):
        serve.load_serving_executables(path)


# -- weights are arguments (ISSUE 40) -------------------------------------

def test_two_seeds_export_the_same_programs_and_serve_each_others_weights(
        bundle, tmp_path):
    """No program holds a weight: another net of the same geometry
    exports the same programs (compared as text less the call stacks they
    were traced under: XLA:CPU's serialized bytes differ from compile to
    compile of one program), and this bundle's programs over the other's
    weights are the other net."""
    def text(exe):
        hlo = exe.as_text().split("\nFileNames\n")[0]
        return re.sub(r"(, )?(metadata|stack_frame_id)=(\{[^}]*\}|\d+)", "",
                      hlo)

    path_a, net_a, _ = bundle
    net_b = micro_llama(seed=6)
    path_b = str(tmp_path / "other-seed.mxaot")
    serve.export_serving_bundle(net_b, path_b, **GEOM_KW)
    g, exes_a, _ = serve.load_serving_executables(path_a)
    _, exes_b, weights_b = serve.load_serving_executables(path_b)
    assert sorted(exes_a) == sorted(exes_b) == [
        "decode", "prefill_16", "prefill_8"]
    for name in exes_a:
        assert text(exes_a[name]) == text(exes_b[name]), name
    arena = serve.PagedKVArena(g)
    srv = serve.LlamaServer.from_parts(
        serve.AOTRunner(exes_a, weights_b, arena), arena)
    prompt = [3, 1, 4, 1, 5]
    with srv:
        got = srv.generate(prompt, max_new_tokens=6)
    assert got == greedy_reference(net_b, prompt, 6)
    assert got != greedy_reference(net_a, prompt, 6)


def test_programs_compile_from_shapes_alone():
    """A geometry is all ``compile_serving_executables`` takes: no net,
    no weight on any device; the weights are arguments of what it
    returns, after the cache state."""
    from mxnet_tpu.serve import model as serve_model

    g = serve_model.KVGeometry(
        num_layers=2, num_heads=2, num_kv_heads=1, head_dim=8, units=16,
        hidden_size=32, vocab_size=64, spec_k=2, prefill_chunk=4,
        **dict(GEOM_KW, max_pages_per_seq=8))
    before = {id(a) for a in jax.live_arrays()}
    exes = serve_model.compile_serving_executables(g)
    assert not [a.shape for a in jax.live_arrays() if id(a) not in before]
    assert sorted(exes) == ["chunk", "decode", "prefill_16", "prefill_8",
                            "verify"]
    leaves = jax.tree_util.tree_leaves
    state, weights = serve_model.state_avals(g), serve_model.weight_avals(g)
    assert len(leaves(weights)) == 1 + 9 * 2 + 1 + 1     # untied head
    for exe in exes.values():
        args, _ = exe.args_info
        assert len(leaves(args[0])) == len(leaves(state)) == 2 * 2
        assert [a.shape for a in leaves(args[1])] == [
            w.shape for w in leaves(weights)]
        assert len(args) == 5       # state, weights and three of the step
        # the cache is donated, the weights are not
        assert all(a.donated for a in leaves(args[0]))
        assert not any(a.donated for a in leaves(args[1]))


def test_bundle_is_its_weights_and_little_more(tmp_path):
    """Where the weights are most of the file, the file is under 1.25 x
    their bytes: each program once, the weights once.  (Closed over, the
    weights were constants of every program: two programs, two copies.)"""
    mx.random.seed(11)
    net = LlamaModel(vocab_size=8192, units=128, hidden_size=256,
                     num_layers=1, num_heads=2, num_kv_heads=1)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))
    path = str(tmp_path / "wide.mxaot")
    serve.export_serving_bundle(net, path, page_size=4, num_pages=16,
                                max_batch=2, prefill_buckets=(8,))
    _, exes, weights = serve.load_serving_executables(path)
    held = sum(w.nbytes for w in jax.tree_util.tree_leaves(weights))
    assert held > 8e6 and sorted(exes) == ["decode", "prefill_8"]
    assert os.path.getsize(path) < 1.25 * held
    with serve.LlamaServer(path) as srv:
        assert srv.generate([5, 6, 7], max_new_tokens=4) \
            == greedy_reference(net, [5, 6, 7], 4)


def test_bundle_without_weights_is_refused_at_load(bundle, tmp_path):
    """Programs that took no weights cannot be told from programs that
    held them: a bundle with no weights entry is refused, by name."""
    from mxnet_tpu import compile_cache

    path, _, _ = bundle
    doc = compile_cache.load_bundle(path)
    entries = {k: v for k, v in doc["entries"].items() if k != "weights"}
    assert len(entries) == len(doc["entries"]) - 1
    old = str(tmp_path / "programs-only.mxaot")
    compile_cache.save_bundle(old, entries, meta=doc["meta"])
    for load in (serve.load_serving_executables, serve.LlamaServer):
        with pytest.raises(
                MXNetError,
                match="re-export with serve.export_serving_bundle"):
            load(old)


def test_bundle_weights_of_other_shapes_are_refused_at_load(bundle,
                                                            tmp_path):
    from mxnet_tpu import compile_cache

    path, _, _ = bundle
    doc = compile_cache.load_bundle(path)
    embed, layers, norm, head = doc["entries"]["weights"]
    entries = dict(doc["entries"], weights=(embed[:, :-1], layers, norm,
                                            head))
    bad = str(tmp_path / "other-shapes.mxaot")
    compile_cache.save_bundle(bad, entries, meta=doc["meta"])
    with pytest.raises(MXNetError, match="not the shapes its geometry"):
        serve.load_serving_executables(bad)


def test_predictor_redirects_serving_bundle(bundle):
    from mxnet_tpu import deploy

    path, _, _ = bundle
    with pytest.raises(MXNetError) as ei:
        deploy.Predictor(path)
    msg = str(ei.value)
    assert "serving bundle" in msg and "LlamaServer" in msg
    assert "pages=32x4" in msg  # the geometry made it into the error


# -- zero live compiles (the AOT warm-start claim) ----------------------

_SERVE_PROC = r"""
import json, os, sys
import numpy as np
from mxnet_tpu import serve
from mxnet_tpu.telemetry import metrics as M

srv = serve.LlamaServer(sys.argv[1]).start()
wl = serve.poisson_workload(8, rate_rps=1e9, prompt_range=(1, 12),
                            max_new_range=(1, 6), vocab_size=64, seed=2)
reqs, _ = serve.drive_workload(srv, wl, timeout=120)
srv.stop()
snap = M.snapshot()
doc = {
    "completed": sum(1 for r in reqs if r.error is None),
    "compiles": sum(s["value"]
                    for s in snap.get("mxnet_compiles_total",
                                      {}).get("series", [])),
    "aot_loads": sum(s["value"]
                     for s in snap.get("mxnet_compile_cache_aot_loads_total",
                                       {}).get("series", [])),
}
print("RESULT " + json.dumps(doc))
"""


def test_fresh_process_serves_with_zero_live_compiles(bundle):
    path, _, _ = bundle
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY"] = "1"
    r = subprocess.run([sys.executable, "-c", _SERVE_PROC, path],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout.split("RESULT ", 1)[1])
    assert doc["completed"] == 8
    assert doc["compiles"] == 0, \
        "a serving process must never jit (AOT warm start)"
    assert doc["aot_loads"] >= 3  # decode + both prefill buckets


# -- speculative decoding + int8 KV (ISSUE 13) ---------------------------

SPEC_K = 4


@pytest.fixture(scope="module")
def spec_bundle(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve_spec") / "spec.mxaot")
    net = micro_llama()
    geometry = serve.export_serving_bundle(net, path, spec_k=SPEC_K,
                                           **GEOM_KW)
    return path, net, geometry


@pytest.fixture(scope="module")
def int8_bundle(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serve_int8") / "int8.mxaot")
    net = micro_llama()
    geometry = serve.export_serving_bundle(net, path, spec_k=SPEC_K,
                                           kv_dtype="int8", **GEOM_KW)
    return path, net, geometry


def _mixed_prompts(seed, n, max_len=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=int(rng.integers(1, max_len))).tolist()
            for _ in range(n)]


@pytest.mark.parametrize("spec_k", [0, 2, 4])
def test_spec_parity_fp32_matches_reference(spec_bundle, spec_k):
    """Greedy output must be token-for-token the full-forward reference
    at every runtime speculation width — acceptance is exact."""
    path, net, _ = spec_bundle
    prompts = _mixed_prompts(7, 8)
    with serve.LlamaServer(path, spec_k=spec_k) as srv:
        reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        st = srv.stats()
    for p, o in zip(prompts, outs):
        assert o == greedy_reference(net, p, 6), (spec_k, p)
    if spec_k:
        assert st["spec_proposed_tokens"] > 0


def test_spec_parity_int8_on_off_identical(int8_bundle):
    """Same int8 bundle, speculation on vs off: identical tokens.  The
    per-page scale is fixed at each page's slot-0 write and never
    requantized, so the arena state — and hence every logit — is
    independent of how tokens were grouped into verify blocks."""
    path, _, _ = int8_bundle
    prompts = _mixed_prompts(11, 8)
    outs = {}
    for spec_k in (0, 2, 4):
        with serve.LlamaServer(path, spec_k=spec_k) as srv:
            reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
            outs[spec_k] = [r.result(timeout=180) for r in reqs]
    assert outs[0] == outs[2] == outs[4]


def test_int8_bounded_divergence_from_fp32(int8_bundle):
    """Int8 is a numerics change, not a correctness bug: the first
    generated token comes out of prefill (which attends full-precision
    in-call K/V, so it is EXACT), and the quantized decode tail must
    track the fp32 reference closely on a micro model."""
    path, net, _ = int8_bundle
    prompts = _mixed_prompts(13, 6)
    with serve.LlamaServer(path, spec_k=0) as srv:
        outs = [srv.generate(p, max_new_tokens=8) for p in prompts]
    agree = total = 0
    for p, o in zip(prompts, outs):
        ref = greedy_reference(net, p, 8)
        assert o[0] == ref[0], "prefill token must be exact under int8"
        agree += sum(a == b for a, b in zip(o, ref))
        total += len(ref)
    assert agree / total >= 0.5, \
        "int8 diverged from fp32 on %d/%d tokens" % (total - agree, total)


def test_int8_page_reuse_resets_scales(int8_bundle):
    """FIFO page recycling: a page freed by one sequence and handed to
    another must quantize against the NEW owner's slot-0 scale.  Churn
    the arena through several reuse cycles, then check a fresh server
    (virgin pages, zero scales) produces the identical sequence."""
    path, _, geometry = int8_bundle
    prompts = _mixed_prompts(3, 10, max_len=9)
    final = [9, 8, 7, 6, 5, 4, 3, 2]
    with serve.LlamaServer(path) as srv:
        for p in prompts:
            srv.generate(p, max_new_tokens=8)
        used = srv.generate(final, max_new_tokens=8)
        assert srv.arena.free_pages == srv.arena.total_pages
    with serve.LlamaServer(path) as srv2:
        fresh = srv2.generate(final, max_new_tokens=8)
    assert used == fresh, "a recycled page leaked its previous scale"


def test_old_schema_bundle_serves_with_defaults(bundle, tmp_path):
    """A pre-PR-13 bundle meta carries neither kv_dtype nor spec_k —
    it must load as fp32 with speculation off and serve identically."""
    from mxnet_tpu import compile_cache

    path, net, _ = bundle
    doc = compile_cache.load_bundle(path)
    meta = dict(doc["meta"])
    geom = dict(meta["geometry"])
    del geom["kv_dtype"], geom["spec_k"]
    meta["geometry"] = geom
    old = str(tmp_path / "old-schema.mxaot")
    compile_cache.save_bundle(old, doc["entries"], meta=meta)
    with serve.LlamaServer(old) as srv:
        assert srv.geometry.kv_dtype == "float32"
        assert srv.geometry.spec_k == 0
        got = srv.generate([3, 1, 4], max_new_tokens=4)
    assert got == greedy_reference(net, [3, 1, 4], 4)


def test_kv_dtype_mismatch_named_at_load(int8_bundle):
    path, _, _ = int8_bundle
    with pytest.raises(MXNetError) as ei:
        serve.LlamaServer(path, kv_dtype="float32")
    msg = str(ei.value)
    assert "kv_dtype" in msg and "int8" in msg
    assert "refusing to serve" in msg


def test_healthz_reports_kv_dtype_and_spec(int8_bundle):
    path, _, _ = int8_bundle
    with serve.LlamaServer(path) as srv:
        st = srv.healthz()
    assert st["kv_dtype"] == "int8" and st["spec_k"] == SPEC_K


def test_memdump_kv_page_bytes_roughly_halve(spec_bundle, int8_bundle):
    """The tentpole memory claim, at identical geometry: int8 pages +
    two f32 scale arrays must come in at <= 0.55x the fp32 arena."""
    _, _, g32 = spec_bundle
    _, _, g8 = int8_bundle
    a32 = serve.PagedKVArena(g32)
    a8 = serve.PagedKVArena(g8)
    bytes32 = sum(b.nbytes for b in jax.tree_util.tree_leaves(a32.buffers()))
    bytes8 = sum(b.nbytes for b in jax.tree_util.tree_leaves(a8.buffers()))
    assert bytes8 <= 0.55 * bytes32, (bytes8, bytes32)


_SPEC_PROC = r"""
import json, os, sys
import numpy as np
from mxnet_tpu import serve
from mxnet_tpu.telemetry import metrics as M

srv = serve.LlamaServer(sys.argv[1]).start()
wl = serve.poisson_workload(6, rate_rps=1e9, prompt_range=(1, 12),
                            max_new_range=(16, 32), vocab_size=64, seed=7)
reqs, _ = serve.drive_workload(srv, wl, timeout=180)
st = srv.stats()
srv.stop()
snap = M.snapshot()


def fam(name):
    return sum(s["value"] for s in snap.get(name, {}).get("series", []))


doc = {
    "completed": sum(1 for r in reqs if r.error is None),
    "compiles": fam("mxnet_compiles_total"),
    "aot_loads": fam("mxnet_compile_cache_aot_loads_total"),
    "spec_proposed": fam("mxnet_serve_spec_proposed_tokens_total"),
    "spec_accepted": fam("mxnet_serve_spec_accepted_tokens_total"),
    "kv_dtype": st["kv_dtype"],
}
print("RESULT " + json.dumps(doc))
"""


def test_spec_int8_process_zero_live_compiles(int8_bundle):
    """The ISSUE 13 zero-live-jit claim: a fresh process serving the
    spec_k=4/int8 bundle runs verify from the MXAOT1 bundle, accepts
    drafts, and never jits."""
    path, _, _ = int8_bundle
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY"] = "1"
    r = subprocess.run([sys.executable, "-c", _SPEC_PROC, path],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout.split("RESULT ", 1)[1])
    assert doc["completed"] == 6
    assert doc["compiles"] == 0, \
        "a serving process must never jit, even with verify in the loop"
    assert doc["aot_loads"] >= 4  # decode + verify + both prefill buckets
    assert doc["spec_accepted"] > 0, \
        "n-gram speculation accepted nothing on a cyclic greedy stream"
    assert doc["kv_dtype"] == "int8"


# -- HTTP front ----------------------------------------------------------

def test_http_generate_metrics_healthz(bundle):
    path, net, _ = bundle
    with serve.LlamaServer(path) as srv:
        host, port = srv.serve_http(port=0)
        base = "http://%s:%d" % (host, port)
        body = json.dumps({"prompt": [3, 1, 4],
                           "max_new_tokens": 4}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                base + "/v1/generate", data=body,
                headers={"Content-Type": "application/json"})) as resp:
            doc = json.loads(resp.read())
        assert doc["tokens"] == greedy_reference(net, [3, 1, 4], 4)
        assert doc["ttft_s"] is None or doc["ttft_s"] >= 0
        # ISSUE 9: responses carry the trace id + TTFT breakdown
        assert doc["trace_id"]
        bd = doc["breakdown"]
        assert set(bd) == {"queue_wait_s", "prefill_s", "first_decode_s",
                           "ttft_s", "cache_hit_tokens"}
        assert bd["queue_wait_s"] >= 0 and bd["prefill_s"] >= 0
        with urllib.request.urlopen(base + "/healthz") as resp:
            stats = json.loads(resp.read())
        assert stats["completed"] >= 1
        # ISSUE 9: operational signals an external prober pages on
        assert stats["ok"] is True
        assert 0.0 <= stats["arena_utilization"] <= 1.0
        assert stats["queue_depth"] >= 0
        assert stats["live_device_bytes"] > 0
        assert stats["device_bytes_by_origin"]["param"] > 0
        assert stats["flight"]["enabled"] in (True, False)
        assert stats["flight"]["capacity"] > 0
        with urllib.request.urlopen(base + "/metrics") as resp:
            text = resp.read().decode()
        assert "mxnet_serve_requests_total" in text
        assert "mxnet_device_bytes" in text
        assert "mxnet_serve_queue_wait_seconds" in text
        # ISSUE 9: per-request trace endpoint replays the request's life
        with urllib.request.urlopen(
                base + "/v1/trace/" + doc["trace_id"]) as resp:
            tr = json.loads(resp.read())
        assert tr["trace_id"] == doc["trace_id"]
        assert tr["status"] == "completed"
        assert tr["tokens"] == doc["tokens"]
        names = [e["event"] for e in tr["events"]]
        assert names[0] == "submit" and "admit" in names
        assert "prefill" in names and "finish" in names
        assert tr["breakdown"]["ttft_s"] >= 0
        # unknown trace id: 404, not 500
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/v1/trace/doesnotexist")
        assert ei.value.code == 404
        # bad request: missing prompt
        bad = urllib.request.Request(base + "/v1/generate", data=b"{}")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400


def test_http_queue_full_returns_503(bundle):
    path, _, _ = bundle
    with serve.LlamaServer(path, queue_depth=0) as srv:
        host, port = srv.serve_http(port=0)
        base = "http://%s:%d" % (host, port)
        req = urllib.request.Request(
            base + "/v1/generate",
            data=json.dumps({"prompt": [1], "max_new_tokens": 2}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 503
        # submit-time rejection (budget over max context) is a client
        # error, not a 500: the scheduler parks it on the future, the
        # HTTP front must translate
        req = urllib.request.Request(
            base + "/v1/generate",
            data=json.dumps({"prompt": [1],
                             "max_new_tokens": 10_000}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        assert b"max context" in ei.value.read()


# -- static baseline (the reference continuous batching is held to) ----

def test_static_generate_matches_continuous_tokens(bundle):
    path, net, _ = bundle
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 64, size=int(rng.integers(1, 10))).tolist()
               for _ in range(5)]
    reqs = [serve.Request(p, max_new_tokens=3) for p in prompts]
    srv = serve.LlamaServer(path)  # NOT started: static runs caller-side
    outs = srv.static_generate(reqs)
    for prompt, out in zip(prompts, outs):
        assert out == greedy_reference(net, prompt, 3)
    assert srv.arena.free_pages == srv.arena.total_pages


# -- robustness: deadlines, cancel, drain, hot-swap (ISSUE 15) -----------

@pytest.fixture(scope="module")
def bundle_b(tmp_path_factory):
    """A second bundle, same geometry, DIFFERENT weights (seed) — the
    hot-swap target.  Post-swap outputs must match THIS net."""
    path = str(tmp_path_factory.mktemp("serve_b") / "micro-b.mxaot")
    net = micro_llama(seed=21)
    geometry = serve.export_serving_bundle(net, path, **GEOM_KW)
    return path, net, geometry


def test_hot_swap_mid_stream_zero_dropped(bundle, bundle_b):
    path_a, net_a, _ = bundle
    path_b, net_b, _ = bundle_b
    prompts = _mixed_prompts(17, 6)
    with serve.LlamaServer(path_a) as srv:
        swapped_at = []
        swap = srv.scheduler.swap

        def timed_swap(*args):
            swapped_at.append(srv.scheduler.clock())
            return swap(*args)

        srv.scheduler.swap = timed_swap
        # traffic in flight on bundle A...
        inflight = [srv.submit(p, max_new_tokens=6) for p in prompts]
        # ...reload blocks until the loop swaps at a step boundary
        srv.reload(path_b, timeout=120)
        assert srv.bundle_path == path_b and len(swapped_at) == 1
        # none dropped.  A request that had its lane before the swap
        # finished on the OLD executables; one still queued then was
        # held and served whole by the new ones (how many of each is a
        # race between reload()'s deserialize and the loop)
        outs = [r.result(timeout=120) for r in inflight]
        assert all(r.error is None for r in inflight)
        for r, p, o in zip(inflight, prompts, outs):
            old = r.admit_t < swapped_at[0]
            assert o == greedy_reference(net_a if old else net_b, p, 6), \
                "hot swap corrupted a sequence admitted %s it" % (
                    "before" if old else "after")
        # post-swap traffic is served by bundle B's weights
        for p in prompts[:3]:
            assert srv.generate(p, max_new_tokens=6) == \
                greedy_reference(net_b, p, 6), \
                "post-swap output does not match the new bundle"
        assert srv.arena.free_pages == srv.arena.total_pages


def test_reload_refuses_incompatible_geometry(bundle, tmp_path):
    path_a, _, _ = bundle
    net = micro_llama(seed=3)
    other = str(tmp_path / "wide.mxaot")
    kw = dict(GEOM_KW)
    kw["page_size"] = 8
    serve.export_serving_bundle(net, other, **kw)
    with serve.LlamaServer(path_a) as srv:
        with pytest.raises(MXNetError) as ei:
            srv.reload(other)
        assert "page_size" in str(ei.value)
        assert srv.bundle_path == path_a  # still serving the old bundle
        assert srv.generate([3, 1], max_new_tokens=2)


def test_http_delete_cancels_queued_request(bundle):
    path, _, _ = bundle
    srv = serve.LlamaServer(path)     # loop NOT started: deterministic
    host, port = srv.serve_http(port=0)
    base = "http://%s:%d" % (host, port)
    req = srv.scheduler.submit(serve.Request([3, 1], max_new_tokens=4))
    delete = urllib.request.Request(
        base + "/v1/generate/" + req.trace_id, method="DELETE")
    with urllib.request.urlopen(delete) as resp:
        assert json.loads(resp.read())["cancelled"] == req.trace_id
    srv.scheduler.step()              # cancel lands at the step boundary
    assert req.done()
    with pytest.raises(serve.ServeCancelled):
        req.result(timeout=0)
    # unknown id: 404, not 500
    delete = urllib.request.Request(
        base + "/v1/generate/req-doesnotexist", method="DELETE")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(delete)
    assert ei.value.code == 404
    srv.arena.assert_quiescent()
    srv.stop()


def test_http_deadline_returns_504(bundle):
    path, _, _ = bundle
    with serve.LlamaServer(path) as srv:
        host, port = srv.serve_http(port=0)
        base = "http://%s:%d" % (host, port)
        body = json.dumps({"prompt": [3, 1], "max_new_tokens": 4,
                           "deadline_s": 1e-9}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/generate", data=body))
        assert ei.value.code == 504
        assert b"deadline" in ei.value.read()
        srv.arena.assert_quiescent()


def test_http_drain_503_with_retry_after_and_healthz_flip(bundle):
    path, _, _ = bundle
    with serve.LlamaServer(path) as srv:
        host, port = srv.serve_http(port=0)
        base = "http://%s:%d" % (host, port)
        assert srv.drain(timeout=5) == 0     # nothing in flight
        body = json.dumps({"prompt": [1], "max_new_tokens": 2}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/generate", data=body))
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        # /healthz goes 503 so probers flip without parsing the body
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["draining"] is True


# -- fleet front over real bundles (ISSUE 18) ----------------------------

def test_fleet_routes_with_greedy_parity_and_shared_sha(bundle):
    path, net, _ = bundle
    servers = [serve.LlamaServer(path).start() for _ in range(2)]
    router = serve.FleetRouter(servers, probe_interval=0, seed=0)
    try:
        router.start(poller=False)
        for p in ([3, 1, 4], [2, 7], [5]):
            assert router.generate(p, max_new_tokens=5, timeout=120) \
                == greedy_reference(net, p, 5)
        body = router.healthz()
        shas = {st["bundle_sha"] for st in body["replicas"].values()}
        assert len(shas) == 1 and None not in shas   # one bundle, fleetwide
        assert body["replicas_healthy"] == 2
    finally:
        router.stop()
        for srv in servers:
            srv.drain(timeout=30)
            srv.stop()
            srv.arena.assert_quiescent()


def test_fleet_rolling_deploy_real_bundles_mid_stream(bundle, bundle_b):
    path_a, net_a, _ = bundle
    path_b, net_b, _ = bundle_b
    servers = [serve.LlamaServer(path_a).start() for _ in range(2)]
    router = serve.FleetRouter(servers, probe_interval=0, seed=0)
    try:
        router.start(poller=False)
        prompts = _mixed_prompts(8, 6)
        inflight = [router.submit(p, max_new_tokens=6, timeout=120)
                    for p in prompts]
        report = router.rolling_deploy(path_b, timeout=120)
        assert report["converged"] and report["dropped"] == 0
        # in-flight work settled — some on A's weights (pre-swap), the
        # rest routed around the deploy — but NOTHING was dropped
        outs = [f.result(timeout=120) for f in inflight]
        for p, o in zip(prompts, outs):
            assert o in (greedy_reference(net_a, p, 6),
                         greedy_reference(net_b, p, 6))
        # post-deploy traffic runs on bundle B's weights everywhere
        for p in prompts[:3]:
            assert router.generate(p, max_new_tokens=6, timeout=120) \
                == greedy_reference(net_b, p, 6)
    finally:
        router.stop()
        for srv in servers:
            srv.drain(timeout=30)
            srv.stop()
            srv.arena.assert_quiescent()


def test_healthz_identity_fields_over_http(bundle):
    path, _, _ = bundle
    with serve.LlamaServer(path) as srv:
        host, port = srv.serve_http(port=0)
        with urllib.request.urlopen(
                "http://%s:%d/healthz" % (host, port), timeout=30) as r:
            body = json.loads(r.read())
        assert body["server_id"].startswith("srv-")
        assert body["uptime_s"] >= 0.0
        sha = body["bundle_sha"]
        assert isinstance(sha, str) and len(sha) == 16
        int(sha, 16)   # hex digest prefix


def test_fleet_cli_sigterm_drains_and_exits_clean(bundle):
    import signal as _signal

    path, _, _ = bundle
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "mxnet_tpu.serve",
         "--bundle", path, "--port", "0", "--fleet", "2",
         "--drain-timeout", "10"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert "serving fleet n=2" in line, line
        proc.send_signal(_signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


# -- prefix cache, chunked prefill & sessions over real bundles (ISSUE 19)

@pytest.fixture(scope="module")
def chunk_bundle(tmp_path_factory):
    """A chunk-capable bundle: same micro net, prefill_chunk=4 adds the
    fixed-shape chunk executable next to the bucket ladder."""
    path = str(tmp_path_factory.mktemp("serve_chunk") / "chunk.mxaot")
    net = micro_llama()
    geometry = serve.export_serving_bundle(net, path, prefill_chunk=4,
                                           **GEOM_KW)
    return path, net, geometry


def test_chunked_greedy_matches_full_forward(chunk_bundle):
    """Over-bucket prompts are accepted and chunk-prefilled — and every
    path (bucket, chunked, spliced re-run) reproduces the full-sequence
    forward token-for-token."""
    path, net, _ = chunk_bundle
    with serve.LlamaServer(path) as srv:
        assert srv.geometry.prefill_chunk == 4
        for prompt in ([3, 1, 4, 1, 5], list(range(20)), [2] * 17):
            got = srv.generate(prompt, max_new_tokens=6)
            assert got == greedy_reference(net, prompt, 6), prompt
        # a second pass over the same prompts hits the radix cache —
        # splicing cached pages must not change a single token
        st0 = srv.stats()
        for prompt in ([3, 1, 4, 1, 5], list(range(20)), [2] * 17):
            got = srv.generate(prompt, max_new_tokens=6)
            assert got == greedy_reference(net, prompt, 6), \
                "spliced prefix changed greedy output"
        st1 = srv.stats()
        assert st1["prefix_hits"] > st0["prefix_hits"]
        assert st1["prefix_cached_tokens"] > 0


def test_prefix_cache_on_off_token_parity(chunk_bundle, monkeypatch):
    """The acceptance gate: greedy output identical cache-on vs
    cache-off for a shared-prefix workload on the same bundle."""
    path, net, _ = chunk_bundle
    system = list(range(16))              # 4 full pages of shared prefix
    deltas = [[20 + i] for i in range(5)]

    def run(cache_on):
        monkeypatch.setenv("MXNET_SERVE_PREFIX_CACHE",
                           "1" if cache_on else "0")
        with serve.LlamaServer(path) as srv:
            outs = [srv.generate(system + d, max_new_tokens=5)
                    for d in deltas]
            st = srv.stats()
        assert st["prefix_enabled"] is cache_on
        if cache_on:
            assert st["prefix_hits"] >= len(deltas) - 1
        return outs

    on, off = run(True), run(False)
    assert on == off
    for d, o in zip(deltas, on):
        assert o == greedy_reference(net, system + d, 5)


def test_chat_session_matches_full_transcript(chunk_bundle):
    """A pinned session prefills only each turn's delta, yet must be
    numerically indistinguishable from replaying the whole transcript."""
    path, net, _ = chunk_bundle
    with serve.LlamaServer(path) as srv:
        sid = srv.open_session()
        p1, p2, p3 = [3, 1, 4, 1, 5], [9, 2, 6], [5, 3]
        out1 = srv.generate(p1, max_new_tokens=4, session=sid)
        assert out1 == greedy_reference(net, p1, 4)
        out2 = srv.generate(p2, max_new_tokens=4, session=sid)
        assert out2 == greedy_reference(net, p1 + out1 + p2, 4), \
            "turn 2 over pinned pages diverged from the full transcript"
        out3 = srv.generate(p3, max_new_tokens=4, session=sid)
        assert out3 == greedy_reference(
            net, p1 + out1 + p2 + out2 + p3, 4)
        assert srv.scheduler.session_count() == 1
        assert srv.close_session(sid) is True
    # stop() flushed shared state; the context manager asserted quiescence


def test_http_chat_sessions_and_prefix_healthz(chunk_bundle):
    path, net, _ = chunk_bundle
    with serve.LlamaServer(path) as srv:
        host, port = srv.serve_http(port=0)
        base = "http://%s:%d" % (host, port)

        def chat(doc):
            body = json.dumps(doc).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    base + "/v1/chat", data=body,
                    headers={"Content-Type": "application/json"})) as r:
                return json.loads(r.read())

        # first turn: no session id -> the server opens one
        d1 = chat({"prompt": [3, 1, 4], "max_new_tokens": 4})
        sid = d1["session"]
        assert sid and d1["tokens"] == greedy_reference(net, [3, 1, 4], 4)
        # second turn continues the pinned session
        d2 = chat({"prompt": [9, 2], "max_new_tokens": 4,
                   "session": sid})
        assert d2["session"] == sid
        assert d2["tokens"] == greedy_reference(
            net, [3, 1, 4] + d1["tokens"] + [9, 2], 4)
        # the trace shows what the splice saved
        with urllib.request.urlopen(
                base + "/v1/trace/" + d2["trace_id"]) as r:
            tr = json.loads(r.read())
        assert tr["breakdown"]["cache_hit_tokens"] == 0  # session turn
        # healthz surfaces the prefix + session telemetry
        with urllib.request.urlopen(base + "/healthz") as r:
            hz = json.loads(r.read())
        assert hz["sessions"] == 1
        assert 0.0 <= hz["prefix_hit_rate"] <= 1.0
        assert hz["prefill_chunk"] == 4
        # unknown session id: typed 404, not a 500
        bad = json.dumps({"prompt": [1], "max_new_tokens": 2,
                          "session": "nope"}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/chat", data=bad))
        assert ei.value.code == 404
        # DELETE closes the session and releases its pages
        close = urllib.request.Request(base + "/v1/chat/" + sid,
                                       method="DELETE")
        with urllib.request.urlopen(close) as r:
            assert json.loads(r.read())["closed"] == sid
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/chat/" + sid, method="DELETE"))
        assert ei.value.code == 404
        assert srv.scheduler.session_count() == 0


def test_chunk_process_zero_live_compiles(chunk_bundle):
    """The zero-live-jit claim holds with the chunk executable in the
    loop: a fresh process serving shared-prefix traffic never compiles."""
    path, _, _ = chunk_bundle
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TELEMETRY"] = "1"
    proc = r"""
import json, sys
from mxnet_tpu import serve
from mxnet_tpu.telemetry import metrics as M

srv = serve.LlamaServer(sys.argv[1]).start()
system = list(range(16))
outs = [srv.generate(system + [20 + i], max_new_tokens=4, timeout=120)
        for i in range(4)]
st = srv.stats()
srv.stop()
snap = M.snapshot()
doc = {
    "completed": len(outs),
    "hits": st["prefix_hits"],
    "compiles": sum(s["value"]
                    for s in snap.get("mxnet_compiles_total",
                                      {}).get("series", [])),
}
print("RESULT " + json.dumps(doc))
"""
    r = subprocess.run([sys.executable, "-c", proc, path],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout.split("RESULT ", 1)[1])
    assert doc["completed"] == 4
    assert doc["hits"] >= 3, "shared prefix never hit the radix cache"
    assert doc["compiles"] == 0, \
        "a serving process must never jit, chunked prefill included"


def test_sigterm_drains_and_exits_clean(bundle):
    import signal as _signal
    import time as _time

    path, _, _ = bundle
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "mxnet_tpu.serve",
         "--bundle", path, "--port", "0", "--drain-timeout", "10"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert "serving" in line, line
        proc.send_signal(_signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
