#!/usr/bin/env python3
"""What serving a decoder costs on the chip, read once (PERF.md 5, 7.1).

``python tools/serve_probe.py --model mistral_7b_d16`` in one process on a
TPU v5e: weights from ``--seed`` at the model's shapes (no gluon net: a
7.5 GB decoder would not fit beside gluon's copies), the bundle written by
``serve.model.save_serving_bundle``, loaded by ``LlamaServer``, one
64-token answer alone, then a full batch driven tick by tick with a
``jax.profiler`` trace over twenty decode steps.  Prints one JSON document:
export, save and load seconds, the bundle's bytes beside the weights', the
answer's seconds, and from the trace the decode step's median on the
device, the operations that took most of it and the share of the traced
window in which the device waited for the host.

No benchmark metric: the served cell (ROADMAP R1) sizes its traffic from
this.  ``--rehearse`` runs the same control flow on the CPU at a tiny size
and prints no device number.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(HERE, "benchmark", "chip")]

# published widths; only the depth is cut (and nothing of SmolLM2's)
MODELS = {
    # mistralai/Mistral-7B-v0.1 config.json (benchmark/chip/configs/
    # mistral_7b_d2.json), 16 of 32 layers: 3,751,940,096 parameters
    "mistral_7b_d16": dict(
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128,
        units=4096, hidden_size=14336, vocab_size=32000, rope_base=10000.0,
        eps=1e-5, num_pages=4096),
    # HuggingFaceTB/SmolLM2-360M config.json, whole
    "smol360m": dict(
        num_layers=32, num_heads=15, num_kv_heads=5, head_dim=64, units=960,
        hidden_size=2560, vocab_size=49152, rope_base=100000.0, eps=1e-5,
        tie_embeddings=True, num_pages=8192),
    "tiny": dict(
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, units=64,
        hidden_size=128, vocab_size=256, num_pages=256, paged_kernel="1"),
}


def make_weights(avals, seed):
    """Normal(0, 0.02) matrices and unit norms at ``avals``' shapes."""
    import jax

    rng = np.random.default_rng(seed)

    def leaf(a):
        if len(a.shape) == 1:
            return np.ones(a.shape, a.dtype)
        return (rng.standard_normal(a.shape, np.float32) * 0.02) \
            .astype(a.dtype)

    return jax.tree_util.tree_map(leaf, avals)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", choices=sorted(MODELS),
                    default="mistral_7b_d16")
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--traced-steps", type=int, default=20)
    ap.add_argument("--kv-dtype", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/serve_probe")
    a = ap.parse_args()
    if a.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        a.model, a.batch, a.prompt, a.new_tokens = "tiny", 4, 12, 8
        a.traced_steps = 3

    import jax

    import mxnet_tpu as mx  # noqa: F401  (configures the compile cache)
    import trace_reduce
    from mxnet_tpu import compile_cache, serve
    from mxnet_tpu.serve import model as serve_model

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        raise SystemExit("no TPU: %s" % (jax.devices(),))
    buckets = (16,) if a.rehearse else (128, 512)
    g = serve_model.KVGeometry(
        page_size=16, max_batch=a.batch, prefill_buckets=buckets,
        max_pages_per_seq=MODELS[a.model]["num_pages"] // a.batch,
        dtype="bfloat16", kv_dtype=a.kv_dtype, **MODELS[a.model])
    doc = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "model": a.model, "seed": a.seed, "geometry": g.describe()}
    with tempfile.TemporaryDirectory(prefix="serve_probe-") as work:
        path = os.path.join(work, "decoder.mxaot")

        t0 = time.perf_counter()
        weights = make_weights(serve_model.weight_avals(g), a.seed)
        doc["weights_s"] = time.perf_counter() - t0
        doc["weight_bytes"] = sum(
            w.nbytes for w in jax.tree_util.tree_leaves(weights))
        t0 = time.perf_counter()
        serve_model.save_serving_bundle(path, g, weights)
        doc["export_s"] = time.perf_counter() - t0
        del weights
        doc["export_programs"] = [
            {k: p[k] for k in ("name", "cache", "trace_s", "lower_s",
                               "backend_s")}
            for p in compile_cache.programs() if p["under"] == "serve.export"]
        doc["bundle_bytes"] = os.path.getsize(path)

        t0 = time.perf_counter()
        srv = serve.LlamaServer(path)
        doc["load_s"] = time.perf_counter() - t0
        doc["load_stage_s"] = {
            s["labels"]["stage"]: s["value"] for s in mx.telemetry.snapshot()
            .get("mxnet_setup_seconds_total", {}).get("series", [])
            if s["labels"]["stage"].startswith("serve.")}

    rng = np.random.default_rng(a.seed)

    def request():
        return serve.Request(
            rng.integers(0, g.vocab_size, a.prompt).tolist(),
            max_new_tokens=a.new_tokens)

    def tick():
        t0 = time.perf_counter()
        srv._loop_tick()
        return time.perf_counter() - t0

    # one answer alone (twice: the first call of a program loads it)
    for key in ("first_answer_s", "answer_s"):
        req = srv.scheduler.submit(request())
        t0 = time.perf_counter()
        while not req.done():
            tick()
        doc[key] = time.perf_counter() - t0
        assert len(req.result(0)) == a.new_tokens
    doc["answer_tokens"] = a.new_tokens

    # a full batch, traced once every lane decodes
    reqs = [srv.scheduler.submit(request()) for _ in range(a.batch)]
    while srv.scheduler.queue_len() or \
            srv.scheduler.active_slots() < a.batch:
        tick()
    tick()
    trace_dir = os.path.join(a.out, a.model)
    jax.profiler.start_trace(trace_dir)
    ticks = [tick() for _ in range(a.traced_steps)]
    jax.profiler.stop_trace()
    doc["decode_tick_host_ms_p50"] = float(np.median(ticks) * 1e3)
    while not all(r.done() for r in reqs):
        tick()
    srv.arena.assert_quiescent()
    doc["batch"] = a.batch

    if not a.rehearse:
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        mods = trace_reduce.module_durations(trace, "decode")
        steps = [d for ds in mods.values() for d in ds]
        doc["decode_step_device_ms_p50"] = float(np.median(steps) * 1e3)
        doc["decode_steps_traced"] = len(steps)
        plane = trace_reduce.device_planes(trace)[0]
        events = sorted(trace_reduce._line(plane, trace_reduce.MODULES_LINE),
                        key=lambda e: e[1])
        t_first, t_last = events[0][1], events[-1][1] + events[-1][2]
        busy = trace_reduce.union_ns([(s, d) for _, s, d in events])
        doc["host_share_between_steps_pct"] = \
            100.0 * (1 - busy / (t_last - t_first))
        per_step = 1e3 / max(1, len(steps))
        doc["top_ops_ms_per_step"] = [
            [name, s * per_step] for name, s in trace_reduce.top_ops(
                trace, 5, t_first, t_last)]
        doc["top_op_kinds_ms_per_step"] = [
            [name, s * per_step] for name, s in trace_reduce.top_ops(
                trace, 8, t_first, t_last, by=trace_reduce._family)]
        # an operation whose result is a copy the size of a layer's pages
        pages = re.compile(
            r"= \w+\[%d,%d,%d,%d\]\S* (copy|transpose)\("
            % srv.arena.buffers()[0][0][0].shape)
        doc["ops_that_copy_a_layers_pages"] = sorted({
            name for name, _, _ in trace_reduce._line(
                plane, trace_reduce.OPS_LINE)
            if pages.search(trace["detail"].get(name, ""))})
    print(json.dumps(doc, indent=1))
    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, a.model + ".json"), "w") as f:
        json.dump(doc, f, indent=1)


if __name__ == "__main__":
    main()
