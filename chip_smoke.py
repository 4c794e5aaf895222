#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` on a machine with one TPU v5e drives the two user
paths once, through the entry points a user would call:

- **train**: ``model_zoo.llama.llama2_7b`` at its published widths (depth
  cut to what one 16 GB chip trains), ``amp.init("bfloat16")``,
  ``parallel.JitTrainStep`` with AdamW, six steps on one fixed batch of
  4 x 512 tokens; then the same child once more for two steps, which must
  find the first one's executables in the persistent compile cache;
- **serve**: a SmolLM2-360M-shaped decoder in bf16 over 8192 pages of 16
  tokens, batch 32, exported with ``serve.export_serving_bundle`` (twice:
  the second export must find every program in the compile cache), served
  by ``python -m mxnet_tpu.serve`` over HTTP (8 requests, 4 of them
  concurrent), then every answer checked against a teacher-forced forward
  of the gluon model.

``--chips 4`` runs instead, and only, the same training model for three
steps on a ``{"data": 2, "model": 2}`` mesh under the shipped Megatron
rules, against a one-device run of the same three steps.

The launcher never imports jax or mxnet_tpu: a chip belongs to one process
at a time, so every phase is a child process (this file with ``--child``)
and they run one after another.  Any failed phase ends the script with a
non-zero exit at once.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--rehearse`` is the control-flow rehearsal tier-1 runs on CPU: tiny
models, the platform assertion relaxed.  It never prints that line.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 22

# llama2_7b's published widths; only the depth is cut
TRAIN = dict(vocab=32000, units=4096, hidden=11008, heads=32, layers=2,
             batch=4, seq=512, lr=3e-4)
# SmolLM2-360M's shape (HuggingFaceTB/SmolLM2-360M, config.json) at the
# geometry whose decode did not fit the chip while the programs copied the
# arena (PERF.md 7.1): the arena is 5.4 GB, 10.7 GB as the chip pads a
# 64-wide head
SERVE = dict(vocab=49152, units=960, hidden=2560, layers=32, heads=15,
             kv_heads=5, tie=True, page_size=16, num_pages=8192,
             max_batch=32, buckets=(128, 512), prompt_lens=(20, 400),
             new_tokens=64)
TINY_TRAIN = dict(vocab=256, units=64, hidden=128, heads=4, layers=2,
                  batch=4, seq=16, lr=3e-3)
TINY_SERVE = dict(vocab=256, units=64, hidden=128, layers=2, heads=4,
                  kv_heads=2, tie=True, page_size=4, num_pages=128, max_batch=8,
                  buckets=(16, 64), prompt_lens=(3, 40), new_tokens=8)

# bf16 on the chip, random weights: an argmax can flip on a near-tie, so
# the served token's logit must be within this of the position's maximum
# in a teacher-forced gluon forward.  These logits have a standard
# deviation of 0.22 and a median gap of 0.02 between the best two; the two
# bf16 paths disagreed by 0.001 at worst when both ran on CPU at these
# widths, and a token from a wrong page would miss by several tenths.
LOGIT_GAP_TOL = 0.02
# the mesh run and the one-device run reduce in different orders, in bf16;
# the losses are about 10
LOSS_TOL_4CHIP = 0.01


def say(msg):
    print("[chip_smoke] %s" % msg, flush=True)


def check(ok, msg):
    """A child's check: unlike ``assert`` it survives ``python -O``."""
    if not ok:
        raise RuntimeError("check failed: %s" % (msg,))


# ---------------------------------------------------------------------------
# children: the only code here that touches jax / mxnet_tpu
# ---------------------------------------------------------------------------

def _device(rehearse):
    import jax

    dev = jax.devices()[0]
    if not rehearse:
        check(dev.platform == "tpu",
              "no TPU: jax.devices() = %s" % (jax.devices(),))
    return dev, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}


def _result(doc):
    print("RESULT " + json.dumps(doc), flush=True)


def _on(dev, tree):
    """Every array of ``tree`` lives on ``dev`` and nowhere else."""
    import jax

    return all(a.devices() == {dev}
               for a in jax.tree_util.tree_leaves(tree))


def child_train(a):
    """JitTrainStep on one device, or on the 2x2 mesh with ``--mesh``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import amp, compile_cache, gluon, parallel
    from mxnet_tpu import random as mx_random
    from mxnet_tpu.gluon.model_zoo import llama
    from mxnet_tpu.telemetry import metrics

    dev, device = _device(a.rehearse)
    cfg = TINY_TRAIN if a.rehearse else TRAIN
    vocab = cfg["vocab"]
    ctx = mx.tpu()
    mesh = parallel.make_mesh({"data": 2, "model": 2}) if a.mesh else None

    def compiles():
        fam = metrics.snapshot().get("mxnet_compiles_total", {})
        return int(sum(s["value"] for s in fam.get("series", [])))

    mx.random.seed(SEED)
    # the default context is cpu(0), which on a TPU host is the host's CPU
    # backend: the scope puts initializers, batches and the shape-resolving
    # forward on the chip
    with ctx:
        net = llama.llama2_7b(vocab_size=vocab, units=cfg["units"],
                              hidden_size=cfg["hidden"],
                              num_layers=cfg["layers"],
                              num_heads=cfg["heads"])
        net.initialize(mx.init.Xavier(), ctx=ctx)
        amp.init("bfloat16")

        class LM(gluon.HybridBlock):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def hybrid_forward(self, F, toks):
                return F.reshape(self.inner(toks), shape=(-1, vocab))

        step = parallel.JitTrainStep(
            LM(net), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
            {"learning_rate": cfg["lr"]}, mesh=mesh,
            rules="megatron" if a.mesh else None)
        rng = np.random.RandomState(SEED)
        toks = rng.randint(0, vocab, (cfg["batch"], cfg["seq"])) \
            .astype(np.int32)
        labels = rng.randint(0, vocab, cfg["batch"] * cfg["seq"]) \
            .astype(np.float32)
        losses, seconds, counts = [], [], []
        for _ in range(a.steps):
            t0 = time.perf_counter()
            loss = step.step(toks, labels)   # the same batch every step
            losses.append(float(jax.block_until_ready(loss)))
            seconds.append(round(time.perf_counter() - t0, 3))
            counts.append((compiles(), step._step_fn._cache_size()))
    n_params = sum(int(np.prod(w.shape)) for w in step._weights)
    say("train: llama2_7b widths units=%d heads=%d hidden=%d vocab=%d, "
        "depth %d, %d parameters, batch %dx%d%s"
        % (cfg["units"], cfg["heads"], cfg["hidden"], vocab, cfg["layers"],
           n_params, cfg["batch"], cfg["seq"],
           ", mesh data=2 model=2" if a.mesh else ""))
    say("train: losses %s" % losses)
    say("train: step seconds %s (the first holds trace and compile)"
        % seconds)
    check(all(np.isfinite(losses)), "non-finite loss")
    mem = dev.memory_stats() or {}
    say("train: peak device bytes %s of %s"
        % (mem.get("peak_bytes_in_use"), mem.get("bytes_limit")))

    doc = {"device": device, "losses": losses, "seconds": seconds,
           "first_step_seconds": seconds[0], "n_params": n_params,
           "depth": cfg["layers"],
           "cache_hits": compile_cache.stats()["hits"],
           "cache_dir": compile_cache.cache_dir(),
           "peak_bytes": mem.get("peak_bytes_in_use")}
    state = [s for s in step._opt_state if s is not None]
    if a.mesh:
        # a column-parallel weight: each of the 4 devices holds the half of
        # dim 0 that its place on the `model` axis gives it
        name, w = next((p.name, w) for p, w in zip(step._params,
                                                   step._weights)
                       if p.name.endswith("gate_weight"))
        shards = w.addressable_shards
        rows = w.shape[0] // 2
        where = {s.device.id: s.index[0].indices(w.shape[0])[:2]
                 for s in shards}
        say("4chip: %s %s sharded %s, shards (device: rows) %s"
            % (name, w.shape, w.sharding.spec, where))
        check(len({s.device for s in shards}) == 4,
              "the weight's shards are not on 4 devices")
        check(all(s.data.shape == (rows, w.shape[1]) for s in shards),
              "a shard is not half of the weight's rows")
        model_of = {d.id: int(np.argwhere(mesh.devices == d)[0][1])
                    for d in mesh.devices.flat}
        check(all(where[i] == (model_of[i] * rows, (model_of[i] + 1) * rows)
                  for i in where),
              "rows %s do not follow the model axis %s" % (where, model_of))
        batch = step._place_batch([mx.nd.array(toks, ctx=ctx)])[0]
        split = sorted((s.device.id, s.data.shape) for s in
                       batch.addressable_shards)
        say("4chip: batch %s sharded %s, shards %s"
            % (batch.shape, batch.sharding.spec, split))
        check(batch.sharding.spec[0] == "data"
              and all(shape == (cfg["batch"] // 2, cfg["seq"])
                      for _, shape in split),
              "the batch is not split in two over `data`")
        doc.update(weight=name, weight_shards={str(k): v for k, v
                                               in where.items()},
                   batch_shards=[[i, list(s)] for i, s in split])
    else:
        check(_on(dev, step._weights), "weights left the device")
        check(_on(dev, state), "optimizer state left the device")
        check(loss.devices() == {dev}, "loss left the device")
        check(_on(dev, [p.data().data() for p in step._params]),
              "a gluon parameter was initialised off the device")
    if a.steps >= 3:
        check(losses[-1] < losses[0], "loss did not fall: %s" % losses)
        # everything compiles in steps 1 and 2 (the donated weights come
        # back with device layouts once)
        check(counts[-1] == counts[1],
              "a compile after step 2: (eager ops, step) = %s" % counts)
    # what the step was lowered to: the flash kernels, not the reference
    aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    with step._mesh_scope():
        lowered = step._step_fn.lower(
            aval(mx_random.next_key()),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.tree_util.tree_map(aval, step._weights),
            jax.tree_util.tree_map(aval, step._opt_state),
            jax.ShapeDtypeStruct((), jnp.int32),
            *step._batch_avals).as_text()
    doc["flash_kernels"] = lowered.count("tpu_custom_call")
    say("train: %d tpu_custom_call in the lowered step"
        % doc["flash_kernels"])
    check(a.rehearse or doc["flash_kernels"] >= 3,
          "the step took the attention reference, not the flash kernels")
    _result(doc)


def _serve_net(cfg, ctx, seed=SEED):
    """The decoder in bf16 on ``ctx``, weights from ``seed``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import llama

    mx.random.seed(seed)
    net = llama.LlamaModel(cfg["vocab"], units=cfg["units"],
                           hidden_size=cfg["hidden"],
                           num_layers=cfg["layers"], num_heads=cfg["heads"],
                           num_kv_heads=cfg["kv_heads"],
                           tie_embeddings=cfg["tie"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))  # deferred shapes
    net.cast("bfloat16")
    return net


def child_export(a):
    """Child A: export the serving bundle, load it back and exit."""
    import hashlib

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, serve
    from mxnet_tpu.serve import model as serve_model

    dev, device = _device(a.rehearse)
    cfg = TINY_SERVE if a.rehearse else SERVE
    with mx.tpu() as ctx:
        t0 = time.perf_counter()
        net = _serve_net(cfg, ctx, a.seed)
        built = time.perf_counter() - t0
        geometry = serve.export_serving_bundle(
            net, a.bundle, page_size=cfg["page_size"],
            num_pages=cfg["num_pages"], max_batch=cfg["max_batch"],
            prefill_buckets=cfg["buckets"],
            # off the chip only the interpreter can stand in for the kernel
            paged_kernel="1" if a.rehearse else None)
        secs = time.perf_counter() - t0 - built
        del net
    compiled = [p for p in compile_cache.programs()
                if p["under"] == "serve.export" and p["cache"] != "hit"]
    t0 = time.perf_counter()
    _, exes, weights = serve_model.load_serving_executables(a.bundle)
    jax.block_until_ready(weights)
    load = time.perf_counter() - t0
    check(_on(dev, weights), "the bundle's weights are not on the device")
    held = sum(w.nbytes for w in jax.tree_util.tree_leaves(weights))
    size = os.path.getsize(a.bundle)
    # the programs as text: the serialized bytes of one program differ
    # from compile to compile
    programs = hashlib.sha256("".join(
        exes[name].as_text() for name in sorted(exes)).encode()
    ).hexdigest()[:16]
    kernels = exes["decode"].as_text().count("tpu_custom_call")
    say("serve: net from seed %d in %.1fs; exported %s in %.1fs (%d of the "
        "programs compiled, the rest from the cache); loaded in %.1fs"
        % (a.seed, built, sorted(exes), secs, len(compiled), load))
    say("serve: bundle %d bytes = weights %d + %d; programs %s; %s"
        % (size, held, size - held, programs, geometry.describe()))
    say("serve: %d tpu_custom_call in the decode executable" % kernels)
    # no program holds a weight: one that did would add their bytes again
    # (what is over them is the compiled code of the unrolled layers)
    check(size - held < held / 2 or a.rehearse,
          "the bundle is %d bytes over its weights" % (size - held))
    if not a.rehearse:
        check(kernels >= cfg["layers"],
              "decode holds the attention reference, not the paged kernel")
    _result({"device": device, "executables": sorted(exes),
             "build_seconds": round(built, 1),
             "export_seconds": round(secs, 1),
             "load_seconds": round(load, 1), "compiled": len(compiled),
             "bundle_bytes": size, "weight_bytes": held,
             "programs": programs, "paged_kernels": kernels})


def child_check(a):
    """Child C: for every answer one teacher-forced gluon forward over
    prompt + served tokens; each served token's logit against its
    position's maximum."""
    import numpy as np

    import mxnet_tpu as mx

    dev, device = _device(a.rehearse)
    cfg = TINY_SERVE if a.rehearse else SERVE
    with open(a.served) as f:
        answers = json.load(f)
    worst, exact, of = 0.0, 0, 0
    with mx.tpu() as ctx:
        net = _serve_net(cfg, ctx)
        for doc in answers:
            prompt, served = doc["prompt"], doc["tokens"]
            out = net(mx.nd.array(np.array([prompt + served], np.int32),
                                  ctx=ctx))
            check(out.data().devices() == {dev}, "logits left the device")
            logits = out.asnumpy()[0].astype(np.float32)
            check(np.isfinite(logits).all(), "non-finite logits")
            # position len(prompt)-1+i predicts served token i
            rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
            gaps = rows.max(axis=-1) - rows[np.arange(len(served)), served]
            same = int((rows.argmax(axis=-1) == np.array(served)).sum())
            say("serve: teacher-forced over %d prompt + %d served tokens: "
                "worst logit gap %.4f (tolerance %.2f), %d/%d the exact "
                "argmax, logit std %.3f"
                % (len(prompt), len(served), gaps.max(), LOGIT_GAP_TOL,
                   same, len(served), rows.std()))
            check(gaps.max() <= LOGIT_GAP_TOL,
                  "served tokens disagree with the gluon model: gaps %s"
                  % gaps)
            worst = max(worst, float(gaps.max()))
            exact, of = exact + same, of + len(served)
    _result({"device": device, "worst_gap": worst, "exact": exact,
             "of": of, "answers": len(answers)})


CHILDREN = {"train": child_train, "export": child_export,
            "check": child_check}


# ---------------------------------------------------------------------------
# launcher
# ---------------------------------------------------------------------------

class PhaseFailed(Exception):
    pass


_live = []   # every process the launcher started and has not reaped


def _kill_all():
    for p in _live:
        if p.poll() is None:
            p.kill()
            p.wait()


def run_child(a, phase, name, flags, env, timeout):
    """One ``--child`` process to its end; its RESULT document."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name] \
        + flags + (["--rehearse"] if a.rehearse else [])
    say("%s: starting %s" % (phase, " ".join(cmd[2:])))
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, stdout=subprocess.PIPE,
                            text=True)
    _live.append(proc)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        sys.stdout.write(out)
        raise PhaseFailed("%s: no end after %ds" % (phase, timeout))
    sys.stdout.write(out)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise PhaseFailed("%s: child exited %d" % (phase, proc.returncode))
    results = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    if not results:
        raise PhaseFailed("%s: child printed no RESULT" % phase)
    say("%s: done in %.1fs" % (phase, time.perf_counter() - t0))
    return json.loads(results[-1][len("RESULT "):])


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url, body=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(urllib.request.Request(url, data=data),
                                timeout=timeout) as r:
        return r.status, r.read()


def _metric(text, family):
    """Sum of a family's series in Prometheus text; None when absent."""
    vals = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith(family) and ln[len(family)] in " {"]
    return sum(vals) if vals else None


def phase_train(a, env, work):
    first = run_child(a, "train", "train", ["--steps", "6"], env, 900)
    again = run_child(a, "train again", "train", ["--steps", "2"], env, 600)
    say("train: first step cold %.1fs, with the cache of the first run "
        "%.1fs; persistent-cache hits %d then %d (cache at %s)"
        % (first["first_step_seconds"], again["first_step_seconds"],
           first["cache_hits"], again["cache_hits"], again["cache_dir"]))
    if not again["cache_hits"] > 0:
        raise PhaseFailed("train again: no persistent-cache hit")
    if again["losses"] != first["losses"][:2]:
        raise PhaseFailed("train again: losses %s differ from the first "
                          "run's %s" % (again["losses"], first["losses"]))
    return first["device"]


def phase_serve(a, env, work):
    cfg = TINY_SERVE if a.rehearse else SERVE
    bundle = os.path.join(work, "decoder.mxaot")
    exported = run_child(a, "serve export", "export", ["--bundle", bundle],
                         env, 900)
    # another net of the same shape: the programs are the same text, and
    # every one of them comes from the compile cache
    other = bundle + ".other"
    again = run_child(a, "serve export again", "export",
                      ["--bundle", other, "--seed", str(SEED + 1)], env, 900)
    os.remove(other)
    if again["programs"] != exported["programs"]:
        raise PhaseFailed("serve export again: another seed's programs "
                          "differ (%s, %s): they hold something of the "
                          "weights" % (again["programs"],
                                       exported["programs"]))
    if again["compiled"]:
        raise PhaseFailed("serve export again: %d programs compiled, the "
                          "cache held the first export's"
                          % again["compiled"])

    port = _free_port()
    base = "http://127.0.0.1:%d" % port
    say("serve: starting python -m mxnet_tpu.serve on port %d" % port)
    t0 = time.perf_counter()
    srv = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.serve", "--bundle", bundle,
         "--port", str(port)], env=env, cwd=HERE)
    _live.append(srv)
    while True:
        if srv.poll() is not None:
            raise PhaseFailed("serve: server exited %d before /healthz"
                              % srv.returncode)
        if time.perf_counter() - t0 > 600:
            raise PhaseFailed("serve: no /healthz after 600s")
        try:
            if _http(base + "/healthz", timeout=5)[0] == 200:
                break
        except OSError:
            time.sleep(0.5)
    say("serve: /healthz after %.1fs" % (time.perf_counter() - t0))

    rng = random.Random(SEED)
    lo, hi = cfg["prompt_lens"]
    lens = [lo, hi] + [rng.randint(lo, hi) for _ in range(6)]
    prompts = [[rng.randrange(cfg["vocab"]) for _ in range(n)] for n in lens]

    def generate(prompt):
        t0 = time.perf_counter()
        status, raw = _http(base + "/v1/generate",
                            {"prompt": prompt,
                             "max_new_tokens": cfg["new_tokens"]})
        return status, json.loads(raw), time.perf_counter() - t0

    # 4 one after another, then 4 at once
    answers = [generate(p) for p in prompts[:4]]
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        answers += list(pool.map(generate, prompts[4:]))
    for p, (status, body, secs) in zip(prompts, answers):
        say("serve: prompt %3d tokens -> %d, %d tokens, first token "
            "%.3fs, whole request %.3fs"
            % (len(p), status, len(body.get("tokens", ())),
               body.get("ttft_s") or -1, secs))
    bad = [(s, b) for s, b, _ in answers
           if s != 200 or len(b["tokens"]) != cfg["new_tokens"]]
    if bad:
        raise PhaseFailed("serve: %d of 8 requests failed: %s"
                          % (len(bad), bad[0]))
    metrics = _http(base + "/metrics")[1].decode()
    compiles = _metric(metrics, "mxnet_compiles_total")
    loads = _metric(metrics, "mxnet_compile_cache_aot_loads_total")
    say("serve: 8/8 requests, live compiles %s, AOT loads %s of %d "
        "executables" % (compiles, loads, len(exported["executables"])))
    if compiles:
        raise PhaseFailed("serve: the server compiled (%s)" % compiles)
    if not loads or loads < len(exported["executables"]):
        raise PhaseFailed("serve: AOT loads %s < %d executables"
                          % (loads, len(exported["executables"])))
    srv.send_signal(signal.SIGTERM)
    try:
        rc = srv.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise PhaseFailed("serve: server still up 120s after SIGTERM")
    if rc != 0:
        raise PhaseFailed("serve: server exited %d after SIGTERM" % rc)
    say("serve: server exited 0 after SIGTERM")

    served = os.path.join(work, "served.json")
    with open(served, "w") as f:
        json.dump([{"prompt": p, "tokens": body["tokens"]}
                   for p, (_, body, _) in zip(prompts, answers)], f)
    run_child(a, "serve check", "check", ["--served", served], env, 900)
    return exported["device"]


def phase_4chip(a, env, work):
    mesh = run_child(a, "4chip mesh", "train", ["--steps", "3", "--mesh"],
                     env, 1200)
    one = run_child(a, "4chip one device", "train", ["--steps", "3"], env,
                    900)
    diffs = [abs(x - y) for x, y in zip(mesh["losses"], one["losses"])]
    say("4chip: losses on the data=2 x model=2 mesh %s" % mesh["losses"])
    say("4chip: losses on one device              %s" % one["losses"])
    say("4chip: worst difference %.5f (tolerance %.2f)"
        % (max(diffs), LOSS_TOL_4CHIP))
    if max(diffs) > LOSS_TOL_4CHIP:
        raise PhaseFailed("4chip: the mesh run's losses left the "
                          "one-device run's")
    return mesh["device"]


def launcher(a):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["MXNET_TELEMETRY"] = "1"
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    if a.rehearse:
        # a CPU process keeps its compile cache off unless it opts in; the
        # rehearsal opts in to a directory of its own so that the second
        # train child has hits to report
        env.update(JAX_PLATFORMS="cpu", MXNET_COMPILE_CACHE_MIN_SECS="0",
                   MXNET_COMPILE_CACHE_DIR=os.path.join(work, "cache"))
        if a.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
    t0 = time.perf_counter()
    try:
        if a.chips == 4:
            device = phase_4chip(a, env, work)
        else:
            device = phase_train(a, env, work)
            served_on = phase_serve(a, env, work)
            if served_on != device:
                raise PhaseFailed("phases saw different devices: %s / %s"
                                  % (device, served_on))
        if device["count"] != a.chips and not a.rehearse:
            raise PhaseFailed("asked for %d chip(s), jax reports %d"
                              % (a.chips, device["count"]))
    except PhaseFailed as e:
        say("FAILED %s" % e)
        return 1
    finally:
        _kill_all()
        shutil.rmtree(work, ignore_errors=True)
    say("all phases passed in %.0fs" % (time.perf_counter() - t0))
    if a.rehearse:
        say("rehearsal on %s: no result line" % device["platform"])
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh training run and its one-device "
                         "comparison, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="control-flow rehearsal on CPU with tiny models; "
                         "never prints the result line")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--steps", type=int, default=6, help=argparse.SUPPRESS)
    ap.add_argument("--mesh", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--bundle", help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=SEED, help=argparse.SUPPRESS)
    ap.add_argument("--served", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child:
        CHILDREN[a.child](a)
        return 0
    return launcher(a)


if __name__ == "__main__":
    sys.exit(main())
