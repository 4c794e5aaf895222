#!/usr/bin/env python
"""ResNet-50 train-throughput sweep: batch size × layout × XLA flag sets.

The round-3 verdict's open perf item (VERDICT.md "What's weak" #3): the
~2.1k img/s chip number was attributed to XLA's conv kernels, but no
attempt was recorded to *move* the ceiling.  This tool is that attempt,
kept in-tree so the study is reproducible: every configuration runs in a
fresh subprocess (XLA flags only take effect before backend init) and
reports one line; the parent prints a table plus the winner.

Usage (on a machine with the chip attached):

    python tools/perf_sweep.py                 # default grid
    python tools/perf_sweep.py --quick         # 3-point sanity grid
    python tools/perf_sweep.py --flags-only    # hold batch fixed, sweep flags

Each child measures the same fused train step bench.py measures (10
device-side steps via JitTrainStep.step_n, donated buffers, bf16 AMP).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, os, sys, time
import numpy as np

sys.path.insert(0, %(root)r)
import jax, jax.numpy as jnp

cfg = json.loads(os.environ["SWEEP_CFG"])
import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import vision

if cfg.get("layout"):
    mx.set_default_layout(cfg["layout"])
mx.random.seed(0)
net = vision.resnet50_v1()
net.initialize(mx.init.Xavier())
from mxnet_tpu import amp
amp.init("bfloat16")
amp.convert_hybrid_block(net)
step = parallel.JitTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.1, "momentum": 0.9})
batch = cfg["batch"]
x = np.random.RandomState(0).rand(batch, 3, 224, 224).astype(np.float32)
x = jnp.asarray(x, jnp.bfloat16)
y = np.random.RandomState(0).randint(0, 1000, batch).astype(np.float32)
n = 10
loss = step.step_n(n, x, y)          # compile + warm
jax.block_until_ready(loss)
loss = step.step_n(n, x, y)
jax.block_until_ready(loss)
t0 = time.perf_counter()
loss = step.step_n(n, x, y)
jax.block_until_ready(loss)
dt = time.perf_counter() - t0
print("RESULT " + json.dumps({"img_s": round(batch * n / dt, 1),
                              "loss": float(loss)}))
"""


def run_cfg(batch, layout=None, xla_flags="", timeout=900):
    env = dict(os.environ)
    env["SWEEP_CFG"] = json.dumps({"batch": batch, "layout": layout})
    base = env.get("XLA_FLAGS", "")
    if xla_flags:
        env["XLA_FLAGS"] = (base + " " + xla_flags).strip()
    try:
        out = subprocess.run(
            [sys.executable, "-c", _CHILD % {"root": _ROOT}],
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"error": "timeout"}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    err = (out.stderr or "").strip().splitlines()
    tail = err[-1][-160:] if err else "no output"
    if "RESOURCE_EXHAUSTED" in (out.stderr or ""):
        tail = "OOM"
    return {"error": tail}


# flag sets worth trying on this jaxlib; unknown flags make XLA abort, so
# each runs isolated and a failure is just reported
FLAG_SETS = {
    "base": "",
    "latency-sched": "--xla_tpu_enable_latency_hiding_scheduler=true",
    "async-all": ("--xla_tpu_enable_latency_hiding_scheduler=true "
                  "--xla_enable_async_all_gather=true "
                  "--xla_enable_async_collective_permute=true"),
    "broadcast-priority": "--xla_tpu_enable_aggressive_broadcast_priority_update=true",
    "flash-fusion": "--xla_tpu_enable_flash_attention=true",
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--flags-only", action="store_true")
    ap.add_argument("--batches", default="")
    args = ap.parse_args()

    results = []
    if args.batches:
        batches = [int(b) for b in args.batches.split(",")]
    elif args.quick:
        batches = [128]
    else:
        batches = [128, 192, 256, 384, 512]

    if not args.flags_only:
        for layout in (None, "NCHW", "NHWC"):
            for b in batches:
                r = run_cfg(b, layout=layout)
                row = {"batch": b, "layout": layout or "auto",
                       "flags": "base", **r}
                results.append(row)
                print(json.dumps(row), flush=True)

    best_batch = max((r for r in results if "img_s" in r),
                     key=lambda r: r["img_s"], default=None)
    # no batch sweep ran (--flags-only) or all failed: use the measured
    # sweet spot (384, docs/perf.md), not the largest/near-OOM batch
    fb = best_batch["batch"] if best_batch else 384
    fl = None if not best_batch or best_batch["layout"] == "auto" \
        else best_batch["layout"]
    for name, flags in FLAG_SETS.items():
        if name == "base" and not args.flags_only:
            continue
        r = run_cfg(fb, layout=fl, xla_flags=flags)
        row = {"batch": fb, "layout": fl or "auto", "flags": name, **r}
        results.append(row)
        print(json.dumps(row), flush=True)

    ok = [r for r in results if "img_s" in r]
    if ok:
        best = max(ok, key=lambda r: r["img_s"])
        print("BEST " + json.dumps(best))


if __name__ == "__main__":
    main()
