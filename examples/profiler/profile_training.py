#!/usr/bin/env python
"""Profile a training loop: chrome-trace dump + aggregate-stats table.

Parity with the reference's ``example/profiler`` scripts
(``profiler_executor.py``/``profiler_ndarray.py``: set_config →
set_state('run') → work → set_state('stop') → dump, plus custom
Domain/Task instrumentation).  Produces:

- a chrome://tracing-loadable JSON (``--out``, default
  ``profile_train.json``),
- the per-op aggregate table on stdout (``mx.profiler.dumps()`` — the
  reference's MXDumpAggregateStats path),
- a custom domain span + counter showing user instrumentation
  (``mx.profiler.Domain`` / ``Task`` / ``Counter``).

    python examples/profiler/profile_training.py [--steps 20]

On TPU the per-op spans come from the engine's dispatch hook; the XLA
device timeline itself is a ``jax.profiler`` trace
(``mx.profiler.set_config(xla_trace_dir=...)``, docs/observability.md).
This example profiles the FRAMEWORK level: op dispatch, custom task
spans, counters.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
from examples import _device_setup  # noqa: E402

_device_setup.ensure_devices(1)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, nd  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default="profile_train.json")
    args = ap.parse_args()

    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})

    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(64, 32).astype(np.float32))
    y = nd.array(rs.randint(0, 10, 64).astype(np.float32))

    mx.profiler.set_config(profile_all=True, filename=args.out,
                           aggregate_stats=True)
    domain = mx.profiler.Domain("example")
    counter = domain.new_counter("samples_seen", 0)

    mx.profiler.set_state("run")
    epoch_task = domain.new_task("training")
    epoch_task.start()
    last = None
    for _ in range(args.steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch_size=64)
        counter.increment(64)
        last = loss
    print("final loss: %.4f" % float(last.mean().asscalar()))
    epoch_task.stop()
    mx.profiler.set_state("stop")

    print(mx.profiler.dumps(format="table", sort_by="total"))
    mx.profiler.dump()
    size = os.path.getsize(args.out)
    print("chrome trace written: %s (%d bytes) — load in "
          "chrome://tracing or perfetto" % (args.out, size))
    assert size > 0


if __name__ == "__main__":
    main()
