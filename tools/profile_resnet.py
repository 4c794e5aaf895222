"""Capture a device trace of the ResNet-50 train step and print top ops.

Usage: python tools/profile_resnet.py [batch]
Writes the xplane under /tmp/rn50_trace and prints the op-profile table
(tensorboard_plugin_profile) so hotspots are visible without tensorboard.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, amp
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    mx.random.seed(0)
    net = vision.resnet50_v1()
    print("layout:", net._layout, file=sys.stderr)
    net.initialize(mx.init.Xavier())
    amp.init("bfloat16")
    amp.convert_hybrid_block(net)
    step = parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, 224, 224), jnp.bfloat16)
    y = rng.randint(0, 1000, batch).astype(np.float32)
    t0 = time.perf_counter()
    loss = step.step(x, y)
    jax.block_until_ready(loss)
    print("first step %.1fs" % (time.perf_counter() - t0), file=sys.stderr)
    loss = step.step_n(10, x, y)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    loss = step.step_n(10, x, y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print("10 steps: %.3fs -> %.1f img/s" % (dt, batch * 10 / dt),
          file=sys.stderr)

    logdir = "/tmp/rn50_trace"
    os.system("rm -rf %s" % logdir)
    with jax.profiler.trace(logdir):
        loss = step.step_n(10, x, y)
        jax.block_until_ready(loss)

    # find the xplane file
    xplane = None
    for root, _, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                xplane = os.path.join(root, f)
    print("xplane:", xplane, file=sys.stderr)


if __name__ == "__main__":
    main()
