"""Faults planted UNDER the harness, in the program's own timed path, for
the test that sees ``correct`` come out false.  Used only by
``test_chip_benchmark.py``: ``python faults.py <fault> <run.py arguments>``
plants the fault and then runs the benchmark's own ``main``.

- ``unchanged``: the optimizer's step returns weight and state as they came;
- ``half``: half of the batch is left out of the loss and the mean taken
  over the rest (sample weights 2 and 0);
- ``no_exchange``: on a mesh, what a device computes when the gradient
  exchange over ``data`` is left out: the mean over its own data shard's
  rows alone, which are the first half of the batch.  GSPMD puts that
  all-reduce in by itself and nothing in the program can take it out, so
  the fault is planted where its effect is: in which rows the loss sees.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def plant(fault):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.optimizer import optimizer as opt

    if fault == "unchanged":
        opt.AdamW._step = lambda self, weight, grad, state, lr, wd, t: \
            (weight, state)
    elif fault in ("half", "no_exchange"):
        orig = gloss.SoftmaxCrossEntropyLoss.hybrid_forward

        def first_half_only(self, F, pred, label, sample_weight=None):
            n = pred.shape[0]
            w = np.zeros((n, 1), np.float32)
            w[:n // 2] = 2.0
            return orig(self, F, pred, label, mx.nd.array(w))
        gloss.SoftmaxCrossEntropyLoss.hybrid_forward = first_half_only
    else:
        raise SystemExit("unknown fault %r" % fault)


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    if "--rehearse" not in argv:
        raise SystemExit("faults are planted in rehearsals only")
    # as run.py does before it imports jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    if fault == "no_exchange":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, CHIP)
    sys.path.insert(0, os.path.dirname(os.path.dirname(CHIP)))
    plant(fault)
    import run

    sys.exit(run.main(argv))
