"""Parallel / mesh tests — run on the 8-device virtual CPU mesh
(model: tests/python/gpu/test_kvstore_gpu.py + nightly dist tests,
re-targeted at jax.sharding)."""
import numpy as np
import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from jax.sharding import PartitionSpec as P


def _mlp(units=16, classes=4, in_units=8):
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation='relu', in_units=in_units),
            nn.BatchNorm(in_channels=units),
            nn.Dense(classes, in_units=units))
    net.initialize(mx.init.Xavier())
    return net


def test_make_mesh():
    mesh = parallel.make_mesh()
    assert mesh.shape['data'] == 8
    mesh2 = parallel.make_mesh({'data': 2, 'model': -1})
    assert mesh2.shape['model'] == 4


def test_no_tpu_is_an_error_unless_told_cpu(monkeypatch):
    """No CPU fallback: where the process was not told JAX_PLATFORMS=cpu,
    a missing accelerator is an error, in the context and in the step."""
    from mxnet_tpu import context
    from mxnet_tpu.base import MXNetError

    # this process was told JAX_PLATFORMS=cpu; take that away (in
    # process: a child with the variable unset would load libtpu)
    monkeypatch.setattr(context, "_told_cpu", lambda: False)
    with pytest.raises(MXNetError, match="no accelerator"):
        context._best_context()
    step = parallel.JitTrainStep(_mlp(), gluon.loss.SoftmaxCrossEntropyLoss(),
                                 'sgd', {'learning_rate': 0.1})
    with pytest.raises(MXNetError, match="no accelerator"):
        step.step(np.zeros((4, 8), 'float32'), np.zeros(4, 'float32'))


def test_jit_train_step_single_matches_trainer():
    """JitTrainStep must agree numerically with the imperative path."""
    np.random.seed(0)
    X = np.random.rand(32, 8).astype('float32')
    Y = np.random.randint(0, 4, 32).astype('float32')

    mx.random.seed(7)
    net_a = _mlp()
    # clone weights into second net
    mx.random.seed(7)
    net_b = _mlp()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # path A: imperative trainer (mean loss => rescale 1/batch handled
    # by taking mean gradient: use batch_size scaling identical below)
    trainer = gluon.Trainer(net_a.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    for _ in range(3):
        with mx.autograd.record():
            out = net_a(mx.nd.array(X))
            loss = loss_fn(out, mx.nd.array(Y))
        loss.backward()
        trainer.step(X.shape[0])

    # path B: one-executable step
    step = parallel.JitTrainStep(net_b, loss_fn, 'sgd',
                                 {'learning_rate': 0.1})
    for _ in range(3):
        step.step(mx.nd.array(X), mx.nd.array(Y))
    step.sync_params()

    pa = [v.data().asnumpy() for v in net_a.collect_params().values()]
    pb = [v.data().asnumpy() for v in net_b.collect_params().values()]
    assert len(pa) == len(pb)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_jit_train_step_data_parallel():
    """dp over the 8-device mesh: loss decreases, params stay replicated."""
    np.random.seed(1)
    X = np.random.rand(64, 8).astype('float32')
    w = np.random.rand(8, 4).astype('float32')
    Y = np.argmax(X @ w, axis=1).astype('float32')

    net = _mlp()
    mesh = parallel.make_mesh()
    step = parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'sgd',
        {'learning_rate': 0.5, 'momentum': 0.9}, mesh=mesh)
    losses = []
    for _ in range(30):
        losses.append(float(step.step(X, Y)))
    assert losses[-1] < losses[0] * 0.5, losses[:3] + losses[-3:]


def test_jit_train_step_tensor_parallel():
    """tp: shard dense weights over the 'model' axis via param_rule."""
    np.random.seed(2)
    X = np.random.rand(16, 8).astype('float32')
    Y = np.random.randint(0, 4, 16).astype('float32')

    net = _mlp(units=32)
    mesh = parallel.make_mesh({'data': 2, 'model': 4})

    def rule(name, shape):
        # Dense weights are (units, in): shard units over 'model'
        if 'weight' in name and len(shape) == 2 and shape[0] % 4 == 0:
            return P('model', None)
        return None

    step = parallel.JitTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), 'adam',
        {'learning_rate': 0.01}, mesh=mesh, param_rule=rule)
    l0 = float(step.step(X, Y))
    for _ in range(10):
        l = float(step.step(X, Y))
    assert np.isfinite(l)
    assert l < l0


def test_shard_params_helper():
    mesh = parallel.make_mesh({'data': 2, 'model': 4})
    params = {'w': np.zeros((8, 8), np.float32),
              'b': np.zeros((8,), np.float32)}
    out = parallel.shard_params(
        mesh, params,
        rule=lambda n, s: P('model', None) if n == 'w' else None)
    assert out['w'].sharding.spec == P('model', None)


def test_step_n_device_loop():
    """n steps in one dispatch (lax.fori_loop) match n separate steps."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    def train(use_loop):
        mx.random.seed(5)
        net = gluon.nn.Dense(4)
        net.initialize(mx.init.Xavier())
        step = parallel.JitTrainStep(
            net, gluon.loss.L2Loss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9})
        rs = np.random.RandomState(0)
        x = rs.randn(8, 6).astype(np.float32)
        y = rs.randn(8, 4).astype(np.float32)
        if use_loop:
            loss = step.step_n(6, x, y)
        else:
            for _ in range(6):
                loss = step.step(x, y)
        step.sync_params()
        return float(loss), net.weight.data().asnumpy()

    l_loop, w_loop = train(True)
    l_ref, w_ref = train(False)
    assert abs(l_loop - l_ref) < 1e-5
    assert np.allclose(w_loop, w_ref, rtol=1e-5, atol=1e-6)


def test_step_n_adam_matches_step():
    """Adam's t-dependent bias correction must match across the two paths."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    def train(use_loop):
        mx.random.seed(6)
        net = gluon.nn.Dense(3)
        net.initialize(mx.init.Xavier())
        step = parallel.JitTrainStep(
            net, gluon.loss.L2Loss(), "adam", {"learning_rate": 0.05})
        rs = np.random.RandomState(1)
        x = rs.randn(8, 5).astype(np.float32)
        y = rs.randn(8, 3).astype(np.float32)
        if use_loop:
            loss = step.step_n(5, x, y)
        else:
            for _ in range(5):
                loss = step.step(x, y)
        step.sync_params()
        return float(loss), net.weight.data().asnumpy()

    l_loop, w_loop = train(True)
    l_ref, w_ref = train(False)
    assert np.isfinite(l_loop)
    assert abs(l_loop - l_ref) < 1e-5
    assert np.allclose(w_loop, w_ref, rtol=1e-5, atol=1e-6)


def test_step_n_with_lr_scheduler_device_side():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    mx.random.seed(7)
    net = gluon.nn.Dense(2)
    net.initialize(mx.init.Xavier())
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    step = parallel.JitTrainStep(
        net, gluon.loss.L2Loss(), "sgd",
        {"learning_rate": 0.1, "lr_scheduler": sched})
    rs = np.random.RandomState(2)
    x = rs.randn(4, 3).astype(np.float32)
    y = rs.randn(4, 2).astype(np.float32)
    loss = step.step_n(4, x, y)
    assert np.isfinite(float(loss))
    assert step._t == 4
    # the schedule must have been applied DEVICE-side (no fallback):
    # compare against an identical model driven by per-step dispatch
    mx.random.seed(7)
    net2 = gluon.nn.Dense(2)
    net2.initialize(mx.init.Xavier())
    sched2 = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    step2 = parallel.JitTrainStep(
        net2, gluon.loss.L2Loss(), "sgd",
        {"learning_rate": 0.1, "lr_scheduler": sched2})
    for _ in range(4):
        step2.step(x, y)
    for a, b in zip(step._weights, step2._weights):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_lr_scheduler_traced_matches_eager():
    import mxnet_tpu as mx
    import jax.numpy as jnp

    scheds = [
        mx.lr_scheduler.FactorScheduler(step=5, factor=0.5, base_lr=0.4,
                                        warmup_steps=3, warmup_begin_lr=0.1),
        mx.lr_scheduler.MultiFactorScheduler(step=[4, 9], factor=0.1,
                                             base_lr=1.0),
        mx.lr_scheduler.PolyScheduler(max_update=12, base_lr=0.5, pwr=2,
                                      final_lr=0.01),
        mx.lr_scheduler.CosineScheduler(max_update=12, base_lr=0.5,
                                        final_lr=0.01, warmup_steps=2),
    ]
    for sched in scheds:
        traced = [float(sched.traced(jnp.asarray(t, jnp.int32)))
                  for t in range(1, 15)]
        eager = [float(sched(t)) for t in range(1, 15)]
        np.testing.assert_allclose(traced, eager, rtol=1e-5, atol=1e-7,
                                   err_msg=type(sched).__name__)


def test_jit_train_step_checkpoint_resume(tmp_path):
    """save_states/load_states: resuming reproduces uninterrupted
    training exactly (weights, Adam moments, bias-correction t)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    def make():
        mx.random.seed(11)
        net = gluon.nn.Dense(3)
        net.initialize(mx.init.Xavier())
        return parallel.JitTrainStep(net, gluon.loss.L2Loss(), "adam",
                                     {"learning_rate": 0.05})

    rs = np.random.RandomState(3)
    x = rs.randn(8, 5).astype(np.float32)
    y = rs.randn(8, 3).astype(np.float32)

    # uninterrupted: 10 steps
    a = make()
    for _ in range(10):
        a.step(x, y)

    # interrupted: 4 steps, checkpoint, fresh object, resume 6 more
    b = make()
    for _ in range(4):
        b.step(x, y)
    ckpt = str(tmp_path / "state.ckpt")
    b.save_states(ckpt)

    c = make()
    c.step(x, y)  # establish placement (overwritten by load)
    c.load_states(ckpt)
    assert c._t == 4
    for _ in range(6):
        c.step(x, y)

    for wa, wc in zip(a._weights, c._weights):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wc),
                                   rtol=1e-6, atol=1e-7)
