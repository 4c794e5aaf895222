"""AMP — automatic mixed precision (parity: python/mxnet/contrib/amp/amp.py).

Reference mechanism: a graph pass (``low_precision_pass.cc``) rewrites the
symbol with ``amp_cast``/``amp_multicast`` around ops according to
allow/deny lists, plus a dynamic ``LossScaler`` folded into backward.

TPU-native mechanism: one dispatch-time dtype rewrite at the op registry
choke point (``ops/registry._prep``) — every op invocation, imperative OR
inside a ``hybridize()``/``JitTrainStep`` trace, passes through it, so a
single hook covers both execution modes (no graph rewrite needed; XLA
fuses the inserted converts for free).  Target dtype defaults to
bfloat16, the MXU-native type; float16 + dynamic loss scaling is kept
for parity.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from ..base import MXNetError
from . import lists
from .loss_scaler import LossScaler

_state = {
    "active": False,
    "target_dtype": None,
    "target_ops": frozenset(),
    "fp32_ops": frozenset(),
    "widest_ops": frozenset(),
    "target_keep": {},
}

_LOW = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def init(target_dtype="bfloat16", target_precision_ops=None,
         conditional_fp32_ops=None, fp32_ops=None):
    """Enable AMP process-wide (parity: amp.py:161).

    target_precision_ops / fp32_ops extend the default lists;
    conditional_fp32_ops is accepted for API parity (treated as fp32).
    """
    if target_dtype not in ("bfloat16", "float16"):
        raise MXNetError("AMP target_dtype must be bfloat16 or float16")
    target = set(lists.TARGET_DTYPE_OPS) | set(target_precision_ops or ())
    fp32 = set(lists.FP32_OPS) | set(fp32_ops or ())
    if conditional_fp32_ops:
        fp32 |= {op for op, _, _ in conditional_fp32_ops} \
            if isinstance(next(iter(conditional_fp32_ops)), tuple) \
            else set(conditional_fp32_ops)
    _state.update(
        active=True,
        target_dtype=jnp.dtype(target_dtype),
        target_ops=frozenset(target),
        fp32_ops=frozenset(fp32),
        widest_ops=frozenset(lists.WIDEST_TYPE_CASTS),
        target_keep=dict(lists.TARGET_DTYPE_KEEP),
    )


def turn_off():
    _state["active"] = False


def is_active():
    return _state["active"]


def transform_inputs(op_name, datas):
    """Dispatch-time dtype rewrite; called from ops/registry._prep."""
    if not _state["active"]:
        return datas
    if op_name in _state["target_ops"]:
        tgt = _state["target_dtype"]
        keep = _state["target_keep"].get(op_name, ())
        return tuple(
            d.astype(tgt)
            if hasattr(d, "dtype") and d.dtype in (jnp.float32,) + _LOW
            and d.dtype != tgt and i not in keep else d
            for i, d in enumerate(datas))
    if op_name in _state["fp32_ops"]:
        return tuple(
            d.astype(jnp.float32)
            if hasattr(d, "dtype") and d.dtype in _LOW else d
            for d in datas)
    if op_name in _state["widest_ops"]:
        fl = [d.dtype for d in datas
              if hasattr(d, "dtype")
              and d.dtype in (jnp.dtype(jnp.float32),) + _LOW]
        if len(set(fl)) > 1:
            widest = jnp.dtype(jnp.float32) if jnp.dtype(jnp.float32) in fl \
                else fl[0]
            return tuple(
                d.astype(widest)
                if hasattr(d, "dtype") and d.dtype in _LOW + (
                    jnp.dtype(jnp.float32),) and d.dtype != widest else d
                for d in datas)
    return datas


def convert_hybrid_block(net, target_dtype="bfloat16"):
    """Cast a Gluon block's parameters for AMP execution (parity:
    amp.convert_hybrid_block).  Also enables AMP if not yet active."""
    if not _state["active"]:
        init(target_dtype)
    net.cast(target_dtype)
    return net


def init_trainer(trainer):
    """Attach a dynamic LossScaler to a Trainer (parity: amp.py:305).

    Wraps ``trainer.step`` so overflowed iterations are skipped and the
    scale adapts.
    """
    if getattr(trainer, "_amp_loss_scaler", None) is not None:
        return
    scaler = LossScaler()
    trainer._amp_loss_scaler = scaler
    orig_step = trainer.step

    def step(batch_size, ignore_stale_grad=False):
        params = [p for p in trainer._params]
        overflow = scaler.has_overflow(params)
        skip = scaler.update_scale(overflow)
        if skip:
            for p in params:
                if p.grad_req != "null":
                    p.zero_grad()
            return
        orig_step(batch_size, ignore_stale_grad=ignore_stale_grad)

    trainer.step = step


def unscale(trainer):
    """Divide current grads by the loss scale (parity: amp.py:406)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        return
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p.grad_req == "null" or p._data is None:
            continue
        raw = p._data._grad
        if raw is None:
            continue
        # write the raw grad buffer (Parameter.grad() returns a fresh
        # wrapper; mutating it would be a no-op)
        p._data._grad = raw * inv
    # grads are now unscaled — undo the 1/loss_scale folded into step()
    if hasattr(trainer, "_amp_orig_scale"):
        trainer._scale = trainer._amp_orig_scale


@contextlib.contextmanager
def scale_loss(loss, trainer):
    """Scale the loss and fold 1/scale into the optimizer's rescale_grad
    (parity: amp.py:380)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        init_trainer(trainer)
        scaler = trainer._amp_loss_scaler
    if not hasattr(trainer, "_amp_orig_scale"):
        trainer._amp_orig_scale = trainer._scale
    # step() divides by batch_size on top of _scale, so folding 1/loss_scale
    # into _scale makes grads come out unscaled after the update
    trainer._scale = trainer._amp_orig_scale / scaler.loss_scale
    if isinstance(loss, (list, tuple)):
        yield [l * scaler.loss_scale for l in loss]
    else:
        yield loss * scaler.loss_scale
