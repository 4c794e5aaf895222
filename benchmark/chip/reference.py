"""The plain reference: any decoder's token-mean cross-entropy, its
gradients and AdamW, in straightforward ``jax.numpy`` and float32 with
every matrix product at ``highest`` precision.  The decoder itself (the
shapes of its weights and its logits) is the configuration's architecture
module, ``archs/<model_type>.py``, which is handed the matrix product to
use.

It imports nothing of the program and takes nothing the program has made.
The weights are the benchmark's own, made from the seed leaf by leaf
(``make_leaf``), so that the program can be handed the same ones and a
leaf can be made again later without keeping a copy.

``precision="fp8"`` is the control of ``correct``: the same mathematics
with every matrix product's operands rounded to an 8-bit float (e4m3
forward, e5m2 for the cotangents; one scale a tensor), the step below the
bfloat16 the configurations state.  It exists to be put in the program's
place and to fail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

import archs

HIGHEST = lax.Precision.HIGHEST


def leaf_specs(cfg):
    """``[(name, shape)]`` of the weights, in the architecture's order."""
    return archs.of(cfg).leaf_specs(cfg)


def seed_key(seed):
    """A key from any whole number up to 2**63 (a driver's seeds pass
    2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_leaf(key, index, shape):
    """Leaf ``index`` of the weights: Xavier-uniform for a matrix (over its
    last two axes: a stack of matrices is so many of them), ones for a
    norm's gain.  Traceable; float32."""
    if len(shape) == 1:
        return jnp.ones(shape, jnp.float32)
    a = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(jax.random.fold_in(key, index), shape,
                              jnp.float32, -a, a)


def make_weights(cfg, seed, shardings=None):
    """Every leaf, on the device, in one jitted call."""
    specs = leaf_specs(cfg)

    def build(key):
        return [make_leaf(key, i, s) for i, (_, s) in enumerate(specs)]
    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


# -- lower precision for the control ---------------------------------------

def _round8(x, exponent_bits, mantissa_bits, top):
    """``x`` rounded to an 8-bit float, one scale for the tensor."""
    s = lax.stop_gradient(jnp.max(jnp.abs(x))) / top
    s = jnp.where(s > 0, s, 1.0)
    return lax.reduce_precision(x / s, exponent_bits, mantissa_bits) * s


_e4m3 = functools.partial(_round8, exponent_bits=4, mantissa_bits=3, top=240.0)
_e5m2 = functools.partial(_round8, exponent_bits=5, mantissa_bits=2,
                          top=57344.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fp8_einsum(a, b, spec):
    return jnp.einsum(spec, _e4m3(a), _e4m3(b), precision=HIGHEST)


def _fp8_fwd(a, b, spec):
    return _fp8_einsum(a, b, spec), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
        _e4m3(a), _e4m3(b))
    return vjp(_e5m2(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _einsum(precision):
    if precision == "fp8":
        return lambda spec, a, b: _fp8_einsum(a, b, spec)
    if precision == "float32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    raise ValueError("unknown precision %r" % (precision,))


# -- the model --------------------------------------------------------------

def forward(cfg, weights, tokens, precision="float32"):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``."""
    return archs.of(cfg).forward(cfg, weights, tokens, _einsum(precision))


def loss_fn(cfg, weights, tokens, labels, precision="float32"):
    """Mean over every token of the cross-entropy of its label."""
    logp = jax.nn.log_softmax(forward(cfg, weights, tokens, precision), -1)
    picked = jnp.take_along_axis(
        logp.reshape(-1, logp.shape[-1]), labels.reshape(-1, 1), axis=1)
    return -jnp.mean(picked)


def adamw(opt, w, g, m, v, t):
    """Decoupled weight decay Adam (Loshchilov & Hutter), bias-corrected;
    ``t`` counts from 1."""
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    lr_t = opt["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = w - lr_t * m / (jnp.sqrt(v) + opt["epsilon"]) \
        - opt["learning_rate"] * opt["wd"] * w
    return w, m, v


def norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves]


def make_step(cfg, opt, precision="float32", rows=None):
    """``step(weights, m, v, t, tokens, labels) -> (weights, m, v, loss,
    gradient norms)``, jitted, the state donated.  ``rows`` plants the
    half-batch fault: only the first ``rows`` rows of the batch count, and
    the mean is taken over them."""
    def step(weights, m, v, t, tokens, labels):
        if rows is not None:
            labels = labels.reshape(tokens.shape)[:rows]
            tokens = tokens[:rows]
        loss, grads = jax.value_and_grad(
            lambda ws: loss_fn(cfg, ws, tokens, labels, precision))(weights)
        tf = t.astype(jnp.float32)
        out = [adamw(opt, w, g, mi, vi, tf)
               for w, g, mi, vi in zip(weights, grads, m, v)]
        return ([o[0] for o in out], [o[1] for o in out],
                [o[2] for o in out], loss, norms(grads))
    return jax.jit(step, donate_argnums=(0, 1, 2))


def delta_norms(cfg, seed, weights):
    """``||w - w0||`` of every leaf, ``w0`` made again from the seed one
    leaf at a time (no second copy of the weights is ever held)."""
    key = seed_key(seed)
    fns = {}
    out = []
    for i, ((_, shape), w) in enumerate(zip(leaf_specs(cfg), weights)):
        if shape not in fns:
            fns[shape] = jax.jit(
                lambda w, key, i, shape=shape: jnp.sqrt(jnp.sum(jnp.square(
                    w.astype(jnp.float32) - make_leaf(key, i, shape)))))
        out.append(fns[shape](w, key, jnp.int32(i)))
    return out


def train_readings(cfg, opt, seed, batches, precision="float32", rows=None,
                   shardings=None, skip_update=False):
    """Follow ``batches`` (``[(tokens, labels)]``, int32) from the seed's
    weights: each step's loss, the first step's gradient norm of every
    leaf, and every leaf's change after the last step.  ``skip_update``
    plants the fault of a step that returns its state unchanged."""
    weights = make_weights(cfg, seed, shardings)
    zeros = jax.jit(lambda ws: [jnp.zeros_like(w) for w in ws],
                    out_shardings=shardings)
    m, v = zeros(weights), zeros(weights)
    step = make_step(cfg, opt, precision, rows)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, 1):
        new_w, m, v, loss, gn = step(weights, m, v, jnp.int32(t),
                                     jnp.asarray(tokens, jnp.int32),
                                     jnp.asarray(labels, jnp.int32))
        weights = make_weights(cfg, seed, shardings) if skip_update \
            else new_w
        losses.append(float(loss))
        if t == 1:
            grad_norms = [float(x) for x in gn]
    deltas = [float(x) for x in delta_norms(cfg, seed, weights)]
    del weights, m, v
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": deltas}
