"""The plain reference: a Llama-shaped decoder (RMSNorm, rotate-half RoPE,
grouped-query causal attention, SwiGLU, no biases), its token-mean
cross-entropy, its gradients and AdamW, in straightforward ``jax.numpy``
and float32 with every matrix product at ``highest`` precision.

It imports nothing of the program and takes nothing the program has made.
The weights are the benchmark's own, made from the seed leaf by leaf
(``make_leaf``), so that the program can be handed the same ones and a
leaf can be made again later without keeping a copy.

``precision="fp8"`` is the control of ``correct``: the same mathematics
with every matrix product's operands rounded to an 8-bit float (e4m3
forward, e5m2 for the cotangents; one scale a tensor), the step below the
bfloat16 the configurations state.  It exists to be put in the program's
place and to fail.

Departures from the published model code, none of which changes a value:
Mistral's ``sliding_window`` 4096 is never reached at the sequence lengths
run here and is not implemented; dropout is 0 in both published configs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PER_LAYER = ("attn_norm", "q", "k", "v", "o", "ffn_norm", "gate", "up",
             "down")


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def leaf_specs(cfg):
    """``[(name, shape)]`` in the order the decoder is written down:
    embedding, the layers, final norm, head.  Dense weights are
    ``(out, in)``, applied as ``x @ W.T``."""
    h, d, f = cfg["hidden_size"], head_dim(cfg), cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    per = {"attn_norm": (h,), "q": (q, h), "k": (kv, h), "v": (kv, h),
           "o": (h, q), "ffn_norm": (h,), "gate": (f, h), "up": (f, h),
           "down": (h, f)}
    specs = [("embed", (cfg["vocab_size"], h))]
    for i in range(cfg["num_hidden_layers"]):
        specs += [("layer%d.%s" % (i, k), per[k]) for k in PER_LAYER]
    specs.append(("norm", (h,)))
    if not cfg.get("tie_word_embeddings"):
        specs.append(("head", (cfg["vocab_size"], h)))
    return specs


def seed_key(seed):
    """A key from any whole number up to 2**63 (a driver's seeds pass
    2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_leaf(key, index, shape):
    """Leaf ``index`` of the weights: Xavier-uniform for a matrix, ones
    for a norm's gain.  Traceable; float32."""
    if len(shape) == 1:
        return jnp.ones(shape, jnp.float32)
    a = math.sqrt(6.0 / (shape[0] + shape[1]))
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

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotate-half rotary embedding on ``(B, T, H, D)``."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-2.0 / d) * math.log(theta))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(cfg, weights, tokens, precision="float32"):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``."""
    ein = _einsum(precision)
    names = [n for n, _ in leaf_specs(cfg)]
    w = dict(zip(names, weights))
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t = tokens.shape
    mask = jnp.tril(jnp.ones((t, t), bool))
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        lw = {k: w["layer%d.%s" % (i, k)] for k in PER_LAYER}
        h = _rmsnorm(x, lw["attn_norm"], eps)
        q = _rope(ein("bti,oi->bto", h, lw["q"]).reshape(b, t, nh, d), theta)
        k = _rope(ein("bti,oi->bto", h, lw["k"]).reshape(b, t, nkv, d), theta)
        v = ein("bti,oi->bto", h, lw["v"]).reshape(b, t, nkv, d)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        s = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = ein("bhqk,bkhd->bqhd", p, v).reshape(b, t, nh * d)
        x = x + ein("bti,oi->bto", a, lw["o"])
        h = _rmsnorm(x, lw["ffn_norm"], eps)
        g = jax.nn.silu(ein("bti,oi->bto", h, lw["gate"]))
        x = x + ein("bti,oi->bto", g * ein("bti,oi->bto", h, lw["up"]),
                    lw["down"])
    x = _rmsnorm(x, w["norm"], eps)
    return ein("bti,vi->btv", x, w.get("head", w["embed"]))


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
