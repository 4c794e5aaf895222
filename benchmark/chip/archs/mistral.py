"""``model_type`` ``mistral``: a Llama-shaped dense decoder (RMSNorm,
rotate-half RoPE, grouped-query causal attention, SwiGLU, no biases, the
head untied or tied to the embedding).

Departures from the published model code, none of which changes a value:
Mistral's ``sliding_window`` 4096 is never reached at the sequence lengths
run here and is not implemented; dropout is 0 in both published configs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from flops import head_dim

PER_LAYER = ("attn_norm", "q", "k", "v", "o", "ffn_norm", "gate", "up",
             "down")


# -- the plain reference's equations ------------------------------------------

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


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``; ``ein(spec, a, b)``
    is every matrix product."""
    names = [n for n, _ in leaf_specs(cfg)]
    w = dict(zip(names, leaves))
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


# -- the count, from shapes alone ------------------------------------------------

def layer_params(cfg):
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and the
    two RMSNorm gains (no biases)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = h * cfg["num_key_value_heads"] * d
    return 2 * q + 2 * kv + 3 * h * cfg["intermediate_size"] + 2 * h


def param_count(cfg):
    """All parameters: embedding, layers, final norm and, where the
    embeddings are not tied, the output head."""
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg.get("tie_word_embeddings") else emb
    return (emb + cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] + head)


def matmul_params(cfg):
    """Parameters that multiply every token: the layers' matrices and the
    head (tied or not); the embedding lookup and the norm gains do not."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * h)
            + cfg["vocab_size"] * h)


def attention_flops_per_token(cfg, seq):
    """QK^T and PV of one forward pass, all layers, per token of a
    sequence of ``seq`` tokens; the causal mask halves what is needed."""
    units = cfg["num_attention_heads"] * head_dim(cfg)
    return 4 * seq * units * cfg["num_hidden_layers"] // 2


def train_flops_per_token(cfg, seq):
    """Forward + backward (2 + 4 operations per multiply-add), no
    recomputation: ``6 * matmul_params`` plus three times the causal
    attention of a forward pass."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_per_token(cfg, seq)


# -- the program -----------------------------------------------------------------

def build(cfg, ctx):
    """``gluon.model_zoo.llama.LlamaModel`` at the configuration's sizes,
    zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import llama

    net = llama.LlamaModel(
        cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        rope_base=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings")))
    net.initialize(mx.init.Zero(), ctx=ctx)
    # one tiny forward resolves the Dense layers' deferred shapes
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
