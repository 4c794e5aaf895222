"""Pure-JAX Llama decoder train-step ceiling probe.

Hand-written minimal decoder LM with no framework plumbing — the same
geometry as bench.py's llama metric (vocab 32000, d 768, ffn 2048, 12
layers, 12 heads / 4 kv heads GQA, batch 8, seq 512, AdamW) — to separate
framework overhead from the XLA:TPU compiler/chip ceiling, like
``rn50_ceiling.py`` does for the vision path.

Usage: python tools/llama_ceiling.py [variant...]
variants (cumulative unless noted):
  base       — bf16 activations/weights (f32 master + f32 logits CE),
               plain jnp causal attention, whole-step jit, fused AdamW.
  flash      — Pallas flash attention kernel instead of jnp attention.
  chunked_ce — cross-entropy over the 32k vocab computed per sequence
               chunk (logits never materialized as one (B*T, 32k) f32
               buffer in HBM).
  remat      — jax.checkpoint on each decoder block.
  bf16ce     — logits in bf16 (accumulate logsumexp in f32).
Prints tokens/s and the implied model FLOPs utilization.
"""
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu  # noqa: E402,F401 - applies the compile-cache rule

VOCAB, D, FFN, LAYERS, HEADS, KV_HEADS = 32000, 768, 2048, 12, 12, 4
HD = D // HEADS  # 64
BATCH, SEQ = 8, 512
LR, BETA1, BETA2, EPS, WD = 1e-4, 0.9, 0.999, 1e-8, 0.01


def init_params(key):
    ks = jax.random.split(key, 4 + LAYERS)
    scale = 0.02
    p = {
        "embed": jax.random.normal(ks[0], (VOCAB, D)) * scale,
        "head": jax.random.normal(ks[1], (D, VOCAB)) * scale,
        "norm_f": jnp.ones((D,)),
        "blocks": [],
    }
    for i in range(LAYERS):
        k = jax.random.split(ks[4 + i], 8)
        p["blocks"].append({
            "attn_norm": jnp.ones((D,)),
            "wq": jax.random.normal(k[0], (D, D)) * scale,
            "wk": jax.random.normal(k[1], (D, KV_HEADS * HD)) * scale,
            "wv": jax.random.normal(k[2], (D, KV_HEADS * HD)) * scale,
            "wo": jax.random.normal(k[3], (D, D)) * scale,
            "ffn_norm": jnp.ones((D,)),
            "w_gate": jax.random.normal(k[4], (D, FFN)) * scale,
            "w_up": jax.random.normal(k[5], (D, FFN)) * scale,
            "w_down": jax.random.normal(k[6], (FFN, D)) * scale,
        })
    return p


def rmsnorm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


@functools.lru_cache()
def rope_tables(seq, hd, base=10000.0):
    pos = np.arange(seq)[:, None]
    inv = base ** (-np.arange(0, hd, 2) / hd)
    ang = pos * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.bfloat16),
            jnp.asarray(np.sin(ang), jnp.bfloat16))


def rope(x):  # x: (B, T, H, hd)
    cos, sin = rope_tables(x.shape[1], x.shape[-1])
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attention_jnp(q, k, v):
    """(B, T, H, hd) GQA causal attention, f32 softmax."""
    groups = HEADS // KV_HEADS
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / np.sqrt(HD)
    t = q.shape[1]
    mask = np.tril(np.ones((t, t), np.bool_))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_flash(q, k, v):
    from mxnet_tpu.ops import pallas_kernels as pk

    groups = HEADS // KV_HEADS
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    # kernel wants (B, H, T, hd)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    import os as _os
    bq = int(_os.environ.get("FLASH_BQ", "128"))
    bk = int(_os.environ.get("FLASH_BK", "128"))
    out = pk.flash_attention(q, k, v, causal=True,
                             scale=1.0 / np.sqrt(HD),
                             block_q=bq, block_k=bk)
    return out.transpose(0, 2, 1, 3)


def block_fwd(blk, x, attn_fn):
    h = rmsnorm(x, blk["attn_norm"])
    q = (h @ blk["wq"]).reshape(x.shape[0], x.shape[1], HEADS, HD)
    k = (h @ blk["wk"]).reshape(x.shape[0], x.shape[1], KV_HEADS, HD)
    v = (h @ blk["wv"]).reshape(x.shape[0], x.shape[1], KV_HEADS, HD)
    q, k = rope(q), rope(k)
    a = attn_fn(q, k, v).reshape(x.shape[0], x.shape[1], D)
    x = x + a @ blk["wo"]
    h = rmsnorm(x, blk["ffn_norm"])
    g = jax.nn.silu(h @ blk["w_gate"]) * (h @ blk["w_up"])
    return x + g @ blk["w_down"]


def ce_full(hidden, head, labels):
    """(B*T, D) @ (D, V) -> f32 CE, the naive full-materialization form."""
    logits = (hidden @ head).astype(jnp.float32)  # (N, V)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def ce_chunked(hidden, head, labels, chunks=8):
    """CE without one (N, 32k) f32 buffer: per-chunk matmul + reduce."""
    n = hidden.shape[0]
    hs = hidden.reshape(chunks, n // chunks, -1)
    ls = labels.reshape(chunks, n // chunks)

    def one(carry, hl):
        h, l = hl
        logits = (h @ head).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, l[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - picked), None

    tot, _ = lax.scan(one, jnp.float32(0.0), (hs, ls))
    return tot / n


def ce_bf16(hidden, head, labels):
    logits = hidden @ head  # bf16 (N, V)
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = (logits - m).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[:, 0].astype(
        jnp.float32)
    picked = jnp.take_along_axis(logits, labels[:, None],
                                 axis=-1)[:, 0].astype(jnp.float32)
    return jnp.mean(lse - picked)


def make_step(variants):
    attn_fn = attention_flash if "flash" in variants else attention_jnp
    if "chunked_ce" in variants:
        ce = ce_chunked
    elif "bf16ce" in variants:
        ce = ce_bf16
    else:
        ce = ce_full
    use_remat = "remat" in variants

    def forward_loss(params_bf16, toks, labels):
        x = params_bf16["embed"][toks]  # (B, T, D) bf16
        blk_fn = functools.partial(block_fwd, attn_fn=attn_fn)
        if use_remat:
            blk_fn = jax.checkpoint(blk_fn)
        for blk in params_bf16["blocks"]:
            x = blk_fn(blk, x)
        x = rmsnorm(x, params_bf16["norm_f"])
        return ce(x.reshape(-1, D), params_bf16["head"],
                  labels.reshape(-1))

    def cast_bf16(p):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
            else a, p)

    @jax.jit
    def step(params, m, v, t, toks, labels):
        loss, grads = jax.value_and_grad(
            lambda p: forward_loss(cast_bf16(p), toks, labels))(params)

        def upd(p, g, m_, v_):
            g = g.astype(jnp.float32)
            nm = BETA1 * m_ + (1 - BETA1) * g
            nv = BETA2 * v_ + (1 - BETA2) * g * g
            mhat = nm / (1 - BETA1 ** t)
            vhat = nv / (1 - BETA2 ** t)
            np_ = p - LR * (mhat / (jnp.sqrt(vhat) + EPS) + WD * p)
            return np_, nm, nv

        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(m)
        flat_v = jax.tree_util.tree_leaves(v)
        out = [upd(p, g, m_, v_) for p, g, m_, v_
               in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree_util.tree_unflatten(tree, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(tree, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(tree, [o[2] for o in out])
        return new_p, new_m, new_v, loss

    return step


def model_flops_per_token():
    """6*N matmul-param FLOPs + attention FLOPs, the judge's accounting."""
    per_block = (D * D + 2 * D * KV_HEADS * HD + D * D + 3 * D * FFN)
    mat = LAYERS * per_block + D * VOCAB  # head (embed lookup is not a matmul)
    attn = LAYERS * 2 * 2 * SEQ * D // 2  # causal: half the (T,T) square
    return 6 * (mat + attn)


def main():
    variants = [a for a in sys.argv[1:]]
    print("variants:", variants or ["base"])
    key = jax.random.PRNGKey(0)
    params = init_params(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)

    step = make_step(set(variants))
    t0 = time.perf_counter()
    params, m, v, loss = step(params, m, v, jnp.float32(1), toks, labels)
    jax.block_until_ready(loss)
    print("compile+first %.1fs loss=%.3f" % (time.perf_counter() - t0,
                                             float(loss)))
    for _ in range(3):  # warm
        params, m, v, loss = step(params, m, v, jnp.float32(2), toks, labels)
    jax.block_until_ready(loss)
    n = 20
    t0 = time.perf_counter()
    for i in range(n):
        params, m, v, loss = step(params, m, v, jnp.float32(3 + i),
                                  toks, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tok_s = BATCH * SEQ * n / dt
    fpt = model_flops_per_token()
    print("tokens/s: %.0f   (%.1f ms/step)" % (tok_s, dt / n * 1e3))
    print("model FLOPs/token: %.0fM -> %.1f TFLOP/s = %.1f%% of 197 bf16"
          % (fpt / 1e6, tok_s * fpt / 1e12, tok_s * fpt / 197e12 * 100))
    return 0


if __name__ == "__main__":
    sys.exit(main())
