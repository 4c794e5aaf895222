"""``model_type`` ``sdar_moe``: the Qwen3-MoE decoder trained as a
block-diffusion model (SDAR; the objective of BD3-LM, Arriola et al.,
"Block Diffusion", arXiv:2503.09573).  The keys are the published
``config.json``'s.

- **noise** (``block_length`` ``L``, assumed): the ``T`` tokens ``x_0`` are
  cut into blocks of ``L``; block ``j`` gets a level ``t_j`` uniform in
  ``[T_MIN, 1]`` and each of its tokens is replaced by the vocabulary's last
  id where a uniform draw is below ``t_j`` (``noise``: keys from
  ``fold_in(PRNGKey(noise_seed), sum of the ids)``, a function of the batch
  alone, drawn alike by the program).
- the model reads ``[x_0 | x_t]``, ``2T`` positions; ``x = x + attn(
  RMSNorm(x)); x = x + moe(RMSNorm(x))`` a layer; after the last an RMSNorm
  and the untied head, over the noised half only: logits ``(B, T, V)``.
- attention: ``num_attention_heads`` query heads and ``num_key_value_heads``
  key and value heads of ``head_dim``, no bias; an RMSNorm over each head's
  channels of q and k (``q_norm``, ``k_norm``; ``rms_norm_eps``), then a
  rotary embedding at positions ``0 .. T-1`` in EACH half, the rotation of
  halves (``x_i, x_{i+D/2}`` a pair, ``phi = t * rope_theta ** (-2i / D)``);
  query head ``h`` reads key head ``h // (H / KV)``; softmax at ``head_dim **
  -0.5`` under the block-diffusion mask (``visible``): a clean query sees
  the clean keys of its block and the blocks before it, a noised query the
  clean keys of the blocks strictly before its own and the noised keys of
  its own block.
- routed feed-forward (Qwen3-MoE): ``p = softmax(u W_r^T)`` over all
  ``router_num_experts``; the ``num_experts_per_tok`` largest of ``p``
  (ties to the lower index); weights ``p[chosen]`` over their sum
  (``norm_topk_prob``); expert ``e`` is ``down_e(silu(gate_e u) * up_e u)``,
  gate and up stacked in one leaf; no shared expert.  **The share**:
  ``num_experts`` counts the experts held here, ``held_experts_first .. +
  num_experts - 1`` of the ``router_num_experts`` the router chooses
  among; what the absent experts would add is left out, here as in the
  program.

``moe_router_force_load_balancing`` (``route``) takes the choice of experts
from the scores and gives it to fixed pseudo-random numbers, so that every
seed's weights route alike.

``jax.checkpoint`` around each sub-block, around blocks of the attention's
queries and around chunks of the experts' tokens bounds what the gradient
keeps (a ``(heads, 8192, 8192)`` float32 score matrix is 8.6 GB); it
changes no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256        # queries of the attention under one checkpoint
EXPERT_ROWS = 2048       # tokens of the experts' products under one
T_MIN = 1e-3             # the least noise level of a block (assumed)


# -- sizes ---------------------------------------------------------------------

def _check(cfg):
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer is routed here (mlp_only_layers [], "
                         "decoder_sparse_step 1)")
    if cfg.get("use_sliding_window") or cfg.get("rope_scaling") \
            or cfg.get("tie_word_embeddings") or cfg.get("attention_bias"):
        raise ValueError("no window, no rope scaling, an untied head, no "
                         "bias")


def _specs(cfg, kind):
    h = cfg["hidden_size"]
    if kind == "attn":
        nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        return [("q", (nh * d, h)), ("k", (kv * d, h)), ("v", (kv * d, h)),
                ("q_norm", (d,)), ("k_norm", (d,)), ("o", (h, nh * d))]
    if kind == "moe":
        held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        return [("router", (cfg["router_num_experts"], h)),
                ("gate_up", (held, 2 * f, h)), ("down", (held, h, f))]
    raise ValueError("no sub-block %r" % kind)


def leaf_specs(cfg):
    """``[(name, shape)]``: embedding; a layer's attention norm, attention,
    feed-forward norm, experts; final norm, head.  Dense weights are ``(out,
    in)``; a layer's experts are stacked ``(held, out, in)``; gate and up
    are one leaf, the gate's rows first."""
    _check(cfg)
    h = cfg["hidden_size"]
    specs = [("embed", (cfg["vocab_size"], h))]
    for i in range(cfg["num_hidden_layers"]):
        for part in ("attn", "moe"):
            specs.append(("layer%d.%s_norm" % (i, part), (h,)))
            specs += [("layer%d.%s.%s" % (i, part, k), s)
                      for k, s in _specs(cfg, part)]
    return specs + [("norm", (h,)), ("head", (cfg["vocab_size"], h))]


# -- the noise ---------------------------------------------------------------------

def noise(cfg, tokens):
    """``(x_t, masked (float32 0 / 1), t)``, each ``(B, T)``: the noised
    ids, the positions masked and the level of each position's block."""
    b, t = tokens.shape
    block = cfg["block_length"]
    ids = tokens.astype(jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(int(cfg["noise_seed"])),
                             jnp.sum(ids, dtype=jnp.int32))
    k_level, k_mask = jax.random.split(key)
    level = jax.random.uniform(k_level, (b, t // block), jnp.float32,
                               T_MIN, 1.0)
    level = jnp.repeat(level, block, axis=1)
    masked = jax.random.uniform(k_mask, (b, t), jnp.float32) < level
    x_t = jnp.where(masked, jnp.int32(cfg["vocab_size"] - 1), ids)
    return x_t, masked.astype(jnp.float32), level


def visible(half, block, rows, cols):
    """Which key of ``cols`` each query of ``rows`` sees (positions of
    ``[x_0 | x_t]``, each half ``half`` long), bool ``(rows, cols)``."""
    rn, cn = rows[:, None] >= half, cols[None, :] >= half
    rb, cb = (rows[:, None] % half) // block, (cols[None, :] % half) // block
    return jnp.where(cn, rn & (cb == rb), jnp.where(rn, cb < rb, cb <= rb))


# -- the plain reference's equations ---------------------------------------------

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta, half):
    """``x (B, 2T, H, D)`` rotated at positions ``0 .. T-1`` of each half,
    channels ``i`` and ``i + D/2`` a pair."""
    d = x.shape[-1]
    rate = jnp.float32(theta) ** (-2.0 * jnp.arange(d // 2,
                                                   dtype=jnp.float32) / d)
    pos = (jnp.arange(x.shape[1]) % half).astype(jnp.float32)
    phi = (pos[:, None] * rate)[None, :, None, :]           # (1, 2T, 1, D/2)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(phi) - b * jnp.sin(phi),
                            b * jnp.cos(phi) + a * jnp.sin(phi)], axis=-1)


def _attn(cfg, w, u, ein, index=0):
    nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, block = cfg["rms_norm_eps"], cfg["block_length"]
    b, t2, _ = u.shape
    half = t2 // 2

    def heads(name, n, gain=None):
        x = ein("bti,oi->bto", u, w[name]).reshape(b, t2, n, d)
        if gain is None:
            return x
        return _rotary(_rmsnorm(x, w[gain], eps), cfg["rope_theta"], half)
    q = heads("q", nh, "q_norm")
    k = jnp.repeat(heads("k", kv, "k_norm"), nh // kv, axis=2)
    v = jnp.repeat(heads("v", kv), nh // kv, axis=2)
    size = math.gcd(t2, QUERY_BLOCK)
    cols = jnp.arange(t2)

    @jax.checkpoint
    def rows(inp):
        qb, start = inp                                # (B, size, H, d)
        s = ein("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        seen = visible(half, block, start + jnp.arange(size), cols)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return ein("bhqk,bkhd->bqhd", p, v)
    blocks = jnp.moveaxis(q.reshape(b, t2 // size, size, nh, d), 1, 0)
    a = lax.map(rows, (blocks, jnp.arange(0, t2, size)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t2, nh * d)
    return ein("bti,oi->bto", a, w["o"])


def route(cfg, scores, index=0):
    """``(chosen (..., k), weights (..., k))`` from softmax probabilities:
    the k largest, ties to the lower index; the weights are the
    probabilities themselves, renormalised over the k.

    Under ``moe_router_force_load_balancing`` (Megatron-LM's switch of
    that name, for measuring throughput at random weights) layer ``index``
    chooses by ``uniform(PRNGKey(index), (tokens, experts))`` in place of
    the probabilities: the same choice for every seed and step, every
    expert the same expected load; the weights stay the probabilities."""
    k = cfg["num_experts_per_tok"]
    choice = scores
    if cfg.get("moe_router_force_load_balancing"):
        choice = jax.random.uniform(
            jax.random.PRNGKey(index), (scores.size // scores.shape[-1],
                                        scores.shape[-1]),
            jnp.float32).reshape(scores.shape)
    chosen = jnp.argsort(-choice, axis=-1, stable=True)[..., :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w


def _swiglu(hid):
    f = hid.shape[-1] // 2
    return jax.nn.silu(hid[..., :f]) * hid[..., f:]


def _moe(cfg, w, u, ein, index=0):
    held, first = cfg["num_experts"], cfg.get("held_experts_first", 0)
    b, t, h = u.shape
    probs = jax.nn.softmax(ein("bti,ei->bte", u, w["router"]), axis=-1)
    chosen, weight = route(cfg, probs, index)
    # the weight each held expert has for each token: 0 where not chosen
    onehot = chosen[..., None] == (first + jnp.arange(held))
    gate = jnp.sum(jnp.where(onehot, weight[..., None], 0.0), axis=-2)
    rows = math.gcd(b * t, EXPERT_ROWS)

    @jax.checkpoint
    def experts(inp):
        x, g = inp                                     # (rows, h), (rows, E)
        hid = _swiglu(ein("ri,efi->ref", x, w["gate_up"]))
        return ein("ref,eif->ri", hid * g[..., None], w["down"])
    out = lax.map(experts, (u.reshape(-1, rows, h),
                            gate.reshape(-1, rows, held)))
    return out.reshape(b, t, h)


BLOCKS = {"attn": _attn, "moe": _moe}


def _layer(cfg, w, index, x, ein):
    """``x`` through layer ``index``, each sub-block under a checkpoint."""
    eps = cfg["rms_norm_eps"]
    for part in ("attn", "moe"):
        pre = "layer%d.%s." % (index, part)
        bw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        gain = w["layer%d.%s_norm" % (index, part)]

        @jax.checkpoint
        def block(x, bw, gain, part=part):
            return x + BLOCKS[part](cfg, bw, _rmsnorm(x, gain, eps), ein,
                                    index)
        x = block(x, bw, gain)
    return x


def _named(cfg, leaves):
    return dict(zip([n for n, _ in leaf_specs(cfg)], leaves))


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of the noised half, ``tokens`` ``(B, T)``;
    ``ein(spec, a, b)`` is every matrix product."""
    w = _named(cfg, leaves)
    t = tokens.shape[1]
    x_t, _, _ = noise(cfg, tokens)
    x = w["embed"][jnp.concatenate([tokens.astype(jnp.int32), x_t], 1)]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(cfg, w, i, x, ein)
    return ein("bti,vi->btv", _rmsnorm(x[:, t:], w["norm"],
                                       cfg["rms_norm_eps"]), w["head"])


def bd_loss(cfg, leaves, tokens, ein):
    """BD3-LM's objective under the linear schedule: the cross-entropy
    against ``x_0`` at the masked positions, each weighted ``1 / t`` of its
    block, summed and divided by ``B * T``."""
    logits = forward(cfg, leaves, tokens, ein)
    _, masked, level = noise(cfg, tokens)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, tokens.astype(jnp.int32)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * masked / level) / tokens.size


# -- the count, from shapes alone ------------------------------------------------

def param_count(cfg):
    """All parameters of the configuration as its keys state it (the
    experts counted are the ``num_experts`` held)."""
    return sum(math.prod(s) for _, s in leaf_specs(cfg))


def attention_layers(cfg):
    """How many layers attend through the flash kernels: every one."""
    return cfg["num_hidden_layers"]


def live_pairs(seq, block):
    """(query, key) pairs the mask leaves live over ``[x_0 | x_t]`` of
    ``seq`` tokens each: ``seq (seq + block)``, half clean queries', half
    noised ones' (block ``j`` of either sees ``(j + 1) block`` keys)."""
    return seq * (seq + block)


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recomputation, a TRAINED token (``seq`` of
    them a sequence): ``6 x`` the matrices that its clean and its noised
    copy meet in every layer and the head on the noised copy alone, plus
    three times a forward pass's attention over the mask's live pairs
    (``live_pairs``: ``4 head_dim`` operations a pair and head).

    **The rule for routed work**: a copy meets the router (all
    ``router_num_experts`` rows) and of the held experts the
    ``num_experts_per_tok * num_experts / router_num_experts`` that uniform
    routing sends it to in expectation: the share of the model's routed
    work that is done here."""
    layer = 0
    for part in ("attn", "moe"):
        specs = {k: s for k, s in _specs(cfg, part) if len(s) > 1}
        if part == "moe":
            share = cfg["num_experts_per_tok"] / cfg["router_num_experts"]
            layer += int((math.prod(specs.pop("gate_up"))
                          + math.prod(specs.pop("down"))) * share)
        layer += sum(math.prod(s) for s in specs.values())
    mats = 2 * cfg["num_hidden_layers"] * layer \
        + cfg["vocab_size"] * cfg["hidden_size"]
    attention = 3 * attention_layers(cfg) * cfg["num_attention_heads"] \
        * 4 * cfg["head_dim"] * live_pairs(seq, cfg["block_length"]) / seq
    return 6 * mats + attention


# -- the kernels' operations and bytes ------------------------------------------------

def bd_flash_calls(cfg, batch, seq, elt=2):
    """``[{name, flops, bytes}]`` of the three flash kernels of one layer
    under the block-diffusion mask over ``2 seq`` positions, counted over
    the mask's live pairs whatever computes them (``bh`` is batch x query
    heads: the program repeats K and V to the query heads).  A kernel call
    that the program makes again in its backward pass is not counted."""
    bh = batch * cfg["num_attention_heads"]
    d = cfg["head_dim"]
    mm = 2 * bh * live_pairs(seq, cfg["block_length"]) * d   # one product
    tile, stat = bh * 2 * seq * d * elt, bh * 2 * seq * 4
    return [
        # S = QK^T, O = PV; reads q k v, writes o and the logsumexp
        {"name": "flash_fwd", "flops": 2 * mm, "bytes": 4 * tile + stat},
        # S, dP = dO V^T, dQ = dS K; reads q k v do + 2 stats, writes dq
        {"name": "flash_bwd_dq", "flops": 3 * mm,
         "bytes": 5 * tile + 2 * stat},
        # S, dV = P^T dO, dP, dK = dS^T Q; reads q k v do + 2 stats,
        # writes dk dv
        {"name": "flash_bwd_dkv", "flops": 4 * mm,
         "bytes": 6 * tile + 2 * stat},
    ]


# -- the program -----------------------------------------------------------------

def build(cfg, ctx):
    """``gluon.model_zoo.sdar_moe.SDARMoEModel`` at the configuration's
    sizes, zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import sdar_moe

    _check(cfg)
    net = sdar_moe.SDARMoEModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"],
        moe_hidden=cfg["moe_intermediate_size"],
        n_experts=cfg["router_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        held=(cfg.get("held_experts_first", 0), cfg["num_experts"]),
        block_length=cfg["block_length"], noise_seed=cfg["noise_seed"],
        force_load_balancing=bool(
            cfg.get("moe_router_force_load_balancing", False)),
        eps=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 2 * cfg["block_length"]), np.int32),
                    ctx=ctx))
    return net
