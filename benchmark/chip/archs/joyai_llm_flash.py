"""``model_type`` ``joyai_llm_flash``: a decoder whose every layer is latent
attention and a feed-forward, each behind its own pre-norm residual: ``x = x
+ attn(RMSNorm(x)); x = x + ffn(RMSNorm(x))``.  Layers count from 0: the
first ``first_k_dense_replace`` have the dense feed-forward, every later one
the routed (``moe_layer_freq`` 1).  After the last layer an RMSNorm and the
untied head.  No projection has a bias.  The keys are the published
``config.json``'s (the DeepSeek-V3 schema).

- latent attention (``num_attention_heads`` heads; ``q_lora_rank``,
  ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
  ``v_head_dim``): ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` as heads of
  ``nope + rope``, split ``q_n | q_r``; ``[c_kv | k_r] = W_kva u`` with
  ``k_r`` one a token; ``[k_n | v] = W_kvb RMSNorm(c_kv)`` a head.  A
  rotary embedding on ``q_r`` of every head and on ``k_r`` at the token's
  position ``t = 0 .. T-1``: for pair ``i`` of ``rope / 2``, ``phi = t *
  rope_theta ** (-2i / rope)``, no scaling (``rope_scaling`` null);
  ``rope_interleave``: the pair is the ADJACENT channels ``(x_2i, x_2i+1)
  -> (x_2i cos phi - x_2i+1 sin phi, x_2i sin phi + x_2i+1 cos phi)``,
  rotated in place.  (The published code first gathers the even and the odd
  channels into halves and rotates the halves: the same scores, queries and
  keys being permuted alike.)  ``k = [k_n | k_r repeated over the heads]``;
  causal softmax attention at ``(nope + rope) ** -0.5``; ``W_o``.
- routed feed-forward: ``s = sigmoid(u W_r^T)`` over all
  ``router_num_experts``; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` (ties to the lower index; ``n_group`` 1: one
  group holds every expert); weights ``s[chosen] / (sum + 1e-20) *
  routed_scaling_factor``; expert ``e`` is ``down_e(silu(gate_e u) * up_e
  u)``, gate and up stacked in one leaf; the shared expert the same form at
  ``n_shared_experts`` times the width, every token.  **The share**:
  ``n_routed_experts`` counts the experts held here, ``held_experts_first
  .. + n_routed_experts - 1`` of the ``router_num_experts`` the router
  chooses among; what the absent experts would add is left out, here as in
  the program.
- dense feed-forward: the same SwiGLU at ``intermediate_size``.
- multi-token prediction (``num_nextn_predict_layers`` 1; the module of the
  DeepSeek-V3 report, section 2.2; tier-1 tests only: the benchmark's step
  has one loss): with ``h_i`` the last layer's result at position ``i``
  (before the final norm), ``E`` the embedding and ``Head`` the output
  matrix, both the main model's own: ``h'_i = W_eh [RMSNorm_e(E[t_{i+1}]) ;
  RMSNorm_h(h_i)]``, ``g = Block(h')`` (one more layer of the routed kind,
  positions as ``i``), ``P_i = softmax(Head RMSNorm_s(g_i))`` for ``i = 0
  .. T-2``; ``L = CE_main + mtp_loss_weight * mean_i CE(P_i, label_{i+1})``.
  The block runs over ``T`` positions, the last one fed ``E[t_0]`` and
  dropped (causal: it moves no other), so that the forced router draws for
  as many tokens as the main layers'.

``moe_router_force_load_balancing`` (``route``) takes the choice of experts
from the scores and gives it to fixed pseudo-random numbers, so that every
seed's weights route alike.

``jax.checkpoint`` around each sub-block and around blocks of the
attention's queries bounds what the gradient keeps (a ``(heads, 8192,
8192)`` float32 score matrix is 8.6 GB); it changes no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256        # queries of the attention under one checkpoint


# -- sizes ---------------------------------------------------------------------

def _kinds(cfg):
    """The feed-forward a layer: ``mlp`` or ``moe``."""
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("moe_layer_freq %r: every layer after the dense "
                         "ones is routed here" % cfg["moe_layer_freq"])
    return ["moe" if i >= cfg["first_k_dense_replace"] else "mlp"
            for i in range(cfg["num_hidden_layers"])]


def _specs(cfg, kind):
    h = cfg["hidden_size"]
    if kind == "mla":
        nh, rq, rank = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                        cfg["kv_lora_rank"])
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        return [("q_a", (rq, h)), ("q_a_norm", (rq,)),
                ("q_b", (nh * (nope + rope), rq)),
                ("kv_a", (rank + rope, h)), ("kv_a_norm", (rank,)),
                ("kv_b", (nh * (nope + vd), rank)), ("o", (h, nh * vd))]
    if kind == "moe":
        held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = f * cfg["n_shared_experts"]
        return [("router", (cfg["router_num_experts"], h)),
                ("router_bias", (cfg["router_num_experts"],)),
                ("gate_up", (held, 2 * f, h)), ("down", (held, h, f)),
                ("shared_gate_up", (2 * fs, h)), ("shared_down", (h, fs))]
    if kind == "mlp":
        f = cfg["intermediate_size"]
        return [("gate_up", (2 * f, h)), ("down", (h, f))]
    raise ValueError("no sub-block %r" % kind)


def _layer_specs(cfg, name, ffn):
    h = cfg["hidden_size"]
    specs = []
    for part, kind in (("mixer", "mla"), ("ffn", ffn)):
        specs.append(("%s.%s_norm" % (name, part), (h,)))
        specs += [("%s.%s.%s" % (name, part, k), s)
                  for k, s in _specs(cfg, kind)]
    return specs


def leaf_specs(cfg):
    """``[(name, shape)]``: embedding; a layer's mixer norm, mixer, ffn
    norm, ffn; final norm, head; then, with ``num_nextn_predict_layers`` 1,
    the prediction module's two norms, ``W_eh``, block and norm.  Dense
    weights are ``(out, in)``; a layer's experts are stacked ``(held, out,
    in)``; gate and up are one leaf, the gate's rows first."""
    h = cfg["hidden_size"]
    specs = [("embed", (cfg["vocab_size"], h))]
    for i, ffn in enumerate(_kinds(cfg)):
        specs += _layer_specs(cfg, "layer%d" % i, ffn)
    specs += [("norm", (h,)), ("head", (cfg["vocab_size"], h))]
    nextn = cfg.get("num_nextn_predict_layers", 0)
    if nextn not in (0, 1):
        raise ValueError("num_nextn_predict_layers %r: none or one" % nextn)
    if nextn:
        specs += [("mtp.embed_norm", (h,)), ("mtp.hidden_norm", (h,)),
                  ("mtp.proj", (h, 2 * h))]
        specs += _layer_specs(cfg, "mtp", "moe") + [("mtp.norm", (h,))]
    return specs


# -- the plain reference's equations ---------------------------------------------

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta):
    """``x (B, T, ..., rope)`` rotated at positions ``0 .. T-1``, adjacent
    channels a pair."""
    t, rope = x.shape[1], x.shape[-1]
    pair = jnp.arange(rope // 2, dtype=jnp.float32)
    rate = jnp.float32(theta) ** (-2.0 * pair / rope)
    phi = jnp.arange(t, dtype=jnp.float32)[:, None] * rate   # (T, rope / 2)
    phi = phi.reshape((1, t) + (1,) * (x.ndim - 3) + (rope // 2,))
    pairs = x.reshape(x.shape[:-1] + (rope // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(phi) - b * jnp.sin(phi),
                     a * jnp.sin(phi) + b * jnp.cos(phi)], axis=-1)
    return out.reshape(x.shape)


def _mla(cfg, w, u, ein, index=0):
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t, _ = u.shape
    c_q = _rmsnorm(ein("bti,ri->btr", u, w["q_a"]), w["q_a_norm"], eps)
    q = ein("btr,or->bto", c_q, w["q_b"]).reshape(b, t, nh, nope + rope)
    kv_a = ein("bti,oi->bto", u, w["kv_a"])
    latent = _rmsnorm(kv_a[..., :rank], w["kv_a_norm"], eps)
    kv = ein("btr,or->bto", latent, w["kv_b"]).reshape(b, t, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], -1)
    k_r = _rotary(kv_a[:, :, None, rank:], theta)         # one a token
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, nh, rope))], axis=-1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    size = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(inp):
        qb, start = inp                                # (B, size, H, d)
        s = ein("bqhd,bkhd->bhqk", qb, k) * scale
        mask = (start + jnp.arange(size))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ein("bhqk,bkhd->bqhd", p, v)
    blocks = jnp.moveaxis(q.reshape(b, t // size, size, nh, nope + rope),
                          1, 0)
    a = lax.map(block, (blocks, jnp.arange(0, t, size)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, nh * vd)
    return ein("bti,oi->bto", a, w["o"])


def route(cfg, scores, bias, index=0):
    """``(chosen (..., k), weights (..., k))`` from sigmoid scores: the k
    largest of ``scores + bias``, ties to the lower index; the weights are
    the scores themselves, normalised and scaled.

    Under ``moe_router_force_load_balancing`` (Megatron-LM's switch of
    that name, for measuring throughput at random weights) layer ``index``
    chooses by ``uniform(PRNGKey(index), (tokens, experts))`` in place of
    ``scores + bias``: the same choice for every seed and step, every
    expert the same expected load; the weights stay the scores."""
    k = cfg["num_experts_per_tok"]
    choice = scores + bias
    if cfg.get("moe_router_force_load_balancing"):
        choice = jax.random.uniform(
            jax.random.PRNGKey(index), (scores.size // scores.shape[-1],
                                        scores.shape[-1]),
            jnp.float32).reshape(scores.shape)
    chosen = jnp.argsort(-choice, axis=-1, stable=True)[..., :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def _swiglu(hid):
    f = hid.shape[-1] // 2
    return jax.nn.silu(hid[..., :f]) * hid[..., f:]


def _mlp(cfg, w, u, ein, index=0):
    return ein("btf,if->bti", _swiglu(ein("bti,fi->btf", u, w["gate_up"])),
               w["down"])


def _moe(cfg, w, u, ein, index=0):
    held, first = cfg["n_routed_experts"], cfg.get("held_experts_first", 0)
    scores = jax.nn.sigmoid(ein("bti,ei->bte", u, w["router"]))
    chosen, weight = route(cfg, scores, w["router_bias"], index)
    # the weight each held expert has for each token: 0 where not chosen
    onehot = chosen[..., None] == (first + jnp.arange(held))
    gate = jnp.sum(jnp.where(onehot, weight[..., None], 0.0), axis=-2)
    hid = _swiglu(ein("bti,efi->btef", u, w["gate_up"]))
    routed = ein("btef,eif->bti", hid * gate[..., None], w["down"])
    shared = ein("btf,if->bti", _swiglu(ein("bti,fi->btf", u,
                                            w["shared_gate_up"])),
                 w["shared_down"])
    return routed + shared


BLOCKS = {"mla": _mla, "mlp": _mlp, "moe": _moe}


def _layer(cfg, w, name, ffn, index, x, ein):
    """``x`` through layer ``name`` (numbered ``index``), each sub-block
    under a checkpoint."""
    eps = cfg["rms_norm_eps"]
    for part, kind in (("mixer", "mla"), ("ffn", ffn)):
        pre = "%s.%s." % (name, part)
        bw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        gain = w["%s.%s_norm" % (name, part)]

        @jax.checkpoint
        def block(x, bw, gain, kind=kind):
            return x + BLOCKS[kind](cfg, bw, _rmsnorm(x, gain, eps), ein,
                                    index)
        x = block(x, bw, gain)
    return x


def _trunk(cfg, w, tokens, ein):
    """The last layer's result ``(B, T, hidden)``, before the final norm."""
    x = w["embed"][tokens]
    for i, ffn in enumerate(_kinds(cfg)):
        x = _layer(cfg, w, "layer%d" % i, ffn, i, x, ein)
    return x


def _named(cfg, leaves):
    return dict(zip([n for n, _ in leaf_specs(cfg)], leaves))


def _head(cfg, w, x, gain, ein):
    return ein("bti,vi->btv", _rmsnorm(x, gain, cfg["rms_norm_eps"]),
               w["head"])


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``; ``ein(spec, a, b)``
    is every matrix product."""
    w = _named(cfg, leaves)
    return _head(cfg, w, _trunk(cfg, w, tokens, ein), w["norm"], ein)


def _mtp_hidden(cfg, w, hidden, tokens, ein):
    """The prediction module's block over ``T`` positions: ``(B, T,
    hidden)``, before its norm."""
    eps = cfg["rms_norm_eps"]
    ahead = w["embed"][jnp.roll(tokens, -1, axis=1)]   # E[t_{i+1}]; last: t_0
    both = jnp.concatenate([_rmsnorm(ahead, w["mtp.embed_norm"], eps),
                            _rmsnorm(hidden, w["mtp.hidden_norm"], eps)], -1)
    return _layer(cfg, w, "mtp", "moe", cfg["num_hidden_layers"],
                  ein("bti,oi->bto", both, w["mtp.proj"]), ein)


def mtp_logits(cfg, leaves, tokens, ein):
    """``(logits (B, T, V), the prediction module's (B, T-1, V))``: position
    ``i`` of the second predicts the token after the next."""
    w = _named(cfg, leaves)
    hidden = _trunk(cfg, w, tokens, ein)
    g = _mtp_hidden(cfg, w, hidden, tokens, ein)
    return (_head(cfg, w, hidden, w["norm"], ein),
            _head(cfg, w, g, w["mtp.norm"], ein)[:, :-1])


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def mtp_loss(cfg, leaves, tokens, labels, ein):
    """``CE_main + mtp_loss_weight * mean_i CE(P_i, label_{i+1})``; ``labels
    (B, T)``: ``labels[:, i]`` is the token after ``tokens[:, i]``."""
    main, ahead = mtp_logits(cfg, leaves, tokens, ein)
    return _cross_entropy(main, labels) \
        + cfg["mtp_loss_weight"] * _cross_entropy(ahead, labels[:, 1:])


# -- the count, from shapes alone ------------------------------------------------

def param_count(cfg):
    """All parameters of the configuration as its keys state it (the
    experts counted are the ``n_routed_experts`` held)."""
    return sum(math.prod(s) for _, s in leaf_specs(cfg))


def attention_layers(cfg):
    """How many layers attend through the flash kernels: every one."""
    return cfg["num_hidden_layers"]


def _attention_flops_per_token(cfg, seq):
    """One forward pass of causal attention, a token and layer: scores over
    ``nope + rope`` channels and the weighted values over ``v_head_dim``,
    half of ``seq`` keys each."""
    return 2 * seq * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) // 2


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recomputation: ``6 x`` the matrices a token
    meets, plus three times a forward pass's causal attention in every
    layer.  The prediction module is not counted: no cell runs it.

    **The rule for routed work**: a token meets the router (all
    ``router_num_experts`` rows), the shared expert, and of the held
    experts the ``num_experts_per_tok * n_routed_experts /
    router_num_experts`` that uniform routing sends it to in expectation:
    the share of the model's routed work that is done here."""
    mats = cfg["vocab_size"] * cfg["hidden_size"]
    for ffn in _kinds(cfg):
        for kind in ("mla", ffn):
            specs = {k: s for k, s in _specs(cfg, kind) if len(s) > 1}
            if kind == "moe":
                share = cfg["num_experts_per_tok"] / cfg["router_num_experts"]
                routed = math.prod(specs.pop("gate_up")) \
                    + math.prod(specs.pop("down"))
                mats += int(routed * share)
            mats += sum(math.prod(s) for s in specs.values())
    return 6 * mats + 3 * attention_layers(cfg) \
        * _attention_flops_per_token(cfg, seq)


# -- the kernels' operations and bytes ------------------------------------------------

def mla_flash_calls(cfg, batch, seq, elt=2):
    """``[{name, flops, bytes}]`` of the three flash kernels of one layer:
    keys of ``nope + rope`` channels and values of ``v_head_dim`` (causal;
    ``bh`` is batch x heads).  A kernel call that the program makes again
    in its backward pass is not counted."""
    bh = batch * cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    qk = bh * seq * seq * dqk        # one causal (seq x dqk) x (dqk x seq)
    pv = bh * seq * seq * dv
    wide, narrow, stat = bh * seq * dqk * elt, bh * seq * dv * elt, \
        bh * seq * 4
    return [
        # S = QK^T, O = PV; reads q k v, writes o and the logsumexp
        {"name": "flash_fwd", "flops": qk + pv,
         "bytes": 2 * wide + 2 * narrow + stat},
        # S, dP = dO V^T, dQ = dS K; reads q k v do + 2 stats, writes dq
        {"name": "flash_bwd_dq", "flops": 2 * qk + pv,
         "bytes": 3 * wide + 2 * narrow + 2 * stat},
        # S, dV = P^T dO, dP, dK = dS^T Q; reads q k v do + 2 stats,
        # writes dk dv
        {"name": "flash_bwd_dkv", "flops": 2 * qk + 2 * pv,
         "bytes": 3 * wide + 3 * narrow + 2 * stat},
    ]


def grouped_calls(cfg, rows, elt=2):
    """``[{name, flops, bytes}]`` of one routed layer's grouped products in
    a train step over ``rows`` landed rows: gate and up in one product
    (``2F`` wide) and down (``F``), forward, and for each the gradient of
    its rows and of its weights.  Each reads or writes the held experts'
    stacked matrix once and the landed rows of its two other operands."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    calls = []
    for proj, width in (("gate_up", 2 * f), ("down", f)):
        flops = 2 * rows * h * width
        bytes_ = (cfg["n_routed_experts"] * h * width
                  + rows * (h + width)) * elt
        calls += [{"name": "gmm_%s_%s" % (proj, what), "flops": flops,
                   "bytes": bytes_} for what in ("fwd", "drows", "dw")]
    return calls


# -- the program -----------------------------------------------------------------

def build(cfg, ctx):
    """``gluon.model_zoo.joyai_llm_flash.JoyAIFlashModel`` at the
    configuration's sizes, zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import joyai_llm_flash

    if not cfg.get("rope_interleave", True) or cfg.get("rope_scaling"):
        raise ValueError("the rotation is of adjacent pairs, unscaled")
    net = joyai_llm_flash.JoyAIFlashModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        dense_hidden=cfg["intermediate_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["router_num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        held=(cfg.get("held_experts_first", 0), cfg["n_routed_experts"]),
        force_load_balancing=bool(
            cfg.get("moe_router_force_load_balancing", False)),
        num_nextn_predict_layers=cfg.get("num_nextn_predict_layers", 0),
        eps=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
