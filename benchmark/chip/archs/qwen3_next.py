"""``model_type`` ``qwen3_next``: a hybrid decoder whose every layer is two
sub-blocks behind pre-norm residuals, ``x = x + mixer(norm(x)); x = x +
moe(norm(x))``; layer ``i`` (from 0) is gated softmax attention where ``(i +
1) % full_attention_interval == 0`` and Gated DeltaNet otherwise; every
layer's feed-forward is routed (``decoder_sparse_step`` 1, ``mlp_only_layers``
[]).  After the last layer a norm and the untied head.  No projection has a
bias.  Every ``norm`` is the zero-centred RMSNorm, ``x / rms(x) * (1 + w)``,
eps ``rms_norm_eps``.  The keys are the published ``config.json``'s.

- Gated DeltaNet (``linear_num_key_heads`` key heads and
  ``linear_num_value_heads`` value heads of ``linear_key_head_dim`` =
  ``linear_value_head_dim`` channels, convolutions of
  ``linear_conv_kernel_dim`` taps): ``[q | k | v | z] = u W_qkvz`` and ``[b |
  a] = u W_ba``; ``q, k, v = silu(conv([q | k | v]))``, one causal depthwise
  convolution over the three, no bias; ``q, k`` divided by ``sqrt(sum x^2 +
  1e-6)`` over a head, ``q`` times ``head_dim ** -0.5``; q and k repeated to
  the value heads (``repeat_interleave``: value head ``j`` reads key head ``j
  // (H_v / H_k)``); ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a +
  dt_bias)``, one log-decay a head and token; for each value head a state
  ``S (keys x values)`` from 0: ``S' = exp(g_t) S_{t-1}``, ``S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``; ``o = RMSNorm_head(o)
  * w_o * silu(z)`` (``w_o`` a plain gain); ``W_out``.  The recurrence is
  run as written, one token at a time (``lax.scan``), not in the chunked form
  the program uses.
- gated attention (``num_attention_heads`` query heads and
  ``num_key_value_heads`` key and value heads of ``head_dim``): ``W_q`` gives
  each query head ``[query | gate]``; ``q, k`` through the zero-centred
  RMSNorm over a head; the first ``head_dim * partial_rotary_factor``
  channels of each head turn at positions ``0 .. T-1``, channel ``i`` with
  ``i + rotary / 2``, ``phi = t * rope_theta ** (-2i / rotary)``, the other
  channels pass; query head ``h`` reads key head ``h // (H / KV)``; causal
  softmax at ``head_dim ** -0.5``; ``o * sigmoid(gate)``; ``W_o``.
- routed feed-forward (Qwen3-MoE's): ``p = softmax(u W_r^T)`` over all
  ``router_num_experts``; the ``num_experts_per_tok`` largest (ties to the
  lower index); weights ``p[chosen]`` over their sum (``norm_topk_prob``);
  expert ``e`` is ``down_e(silu(gate_e u) * up_e u)``, gate and up stacked
  in one leaf; plus the shared expert, the same SwiGLU at
  ``shared_expert_intermediate_size``, times ``sigmoid(u w_sg)``.  **The
  share**: ``num_experts`` counts the experts held here,
  ``held_experts_first .. + num_experts - 1`` of the ``router_num_experts``
  the router chooses among; what the absent experts would add is left out,
  here as in the program; the shared expert is every chip's alike.

``moe_router_force_load_balancing`` (``route``) takes the choice of experts
from the probabilities and gives it to fixed pseudo-random numbers, so that
every seed's weights route alike.

``jax.checkpoint`` around each sub-block, around stretches of the
recurrence, around blocks of the attention's queries and around chunks of
the experts' tokens bounds what the gradient keeps (a ``(heads, 8192,
8192)`` float32 score matrix is 4.3 GB); it changes no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SCAN_STRETCH = 64        # tokens of the recurrence under one checkpoint
SCAN_UNROLL = 8          # tokens of the recurrence a loop iteration
QUERY_BLOCK = 256        # queries of the attention under one checkpoint
EXPERT_ROWS = 2048       # tokens of the experts' products under one
GDN_CHUNK = 64           # the program's chunk, where the file gives none


# -- sizes ---------------------------------------------------------------------

def _check(cfg):
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer is routed here (mlp_only_layers [], "
                         "decoder_sparse_step 1)")
    if cfg.get("use_sliding_window") or cfg.get("rope_scaling") \
            or cfg.get("tie_word_embeddings"):
        raise ValueError("no window, no rope scaling, an untied head")
    if cfg["linear_key_head_dim"] != cfg["linear_value_head_dim"]:
        raise ValueError("keys and values of one width in the linear "
                         "attention")


def kinds(cfg):
    """The mixer of each layer: ``gdn`` or ``attn``."""
    every = cfg["full_attention_interval"]
    return ["attn" if (i + 1) % every == 0 else "gdn"
            for i in range(cfg["num_hidden_layers"])]


def attention_layers(cfg):
    """How many layers attend through the flash kernels."""
    return kinds(cfg).count("attn")


def gdn_layers(cfg):
    """How many layers are Gated DeltaNet."""
    return kinds(cfg).count("gdn")


def rotary_dim(cfg):
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def _gdn_sizes(cfg):
    """``(key heads, value heads, channels a head)``."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"])


def _specs(cfg, kind):
    h = cfg["hidden_size"]
    if kind == "gdn":
        hk, hv, d = _gdn_sizes(cfg)
        return [("qkvz", (2 * hk * d + 2 * hv * d, h)), ("ba", (2 * hv, h)),
                ("conv", (2 * hk * d + hv * d,
                          cfg["linear_conv_kernel_dim"])),
                ("dt_bias", (1, hv)), ("A_log", (1, hv)), ("norm", (d,)),
                ("out", (h, hv * d))]
    if kind == "attn":
        nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        return [("q", (nh * 2 * d, h)), ("k", (kv * d, h)), ("v", (kv * d, h)),
                ("q_norm", (d,)), ("k_norm", (d,)), ("o", (h, nh * d))]
    if kind == "moe":
        held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs = cfg["shared_expert_intermediate_size"]
        return [("router", (cfg["router_num_experts"], h)),
                ("gate_up", (held, 2 * f, h)), ("down", (held, h, f)),
                ("shared_gate_up", (2 * fs, h)), ("shared_down", (h, fs)),
                ("shared_gate", (1, h))]
    raise ValueError("no sub-block %r" % kind)


def leaf_specs(cfg):
    """``[(name, shape)]``: embedding; a layer's mixer norm, mixer, feed-
    forward norm, experts; final norm, head.  Dense weights are ``(out,
    in)``; a layer's experts are stacked ``(held, out, in)``; gate and up are
    one leaf, the gate's rows first; ``W_qkvz``'s rows are ``[q | k | v |
    z]`` and ``W_ba``'s ``[b | a]``."""
    _check(cfg)
    h = cfg["hidden_size"]
    specs = [("embed", (cfg["vocab_size"], h))]
    for i, kind in enumerate(kinds(cfg)):
        for part, k in (("mixer", kind), ("moe", "moe")):
            specs.append(("layer%d.%s_norm" % (i, part), (h,)))
            specs += [("layer%d.%s.%s" % (i, k, name), s)
                      for name, s in _specs(cfg, k)]
    return specs + [("norm", (h,)), ("head", (cfg["vocab_size"], h))]


# -- the plain reference's equations ---------------------------------------------

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return _rmsnorm(x, 1.0 + w, eps)


def _conv(x, weight):
    """Causal depthwise convolution over time: ``x (B, T, C)``, ``weight
    (C, K)``."""
    t, k = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * weight[:, j] for j in range(k))


def _delta_rule(q, k, v, g, beta, ein):
    """``o_t = S_t^T q_t`` with ``S' = exp(g_t) S_{t-1}``, ``S_t = S' +
    beta_t k_t (v_t - S'^T k_t)^T``; ``q, k (B, T, H, d)``, ``v (B, T, H,
    e)``, ``g, beta (B, T, H)``.  Token by token, in stretches under a
    checkpoint."""
    b, t, h, d = q.shape
    e = v.shape[-1]

    def token(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None, None]
        old = ein("bhde,bhd->bhe", state, kt)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - old)[..., None, :]
        return state, ein("bhde,bhd->bhe", state, qt)

    @jax.checkpoint
    def stretch(state, inp):
        return lax.scan(token, state, inp, unroll=SCAN_UNROLL)

    size = math.gcd(t, SCAN_STRETCH)
    seq = tuple(jnp.moveaxis(x, 1, 0).reshape((t // size, size) + x.shape[:1]
                                              + x.shape[2:])
                for x in (q, k, v, g, beta))
    _, o = lax.scan(stretch, jnp.zeros((b, h, d, e), jnp.float32), seq)
    return jnp.moveaxis(o.reshape((t, b, h, e)), 0, 1)


def _gdn(cfg, w, u, ein, index=0):
    hk, hv, d = _gdn_sizes(cfg)
    b, t, _ = u.shape
    proj = ein("bti,oi->bto", u, w["qkvz"])
    mixed = jax.nn.silu(_conv(proj[..., :2 * hk * d + hv * d], w["conv"]))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def key_heads(x):
        return jnp.repeat(unit(x.reshape(b, t, hk, d)), hv // hk, axis=2)
    q = key_heads(mixed[..., :hk * d]) * d ** -0.5
    k = key_heads(mixed[..., hk * d:2 * hk * d])
    v = mixed[..., 2 * hk * d:].reshape(b, t, hv, d)
    z = proj[..., 2 * hk * d + hv * d:].reshape(b, t, hv, d)
    ba = ein("bti,oi->bto", u, w["ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(w["A_log"].reshape(hv)) \
        * jax.nn.softplus(ba[..., hv:] + w["dt_bias"].reshape(hv))
    o = _delta_rule(q, k, v, g, beta, ein)
    o = _rmsnorm(o, w["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return ein("bti,oi->bto", o.reshape(b, t, hv * d), w["out"])


def _rotary(x, theta, width):
    """``x (B, T, H, D)``: the first ``width`` channels turned at positions
    ``0 .. T-1``, channels ``i`` and ``i + width/2`` a pair."""
    half = width // 2
    rate = jnp.float32(theta) ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                                  / width)
    phi = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * rate)[
        None, :, None, :]                              # (1, T, 1, width/2)
    a, b = x[..., :half], x[..., half:width]
    return jnp.concatenate([a * jnp.cos(phi) - b * jnp.sin(phi),
                            b * jnp.cos(phi) + a * jnp.sin(phi),
                            x[..., width:]], axis=-1)


def _attn(cfg, w, u, ein, index=0):
    nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta, width = cfg["rms_norm_eps"], cfg["rope_theta"], rotary_dim(cfg)
    b, t, _ = u.shape
    qg = ein("bti,oi->bto", u, w["q"]).reshape(b, t, nh, 2 * d)
    gate = jax.nn.sigmoid(qg[..., d:])
    q = _rotary(_norm(qg[..., :d], w["q_norm"], eps), theta, width)
    k = _rotary(_norm(ein("bti,oi->bto", u, w["k"]).reshape(b, t, kv, d),
                      w["k_norm"], eps), theta, width)
    k = jnp.repeat(k, nh // kv, axis=2)
    v = jnp.repeat(ein("bti,oi->bto", u, w["v"]).reshape(b, t, kv, d),
                   nh // kv, axis=2)
    size = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def rows(inp):
        qb, start = inp                                # (B, size, H, d)
        s = ein("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        seen = (start + jnp.arange(size))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return ein("bhqk,bkhd->bqhd", p, v)
    blocks = jnp.moveaxis(q.reshape(b, t // size, size, nh, d), 1, 0)
    a = lax.map(rows, (blocks, jnp.arange(0, t, size)))
    a = jnp.moveaxis(a, 0, 1).reshape(b, t, nh, d) * gate
    return ein("bti,oi->bto", a.reshape(b, t, nh * d), w["o"])


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
    routed = lax.map(experts, (u.reshape(-1, rows, h),
                               gate.reshape(-1, rows, held)))
    shared = ein("btf,if->bti", _swiglu(ein("bti,fi->btf", u,
                                            w["shared_gate_up"])),
                 w["shared_down"]) \
        * jax.nn.sigmoid(ein("bti,oi->bto", u, w["shared_gate"]))
    return routed.reshape(b, t, h) + shared


BLOCKS = {"gdn": _gdn, "attn": _attn, "moe": _moe}


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``; ``ein(spec, a, b)``
    is every matrix product."""
    w = dict(zip([n for n, _ in leaf_specs(cfg)], leaves))
    eps = cfg["rms_norm_eps"]
    x = w["embed"][tokens]
    for i, kind in enumerate(kinds(cfg)):
        for part, k in (("mixer", kind), ("moe", "moe")):
            pre = "layer%d.%s." % (i, k)
            bw = {n[len(pre):]: v for n, v in w.items() if n.startswith(pre)}
            gain = w["layer%d.%s_norm" % (i, part)]

            @jax.checkpoint
            def block(x, bw, gain, k=k, i=i):
                return x + BLOCKS[k](cfg, bw, _norm(x, gain, eps), ein, i)
            x = block(x, bw, gain)
    return ein("bti,vi->btv", _norm(x, w["norm"], eps), w["head"])


# -- the count, from shapes alone ------------------------------------------------

def param_count(cfg):
    """All parameters of the configuration as its keys state it (the
    experts counted are the ``num_experts`` held)."""
    return sum(math.prod(s) for _, s in leaf_specs(cfg))


def _delta_flops_per_token(cfg):
    """One forward pass of the delta rule, a token and layer: for each
    value head the read of the old value (``S'^T k``), the rank-one write
    and the read of the output (``S^T q``), each ``2 d e``."""
    _, hv, d = _gdn_sizes(cfg)
    return hv * 3 * 2 * d * d


def _attention_flops_per_token(cfg, seq):
    """One forward pass of causal attention, a token and layer: scores and
    the weighted values over ``head_dim`` each, half of ``seq`` keys."""
    return 2 * seq * cfg["num_attention_heads"] * 2 * cfg["head_dim"] // 2


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recomputation: ``6 x`` the matrices a token
    meets (the convolution's taps among them), plus three times a forward
    pass's causal attention and delta rule.

    **The rule for routed work**: a token meets the router (all
    ``router_num_experts`` rows), the shared expert and its gate, and of the
    held experts the ``num_experts_per_tok * num_experts /
    router_num_experts`` that uniform routing sends it to in expectation:
    the share of the model's routed work that is done here."""
    mats = cfg["vocab_size"] * cfg["hidden_size"]
    extra = 0
    for kind in kinds(cfg):
        for k in (kind, "moe"):
            specs = {n: s for n, s in _specs(cfg, k)
                     if len(s) > 1 and n not in ("A_log", "dt_bias")}
            if k == "moe":
                share = cfg["num_experts_per_tok"] / cfg["router_num_experts"]
                mats += int((math.prod(specs.pop("gate_up"))
                             + math.prod(specs.pop("down"))) * share)
            mats += sum(math.prod(s) for s in specs.values())
        extra += _delta_flops_per_token(cfg) if kind == "gdn" \
            else _attention_flops_per_token(cfg, seq)
    return 6 * mats + 3 * extra


# -- the kernels' operations and bytes ------------------------------------------

def gdn_calls(cfg, batch, seq, elt=2):
    """``[{name, flops, bytes}]`` of one GDN layer's mixer kernels in a train
    step: the forward pass and the backward pass (twice the products; the
    forward made again for the backward does not count), at the
    recurrence's own count (``_delta_flops_per_token``: what the chunked form
    adds, the triangular solve and the products within a chunk, is the
    program's choice).  Bytes: the two projections' results ``[q | k | v |
    z]`` and ``[b | a]`` in, ``elt`` bytes an element (bfloat16 under the
    configuration's AMP), and ``o`` out in float32; backward reads those and
    ``do`` (float32) and writes their cotangents."""
    hk, hv, d = _gdn_sizes(cfg)
    tokens = batch * seq
    fwd = tokens * _delta_flops_per_token(cfg)
    operands = tokens * (2 * hk * d + 2 * hv * d + 2 * hv) * elt
    out = tokens * hv * d * 4
    return [{"name": "gdn_fwd", "flops": fwd, "bytes": operands + out},
            {"name": "gdn_bwd", "flops": 2 * fwd,
             "bytes": 2 * operands + out}]


def attention_calls(cfg, batch, seq, elt=2):
    """``[{name, flops, bytes}]`` of the three flash kernels of one gated
    attention layer: causal, keys and values of ``head_dim`` channels, ``bh``
    batch x query heads (the program repeats K and V to the query heads).
    A kernel call that the program makes again in its backward pass is not
    counted."""
    bh = batch * cfg["num_attention_heads"]
    d = cfg["head_dim"]
    mm = bh * seq * seq * d         # one causal (seq x d) x (d x seq) product
    tile, stat = bh * seq * d * elt, bh * seq * 4
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
    """``gluon.model_zoo.qwen3_next.Qwen3NextModel`` at the configuration's
    sizes, zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import qwen3_next

    _check(cfg)
    hk, hv, d = _gdn_sizes(cfg)
    net = qwen3_next.Qwen3NextModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        linear_key_heads=hk, linear_value_heads=hv, linear_head_dim=d,
        conv_kernel=cfg["linear_conv_kernel_dim"],
        chunk_size=cfg.get("gdn_chunk_size", GDN_CHUNK),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], rotary_dim=rotary_dim(cfg),
        moe_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["shared_expert_intermediate_size"],
        n_experts=cfg["router_num_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        held=(cfg.get("held_experts_first", 0), cfg["num_experts"]),
        force_load_balancing=bool(
            cfg.get("moe_router_force_load_balancing", False)),
        eps=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
