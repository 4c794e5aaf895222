"""``model_type`` ``kimi_linear``: a hybrid decoder whose every layer is two
sub-blocks, each behind its own pre-norm residual: ``x = x + mixer(
RMSNorm(x)); x = x + ffn(RMSNorm(x))``.  Layers count from 1:
``linear_attn_config.kda_layers`` and ``.full_attn_layers`` say which mixer
a layer has; the first ``first_k_dense_replace`` layers have the dense
feed-forward, every later one the routed.  After the last layer an RMSNorm
and the untied head.  No projection has a bias.

- KDA (Kimi Delta Attention; ``linear_attn_config``: ``num_heads`` heads of
  ``head_dim`` channels for keys and values, convolutions of
  ``short_conv_kernel_size`` taps): ``q, k, v = silu(conv(W u))``, a causal
  depthwise convolution each, no bias; ``q, k`` L2-normalised over a head's
  channels (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times ``head_dim ** -0.5``;
  ``g = -exp(A_log[h]) * softplus(W_fb W_fa u + dt_bias)``, one log-decay a
  channel; ``beta = sigmoid(W_b u)``, one a head; for each head a state ``S
  (keys x values)`` from 0: ``S' = diag(exp(g_t)) S_{t-1}``, ``S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``; ``o =
  RMSNorm_head(o) * sigmoid(W_gb W_ga u)``; ``W_o``.  The recurrence is run
  as written, one token at a time (``lax.scan``), not in the chunked form
  the program uses.
- MLA (latent attention, materialised; ``mla_use_nope``: **no rotary
  embedding**, the ``qk_rope_head_dim`` channels are plain channels): ``q =
  W_q u`` as heads of ``nope + rope``; ``[c | k_r] = W_kva u``; ``[k_n | v]
  = W_kvb RMSNorm(c)`` a head; ``k = [k_n | k_r]`` with ``k_r`` shared by
  the heads; causal softmax attention at ``(nope + rope) ** -0.5``; ``W_o``.
- routed feed-forward: ``s = sigmoid(u W_r^T)`` over all
  ``router_num_experts``; the ``num_experts_per_token`` largest of ``s + b``
  (ties to the lower index; one expert group); weights ``s[chosen] / (sum +
  1e-20) * routed_scaling_factor``; expert ``e`` is ``down_e(silu(gate_e u)
  * up_e u)``, gate and up stacked in one leaf; the shared expert the same
  form, every token.  **The share**: ``num_experts`` counts the experts
  held here, ``held_experts_first .. + num_experts - 1`` of the
  ``router_num_experts`` the router chooses among; what the absent experts
  would add is left out, here as in the program.
- dense feed-forward: the same SwiGLU at ``intermediate_size``.

``moe_router_force_load_balancing`` (``route``) takes the choice of experts
from the scores and gives it to fixed pseudo-random numbers, so that every
seed's weights route alike.

``jax.checkpoint`` around each sub-block, around stretches of the
recurrence and around blocks of the attention's queries bounds what the
gradient keeps (a ``(heads, 4096, 4096)`` float32 score matrix is 2 GB);
it changes no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SCAN_STRETCH = 64        # tokens of the recurrence under one checkpoint
QUERY_BLOCK = 512        # queries of the attention under one checkpoint
KDA_CHUNK = 64           # the program's chunk, where the file gives none


# -- sizes ---------------------------------------------------------------------

def _kinds(cfg):
    """``[(mixer, ffn)]`` a layer: ``kda`` or ``mla``, ``mlp`` or ``moe``."""
    lin = cfg["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    layers = range(1, cfg["num_hidden_layers"] + 1)
    if kda & full or kda | full != set(layers):
        raise ValueError("kda_layers %r and full_attn_layers %r do not name "
                         "each of the %d layers once"
                         % (sorted(kda), sorted(full), len(layers)))
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("moe_layer_freq %r: every layer after the dense "
                         "ones is routed here" % cfg["moe_layer_freq"])
    return [("kda" if i in kda else "mla",
             "moe" if i > cfg["first_k_dense_replace"] else "mlp")
            for i in layers]


def _kda_sizes(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def _specs(cfg, kind):
    h = cfg["hidden_size"]
    if kind == "kda":
        heads, hd = _kda_sizes(cfg)
        inner, taps = heads * hd, cfg["linear_attn_config"][
            "short_conv_kernel_size"]
        return [("q", (inner, h)), ("k", (inner, h)), ("v", (inner, h)),
                ("q_conv", (inner, taps)), ("k_conv", (inner, taps)),
                ("v_conv", (inner, taps)), ("A_log", (1, heads)),
                ("f_a", (hd, h)), ("f_b", (inner, hd)),
                ("dt_bias", (heads, hd)), ("b", (heads, h)),
                ("g_a", (hd, h)), ("g_b", (inner, hd)), ("o_norm", (hd,)),
                ("o", (h, inner))]
    if kind == "mla":
        nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        return [("q", (nh * (nope + rope), h)), ("kv_a", (rank + rope, h)),
                ("kv_a_norm", (rank,)), ("kv_b", (nh * (nope + vd), rank)),
                ("o", (h, nh * vd))]
    if kind == "moe":
        held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs = f * cfg["num_shared_experts"]
        return [("router", (cfg["router_num_experts"], h)),
                ("router_bias", (cfg["router_num_experts"],)),
                ("gate_up", (held, 2 * f, h)), ("down", (held, h, f)),
                ("shared_gate_up", (2 * fs, h)), ("shared_down", (h, fs))]
    if kind == "mlp":
        f = cfg["intermediate_size"]
        return [("gate_up", (2 * f, h)), ("down", (h, f))]
    raise ValueError("no sub-block %r" % kind)


def leaf_specs(cfg):
    """``[(name, shape)]``: embedding; a layer's mixer norm, mixer, ffn
    norm, ffn; final norm, head.  Dense weights are ``(out, in)``; a layer's
    experts are stacked ``(held, out, in)``; gate and up are one leaf, the
    gate's rows first."""
    h = cfg["hidden_size"]
    specs = [("embed", (cfg["vocab_size"], h))]
    for i, (mixer, ffn) in enumerate(_kinds(cfg), 1):
        for part, kind in (("mixer", mixer), ("ffn", ffn)):
            specs.append(("layer%d.%s_norm" % (i, part), (h,)))
            specs += [("layer%d.%s.%s" % (i, part, k), s)
                      for k, s in _specs(cfg, kind)]
    return specs + [("norm", (h,)), ("head", (cfg["vocab_size"], h))]


# -- the plain reference's equations ---------------------------------------------

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _conv(x, weight):
    """Causal depthwise convolution over time: ``x (B, T, C)``, ``weight
    (C, K)``."""
    t, k = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * weight[:, j] for j in range(k))


def _delta_rule(q, k, v, g, beta, ein):
    """``o_t = S_t^T q_t`` with ``S' = diag(exp(g_t)) S_{t-1}``, ``S_t = S' +
    beta_t k_t (v_t - S'^T k_t)^T``; ``q, k, g (B, T, H, d)``, ``v (B, T,
    H, e)``, ``beta (B, T, H)``.  Token by token, in stretches under a
    checkpoint."""
    b, t, h, d = q.shape
    e = v.shape[-1]

    def token(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        old = ein("bhde,bhd->bhe", state, kt)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - old)[..., None, :]
        return state, ein("bhde,bhd->bhe", state, qt)

    @jax.checkpoint
    def stretch(state, inp):
        return lax.scan(token, state, inp)

    size = math.gcd(t, SCAN_STRETCH)
    seq = tuple(jnp.moveaxis(x, 1, 0).reshape((t // size, size) + x.shape[:1]
                                              + x.shape[2:])
                for x in (q, k, v, g, beta))
    _, o = lax.scan(stretch, jnp.zeros((b, h, d, e), jnp.float32), seq)
    return jnp.moveaxis(o.reshape((t, b, h, e)), 0, 1)


def _kda(cfg, w, u, ein, index=0):
    heads, hd = _kda_sizes(cfg)
    b, t, _ = u.shape

    def mixed(proj, conv):
        x = jax.nn.silu(_conv(ein("bti,oi->bto", u, w[proj]), w[conv]))
        return x.reshape(b, t, heads, hd)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = unit(mixed("q", "q_conv")) * hd ** -0.5
    k = unit(mixed("k", "k_conv"))
    v = mixed("v", "v_conv")
    rate = jax.nn.softplus(
        ein("btr,or->bto", ein("bti,ri->btr", u, w["f_a"]), w["f_b"])
        + w["dt_bias"].reshape(-1))
    g = -jnp.exp(w["A_log"].reshape(heads, 1)) * rate.reshape(b, t, heads, hd)
    beta = jax.nn.sigmoid(ein("bti,hi->bth", u, w["b"]))
    o = _delta_rule(q, k, v, g, beta, ein)
    gate = jax.nn.sigmoid(
        ein("btr,or->bto", ein("bti,ri->btr", u, w["g_a"]), w["g_b"]))
    o = _rmsnorm(o, w["o_norm"], cfg["rms_norm_eps"]) \
        * gate.reshape(b, t, heads, hd)
    return ein("bti,oi->bto", o.reshape(b, t, heads * hd), w["o"])


def _mla(cfg, w, u, ein, index=0):
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    b, t, _ = u.shape
    q = ein("bti,oi->bto", u, w["q"]).reshape(b, t, nh, nope + rope)
    kv_a = ein("bti,oi->bto", u, w["kv_a"])
    latent = _rmsnorm(kv_a[..., :rank], w["kv_a_norm"], cfg["rms_norm_eps"])
    kv = ein("btr,or->bto", latent, w["kv_b"]).reshape(b, t, nh, nope + vd)
    k_rope = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, t, nh, rope))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
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
    k = cfg["num_experts_per_token"]
    choice = scores + bias
    if cfg.get("moe_router_force_load_balancing"):
        choice = jax.random.uniform(
            jax.random.PRNGKey(index), (scores.size // scores.shape[-1],
                                        scores.shape[-1]),
            jnp.float32).reshape(scores.shape)
    chosen = jnp.argsort(-choice, axis=-1, stable=True)[..., :k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("moe_renormalize", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def _swiglu(hid):
    f = hid.shape[-1] // 2
    return jax.nn.silu(hid[..., :f]) * hid[..., f:]


def _mlp(cfg, w, u, ein, index=0):
    return ein("btf,if->bti", _swiglu(ein("bti,fi->btf", u, w["gate_up"])),
               w["down"])


def _moe(cfg, w, u, ein, index=0):
    held, first = cfg["num_experts"], cfg.get("held_experts_first", 0)
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


BLOCKS = {"kda": _kda, "mla": _mla, "mlp": _mlp, "moe": _moe}


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``; ``ein(spec, a, b)``
    is every matrix product."""
    w = dict(zip([n for n, _ in leaf_specs(cfg)], leaves))
    eps = cfg["rms_norm_eps"]
    x = w["embed"][tokens]
    for i, kinds in enumerate(_kinds(cfg), 1):
        for part, kind in zip(("mixer", "ffn"), kinds):
            pre = "layer%d.%s." % (i, part)
            bw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
            gain = w["layer%d.%s_norm" % (i, part)]

            @jax.checkpoint
            def block(x, bw, gain, kind=kind, i=i):
                return x + BLOCKS[kind](cfg, bw, _rmsnorm(x, gain, eps),
                                        ein, i)
            x = block(x, bw, gain)
    return ein("bti,vi->btv", _rmsnorm(x, w["norm"], eps), w["head"])


# -- the count, from shapes alone ------------------------------------------------

def param_count(cfg):
    """All parameters of the configuration as its keys state it (the
    experts counted are the ``num_experts`` held)."""
    return sum(math.prod(s) for _, s in leaf_specs(cfg))


def _delta_flops_per_token(cfg):
    """One forward pass of the delta rule, a token and layer: for each head
    the read of the old value (``S'^T k``), the rank-one write and the read
    of the output (``S^T q``), each ``2 d e``."""
    heads, hd = _kda_sizes(cfg)
    return heads * 3 * 2 * hd * hd


def _attention_flops_per_token(cfg, seq):
    """One forward pass of causal attention, a token and layer: scores over
    ``nope + rope`` channels and the weighted values over ``v_head_dim``,
    half of ``seq`` keys each."""
    return 2 * seq * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) // 2


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recomputation: ``6 x`` the matrices a token
    meets (the convolutions' taps among them), plus three times a forward
    pass's causal attention and delta rule.

    **The rule for routed work**: a token meets the router (all
    ``router_num_experts`` rows), the shared expert, and of the held
    experts the ``num_experts_per_token * num_experts /
    router_num_experts`` that uniform routing sends it to in expectation:
    the share of the model's routed work that is done here."""
    mats = cfg["vocab_size"] * cfg["hidden_size"]
    extra = 0
    for kinds in _kinds(cfg):
        for kind in kinds:
            specs = {k: s for k, s in _specs(cfg, kind) if len(s) > 1}
            if kind == "moe":
                share = cfg["num_experts_per_token"] \
                    / cfg["router_num_experts"]
                routed = math.prod(specs.pop("gate_up")) \
                    + math.prod(specs.pop("down"))
                mats += int(routed * share)
            mats += sum(math.prod(s) for k, s in specs.items()
                        if k not in ("A_log", "dt_bias"))
            if kind == "kda":
                extra += _delta_flops_per_token(cfg)
            elif kind == "mla":
                extra += _attention_flops_per_token(cfg, seq)
    return 6 * mats + 3 * extra


# -- the new kernels' operations and bytes ------------------------------------------

def kda_calls(cfg, batch, seq, elt=4):
    """``[{name, flops, bytes}]`` of one KDA layer's scan in a train step:
    the forward pass and the backward pass (twice the products; the
    recomputed forward does not count), at the recurrence's own count
    (``_delta_flops_per_token``: what the chunked form adds, the triangular
    solve and the products within a chunk, is the program's choice).
    Bytes: ``q``, ``k``, ``g``, ``v``, ``beta`` in and ``o`` out, ``elt``
    bytes an element (the operator is float32); backward reads those and
    ``do`` and writes the five gradients."""
    heads, hd = _kda_sizes(cfg)
    tokens = batch * seq
    fwd = tokens * _delta_flops_per_token(cfg)
    operands = tokens * heads * (4 * hd + 1) * elt
    out = tokens * heads * hd * elt
    return [{"name": "kda_fwd", "flops": fwd, "bytes": operands + out},
            {"name": "kda_bwd", "flops": 2 * fwd,
             "bytes": 2 * operands + 2 * out}]


def mla_flash_calls(cfg, batch, seq, elt=2):
    """``[{name, flops, bytes}]`` of the three flash kernels of one MLA
    layer: ``flops.flash_calls`` with keys of ``nope + rope`` channels and
    values of ``v_head_dim`` (causal; ``bh`` is batch x heads)."""
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
        bytes_ = (cfg["num_experts"] * h * width + rows * (h + width)) * elt
        calls += [{"name": "gmm_%s_%s" % (proj, what), "flops": flops,
                   "bytes": bytes_} for what in ("fwd", "drows", "dw")]
    return calls


# -- the program -----------------------------------------------------------------

def build(cfg, ctx):
    """``gluon.model_zoo.kimi_linear.KimiLinearModel`` at the
    configuration's sizes, zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    lin = cfg["linear_attn_config"]
    net = kimi_linear.KimiLinearModel(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        kda_layers=lin["kda_layers"],
        full_attn_layers=lin["full_attn_layers"],
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        chunk_size=cfg.get("kda_chunk_size", KDA_CHUNK),
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        dense_hidden=cfg["intermediate_size"],
        first_k_dense=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["router_num_experts"],
        num_experts_per_tok=cfg["num_experts_per_token"],
        moe_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        norm_topk_prob=cfg.get("moe_renormalize", True),
        held=(cfg.get("held_experts_first", 0), cfg["num_experts"]),
        force_load_balancing=bool(
            cfg.get("moe_router_force_load_balancing", False)),
        eps=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
