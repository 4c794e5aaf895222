"""``model_type`` ``nemotron_h``: a hybrid decoder whose every layer is one
mixer behind a pre-norm residual, ``x = x + mixer_i(RMSNorm(x))``, the kind
of mixer ``i`` given by character ``i`` of ``hybrid_override_pattern``; after
the last layer an RMSNorm and the untied head.  No projection has a bias.

- ``M``, Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC) +
  bias)``, a causal depthwise convolution of ``conv_kernel`` taps; ``xBC ->
  x (heads x head_dim), B, C (n_groups x ssm_state_size each; head h reads
  group h // (heads / n_groups))``; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; for each head ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t
  B_t^T``, ``y_t = H_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))`` over
  groups of ``inner / n_groups`` channels; ``out_proj``.  The recurrence is
  run as written, one token at a time (``lax.scan``), not in the chunked
  form the program uses.
- ``E``, experts: ``s = sigmoid(u W_r^T)`` over all ``router_num_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` (ties to the lower
  index); weights ``s[chosen] / (sum + 1e-20) * routed_scaling_factor``;
  expert ``e`` is ``down_e(relu(up_e(u))^2)``; the shared expert the same
  form, every token.  **The share**: ``n_routed_experts`` counts the
  experts held here, ``held_experts_first .. + n_routed_experts - 1`` of
  the ``router_num_experts`` the router chooses among; what the absent
  experts would add is left out, here as in the program.
- ``*``, attention: grouped-query causal softmax attention at
  ``1 / sqrt(head_dim)``, no rotary embedding.

``moe_router_force_load_balancing`` (a key of the configuration; ``route``)
takes the choice of experts from the scores and gives it to fixed
pseudo-random numbers, so that every seed's weights route alike.

Departures, stated in the configuration's file too: the TwoTower release's
second (denoiser) tower, adaLN and block diffusion are not built;
``A_log`` and ``dt_bias`` are ``(1, heads)`` leaves so that the benchmark's
initialiser draws them.  ``jax.checkpoint`` around each layer and around
stretches of the scan bounds what the gradient keeps; it changes no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SCAN_STRETCH = 64        # tokens of the recurrence under one checkpoint


# -- sizes ---------------------------------------------------------------------

def _pattern(cfg):
    pat = cfg["hybrid_override_pattern"]
    if len(pat) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern %r has %d layers, "
                         "num_hidden_layers is %d"
                         % (pat, len(pat), cfg["num_hidden_layers"]))
    return pat


def _mamba_sizes(cfg):
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return heads, hd, heads * hd, gn


def _layer_specs(cfg, kind):
    h = cfg["hidden_size"]
    if kind == "M":
        heads, _, inner, gn = _mamba_sizes(cfg)
        conv = inner + 2 * gn
        return [("in_proj", (inner + conv + heads, h)),
                ("conv_weight", (conv, cfg["conv_kernel"])),
                ("conv_bias", (conv,)), ("dt_bias", (1, heads)),
                ("A_log", (1, heads)), ("D", (heads,)),
                ("norm_weight", (inner,)), ("out_proj", (h, inner))]
    if kind == "E":
        held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        return [("router", (cfg["router_num_experts"], h)),
                ("router_bias", (cfg["router_num_experts"],)),
                ("up", (held, f, h)), ("down", (held, h, f)),
                ("shared_up", (fs, h)), ("shared_down", (h, fs))]
    if kind == "*":
        d = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        return [("q", (q, h)), ("k", (kv, h)), ("v", (kv, h)), ("o", (h, q))]
    raise ValueError("no layer kind %r (M, E, *)" % kind)


def leaf_specs(cfg):
    """``[(name, shape)]``: embedding, each layer's norm gain and its
    mixer's weights, final norm, head.  Dense weights are ``(out, in)``;
    a layer's experts are stacked ``(held, out, in)``."""
    h = cfg["hidden_size"]
    specs = [("embed", (cfg["vocab_size"], h))]
    for i, kind in enumerate(_pattern(cfg)):
        specs.append(("layer%d.norm" % i, (h,)))
        specs += [("layer%d.%s" % (i, k), s) for k, s in
                  _layer_specs(cfg, kind)]
    return specs + [("norm", (h,)), ("head", (cfg["vocab_size"], h))]


# -- the plain reference's equations ---------------------------------------------

def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _recurrence(x, dt, a, bh, ch, ein):
    """``y_t = H_t C_t`` with ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t
    B_t^T``; ``x (B, T, H, P)``, ``dt (B, T, H)``, ``a (H,)``, ``bh``/``ch
    (B, T, H, N)``.  Token by token, in stretches under a checkpoint."""
    b, t, h, p = x.shape
    n = bh.shape[-1]

    def token(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, ein("bhpn,bhn->bhp", state, ct)

    @jax.checkpoint
    def stretch(state, inp):
        return lax.scan(token, state, inp)

    size = math.gcd(t, SCAN_STRETCH)
    seq = tuple(jnp.moveaxis(v, 1, 0).reshape((t // size, size) + v.shape[:1]
                                              + v.shape[2:])
                for v in (x, dt, bh, ch))
    _, y = lax.scan(stretch, jnp.zeros((b, h, p, n), jnp.float32), seq)
    return jnp.moveaxis(y.reshape((t, b, h, p)), 0, 1)


def _mamba2(cfg, w, u, ein, index=0):
    heads, hd, inner, gn = _mamba_sizes(cfg)
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    b, t, _ = u.shape
    zxbcdt = ein("bti,oi->bto", u, w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    k = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, j:j + t] for j in range(k)], axis=-1)
    xbc = jax.nn.silu(jnp.sum(windows * w["conv_weight"], axis=-1)
                      + w["conv_bias"])
    x, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    x = x.reshape(b, t, heads, hd)
    rep = heads // groups
    bh = jnp.repeat(bm.reshape(b, t, groups, state), rep, axis=2)
    ch = jnp.repeat(cm.reshape(b, t, groups, state), rep, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"].reshape(heads))
    a = -jnp.exp(w["A_log"].reshape(heads))
    y = _recurrence(x, dt, a, bh, ch, ein) + x * w["D"][:, None]
    y = y.reshape(b, t, inner) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(b, t, groups, inner // groups),
                 w["norm_weight"].reshape(groups, inner // groups),
                 cfg["layer_norm_epsilon"]).reshape(b, t, inner)
    return ein("bti,oi->bto", y, w["out_proj"])


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


def _experts(cfg, w, u, ein, index=0):
    held, first = cfg["n_routed_experts"], cfg.get("held_experts_first", 0)
    scores = jax.nn.sigmoid(ein("bti,ei->bte", u, w["router"]))
    chosen, weight = route(cfg, scores, w["router_bias"], index)
    # the weight each held expert has for each token: 0 where not chosen
    onehot = chosen[..., None] == (first + jnp.arange(held))
    gate = jnp.sum(jnp.where(onehot, weight[..., None], 0.0), axis=-2)
    hid = _relu2(ein("bti,efi->btef", u, w["up"]))
    routed = ein("btef,eif->bti", hid * gate[..., None], w["down"])
    shared = ein("btf,if->bti", _relu2(ein("bti,fi->btf", u, w["shared_up"])),
                 w["shared_down"])
    return routed + shared


def _attention(cfg, w, u, ein, index=0):
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    b, t, _ = u.shape
    q = ein("bti,oi->bto", u, w["q"]).reshape(b, t, nh, d)
    k = ein("bti,oi->bto", u, w["k"]).reshape(b, t, nkv, d)
    v = ein("bti,oi->bto", u, w["v"]).reshape(b, t, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = ein("bhqk,bkhd->bqhd", p, v).reshape(b, t, nh * d)
    return ein("bti,oi->bto", a, w["o"])


MIXERS = {"M": _mamba2, "E": _experts, "*": _attention}


def forward(cfg, leaves, tokens, ein):
    """Logits ``(B, T, V)`` of ``tokens`` ``(B, T)``; ``ein(spec, a, b)``
    is every matrix product."""
    w = dict(zip([n for n, _ in leaf_specs(cfg)], leaves))
    eps = cfg["layer_norm_epsilon"]
    x = w["embed"][tokens]
    for i, kind in enumerate(_pattern(cfg)):
        pre = "layer%d." % i
        lw = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}

        @jax.checkpoint
        def layer(x, lw, kind=kind, i=i):
            return x + MIXERS[kind](cfg, lw, _rmsnorm(x, lw["norm"], eps),
                                    ein, i)
        x = layer(x, lw)
    return ein("bti,vi->btv", _rmsnorm(x, w["norm"], eps), w["head"])


# -- the count, from shapes alone ------------------------------------------------

def param_count(cfg):
    """All parameters of the configuration as its keys state it (the
    experts counted are the ``n_routed_experts`` held)."""
    return sum(math.prod(s) for _, s in leaf_specs(cfg))


def _scan_flops_per_token(cfg):
    """One forward pass of the chunked scan, a token and layer: within a
    chunk ``C B^T`` (a group) and its product with ``x`` (a head), of which
    the causal mask needs half; the state a chunk adds and the carried
    state's read-out (a head, each ``2 P N``)."""
    heads, hd, _, _ = _mamba_sizes(cfg)
    n, chunk = cfg["ssm_state_size"], cfg["chunk_size"]
    within = (cfg["n_groups"] * 2 * n * chunk + heads * 2 * hd * chunk) // 2
    return within + 2 * heads * 2 * hd * n


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recomputation: ``6 x`` the matrices a token
    meets, plus three times a forward pass's causal attention and the
    scan's within-chunk and state products.

    **The rule for routed work**: a token meets the router (all
    ``router_num_experts`` rows), the shared expert, and of the held
    experts the ``num_experts_per_tok * n_routed_experts /
    router_num_experts`` that uniform routing sends it to in expectation:
    the share of the model's routed work that is done here.  What a step
    really routed is counted by the program (``mxnet_moe_assignments_held_
    total``) and read by ``moe_grouped_roofline``, not here."""
    h = cfg["hidden_size"]
    mats, extra = cfg["vocab_size"] * h, 0
    for kind in _pattern(cfg):
        specs = dict(_layer_specs(cfg, kind))
        if kind == "E":
            share = cfg["num_experts_per_tok"] / cfg["router_num_experts"]
            routed = math.prod(specs["up"]) + math.prod(specs["down"])
            mats += sum(math.prod(specs[k]) for k in
                        ("router", "shared_up", "shared_down"))
            mats += int(routed * share)
        elif kind == "M":
            mats += math.prod(specs["in_proj"]) + math.prod(specs["out_proj"])
            extra += _scan_flops_per_token(cfg)
        else:
            mats += sum(math.prod(s) for s in specs.values())
            extra += 4 * seq * cfg["num_attention_heads"] \
                * cfg["head_dim"] // 2
    return 6 * mats + 3 * extra


# -- the new kernels' operations and bytes ------------------------------------------

def ssd_calls(cfg, batch, seq, elt=4):
    """``[{name, flops, bytes}]`` of one Mamba-2 layer's scan in a train
    step: the forward pass and the backward pass (twice the products; the
    recomputed forward does not count).  Bytes: ``x``, ``B``, ``C``, ``dt``
    in and ``y`` out, ``elt`` bytes an element (the operator is float32);
    backward reads those and ``dy`` and writes the four gradients."""
    heads, hd, inner, gn = _mamba_sizes(cfg)
    tokens = batch * seq
    fwd = tokens * _scan_flops_per_token(cfg)
    operands = tokens * (inner + 2 * gn + heads) * elt
    out = tokens * inner * elt
    return [{"name": "ssd_fwd", "flops": fwd, "bytes": operands + out},
            {"name": "ssd_bwd", "flops": 2 * fwd,
             "bytes": 2 * operands + 2 * out}]


def grouped_calls(cfg, rows, elt=2):
    """``[{name, flops, bytes}]`` of one expert layer's grouped products in
    a train step over ``rows`` landed rows (assignments to a held expert):
    up and down forward, and for each the gradient of its rows and of its
    weights: six products of ``2 * rows * hidden * moe_intermediate``.
    Each reads or writes the held experts' stacked matrix once and the
    landed rows of its two other operands."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2 * rows * h * f
    bytes_ = (cfg["n_routed_experts"] * h * f + rows * (h + f)) * elt
    return [{"name": "gmm_%s_%s" % (proj, what), "flops": flops,
             "bytes": bytes_}
            for proj in ("up", "down") for what in ("fwd", "drows", "dw")]


# -- the program -----------------------------------------------------------------

def build(cfg, ctx):
    """``gluon.model_zoo.nemotron_h.NemotronHModel`` at the configuration's
    sizes, zero-initialised on ``ctx``."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import nemotron_h

    net = nemotron_h.NemotronHModel(
        cfg["vocab_size"], cfg["hidden_size"], _pattern(cfg),
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_routed_experts=cfg["router_num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        held=(cfg.get("held_experts_first", 0), cfg["n_routed_experts"]),
        force_load_balancing=bool(
            cfg.get("moe_router_force_load_balancing", False)),
        eps=cfg["layer_norm_epsilon"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    net(mx.nd.array(np.zeros((1, 8), np.int32), ctx=ctx))
    return net
