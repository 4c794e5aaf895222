"""Operations and bytes that the algorithm needs, from shapes alone.

The yardstick every utilization and roofline share of this benchmark is
measured with.  Nothing here reads the program: a configuration is the
dict of a file under ``configs/`` (the published ``config.json`` keys).
Recomputed operations never count, and neither does the embedding lookup.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError("device_kind %r is not in peaks.json (known: %s)"
                       % (device_kind, sorted(table)))
    return table[device_kind]


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg):
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and the
    two RMSNorm gains (no biases)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = h * cfg["num_key_value_heads"] * d
    return 2 * q + 2 * kv + 3 * h * cfg["intermediate_size"] + 2 * h


def param_count(cfg, num_layers=None):
    """All parameters: embedding, layers, final norm and, where the
    embeddings are not tied, the output head."""
    n = cfg["num_hidden_layers"] if num_layers is None else num_layers
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg.get("tie_word_embeddings") else emb
    return emb + n * layer_params(cfg) + cfg["hidden_size"] + head


def matmul_params(cfg):
    """Parameters that multiply every token: the layers' matrices and the
    head (tied or not); the embedding lookup and the norm gains do not."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * h)
            + cfg["vocab_size"] * h)


def attention_flops_per_token(cfg, seq, causal=True):
    """QK^T and PV of one forward pass, all layers, per token of a
    sequence of ``seq`` tokens; a causal mask halves what is needed."""
    units = cfg["num_attention_heads"] * head_dim(cfg)
    full = 4 * seq * units * cfg["num_hidden_layers"]
    return full // 2 if causal else full


def train_flops_per_token(cfg, seq):
    """Forward + backward (2 + 4 operations per multiply-add), no
    recomputation: ``6 * matmul_params`` plus three times the causal
    attention of a forward pass."""
    return 6 * matmul_params(cfg) + 3 * attention_flops_per_token(cfg, seq)


def forward_flops_per_token(cfg, context):
    """One forward pass of one token that attends to ``context`` keys."""
    units = cfg["num_attention_heads"] * head_dim(cfg)
    return (2 * matmul_params(cfg)
            + 4 * context * units * cfg["num_hidden_layers"])


# -- the three flash-attention kernels of one layer ------------------------
# Each call sees (bh, seq, d) operands of ``elt`` bytes an element: bh is
# batch * query heads (the program repeats K/V to the query heads before
# the kernel).  The row statistics (logsumexp, delta) are one float32 a row.

def flash_calls(batch, heads, seq, d, elt=2, causal=True):
    """``[{name, flops, bytes}]`` for the forward, dq and dk/dv kernels."""
    bh = batch * heads
    mm = 2 * bh * seq * seq * d          # one (seq x d) x (d x seq) product
    if causal:
        mm //= 2
    tile = bh * seq * d * elt
    stat = bh * seq * 4
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


def least_seconds(calls, peak):
    """The least time the chip could take for ``calls``: for each the
    larger of operations over peak FLOP/s and bytes over peak bytes/s.
    Returns ``(seconds, bound)`` with ``bound`` the side that set most of
    it."""
    total, by = 0.0, {"flops": 0.0, "bytes": 0.0}
    for c in calls:
        tf = c["flops"] / peak["bf16_flops"]
        tb = c["bytes"] / peak["hbm_bytes_per_s"]
        total += max(tf, tb)
        by["flops" if tf >= tb else "bytes"] += max(tf, tb)
    return total, max(by, key=by.get)
