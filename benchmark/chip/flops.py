"""Operations and bytes that the algorithm needs, from shapes alone.

The yardstick every utilization and roofline share of this benchmark is
measured with.  Nothing here reads the program: a configuration is the
dict of a file under ``configs/`` (the published ``config.json`` keys).
What is one architecture's own (its parameters, the operations a token
needs) is stated by ``archs/<model_type>.py`` and looked up here, so that a
reader calls one name for every configuration.  Recomputed operations never
count, and neither does the embedding lookup.
"""
from __future__ import annotations

import json
import os

import archs

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


# -- the model's own count: the configuration's architecture states it ------

def param_count(cfg):
    """All parameters of the configuration as it is run."""
    return archs.of(cfg).param_count(cfg)


def train_flops_per_token(cfg, seq):
    """Operations one token of a ``seq``-token sequence needs in a train
    step: forward + backward, no recomputation."""
    return archs.of(cfg).train_flops_per_token(cfg, seq)


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
