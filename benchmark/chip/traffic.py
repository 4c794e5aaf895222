"""Seeded traffic: the one generator every driver draws from.

Everything a cell sends comes from ``--seed`` and the parameters in its
``workloads/<cell>.json``; the program receives only the generated inputs.
Every seed gives a cell the same sizes and the same number of arrivals a
second, in another order, so that no seed changes the work.

``poisson_requests`` is a copy of ``mxnet_tpu.serve.server.poisson_workload``
(seeded, open loop, heavy-tailed budgets), repaired: each request carries
the time it is due, counted from the window's start, so that a driver times
it from then and reports how late it was sent.  The original is listed in
PERF.md for a later PR to delete.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed, stream):
    """An independent generator for ``stream`` (a small int) of ``seed``;
    any whole number up to 2**63 is a seed."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), int(stream)])


def token_batches(seed, batch, seq, vocab):
    """An endless feed of ``(tokens (batch, seq) int32, labels (batch*seq,)
    float32)``, every row different, drawn on the host as an input pipeline
    would hand them over.  Labels are next-token ids drawn independently
    (random weights: nothing is learned), in the float32 the gluon loss
    takes."""
    rng = rng_for(seed, 1)
    while True:
        toks = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
        labels = rng.integers(0, vocab, batch * seq, dtype=np.int32)
        yield toks, labels.astype(np.float32)


def poisson_requests(seed, seconds, rate, prompt_len, new_tokens, vocab,
                     tail=1.5):
    """Open-loop arrivals for ``seconds`` at ``rate`` requests a second:
    ``[{"due_s", "prompt", "max_new_tokens"}]`` sorted by ``due_s``.

    Inter-arrival gaps are exponential; prompt lengths uniform over
    ``prompt_len = (lo, hi)``; output budgets heavy-tailed over
    ``new_tokens = (lo, hi)`` (a Pareto of shape ``tail`` cut to the range),
    token ids uniform over the vocabulary.  The number of requests is fixed
    by ``rate * seconds`` and not drawn, and lengths are a seed-independent
    set in a seed-dependent order."""
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 2)
    gaps = rng.exponential(1.0, n)
    due = np.cumsum(gaps) / gaps.sum() * seconds * n / (n + 1.0)
    fixed = np.random.default_rng(0)      # the same sizes for every seed
    lo, hi = prompt_len
    lens = fixed.integers(lo, hi + 1, n)
    blo, bhi = new_tokens
    budgets = np.minimum(bhi, blo * (1.0 + fixed.pareto(tail, n))) \
        .astype(np.int64)
    order = rng.permutation(n)
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, int(lens[j])).tolist(),
             "max_new_tokens": int(budgets[j])}
            for i, j in enumerate(order)]
