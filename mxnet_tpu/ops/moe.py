"""Routed-expert operators: top-k routing without drops, and the grouped
feed-forward over the experts a chip holds.

TPU-era additions with no reference counterpart.  ``parallel/moe.py`` hands
both out beside the older top-1, capacity-factor layer and says what they
are for; ``docs/moe_ssm.md`` has shapes and dtypes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_kernels import grouped_matmul
from .registry import register


@register("_contrib_moe_router_topk", num_outputs=2,
          inputs=("data", "weight", "bias"))
def router_topk(data, weight, bias, k=1, scale=1.0, normalize=True,
                balance_seed=None):
    """Sigmoid top-k routing: ``(index (S, k) int32, weight (S, k) f32)``.

    ``data (S, D)``, ``weight (E, D)`` (one row an expert, all E of them
    whichever are held here), ``bias (E,)``.  ``s = sigmoid(data @
    weight.T)`` in float32 at the highest matmul precision (the choice is
    discrete: a rounded score flips it); the ``k`` largest of ``s + bias``
    are chosen, ties to the lower index (``lax.top_k``); the weights are
    ``s`` at the chosen experts, without the bias, over their sum (+1e-20)
    where ``normalize``, times ``scale``.  The bias takes no gradient: it
    moves indices only.

    ``balance_seed`` (an int; for measuring throughput only) forces the
    load to balance, as Megatron-LM's ``--moe-router-force-load-balancing``
    does: the ``k`` experts of a row are the largest of ``uniform(PRNGKey(
    balance_seed), (S, E))`` in place of ``s + bias``, so every expert gets
    the same expected load whatever the weights and the data are; the
    weights are still ``s`` at the chosen experts.  Random weights route
    unevenly, each draw in its own way, and a step's cost follows the
    routing."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.einsum(
            "sd,ed->se", data.astype(jnp.float32),
            weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if balance_seed is None:
            choice = s + bias.astype(jnp.float32)
        else:
            choice = jax.random.uniform(
                jax.random.PRNGKey(int(balance_seed)), s.shape, jnp.float32)
        _, idx = jax.lax.top_k(choice, int(k))
        w = jnp.take_along_axis(s, idx, axis=1)
        if normalize:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# Tokens to their sorted assignments and back.  ``order`` is a permutation
# of the ``S * k`` assignments (assignment ``i`` is token ``i // k``) and
# ``inverse`` undoes it.  Each map's transpose is the other, so both
# directions of both are gathers: jax's own transpose of a gather is a
# scatter-add, which a TPU serialises.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_sorted(x, order, inverse, k):
    """``(S, D) -> (S * k, D)``: row ``i`` is the token of assignment
    ``order[i]``."""
    return x[order // k]


def _to_sorted_fwd(x, order, inverse, k):
    return _to_sorted(x, order, inverse, k), (order, inverse)


def _to_sorted_bwd(k, res, g):
    return _from_sorted(g, *res, k), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _from_sorted(rows, order, inverse, k):
    """``(S * k, D) -> (S, D)``: the sum over each token's ``k``
    assignments."""
    return rows[inverse].reshape(-1, k, rows.shape[1]).sum(axis=1)


def _from_sorted_fwd(rows, order, inverse, k):
    return _from_sorted(rows, order, inverse, k), (order, inverse)


def _from_sorted_bwd(k, res, g):
    return _to_sorted(g, *res, k), None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)
_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


@register("_contrib_moe_grouped_ffn", num_outputs=2,
          inputs=("data", "topk_idx", "topk_weight", "up", "down"))
def grouped_ffn(data, topk_idx, topk_weight, up, down, first=0):
    """The held experts' part of a routed feed-forward layer, no drops.

    ``data (S, D)``; ``topk_idx``/``topk_weight (S, k)`` from
    ``router_topk``; ``up (held, F, D)`` and ``down (held, D, F)``: the
    experts ``first .. first + held - 1`` of the layer, stacked, each
    ``down_e(relu(up_e(u))^2)``.  Returns ``(out (S, D) float32, counts
    (held + 2,) uint32)``: ``out[s] = sum`` over the assignments of token
    ``s`` to a held expert of ``weight * expert(data[s])``; ``counts`` are
    the assignments that landed on each held expert, the assignments in
    all (``S * k``), and those to a held expert whose row did not pass
    into the result (the assignments the indices send here less the rows
    ``_computed`` lets through: it has to stay 0).

    The ``S * k`` assignments are sorted by expert, absent experts last;
    the sorted rows go through one grouped product a projection
    (``pallas_kernels.grouped_matmul``: the kernels ``mx_gmm`` and, for
    the weights' gradient, ``mx_gmm_dw``) whose group sizes are the rows
    that landed, so rows of absent experts cost no product and a call
    costs what landed.  Shapes are static at the bound ``S * k`` (every
    assignment may land here): there is no capacity and no smaller bound
    to overflow.  The matrix products run in ``data``'s dtype, accumulated
    in float32, on the weights as they are stored (nothing is transposed);
    the weighted sum over a token's assignments is float32.  Nothing of the
    sorted layout is kept for the backward pass (``jax.checkpoint``): it
    is made again from the inputs."""
    with jax.named_scope("moe_experts"):
        return jax.checkpoint(_grouped_ffn, static_argnums=(5,))(
            data, topk_idx, topk_weight, up, down, int(first))


def _computed(key, order, held):
    """Which of the sorted assignments are rows of a held expert (``key``
    is an assignment's expert among the held, ``held`` for an absent one):
    those, and no other, pass into the products and out of them."""
    return key[order] < held


def _grouped_ffn(data, topk_idx, topk_weight, up, down, first):
    s, _ = data.shape
    k = topk_idx.shape[1]
    held = up.shape[0]
    local = topk_idx.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)               # absent experts last
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    computed = _computed(key, order, held)
    mask = computed[:, None]
    # a grouped product leaves the rows past the last group undefined: they
    # are masked on the way in and on the way out, each time by a select
    # that stands next to the product, so that no undefined value meets
    # arithmetic in either direction (0 * nan is nan)
    rows = jnp.where(mask, _to_sorted(data, order, inverse, k), 0)
    hid = grouped_matmul(rows, up.astype(data.dtype), sizes)
    hid = _relu2(jnp.where(mask, hid, 0))
    res = grouped_matmul(hid, down.astype(data.dtype), sizes)
    w = topk_weight.reshape(-1)[order].astype(jnp.float32)
    res = jnp.where(mask, res, 0).astype(jnp.float32) * w[:, None]
    out = _from_sorted(res, order, inverse, k)
    counts = jnp.concatenate([
        sizes, jnp.array([s * k], jnp.int32),
        (jnp.sum(here, dtype=jnp.int32)
         - jnp.sum(computed, dtype=jnp.int32))[None]])
    return out, jax.lax.stop_gradient(counts.astype(jnp.uint32))
