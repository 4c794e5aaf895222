"""Routed-expert operators: top-k routing without drops, and the grouped
feed-forward over the experts a chip holds.

TPU-era additions with no reference counterpart.  ``parallel/moe.py`` hands
both out beside the older top-1, capacity-factor layer and says what they
are for; ``docs/moe_ssm.md`` has shapes and dtypes.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from .pallas_kernels import grouped_matmul, rows_combine, rows_relu2, \
    rows_swiglu, rows_take, rows_walked
from .registry import register


@register("_contrib_moe_router_topk", num_outputs=2,
          inputs=("data", "weight", "bias"))
def router_topk(data, weight, bias=None, k=1, scale=1.0, normalize=True,
                balance_seed=None, scoring="sigmoid"):
    """Top-k routing: ``(index (S, k) int32, weight (S, k) f32)``.

    ``data (S, D)``, ``weight (E, D)`` (one row an expert, all E of them
    whichever are held here).  The scores are float32 at the highest matmul
    precision (the choice is discrete: a rounded score flips it), by
    ``scoring``:

    - ``sigmoid`` (DeepSeek's rule): ``s = sigmoid(data @ weight.T)``; the
      ``k`` largest of ``s + bias`` (``bias (E,)``) are chosen, ties to the
      lower index (``lax.top_k``); the weights are ``s`` at the chosen
      experts, without the bias.  The bias takes no gradient: it moves
      indices only.
    - ``softmax`` (Qwen3-MoE's rule): ``s = softmax(data @ weight.T)`` over
      all E experts; the ``k`` largest of ``s`` are chosen; the weights are
      ``s`` at the chosen experts.  No bias (``bias`` must be None).

    The weights are then divided by their sum (+1e-20) where ``normalize``
    and multiplied by ``scale``.

    ``balance_seed`` (an int; for measuring throughput only) forces the
    load to balance, as Megatron-LM's ``--moe-router-force-load-balancing``
    does: the ``k`` experts of a row are the largest of ``uniform(PRNGKey(
    balance_seed), (S, E))`` in place of the scores (and bias), so every
    expert gets the same expected load whatever the weights and the data
    are; the weights are still ``s`` at the chosen experts.  Random weights
    route unevenly, each draw in its own way, and a step's cost follows the
    routing."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError("router_topk: no scoring %r (sigmoid, softmax)"
                         % (scoring,))
    if (bias is None) != (scoring == "softmax"):
        raise ValueError("router_topk: sigmoid scores take a bias, softmax "
                         "scores none")
    with jax.named_scope("moe_router"):
        logits = jnp.einsum(
            "sd,ed->se", data.astype(jnp.float32),
            weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
            else jax.nn.sigmoid(logits)
        if balance_seed is not None:
            choice = jax.random.uniform(
                jax.random.PRNGKey(int(balance_seed)), s.shape, jnp.float32)
        else:
            choice = s if bias is None else s + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, int(k))
        w = jnp.take_along_axis(s, idx, axis=1)
        if normalize:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale


@register("_contrib_moe_grouped_ffn", num_outputs=2,
          inputs=("data", "topk_idx", "topk_weight", "up", "down"))
def grouped_ffn(data, topk_idx, topk_weight, up, down, first=0,
                activation="relu2"):
    """The held experts' part of a routed feed-forward layer, no drops.

    ``data (S, D)``; ``topk_idx``/``topk_weight (S, k)`` from
    ``router_topk``; ``up`` and ``down (held, D, F)``: the experts ``first
    .. first + held - 1`` of the layer, stacked.  ``activation`` is the
    experts' own: ``relu2``, ``up (held, F, D)`` and each expert
    ``down_e(relu(up_e(u))^2)``; or ``swiglu``, ``up (held, 2F, D)`` holding
    the gate's rows and then the up-projection's (one grouped product for
    both) and each expert ``down_e(silu(gate_e(u)) * up_e(u))``.  Returns
    ``(out (S, D) float32, counts (held + 3,) uint32)``: ``out[s] = sum``
    over the assignments of token ``s`` to a held expert of ``weight *
    expert(data[s])``; ``counts`` are
    the assignments that landed on each held expert, the assignments in
    all (``S * k``), those to a held expert whose row did not pass into
    the result (the assignments the indices send here less the rows
    ``_computed`` lets through: it has to stay 0), and the rows of the
    sorted layout that the kernels walked.

    The ``S * k`` assignments are sorted by expert, absent experts last,
    and the rows that landed are the first of a layout ``(S * k, .)``
    whose shapes are static at that bound (every assignment may land
    here): there is no capacity and no smaller bound to overflow.  Only
    kernels touch the layout, and each walks the 128-row tiles that hold
    rows and no other, so a call costs what landed (``pallas_kernels``:
    ``rows_take`` gathers the tokens' rows, ``grouped_matmul`` is one
    product a projection with the landed rows as its group sizes,
    ``rows_relu2`` or ``rows_swiglu`` the activation, ``rows_combine`` the
    weighted sum over a token's assignments).  What lies past the landed
    rows is undefined
    and no one may read it: XLA's arithmetic sees the layout's integers
    alone (the sorts, the sizes).  The matrix products run in
    ``data``'s dtype, accumulated in float32, on the weights as they are
    stored (nothing is transposed); the weighted sum is float32, in the
    order of a token's experts.  The backward pass is written by hand of
    the same kernels; it keeps the layout's integers and makes the
    layout's rows again from the inputs, once the result's gradient is
    there."""
    if activation not in ("relu2", "swiglu"):
        raise ValueError("grouped_ffn: no activation %r (relu2, swiglu)"
                         % (activation,))
    with jax.named_scope("moe_experts"):
        return _grouped_ffn(data, topk_idx, topk_weight, up, down,
                            int(first), activation)


def _computed(key, order, held):
    """Which of the sorted assignments are rows of a held expert (``key``
    is an assignment's expert among the held, ``held`` for an absent one):
    those, and no other, pass into the result."""
    return key[order] < held


# Sorted row ``p`` is assignment ``order[p]`` (of token ``token[p]``); the
# first ``landed[0]`` rows are the held experts', ``sizes`` of them an expert;
# assignment ``i`` passes into the result where ``passes[i]``, with
# ``weight[i]`` (0 where not); ``counts`` as ``grouped_ffn`` returns them.
_Layout = collections.namedtuple(
    "_Layout", "token order sizes landed passes weight counts")


def _by_assignment(order, sorted_values):
    """``sorted_values`` (one a sorted row) in the assignments' own order:
    a sort by ``order``.  A TPU sorts the keys in a fraction of the time
    its gather ``sorted_values[argsort(order)]`` takes them one by one."""
    return jax.lax.sort_key_val(order, sorted_values)[1]


def _layout(topk_idx, topk_weight, held, first):
    """The sorted layout's integers, and the weights by assignment."""
    s, k = topk_idx.shape
    local = topk_idx.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)               # absent experts last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    landed = jnp.sum(sizes, keepdims=True)
    computed = _computed(key, order, held)
    passes = _by_assignment(order, computed)
    weight = jnp.where(passes, topk_weight.reshape(-1).astype(jnp.float32),
                       0)
    counts = jnp.concatenate([
        sizes, jnp.array([s * k], jnp.int32),
        (jnp.sum(here, dtype=jnp.int32)
         - jnp.sum(computed, dtype=jnp.int32))[None],
        rows_walked(landed, s * k)[None]])
    return _Layout(order // k, order, sizes, landed, passes, weight,
                   counts.astype(jnp.uint32))


def _rows_act(activation):
    """The experts' activation along the sorted layout."""
    return rows_swiglu if activation == "swiglu" else rows_relu2


def _sorted_forward(data, lay, up, down, activation):
    """Along the sorted layout: the up-projection's result and vjp, the
    down-projection's result and vjp."""
    rows = rows_take(data.astype(jnp.float32), lay.token, lay.order,
                     jnp.ones_like(lay.weight), lay.landed,
                     lay.order.shape[0], data.dtype)

    def product(rows, w):
        return grouped_matmul(rows, w, lay.sizes)
    hid, vjp_up = jax.vjp(product, rows, up.astype(data.dtype))
    res, vjp_down = jax.vjp(product, _rows_act(activation)(hid, lay.landed),
                            down.astype(data.dtype))
    return hid, vjp_up, res, vjp_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_ffn(data, topk_idx, topk_weight, up, down, first,
                 activation="relu2"):
    return _grouped_ffn_fwd(data, topk_idx, topk_weight, up, down, first,
                            activation)[0]


def _grouped_ffn_fwd(data, topk_idx, topk_weight, up, down, first,
                     activation):
    lay = _layout(topk_idx, topk_weight, up.shape[0], first)
    _, _, res, _ = _sorted_forward(data, lay, up, down, activation)
    out = rows_combine(res, lay.token, lay.order, lay.weight, lay.landed,
                       data.shape[0])
    return (out, lay.counts), (data, topk_weight, up, down, lay)


def _grouped_ffn_bwd(first, activation, kept, grads):
    data, topk_weight, up, down, lay = kept
    # the layout's rows are made again when the result's gradient is there
    # and not before: left free, the compiler makes every layer's rows
    # early and holds them all at once (a layer's are 0.5 GB at 32,768 rows)
    data, d_out = jax.lax.optimization_barrier((data, grads[0]))
    hid, vjp_up, res, vjp_down = _sorted_forward(data, lay, up, down,
                                                 activation)
    d_res, dots = rows_take(d_out.astype(jnp.float32), lay.token,
                            lay.order, lay.weight, lay.landed,
                            lay.order.shape[0], data.dtype, other=res)
    d_act, d_down = vjp_down(d_res)
    d_rows, d_up = vjp_up(_rows_act(activation)(hid, lay.landed, grad=d_act))
    d_data = rows_combine(d_rows, lay.token, lay.order,
                          jnp.ones_like(lay.weight), lay.landed,
                          data.shape[0])
    # a select: the dots past the tiles that hold rows are undefined
    d_weight = jnp.where(lay.passes, _by_assignment(lay.order, dots), 0)
    return (d_data.astype(data.dtype), None,
            d_weight.reshape(topk_weight.shape).astype(topk_weight.dtype),
            d_up.astype(up.dtype), d_down.astype(down.dtype))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)
