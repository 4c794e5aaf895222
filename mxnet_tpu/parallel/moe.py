"""Expert parallelism: mixture-of-experts FFN sharded over a mesh axis.

No reference counterpart (SURVEY §2.4 lists EP/MoE as absent) — designed
TPU-first in the GShard/Switch mold: top-1 token routing with a capacity
factor, dense einsum dispatch/combine (XLA-friendly — no dynamic
shapes), experts laid out along an ``expert`` mesh axis so each device
holds ``E / ep`` expert FFNs.  Inside ``shard_map`` the dispatch einsum
contracts the LOCAL expert slice only; the final combine ``psum``s
partial outputs over the axis — the all-to-all of classic MoE expressed
as (replicated tokens × sharded experts), which XLA lowers to ICI
collectives under jit.

Because routing is a straight-through top-1 (gate value scales the
expert output), the whole layer is differentiable; dropped tokens
(capacity overflow) contribute zero output and zero gradient, exactly
like Switch Transformer.

Beside it, the layer of today's sparse models: ``router_topk`` (sigmoid
scores, a correction bias that moves the choice and not the weight, k of E,
normalised and scaled) and ``grouped_ffn``, which has **no capacity and
drops nothing**: assignments are sorted by expert and each projection is
one grouped product (``ops/pallas_kernels.py:grouped_matmul``: the Pallas
kernels ``mx_gmm`` / ``mx_gmm_dw``, whose grid follows the group sizes) over
the rows that landed.  ``first`` and the leading axis of the stacked weights
tell the layer which experts it holds: it routes over all of them and
computes its own experts' part of the result, which is what expert
parallelism asks of each chip; on one chip there is no exchange, and
nothing stands in for the absent chips.  Both are registered operators
(``F.contrib.moe_router_topk``, ``F.contrib.moe_grouped_ffn``), so their
code lives with the operators in ``ops/moe.py`` and is handed out here;
``docs/moe_ssm.md`` has shapes and dtypes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ops.moe import grouped_ffn, router_topk  # noqa: F401
from ..telemetry import metrics

__all__ = ["moe_ffn", "moe_ffn_sharded", "router_top1", "router_topk",
           "grouped_ffn"]


def router_top1(x, router_w, n_experts, capacity):
    """Top-1 routing: returns (dispatch (S,E,C), combine (S,E,C), aux_loss).

    ``dispatch`` is a 0/1 mask placing each kept token into an expert
    capacity slot; ``combine`` carries the gate probability in the same
    slot.  ``aux_loss`` is the Switch load-balancing loss
    (E * Σ_e fraction_e * prob_e).
    """
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)          # (S, E)
    expert = jnp.argmax(probs, axis=-1)              # (S,)
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
    # position of each token within its expert's queue
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (S, E)
    kept = (pos < capacity) & (onehot > 0)
    slot = pos.astype(jnp.int32)
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32) \
        * kept[..., None]                            # (S, E, C)
    dispatch = slot_oh
    combine = dispatch * gate[:, None, None]
    # load-balancing auxiliary (Switch eq. 4)
    frac = jnp.mean(onehot, axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac * prob_mean)
    return dispatch, combine, aux


def _expert_ffn(w_in, w_out, h):
    """(E, C, D) tokens through per-expert SwiGLU-free MLPs: gelu MLP."""
    a = jnp.einsum("ecd,edh->ech", h, w_in)
    a = jax.nn.gelu(a)
    return jnp.einsum("ech,ehd->ecd", a, w_out)


def moe_ffn(x, router_w, w_in, w_out, capacity_factor=1.25):
    """Single-device MoE FFN (the semantics oracle for the sharded path).

    x (S, D); router_w (D, E); w_in (E, D, H); w_out (E, H, D).
    Returns (y (S, D), aux_loss).
    """
    s, d = x.shape
    e = router_w.shape[1]
    capacity = max(1, int(capacity_factor * s / e))
    dispatch, combine, aux = router_top1(x, router_w, e, capacity)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch,
                           x.astype(jnp.float32))
    expert_out = _expert_ffn(w_in.astype(jnp.float32),
                             w_out.astype(jnp.float32), expert_in)
    y = jnp.einsum("sec,ecd->sd", combine, expert_out)
    return y.astype(x.dtype), aux


def moe_ffn_sharded(x, router_w, w_in, w_out, mesh, axis_name="expert",
                    capacity_factor=1.25):
    """Expert-parallel MoE FFN over ``axis_name`` of ``mesh``.

    Tokens are replicated along the expert axis; the expert weight
    tables (E, ...) are sharded so each device runs only its local
    E/ep experts, and partial outputs are ``psum``-combined.  Numerics
    match :func:`moe_ffn` exactly (same routing, same capacity).
    """
    from ..analysis.collective_check import check_axis
    from .. import sharding as _sharding

    mesh = _sharding.as_jax_mesh(mesh)
    check_axis(mesh, axis_name, op="moe_ffn_sharded")
    ep = mesh.shape[axis_name]
    e = router_w.shape[1]
    if e % ep != 0:
        raise MXNetError("n_experts (%d) must divide the %r axis (%d)"
                         % (e, axis_name, ep))
    s = x.shape[0]
    capacity = max(1, int(capacity_factor * s / e))

    def local(xl, rw, wi, wo):
        # routing is computed identically everywhere (replicated inputs,
        # full router table); only the expert compute is sharded
        dispatch, combine, aux = router_top1(xl, rw, e, capacity)
        idx = jax.lax.axis_index(axis_name)
        lo = idx * (e // ep)
        dloc = jax.lax.dynamic_slice_in_dim(dispatch, lo, e // ep, 1)
        cloc = jax.lax.dynamic_slice_in_dim(combine, lo, e // ep, 1)
        expert_in = jnp.einsum("sec,sd->ecd", dloc,
                               xl.astype(jnp.float32))
        expert_out = _expert_ffn(wi.astype(jnp.float32),
                                 wo.astype(jnp.float32), expert_in)
        y = jnp.einsum("sec,ecd->sd", cloc, expert_out)
        return jax.lax.psum(y, axis_name).astype(xl.dtype), aux

    from .mesh import shard_map

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P()),
        check_vma=False,
    )(x, router_w, w_in, w_out)



# -- counters, fed from the train steps' accumulated counts when read -------

STAT_PREFIX = "moe/"      # a layer's statistic: "moe/<layer>/<first expert>"
_seen = {}                # (step, statistic) -> the counts last read


def _telemetry_collector():
    """``grouped_ffn``'s counts leave a ``JitTrainStep`` program as a
    statistic it accumulates on the device (``gluon.block.
    record_step_stat``); a snapshot fetches them and adds what is new
    (modulo the accumulators' 32 bits) to the four counter families."""
    from .train_step import read_step_stats

    total = dropped = walked = 0
    every = read_step_stats(STAT_PREFIX)
    if not every:       # no step of this process routes: no family either
        return
    for owner, stats in every:
        for name, counts in stats.items():
            counts = [int(c) for c in counts]
            last = _seen.get((owner, name), [0] * len(counts))
            _seen[(owner, name)] = counts
            new = [(c - p) % (1 << 32) for c, p in zip(counts, last)]
            _, layer, first = name.split("/")
            for j, n in enumerate(new[:-3]):
                if n:
                    metrics.counter(
                        "mxnet_moe_assignments_held_total",
                        help="routed assignments that landed on an expert "
                             "held here", layer=layer,
                        expert=str(int(first) + j)).inc(n)
            total += new[-3]
            dropped += new[-2]
            walked += new[-1]
    metrics.counter("mxnet_moe_assignments_total",
                    help="routed assignments (tokens x experts a token), "
                         "every routed layer").inc(total)
    metrics.counter("mxnet_moe_dropped_total",
                    help="assignments to a held expert that were not "
                         "computed; has to stay 0").inc(dropped)
    metrics.counter("mxnet_moe_sorted_rows_walked_total",
                    help="rows of the sorted layout (of as many as there "
                         "are assignments, a layer) that the kernels "
                         "around the grouped products walked: the tiles "
                         "that hold a landed row").inc(walked)


metrics.register_collector(_telemetry_collector)
