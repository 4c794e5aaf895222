"""Faults planted UNDER the harness in the program's Mamba-2 and expert
layers, for the tests that see ``correct`` come out false on the Nemotron-H
cell (``test_nemotron_h_cell.py`` and tier-1's ``tests/test_nemotron_h.py``):
``python faults_nemotron_h.py <fault> <run.py arguments>`` plants the fault
and then runs the benchmark's own ``main``, in a rehearsal only.

- ``no_carry``: the scan's state is not carried over a chunk boundary
  (every chunk starts from zero);
- ``seventh_expert``: the router takes the expert ranked one below its
  last choice in that choice's place (the 7th for the 6th at k = 6);
- ``not_normalised``: the routing weights are not divided by their sum;
- ``dropped``: assignments are dropped as a capacity factor of 1.0 drops
  them: each held expert keeps the first rows that landed on it, up to the
  mean load of the held experts, and the rest are left out of the result
  (planted in ``ops/moe.py:_computed``, which says what passes into the grouped
  products and out of them, so ``mxnet_moe_dropped_total`` counts them).  (One assignment
  alone is 1 of 128 a layer at the tiny size and reads inside the spread
  that the program's own flipped routing choices have there.)
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def plant(fault):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (registers the operators)
    from mxnet_tpu.ops import registry, ssm

    def wrap(op, make):
        reg = registry.get(op)
        reg.forward = make(reg.forward)

    if fault == "no_carry":
        class NoCarry:
            Precision = jax.lax.Precision

            @staticmethod
            def scan(f, init, xs):
                return init, jnp.zeros((xs[0].shape[0],) + init.shape,
                                       init.dtype)
        ssm.lax = NoCarry
    elif fault == "seventh_expert":
        top_k = jax.lax.top_k

        def next_for_last(x, k):
            vals, idx = top_k(x, k + 1)
            keep = jnp.r_[jnp.arange(k - 1), k]
            return vals[..., keep], idx[..., keep]
        jax.lax.top_k = next_for_last
    elif fault == "not_normalised":
        wrap("_contrib_moe_router_topk", lambda f: lambda *a, **kw: f(
            *a, **dict(kw, normalize=False)))
    elif fault == "dropped":
        from mxnet_tpu.ops import moe

        def capped(key, order, held):
            expert = key[order]                     # sorted: held, then absent
            place = jnp.arange(expert.size) - jnp.searchsorted(
                expert, expert, side="left")        # place in its queue
            capacity = jnp.sum(expert < held) // held
            return (expert < held) & (place < capacity)
        moe._computed = capped
    else:
        raise SystemExit("unknown fault %r" % fault)


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    if "--rehearse" not in argv:
        raise SystemExit("faults are planted in rehearsals only")
    os.environ["JAX_PLATFORMS"] = "cpu"      # as run.py does before jax
    sys.path.insert(0, CHIP)
    sys.path.insert(0, os.path.dirname(os.path.dirname(CHIP)))
    plant(fault)
    import run

    sys.exit(run.main(argv))
