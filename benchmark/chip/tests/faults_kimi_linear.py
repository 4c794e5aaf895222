"""Faults planted UNDER the harness in the program's delta-rule, latent
attention and routed layers, for the tests that see ``correct`` come out
false on the Kimi Linear cell (``test_kimi_linear_cell.py`` and tier-1's
``tests/test_kimi_linear_faults.py``): ``python faults_kimi_linear.py
<fault> <run.py arguments>`` plants the fault and then runs the benchmark's
own ``main``, in a rehearsal only.

- ``no_carry``: the delta rule's state is not carried over a chunk
  boundary (every chunk starts from zero);
- ``decay_per_head``: the decay is applied a head and not a channel (every
  channel of a head takes the mean of the head's log-decays);
- ``no_beta``: ``beta`` is left out of the delta rule (taken as one);
- ``rotary``: a rotary embedding is applied to the ``qk_rope_head_dim``
  channels of the latent attention's queries and keys (the model has none:
  ``mla_use_nope``).  **``correct`` does not see this one**: a rotation
  keeps the norm of every gradient and of every leaf's change, which is
  all that ``compare.train_numbers`` reads, and at random weights the
  rotated scores are as good as the plain ones (0.048 / 0.0078 at the tiny
  size, inside the program's own 0.017-0.071 / 0.003-0.008).  Tier-1 plants
  it and sees it in the logits (``tests/test_kimi_linear.py``);
- ``ninth_expert``: the router takes the expert ranked one below its last
  choice in that choice's place (the 9th for the 8th at k = 8);
- ``not_normalised``: the routing weights are not divided by their sum.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def plant(fault):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (registers the operators)
    from mxnet_tpu.ops import kda, registry

    def wrap(op, make):
        reg = registry.get(op)
        reg.forward = make(reg.forward)

    def chunked(change):
        # the delta rule as the mixer's operator calls it
        rule = kda._kda_chunked
        kda._kda_chunked = lambda q, k, v, g, beta, chunk: rule(
            *change(q, k, v, g, beta), chunk)

    if fault == "no_carry":
        import types

        lax = types.SimpleNamespace(**{k: getattr(jax.lax, k)
                                       for k in dir(jax.lax)})
        lax.scan = lambda f, init, xs: jax.lax.scan(
            lambda state, x: (init, f(init, x)[1]), init, xs)
        kda.lax = lax
    elif fault == "decay_per_head":
        chunked(lambda q, k, v, g, beta: (q, k, v, jnp.broadcast_to(
            jnp.mean(g, -1, keepdims=True), g.shape), beta))
    elif fault == "no_beta":
        chunked(lambda q, k, v, g, beta: (q, k, v, g, jnp.ones_like(beta)))
    elif fault == "rotary":
        def turn(x, rope):
            # rotate-half over the last ``rope`` channels of (B, H, T, D)
            half = rope // 2
            inv = jnp.exp(jnp.arange(half) * (-2.0 / rope)
                          * jnp.log(10000.0))
            ang = jnp.arange(x.shape[2])[:, None] * inv[None, :]
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            x1, x2 = x[..., -rope:-half], x[..., -half:]
            return jnp.concatenate(
                [x[..., :-rope], (x1 * cos - x2 * sin).astype(x.dtype),
                 (x2 * cos + x1 * sin).astype(x.dtype)], axis=-1)

        # the rope channels: what a key has more than a value
        wrap("_contrib_flash_attention",
             lambda f: lambda query, key, value, **kw: f(
                 turn(query, query.shape[-1] - value.shape[-1]),
                 turn(key, query.shape[-1] - value.shape[-1]), value, **kw))
    elif fault == "ninth_expert":
        top_k = jax.lax.top_k

        def next_for_last(x, k):
            vals, idx = top_k(x, k + 1)
            keep = jnp.r_[jnp.arange(k - 1), k]
            return vals[..., keep], idx[..., keep]
        jax.lax.top_k = next_for_last
    elif fault == "not_normalised":
        wrap("_contrib_moe_router_topk", lambda f: lambda *a, **kw: f(
            *a, **dict(kw, normalize=False)))
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
