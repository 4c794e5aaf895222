"""Faults planted UNDER the harness in the program's block-diffusion
attention and routed layers, for the tests that see ``correct`` come out
false on the SDAR cell (``test_sdar_cell.py``): ``python faults_sdar_moe.py
<fault> <run.py arguments>`` plants the fault and then runs the benchmark's
own ``main``, in a rehearsal only.

- ``noised_causal``: the pass over ``[x_0 | x_t]`` is attended causally (a
  noised position sees every clean one and the noised ones before it);
- ``noised_prefix``: a noised position sees the noised blocks up to its own
  in place of the clean blocks before it;
- ``noised_positions``: the noised copy is turned at positions ``T .. 2T-1``
  in place of ``0 .. T-1``;
- ``no_qk_norm``: the RMSNorm over each head's channels of q and k is left
  out;
- ``sigmoid``: the router scores by a sigmoid (DeepSeek's rule, no bias) in
  place of the softmax over all experts;
- ``seventeenth_expert``: the share takes the assignments of the expert just
  past it (the 17th at the cell's 16 held, the 5th at the tiny sizes' 4) as
  its last expert's.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def plant(fault, cfg):
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (registers the operators)
    from mxnet_tpu.gluon.model_zoo import sdar_moe
    from mxnet_tpu.ops import registry

    def wrap(op, make):
        reg = registry.get(op)
        reg.forward = make(reg.forward)

    def attend_under(mask_of):
        # the program's attention with another dense mask over [x_0 | x_t]
        def attend(f):
            def masked(query, key, value, scale=None, mask=None, **kw):
                if mask is None:
                    return f(query, key, value, scale=scale, **kw)
                b, h, t, d = query.shape
                s = jnp.einsum("bhqd,bhkd->bhqk", query.astype(jnp.float32),
                               key.astype(jnp.float32)) * scale
                s = jnp.where(mask_of(t // 2, mask[2]), s, -jnp.inf)
                p = jnp.exp(s - s.max(-1, keepdims=True))
                p = p / p.sum(-1, keepdims=True)
                return jnp.einsum("bhqk,bhkd->bhqd", p, value.astype(
                    jnp.float32)).astype(query.dtype)
            return masked
        wrap("_contrib_flash_attention", attend)

    if fault == "noised_causal":
        attend_under(lambda half, block: jnp.tril(
            jnp.ones((2 * half, 2 * half), bool)))
    elif fault == "noised_prefix":
        def prefix(half, block):
            i = jnp.arange(2 * half)
            noised, blk = i >= half, (i % half) // block
            return jnp.where(
                noised[None, :], noised[:, None]
                & (blk[None, :] <= blk[:, None]),
                ~noised[:, None] & (blk[None, :] <= blk[:, None]))
        attend_under(prefix)
    elif fault == "noised_positions":
        turn = sdar_moe._rope

        def whole(F, x, theta):
            b, n2, half, d = x.shape
            out = turn(F, F.reshape(x, shape=(b, n2 // 2, 2 * half, d)),
                       theta)
            return F.reshape(out, shape=(b, n2, half, d))
        sdar_moe._rope = whole
    elif fault == "no_qk_norm":
        width = cfg["head_dim"]
        wrap("RMSNorm", lambda f: lambda data, gamma, **kw: data
             if gamma.shape == (width,) else f(data, gamma, **kw))
    elif fault == "sigmoid":
        wrap("_contrib_moe_router_topk", lambda f: lambda data, weight,
             bias=None, **kw: f(data, weight,
                                jnp.zeros((weight.shape[0],), jnp.float32),
                                **dict(kw, scoring="sigmoid")))
    elif fault == "seventeenth_expert":
        last = cfg.get("held_experts_first", 0) + cfg["num_experts"]

        def one_more(f):
            def grouped(data, topk_idx, *a, **kw):
                return f(data, jnp.where(topk_idx == last, last - 1,
                                         topk_idx), *a, **kw)
            return grouped
        wrap("_contrib_moe_grouped_ffn", one_more)
    else:
        raise SystemExit("unknown fault %r" % fault)


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    if "--rehearse" not in argv:
        raise SystemExit("faults are planted in rehearsals only")
    os.environ["JAX_PLATFORMS"] = "cpu"      # as run.py does before jax
    sys.path.insert(0, CHIP)
    sys.path.insert(0, os.path.dirname(os.path.dirname(CHIP)))
    import run

    plant(fault, run.load_cell(argv[argv.index("--workload") + 1],
                               rehearse=True)[1])
    sys.exit(run.main(argv))
