"""Faults planted UNDER the harness in the program's latent attention and
routed layers, for the tests that see ``correct`` come out false on the
JoyAI-LLM Flash cell (``test_joyai_cell.py``): ``python
faults_joyai_llm_flash.py <fault> <run.py arguments>`` plants the fault and
then runs the benchmark's own ``main``, in a rehearsal only.

- ``no_query_norm``: the RMSNorm of the query's latent ``c_q`` is left out;
- ``no_kv_norm``: the RMSNorm of the keys' and values' latent is left out;
- ``key_a_head``: the rope key is not shared by the heads (head ``h`` takes
  it with its channels shifted by ``h`` pairs);
- ``ninth_expert``: the router takes the expert ranked one below its last
  choice in that choice's place (the 9th for the 8th at k = 8);
- ``not_normalised``: the routing weights are not divided by their sum;
- ``scale_nope``: the scores are scaled by ``qk_nope_head_dim ** -0.5``
  (128) and not by ``(nope + rope) ** -0.5`` (192).

A fault of the rotation alone (none, halves for adjacent pairs, another
``theta``) is not here: a rotation keeps the norm of every gradient and of
every leaf's change, which is all that ``compare.train_numbers`` reads
(``PERF.md`` 7.14).  Tier-1 plants those and sees them in the logits
(``tests/test_joyai_llm_flash.py``).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def plant(fault, cfg):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (registers the operators)
    from mxnet_tpu.ops import registry

    def wrap(op, make):
        reg = registry.get(op)
        reg.forward = make(reg.forward)

    def norm_left_out(width):
        # the mixer's norms are told apart by their width
        wrap("RMSNorm", lambda f: lambda data, gamma, **kw: data
             if gamma.shape == (width,) else f(data, gamma, **kw))

    if fault == "no_query_norm":
        norm_left_out(cfg["q_lora_rank"])
    elif fault == "no_kv_norm":
        norm_left_out(cfg["kv_lora_rank"])
    elif fault == "key_a_head":
        def a_head(repeat):
            def repeated(data, **kw):
                out = repeat(data, **kw)
                if data.ndim != 4 or data.shape[1] != 1:
                    return out
                return jnp.stack([jnp.roll(out[:, h], 2 * h, axis=-1)
                                  for h in range(out.shape[1])], axis=1)
            return repeated
        wrap("broadcast_axis", a_head)
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
    elif fault == "scale_nope":
        wrap("_contrib_flash_attention", lambda f: lambda *a, **kw: f(
            *a, **dict(kw, scale=cfg["qk_nope_head_dim"] ** -0.5)))
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
