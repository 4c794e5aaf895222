"""Faults planted UNDER the harness in the program's Gated DeltaNet, gated
attention and routed layers, for the tests that see ``correct`` come out
false on the Qwen3-Next cell (``test_qwen3_next_cell.py``): ``python
faults_qwen3_next.py <fault> <run.py arguments>`` plants the fault and then
runs the benchmark's own ``main``, in a rehearsal only.

- ``key_head``: value head ``j`` reads key head ``j mod H_k`` in place of ``j
  // (H_v / H_k)`` (q and k tiled over the value heads, not repeated);
- ``sigmoid_z``: the GDN output gate is ``sigmoid(z)`` in place of
  ``silu(z)``;
- ``no_attention_gate``: the gated attention's ``o * sigmoid(gate)`` loses
  its gate;
- ``last_channels``: the attention turns the last ``rotary_dim`` channels of
  each head (adjacent pairs) in place of the first, in halves;
- ``ungated_shared``: the shared expert's ``sigmoid(u w_sg)`` is left out;
- ``eleventh_expert``: the router chooses one expert more than
  ``num_experts_per_tok`` (the 11th at the cell's 10, the 4th at the tiny
  sizes' 3), renormalised over them.

At the tiny sizes the GDN mixer is its composition, which ``gdn`` below
writes out again with the two faults of its own.
"""
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)


def gdn(qkvz, ba, conv, A_log, dt_bias, o_norm, chunk=64, eps=1e-6,
        tiled=False, gate=None):
    """``ops/kda.py:_gdn_attention``, q and k ``tiled`` over the value heads
    and ``gate`` in place of SiLU where given."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda
    from mxnet_tpu.ops.ssm import causal_conv1d

    f32 = jnp.float32
    b, t, _ = qkvz.shape
    hk, hv, d = kda._gdn_sizes(qkvz, A_log, o_norm)
    x, gates = qkvz.astype(f32), ba.astype(f32)
    mixed = jax.nn.silu(causal_conv1d(x[..., :2 * hk * d + hv * d], conv))

    def key_heads(y):
        y = y.reshape(b, t, hk, d)
        y = y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        if tiled:
            return jnp.tile(y, (1, 1, hv // hk, 1))
        return jnp.repeat(y, hv // hk, axis=2)
    g = -jnp.exp(A_log.astype(f32).reshape(hv)) * jax.nn.softplus(
        gates[..., hv:] + dt_bias.astype(f32).reshape(hv))
    o = kda._kda_chunked(
        key_heads(mixed[..., :hk * d]) * d ** -0.5,
        key_heads(mixed[..., hk * d:2 * hk * d]),
        mixed[..., 2 * hk * d:].reshape(b, t, hv, d),
        jnp.broadcast_to(g[..., None], (b, t, hv, d)),
        jax.nn.sigmoid(gates[..., :hv]), chunk)
    z = x[..., 2 * hk * d + hv * d:].reshape(b, t, hv, d)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * o_norm.astype(f32) * (gate or jax.nn.silu)(z)
    return o.reshape(b, t, hv * d)


def plant(fault, cfg):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (registers the operators)
    from mxnet_tpu.ops import registry

    def wrap(op, make):
        reg = registry.get(op)
        reg.forward = make(reg.forward)

    def sigmoid_ones(width):
        # the sigmoid of an input whose last axis is ``width``: ones
        wrap("sigmoid", lambda f: lambda data, **kw: jnp.ones_like(data)
             if data.shape[-1] == width else f(data, **kw))

    if fault == "key_head":
        wrap("_contrib_gdn_attention", lambda f: functools.partial(
            gdn, tiled=True))
    elif fault == "sigmoid_z":
        wrap("_contrib_gdn_attention", lambda f: functools.partial(
            gdn, gate=jax.nn.sigmoid))
    elif fault == "no_attention_gate":
        sigmoid_ones(cfg["num_attention_heads"] * cfg["head_dim"])
    elif fault == "last_channels":
        wrap("_contrib_rotary_embedding", lambda f: lambda data, halves=False,
             **kw: f(data, **kw))
    elif fault == "ungated_shared":
        sigmoid_ones(1)
    elif fault == "eleventh_expert":
        wrap("_contrib_moe_router_topk", lambda f: lambda data, weight,
             bias=None, k=1, **kw: f(data, weight, bias, k=k + 1, **kw))
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
