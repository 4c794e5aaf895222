"""Pure-JAX NHWC ResNet-50 train-step ceiling probe.

Hand-written minimal ResNet-50 v1 (bf16 activations, f32 BN stats, SGD
momentum) with no framework plumbing — measures what XLA:TPU delivers on
this chip for the same math, to separate framework overhead from compiler
ceiling.  Usage: python tools/rn50_ceiling.py [batch] [variant...]
variants:
  bf16stats — BN batch stats computed in bf16 instead of f32.
  s2d       — space-to-depth stem (the MLPerf TPU ResNet transform): the
              7x7/s2 conv over 3 input channels packs terribly onto the
              128x128 MXU (contraction dim 7*7*3=147 but channel dim 3);
              pad the kernel to 8x8 and fold a 2x2 space-to-depth block
              into channels, giving an equivalent 4x4/s1 conv over 12
              channels on a 112x112 grid.  Same math (zero-padded taps),
              MXU-friendly shape.
"""
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu  # noqa: E402,F401 - applies the compile-cache rule

BF16_STATS = "bf16stats" in sys.argv
S2D = "s2d" in sys.argv
ONEPASS_STATS = "onepass" in sys.argv


def conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def stem_s2d(x, w7):
    """7x7/s2 SAME stem conv, rewritten space-to-depth.

    Equivalence: SAME for k=7,s=2,in=224 pads (2,3); padding the kernel
    with one zero row/col (8x8) and the input to (2,4) keeps every tap
    aligned.  An 8x8/s2 conv is then exactly a 4x4/s1 conv on the 2x2
    space-to-depth transform of the input (block offset (di,dj) becomes
    a channel), with the kernel regrouped the same way.
    """
    w8 = jnp.pad(w7, ((0, 1), (0, 1), (0, 0), (0, 0)))
    xp = jnp.pad(x, ((0, 0), (2, 4), (2, 4), (0, 0)))
    n, h, w_, c = xp.shape
    xs = xp.reshape(n, h // 2, 2, w_ // 2, 2, c).transpose(
        0, 1, 3, 2, 4, 5).reshape(n, h // 2, w_ // 2, 4 * c)
    w4 = w8.reshape(4, 2, 4, 2, c, w7.shape[-1]).transpose(
        0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, w7.shape[-1])
    return lax.conv_general_dilated(
        xs, w4, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bn_train(x, gamma, beta):
    if ONEPASS_STATS:
        # sibling sum/sumsq reduces over one input: XLA multi-output
        # fusion computes both in a single HBM pass (vs mean->var's two
        # dependent passes).  Probe uses shift c=0; the framework BN
        # shifts by the running mean to kill cancellation.
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=(0, 1, 2))
        msq = jnp.mean(x32 * x32, axis=(0, 1, 2))
        var = msq - mean * mean
        inv = (lax.rsqrt(var + 1e-5) * gamma.astype(jnp.float32))
        scale = inv.astype(x.dtype)
        shift = (beta.astype(jnp.float32) - mean * inv).astype(x.dtype)
        return x * scale + shift
    if BF16_STATS:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        inv = lax.rsqrt(var + jnp.bfloat16(1e-5)) * gamma
        return x * inv + (beta - mean * inv)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 1, 2))
    var = jnp.var(x32, axis=(0, 1, 2))
    inv = (lax.rsqrt(var + 1e-5) * gamma.astype(jnp.float32))
    scale = inv.astype(x.dtype)
    shift = (beta.astype(jnp.float32) - mean * inv).astype(x.dtype)
    return x * scale + shift


def bottleneck(x, p, stride, project):
    out = bn_train(conv(x, p["w1"], stride), p["g1"], p["b1"])
    out = jax.nn.relu(out)
    out = bn_train(conv(out, p["w2"]), p["g2"], p["b2"])
    out = jax.nn.relu(out)
    out = bn_train(conv(out, p["w3"]), p["g3"], p["b3"])
    if project:
        sc = bn_train(conv(x, p["ws"], stride), p["gs"], p["bs"])
    else:
        sc = x
    return jax.nn.relu(out + sc)


LAYERS = [(3, 256, 1), (4, 512, 2), (6, 1024, 2), (3, 2048, 2)]


def init_params(key):
    rs = np.random.RandomState(0)
    P = {}

    def W(*shape):
        fan_in = int(np.prod(shape[:-1]))
        return jnp.asarray(
            rs.randn(*shape) * np.sqrt(2.0 / fan_in), jnp.bfloat16)

    P["stem_w"] = W(7, 7, 3, 64)
    P["stem_g"] = jnp.ones((64,), jnp.bfloat16)
    P["stem_b"] = jnp.zeros((64,), jnp.bfloat16)
    in_ch = 64
    for si, (n, ch, stride) in enumerate(LAYERS):
        mid = ch // 4
        for bi in range(n):
            p = {}
            cin = in_ch if bi == 0 else ch
            s = stride if bi == 0 else 1
            p["w1"] = W(1, 1, cin, mid)
            p["w2"] = W(3, 3, mid, mid)
            p["w3"] = W(1, 1, mid, ch)
            for t in ("1", "2", "3"):
                p["g" + t] = jnp.ones(
                    (mid if t != "3" else ch,), jnp.bfloat16)
                p["b" + t] = jnp.zeros(
                    (mid if t != "3" else ch,), jnp.bfloat16)
            if bi == 0:
                p["ws"] = W(1, 1, cin, ch)
                p["gs"] = jnp.ones((ch,), jnp.bfloat16)
                p["bs"] = jnp.zeros((ch,), jnp.bfloat16)
            P["s%d_%d" % (si, bi)] = p
        in_ch = ch
    P["fc_w"] = W(2048, 1000)
    P["fc_b"] = jnp.zeros((1000,), jnp.bfloat16)
    return P


def forward(P, x):
    x = stem_s2d(x, P["stem_w"]) if S2D else conv(x, P["stem_w"], 2)
    x = jax.nn.relu(bn_train(x, P["stem_g"], P["stem_b"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    for si, (n, ch, stride) in enumerate(LAYERS):
        for bi in range(n):
            x = bottleneck(x, P["s%d_%d" % (si, bi)],
                           stride if bi == 0 else 1, bi == 0)
    x = jnp.mean(x, axis=(1, 2))
    return x.astype(jnp.float32) @ P["fc_w"].astype(jnp.float32) \
        + P["fc_b"].astype(jnp.float32)


def loss_fn(P, x, y):
    logits = forward(P, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


@jax.jit
def train_n(P, M, x, y, n):
    def step(i, carry):
        P, M, _ = carry
        loss, g = jax.value_and_grad(loss_fn)(P, x, y)
        newM = jax.tree_util.tree_map(
            lambda m, gg: 0.9 * m + gg.astype(m.dtype), M, g)
        newP = jax.tree_util.tree_map(
            lambda p, m: (p.astype(jnp.float32)
                          - 0.1 * m.astype(jnp.float32)).astype(p.dtype),
            P, newM)
        return newP, newM, loss

    return lax.fori_loop(0, n, step, (P, M, jnp.float32(0)))


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else 256
    P = init_params(0)
    M = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), P)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 224, 224, 3), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, 1000, batch), jnp.int32)
    n = 10
    # RN50_COMPILER_OPTS: JSON dict of XLA compiler options, passed per
    # PJRT compile
    run = train_n
    opts = os.environ.get("RN50_COMPILER_OPTS")
    if opts:
        import json

        run = train_n.lower(P, M, x, y, n).compile(
            compiler_options=json.loads(opts))
        print("compiler options: %s" % opts, file=sys.stderr)
    t0 = time.perf_counter()
    out = run(P, M, x, y, n)
    jax.block_until_ready(out)
    print("compile+first: %.1fs loss=%.3f"
          % (time.perf_counter() - t0, float(out[2])), file=sys.stderr)
    t0 = time.perf_counter()
    out = run(P, M, x, y, n)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print("pure-jax rn50 b%d%s: %.3fs -> %.1f img/s"
          % (batch, " bf16stats" if BF16_STATS else "", dt, batch * n / dt))


if __name__ == "__main__":
    main()
