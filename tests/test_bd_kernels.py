"""Block-diffusion attention through the flash kernels with its operands read
where the projections wrote them (``ops/bd_kernels.py``: ``mx_flash_fwd_bd``,
``mx_flash_bwd_dq_bd``, ``mx_flash_bwd_dkv_bd`` over ``(B, 2T, H * 128)``) in
the interpreter, at lane-tiling shapes kept small (head_dim 128, 2 x 256
positions in 128-token tiles, blocks of 4), against the composition it
replaces: heads transposed to ``(B, H, 2T, D)``, ``RMSNorm`` over each head
of q and k, the rotation of halves at positions ``0 .. T-1`` of each half
(``llama._rope``'s angles), K and V repeated to the query heads, attention
under the dense block-diffusion mask.

Tolerances.  In float32 the two are the same sums in another order: 2e-5 of
the largest value.  In bfloat16 the composition rounds the turned queries to
bfloat16 before its kernels read them and the in-place kernels turn a
bfloat16 block in float32: 2e-2.
"""
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon, parallel
from mxnet_tpu.gluon.model_zoo import sdar_moe
from mxnet_tpu.ops import bd_kernels
from mxnet_tpu.ops.pallas_kernels import (BlockDiffusion, _attention_ref,
                                          _bd_query_tile, _bd_visible,
                                          block_diffusion_mask)
from mxnet_tpu.telemetry import metrics

D, HALF, BLOCK, THETA, EPS = 128, 256, 4, 1e6, 1e-6


def _operands(h, kv, half=HALF, dtype=jnp.float32, seed=0):
    """``(q, k, v, q_norm, k_norm, w)``: the gains float32, ``w`` a
    cotangent of the result's shape."""
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, 2 * half, n * D)),
                              jnp.float32).astype(dtype)
                  for n in (h, kv, kv, h))
    gains = (jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32)
             for _ in range(2))
    return (q, k, v, *gains, w)


def _rotated(x, gain, n, half):
    """``x (B, 2T, n * D)`` as ``(B, n, 2T, D)``, each head normed as
    ``RMSNorm`` norms it and turned as ``llama._rope`` turns it at positions
    ``0 .. T-1`` of each half, float32."""
    b = x.shape[0]
    x = x.astype(jnp.float32).reshape(b, 2, half, n, D) \
        .transpose(0, 3, 1, 2, 4)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * gain
    rate = jnp.exp(jnp.arange(D // 2, dtype=jnp.float32) * (-2.0 / D)
                   * math.log(THETA))
    phi = jnp.arange(half, dtype=jnp.float32)[:, None] * rate[None, :]
    c, s = jnp.cos(phi), jnp.sin(phi)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1) \
        .reshape(b, n, 2 * half, D)


def _composition(q, k, v, q_norm, k_norm, h, kv, half=HALF):
    b, t2, _ = q.shape
    qh = _rotated(q, q_norm, h, half).astype(q.dtype)
    kh = jnp.repeat(_rotated(k, k_norm, kv, half).astype(q.dtype), h // kv,
                    axis=1)
    vh = jnp.repeat(v.reshape(b, t2, kv, D).transpose(0, 2, 1, 3), h // kv,
                    axis=1)
    out = _attention_ref(*(x.reshape(b * h, t2, D) for x in (qh, kh, vh)),
                         D ** -0.5, False, BlockDiffusion(half, BLOCK))
    return out.reshape(b, h, t2, D).transpose(0, 2, 1, 3).reshape(b, t2, h * D)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), \
        (np.abs(a - b).max(), np.abs(b).max())


@pytest.mark.parametrize("h, kv, dtype", [
    (2, 2, "float32"), (4, 2, "float32"), (8, 1, "float32"),
    (4, 2, "bfloat16")], ids=["mha", "two_a_key", "eight_a_key", "bfloat16"])
def test_the_operator_is_the_composition(h, kv, dtype):
    # two halves of two 128-token tiles, blocks of 4: the result and the
    # gradient of q, k, v and the two head norms' gains, K and V read at
    # their own heads
    *args, w = _operands(h, kv, dtype=jnp.dtype(dtype))
    tol = 2e-5 if dtype == "float32" else 2e-2

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a) * w).astype(jnp.float32).sum(),
            argnums=range(5)))(*args)
    new = functools.partial(bd_kernels._attend, heads=h, block=BLOCK,
                            theta=THETA, eps=EPS, tile=128)
    old = functools.partial(_composition, h=h, kv=kv)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: new(*a).astype(jnp.float32).sum(), argnums=range(5)))(
        *args))
    for name in ("mx_flash_fwd_bd", "mx_flash_bwd_dq_bd",
                 "mx_flash_bwd_dkv_bd"):
        assert name in text
    got = new(*args)
    assert got.dtype == args[0].dtype and got.shape == w.shape
    _close(got, old(*args), tol)
    (value, grads), (want_value, want) = value_and_grads(new), \
        value_and_grads(old)
    _close(value, want_value, tol)
    for a, g in zip(grads, want):
        assert a.dtype == g.dtype and a.shape == g.shape
        _close(a, g, tol)


def test_dead_tiles_are_never_read():
    # the mask's dead tiles are neither computed nor fetched: NaN in the
    # noised keys and values leaves every clean query's result and dq as
    # they were, and NaN in the clean (turned) queries, dO and statistics
    # leaves the noised keys' dk and dv as they were (a masked score times
    # a NaN would be NaN)
    h, kv, t2 = 4, 2, 2 * HALF
    q, k, v, _, _, do = _operands(h, kv, seed=5)
    tables = bd_kernels.rope_tables(HALF, D, THETA)
    static = dict(heads=h, mask=BlockDiffusion(HALF, BLOCK), tile=128,
                  interpret=True)
    fwd = jax.jit(functools.partial(bd_kernels._fwd_pallas, **static))
    bwd = jax.jit(functools.partial(bd_kernels._bwd_pallas, **static))
    out, lse, qt = fwd(q, k, v, *tables)
    dq, dk, dv = bwd(qt, k, v, out, do, lse, *tables)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in (out, lse, qt, dq, dk, dv))

    noised = (jnp.arange(t2) >= HALF)[None, :, None]
    k_bad, v_bad = (jnp.where(noised, jnp.nan, x) for x in (k, v))
    out_bad, lse_bad, _ = fwd(q, k_bad, v_bad, *tables)
    np.testing.assert_array_equal(out_bad[:, :HALF], out[:, :HALF])
    np.testing.assert_array_equal(lse_bad[:, :, :HALF], lse[:, :, :HALF])
    assert np.isnan(np.asarray(out_bad[:, HALF:])).all()
    dq_bad, _, _ = bwd(qt, k_bad, v_bad, out, do, lse, *tables)
    np.testing.assert_array_equal(dq_bad[:, :HALF], dq[:, :HALF])

    clean = ~noised
    _, dk_bad, dv_bad = bwd(
        jnp.where(clean, jnp.nan, qt), k, v, jnp.where(clean, jnp.nan, out),
        jnp.where(clean, jnp.nan, do), jnp.where(clean[:, None], jnp.nan, lse),
        *tables)
    np.testing.assert_array_equal(dk_bad[:, HALF:], dk[:, HALF:])
    np.testing.assert_array_equal(dv_bad[:, HALF:], dv[:, HALF:])
    assert np.isnan(np.asarray(dk_bad[:, :HALF])).all()


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_dkv_grid_visits_each_live_pair_once_in_the_masks_order(n, group):
    # the dk/dv kernel's grid is the list of the mask's live (key tile, query
    # head, query tile) visits: each once, key tile after key tile, a key
    # tile's heads in turn and each head's query tiles in the order that
    # ``_bd_query_tile`` gives (the sums of dk and dv are the composition's,
    # term for term), zeroed at a key tile's first visit and written at its
    # last, masked exactly where the pair is partly live, by ``_bd_visible``
    tile, block = 16, 4
    mask = BlockDiffusion(n * tile, block)
    visits = bd_kernels._dkv_visits(n, group)
    assert visits.dtype == np.int32 and visits.shape == (group * n * (n + 2),)
    ki, head, qi, flags = (np.asarray(x) for x in bd_kernels._visit(
        visits, n, group))
    dense = np.asarray(block_diffusion_mask(*mask))
    seen = {(q, k): dense[q * tile:(q + 1) * tile, k * tile:(k + 1) * tile]
            for q in range(2 * n) for k in range(2 * n)}
    live = sorted((k, h, q) for (q, k), vis in seen.items() if vis.any()
                  for h in range(group))
    assert sorted(zip(ki.tolist(), head.tolist(), qi.tolist())) == live
    assert (np.diff(ki) >= 0).all()
    for k in range(2 * n):
        mine = np.flatnonzero(ki == k)
        count = 1 if k >= n else 2 * (n - k)
        assert head[mine].tolist() == np.repeat(np.arange(group),
                                                count).tolist()
        assert qi[mine].tolist() == [int(_bd_query_tile(k, i, n))
                                     for i in range(count)] * group
        first = (flags[mine] & bd_kernels._FIRST) != 0
        last = (flags[mine] & bd_kernels._LAST) != 0
        assert first.tolist() == [True] + [False] * (len(mine) - 1)
        assert last.tolist() == [False] * (len(mine) - 1) + [True]
    partial_ = (flags & bd_kernels._PARTIAL) != 0
    assert partial_.tolist() == [not seen[int(q), int(k)].all()
                                 for q, k in zip(qi, ki)]
    for q, k in zip(qi[partial_].tolist(), ki[partial_].tolist()):
        np.testing.assert_array_equal(_bd_visible(q, k, mask, tile),
                                      seen[q, k])
    assert bd_kernels.dkv_steps(4 * group, 4, n * 512, 512) \
        == 4 * len(visits)


# sha256 of the bytes of dq, dk and dv from ``bd_flash_attention``'s
# gradient in the interpreter, recorded from the tree whose dk/dv kernel
# walked every (key tile, query head, query tile) of the rectangle and
# skipped the dead ones under ``pl.when``
_GRAD_DIGESTS = {
    "float32": ("de0942efe9af2c44", "2b843e605d2641a3", "a1b0f066c2a077bd"),
    "bfloat16": ("728ac1786430b08f", "75cfb29c2054f0b4", "97a51f6d4b4de07d")}


@pytest.mark.parametrize("h, half, dtype", [
    (4, 512, "float32"), (8, 256, "bfloat16")])
def test_the_gradient_is_the_rectangle_walks_bit_for_bit(h, half, dtype):
    # two key heads, four or eight query heads, 128-token tiles: the live
    # visits add the same terms in the same order as the full grid did
    import hashlib

    kv = 2
    rng = np.random.default_rng(44)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, 2 * half, n * D)),
                              jnp.float32).astype(dtype)
                  for n in (h, kv, kv, h))
    gq, gk = (jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32)
              for _ in range(2))
    attend = functools.partial(bd_kernels._attend, q_norm=gq, k_norm=gk,
                               heads=h, block=BLOCK, theta=THETA, eps=EPS,
                               tile=128)
    grads = jax.jit(jax.grad(
        lambda q, k, v: (attend(q, k, v) * w).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert tuple(hashlib.sha256(np.asarray(g).tobytes()).hexdigest()[:16]
                 for g in grads) == _GRAD_DIGESTS[dtype]


def test_the_path_is_a_static_test_of_the_shapes():
    tiles, fits = bd_kernels.tiles, bd_kernels._fits
    # the cell: 32 / 4 heads of 128, 2 x 4096 positions, blocks of 4
    assert tiles(32, 4, 128, 4096, 4) == 512
    assert tiles(16, 16, 256, 1024, 16) == 512
    assert fits(4, 2, 128, 256, 4, 128) == 128
    # the tier-1 models' head_dim; heads that are no whole number of key
    # heads' groups
    assert tiles(4, 2, 8, 512, 4) is None
    assert tiles(4, 2, 64, 512, 4) is None
    assert tiles(6, 4, 128, 512, 4) is None
    # positions flash_attention hands to XLA, tiles that do not divide a
    # half, blocks that are no power of two or do not divide the tile
    assert tiles(4, 2, 128, 128, 4) is None
    assert tiles(4, 2, 128, 256, 4) is None
    assert tiles(4, 2, 128, 512, 3) is None
    assert fits(4, 2, 128, 256, 256, 128) is None
    assert fits(4, 2, 128, 256, 4, 96) is None
    # a context mesh: GSPMD cannot partition a Mosaic kernel
    with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("data",))):
        assert tiles(32, 4, 128, 4096, 4) is None
    with pytest.raises(ValueError, match="do not tile"):
        bd_kernels.bd_flash_attention(*_operands(4, 2, half=64)[:5],
                                      num_heads=4)


def _layer(h, kv, d, units=64, layer=0, seed=3):
    attn = sdar_moe.BDAttention(units, h, kv, d, THETA, BLOCK, layer=layer)
    attn.initialize(mx.init.Xavier())
    rng = np.random.default_rng(seed)
    for p in attn.collect_params().values():
        if p.shape == (d,):     # the head norms' gains, away from ones
            p.set_data(mx.nd.array(rng.uniform(0.5, 1.5, d)
                                   .astype(np.float32)))
    return attn


def _layer_grads(attn, u, w):
    x = mx.nd.array(u)
    x.attach_grad()
    with mx.autograd.record():
        out = attn(x)
        loss = (out * mx.nd.array(w)).sum()
    loss.backward()
    return out.asnumpy(), {"u": x.grad.asnumpy(), **{
        name: p.grad().asnumpy()
        for name, p in attn.collect_params().items()}}


def _ops_traced(attn, u):
    with mx.autograd.pause():
        fn = lambda x: attn(mx.nd.NDArray(x)).data()     # noqa: E731
        return str(jax.make_jaxpr(fn)(jnp.asarray(u)))


def test_the_layer_reads_in_place_what_the_composition_copies(monkeypatch):
    # one layer of 4 / 2 heads of 128 at 2 x 512 positions (the operator's
    # own 512-token tile): the result and the gradient of every leaf, the
    # head norms' gains included, against the same layer sent to the
    # composition
    attn = _layer(4, 2, D)
    rng = np.random.default_rng(11)
    u = rng.normal(size=(1, 1024, 64)).astype(np.float32)
    w = rng.normal(size=(1, 1024, 64)).astype(np.float32)
    text = _ops_traced(attn, u)
    assert "mx_flash_fwd_bd" in text and "bd_flash_attention" in text
    out, grads = _layer_grads(attn, u, w)
    monkeypatch.setattr(bd_kernels, "tiles", lambda *a, **k: None)
    text = _ops_traced(attn, u)
    assert "mx_flash_fwd_bd" in text and "bd_flash_attention" not in text
    want_out, want = _layer_grads(attn, u, w)
    _close(out, want_out, 2e-5)
    assert sorted(grads) == sorted(want) and len(grads) == 7
    for name in want:
        assert np.abs(want[name]).max() > 0, name
        _close(grads[name], want[name], 5e-5)


def test_the_tiny_widths_and_a_mesh_take_the_composition():
    u = np.zeros((1, 1024, 64), np.float32)
    assert "bd_flash_attention" not in _ops_traced(_layer(4, 2, 8), u)
    attn = _layer(4, 2, D)
    assert "bd_flash_attention" in _ops_traced(attn, u)
    with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("data",))):
        text = _ops_traced(attn, np.zeros((2, 1024, 64), np.float32))
    assert "bd_flash_attention" not in text and "mx_flash_fwd_bd" in text


def test_under_amp_the_operator_takes_bfloat16_and_the_gains_in_float32():
    assert "_contrib_bd_flash_attention" in amp.lists.TARGET_DTYPE_OPS
    attn = _layer(4, 2, D)
    u = np.random.default_rng(13).normal(size=(1, 1024, 64)) \
        .astype(np.float32)
    want = attn(mx.nd.array(u)).asnumpy()
    seen, orig = {}, amp.transform_inputs

    def spy(op_name, datas):
        out = orig(op_name, datas)
        seen.setdefault(op_name, [getattr(d, "dtype", None) for d in out])
        return out
    amp.init("bfloat16")
    amp.transform_inputs = spy
    try:
        got = attn(mx.nd.array(u))
    finally:
        amp.transform_inputs = orig
        amp.turn_off()
    f32, bf16 = jnp.dtype("float32"), jnp.dtype("bfloat16")
    assert seen["_contrib_bd_flash_attention"] == [bf16] * 3 + [f32] * 2
    assert got.dtype == bf16
    _close(got.asnumpy().astype(np.float32), want, 3e-2)


def _counted(name):
    series = metrics.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series)


@pytest.mark.parametrize("d, kernel", [(8, 0), (D, 1)],
                         ids=["tiny_widths", "lane_tiles"])
def test_the_counter_counts_the_layers_read_in_place(d, kernel):
    """A train step over one block-diffusion attention layer:
    ``mxnet_bd_layers_total`` counts the layer a step,
    ``mxnet_bd_kernel_layers_total`` the same where the shapes tile and
    nothing where the layer took the composition;
    ``mxnet_flash_dkv_steps_total`` the dk/dv kernel's grid steps, batch x
    key heads x the live visits in place (2 x 512 positions in 512-token
    tiles: ``n`` 1, a group of 2, 2 x 1 x 3 visits) and batch x heads x
    ``(2 n)^2`` tiles in the composition."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.attn = sdar_moe.BDAttention(64, 2, 1, d, THETA, BLOCK,
                                             layer=3)

        def hybrid_forward(self, F, x):
            return self.attn(x)
    metrics.snapshot()      # what earlier steps counted is not this test's
    before = [_counted("mxnet_bd_layers_total"),
              _counted("mxnet_bd_kernel_layers_total"),
              _counted("mxnet_flash_dkv_steps_total")]
    net = Net()
    net.initialize()
    net.hybridize()
    step = parallel.JitTrainStep(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = np.random.default_rng(0)
    for _ in range(2):
        loss = float(step.step(
            rng.normal(size=(1, 1024, 64)).astype(np.float32),
            rng.normal(size=(1, 1024, 64)).astype(np.float32)))
        assert np.isfinite(loss)
    stats = step.step_stats()
    assert int(stats["bd/3"][0]) == 2
    assert int(stats["bd_kernel/3"][0]) == 2 * kernel
    assert _counted("mxnet_bd_layers_total") - before[0] == 2
    assert _counted("mxnet_bd_kernel_layers_total") - before[1] == 2 * kernel
    steps = 1 * 1 * 6 if kernel else 1 * 2 * 2 ** 2
    assert int(stats["bd_dkv_steps/3"][0]) == 2 * steps
    assert _counted("mxnet_flash_dkv_steps_total") - before[2] == 2 * steps
