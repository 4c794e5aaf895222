"""Paged-attention kernel (ISSUE 14): numerics, masking, e2e parity.

The Pallas kernel runs in interpreter mode off-TPU (``use_kernel=1``),
so every test here exercises the same trace the CI parity path bakes
into AOT bundles.  The reference path (``use_kernel=0``) is the
pure-jnp gather + grouped-einsum formulation the serving graphs use on
CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.llama import LlamaModel
from mxnet_tpu.ops.paged_attention import paged_attention


def _case(seed, b=2, k1=1, h=2, kv=2, d=4, pages=6, s_page=4, int8=False):
    """Two lanes over a 3-slot block table; lane 0 keeps a null slot."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, k1, h, d)).astype(np.float32)
    if int8:
        kp = rng.integers(-127, 128, size=(pages, kv, s_page, d),
                          dtype=np.int64).astype(np.int8)
        vp = rng.integers(-127, 128, size=(pages, kv, s_page, d),
                          dtype=np.int64).astype(np.int8)
        scales = (rng.uniform(0.01, 0.05, size=pages).astype(np.float32),
                  rng.uniform(0.01, 0.05, size=pages).astype(np.float32))
    else:
        kp = rng.standard_normal((pages, kv, s_page, d)).astype(np.float32)
        vp = rng.standard_normal((pages, kv, s_page, d)).astype(np.float32)
        scales = ()
    tbl = np.array([[1, 2, 0], [3, 4, 5]], np.int32)
    pos = np.array([4, 7], np.int32)        # pos + k1 - 1 stays in-page
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tbl), jnp.asarray(pos)) \
        + tuple(jnp.asarray(s) for s in scales)


@pytest.mark.parametrize("h,kv", [(2, 2), (4, 1)],
                         ids=["mha", "gqa4x"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("k1", [1, 3], ids=["decode", "verify"])
def test_kernel_matches_reference(k1, kv_dtype, h, kv):
    args = _case(seed=k1 * 100 + (kv_dtype == "int8") * 10 + h,
                 k1=k1, h=h, kv=kv, int8=kv_dtype == "int8")
    ref = paged_attention(*args, use_kernel=0)
    ker = paged_attention(*args, use_kernel=1)
    assert ker.shape == args[0].shape and ker.dtype == args[0].dtype
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("use_kernel", [0, 1])
def test_block_table_permutation_invariance(use_kernel):
    # renaming page ids (keeping null page 0 fixed) and rewriting the
    # table consistently must not change a single bit: attention depends
    # on the table's slot order, never on physical page numbering
    q, kp, vp, tbl, pos, ks, vs = _case(seed=77, k1=3, h=4, kv=1,
                                        int8=True)
    base = paged_attention(q, kp, vp, tbl, pos, ks, vs,
                           use_kernel=use_kernel)
    perm = np.array([0, 3, 5, 1, 4, 2], np.int32)   # perm[0] == 0
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm), dtype=np.int32)

    def renum(pages):
        return jnp.asarray(np.asarray(pages)[inv])

    got = paged_attention(q, renum(kp), renum(vp),
                          jnp.asarray(perm[np.asarray(tbl)]), pos,
                          renum(ks), renum(vs), use_kernel=use_kernel)
    assert np.array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("use_kernel", [0, 1])
def test_null_page_masking(use_kernel):
    q, kp, vp, tbl, pos = _case(seed=5, k1=1, h=2, kv=2)
    # a lane whose table is all null pages has nothing to attend: exact 0
    null_tbl = jnp.zeros_like(tbl)
    out = paged_attention(q, kp, vp, null_tbl, pos,
                          use_kernel=use_kernel)
    assert np.array_equal(np.asarray(out), np.zeros(q.shape, np.float32))
    # appending a trailing null slot (longer table, same live pages)
    # leaves the output bitwise unchanged
    base = paged_attention(q, kp, vp, tbl, pos, use_kernel=use_kernel)
    wide = jnp.concatenate([tbl, jnp.zeros((2, 1), jnp.int32)], axis=1)
    got = paged_attention(q, kp, vp, wide, pos, use_kernel=use_kernel)
    assert np.array_equal(np.asarray(got), np.asarray(base))


def test_paged_attention_validates_inputs():
    q, kp, vp, tbl, pos, ks, vs = _case(seed=1, int8=True)
    with pytest.raises(MXNetError, match="both k_scale"):
        paged_attention(q, kp, vp, tbl, pos, k_scale=ks)
    with pytest.raises(MXNetError, match="query"):
        paged_attention(q[0], kp, vp, tbl, pos)
    with pytest.raises(MXNetError, match="group"):
        paged_attention(jnp.concatenate([q, q, q], axis=2)[:, :, :3],
                        kp, vp, tbl, pos)


# -- satellite: grouped-einsum GQA fallback ------------------------------

def test_grouped_einsum_matches_repeat():
    """The serving fallback's grouped einsums vs the jnp.repeat
    formulation — decode/verify AND prefill shapes, through the full
    mask + softmax + value pipeline on the CPU backend.  The two
    contract in a different order, so they agree to float32 rounding
    (one ulp under the installed XLA:CPU), not bitwise."""
    rng = np.random.default_rng(11)
    b, k1, h, kv, d, ctx = 2, 3, 4, 1, 4, 12
    grp = h // kv
    scale = 1.0 / d ** 0.5
    q = jnp.asarray(rng.standard_normal((b, k1, h, d)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((b, ctx, kv, d)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((b, ctx, kv, d)), jnp.float32)
    valid = jnp.asarray(
        rng.integers(0, 2, size=(b, k1, ctx)).astype(bool))

    @jax.jit
    def old(q, keys, vals):
        kr = jnp.repeat(keys, grp, axis=2)
        vr = jnp.repeat(vals, grp, axis=2)
        s = jnp.einsum("bkhd,bchd->bkhc", q, kr) * scale
        s = jnp.where(valid[:, :, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkhc,bchd->bkhd", p, vr)

    @jax.jit
    def new(q, keys, vals):
        qg = q.reshape(b, k1, kv, grp, d)
        s = jnp.einsum("bkvgd,bcvd->bkvgc", qg, keys) * scale
        s = jnp.where(valid[:, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkvgc,bcvd->bkvgd", p, vals) \
            .reshape(b, k1, h, d)

    np.testing.assert_allclose(np.asarray(old(q, keys, vals)),
                               np.asarray(new(q, keys, vals)),
                               rtol=1e-6, atol=1e-6)

    # prefill shapes: (t, H, D) queries against (u, KV, D) keys
    t, u = 6, 8
    q2 = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k2 = jnp.asarray(rng.standard_normal((u, kv, d)), jnp.float32)
    v2 = jnp.asarray(rng.standard_normal((u, kv, d)), jnp.float32)
    causal = jnp.asarray(np.tril(np.ones((t, u), bool), k=u - t))

    @jax.jit
    def old_pre(q, k, v):
        kr = jnp.repeat(k, grp, axis=1)
        vr = jnp.repeat(v, grp, axis=1)
        s = jnp.einsum("thd,uhd->htu", q, kr) * scale
        s = jnp.where(causal[None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("htu,uhd->thd", p, vr).reshape(t, h * d)

    @jax.jit
    def new_pre(q, k, v):
        qg = q.reshape(t, kv, grp, d)
        s = jnp.einsum("tvgd,uvd->vgtu", qg, k) * scale
        s = jnp.where(causal[None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("vgtu,uvd->tvgd", p, v).reshape(t, h * d)

    np.testing.assert_allclose(np.asarray(old_pre(q2, k2, v2)),
                               np.asarray(new_pre(q2, k2, v2)),
                               rtol=1e-6, atol=1e-6)


# -- geometry plumbing ---------------------------------------------------

def test_geometry_paged_kernel_field():
    from mxnet_tpu.serve.model import KVGeometry

    kw = dict(num_layers=1, num_heads=2, num_kv_heads=1, head_dim=4,
              units=8, hidden_size=16, vocab_size=32, page_size=4,
              num_pages=8, max_pages_per_seq=4, max_batch=2,
              prefill_buckets=(4,))
    assert KVGeometry(**kw).paged_kernel == "auto"
    assert KVGeometry(paged_kernel=True, **kw).paged_kernel == "1"
    assert KVGeometry(paged_kernel=0, **kw).paged_kernel == "0"
    g = KVGeometry(paged_kernel="1", **kw)
    assert g.to_dict()["paged_kernel"] == "1"
    assert "paged_kernel=1" in g.describe()
    assert KVGeometry(**dict(g.to_dict())).paged_kernel == "1"
    # old bundles (no field) default to auto
    legacy = {k: v for k, v in g.to_dict().items() if k != "paged_kernel"}
    assert KVGeometry(**legacy).paged_kernel == "auto"
    with pytest.raises(MXNetError, match="paged_kernel"):
        KVGeometry(paged_kernel="tpu", **kw)


# -- e2e: kernel-on vs kernel-off through LlamaServer --------------------

def _micro_llama(seed=5):
    mx.random.seed(seed)
    net = LlamaModel(vocab_size=64, units=16, hidden_size=32,
                     num_layers=2, num_heads=2, num_kv_heads=1)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))  # resolve deferred shapes
    return net


def test_e2e_greedy_parity_kernel_on_vs_off(tmp_path):
    """Same net, spec + int8 arena: the interpreter-kernel bundle and
    the reference bundle must emit identical greedy tokens."""
    from mxnet_tpu.serve.model import read_bundle_geometry

    geom = dict(page_size=4, num_pages=32, max_batch=2,
                prefill_buckets=(8,), spec_k=2, kv_dtype="int8")
    net = _micro_llama()
    outs = {}
    for mode in ("0", "1"):
        path = str(tmp_path / ("paged_%s.mxaot" % mode))
        g = serve.export_serving_bundle(net, path, paged_kernel=mode,
                                        **geom)
        assert g.paged_kernel == mode
        got, _ = read_bundle_geometry(path)
        assert got.to_dict()["paged_kernel"] == mode
        with serve.LlamaServer(path) as srv:
            outs[mode] = [srv.generate(p, max_new_tokens=6)
                          for p in ([3, 1, 4, 1, 5], [2])]
    assert outs["0"] == outs["1"]
