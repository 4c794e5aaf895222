"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (the chip's compiler is installed with libtpu).

Interpret-mode parity tests cannot see what the Mosaic lowering refuses:
block shapes it will not tile, operands that outgrow VMEM.  These
compiles can, at the real widths, in about two seconds each and at no
chip time.  Nothing runs, so they say nothing about results or speed —
``chip_smoke.py`` does that on the chip.

All of it lives in this one file and compiles in the test's own
process: only one process at a time may load libtpu, and the worker
that is handed this file keeps it until it exits.  The topology is
described inside a fixture, never at import.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from mxnet_tpu.ops.kda import kda_scan
from mxnet_tpu.ops.moe import grouped_ffn
from mxnet_tpu.ops.paged_attention import paged_attention
from mxnet_tpu.ops.pallas_kernels import _flash_bwd_pallas, flash_attention
from mxnet_tpu.ops.ssm import ssd_scan
from mxnet_tpu.parallel.train_step import mesh_compiler_options


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip would be written to the persistent
    # cache but can never be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    avals = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in shapes]
    return jax.jit(fn).lower(*avals).compile()


def _flash_loss(q, k, v, w):
    # a cotangent that is data, as in training
    out = flash_attention(q, k, v, causal=True)
    return (out * w).astype(jnp.float32).sum()


def _flash_shapes(t, n=3):
    # llama2_7b's 32 heads x head_dim 128, batch 1
    return [((1, 32, t, 128), jnp.bfloat16)] * n


def test_flash_forward_t512(one_chip):
    c = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                 one_chip, *_flash_shapes(512))
    assert "tpu_custom_call" in c.as_text()


def test_flash_backward_t512(one_chip):
    c = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), one_chip,
                 *_flash_shapes(512, 4))
    # forward kernel + dq kernel + dk/dv kernel, each an instruction under
    # the name the program gave it (what a device trace shows)
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in ("mx_flash_fwd", "mx_flash_bwd_dq", "mx_flash_bwd_dkv"):
        assert "%%%s." % name in text


@pytest.mark.parametrize("d_qk, d_v", [(128, 128), (192, 128)],
                         ids=["d128", "d192_v128"])
def test_flash_backward_t4096(one_chip, d_qk, d_v):
    # the kernel pair called as flash_attention's vjp calls it, at 32 heads
    # x 4096 tokens: the dk/dv kernel streams its query blocks over a grid
    # axis (whole-T q, dO, lse and delta did not fit VMEM before PR 34), at
    # one width for q, k and v and at latent attention's 192 / 128
    bwd = functools.partial(_flash_bwd_pallas, scale=d_qk ** -0.5,
                            causal=True, block_q=512, block_k=512)
    bf16 = jnp.bfloat16
    c = _compile(bwd, one_chip, *[((32, 4096, d_qk), bf16)] * 2,
                 *[((32, 4096, d_v), bf16)] * 2,
                 *[((32, 4096, 128), jnp.float32)] * 2)
    for name in ("mx_flash_bwd_dq", "mx_flash_bwd_dkv"):
        assert "%%%s" % name in c.as_text()


def test_flash_forward_and_backward_t4096_with_narrower_values(one_chip):
    # through the operator: q, k of 192 channels, v of 128, no padded copy
    # of v (no 192-wide value operand reaches a kernel)
    def loss(q, k, v, w):
        out = flash_attention(q, k, v, scale=192 ** -0.5, causal=True)
        return (out * w).astype(jnp.float32).sum()
    bf16 = jnp.bfloat16
    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                 *[((1, 32, 4096, 192), bf16)] * 2,
                 *[((1, 32, 4096, 128), bf16)] * 2)
    text = c.as_text()
    for name in ("mx_flash_fwd", "mx_flash_bwd_dq", "mx_flash_bwd_dkv"):
        assert "%%%s" % name in text
    assert "pad(" not in text


def test_flash_on_a_mesh_runs_per_shard(topo):
    # GSPMD cannot partition a Mosaic kernel; traced under a context mesh
    # (JitTrainStep with a mesh does this) it runs inside shard_map:
    # batch over `data`, heads over `model`
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    sharded = NamedSharding(mesh, P("data", "model", None, None))

    with jax.set_mesh(mesh):
        c = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), sharded,
                     *[((4, 32, 512, 128), jnp.bfloat16)] * 4)
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 3
    # each device runs the kernel on its own batch rows and heads
    assert "bf16[32,512,128]" in text and "all-gather" not in text


def test_the_bd_kernels_on_a_mesh_run_per_shard(topo):
    # the block-diffusion kernels traced under a 2 x 2 mesh run inside
    # shard_map: the batch over `data`, the heads' lanes over `model` (8 / 2
    # heads of 128: 4 / 1 a shard), 2 x 1024 positions in 512-token tiles
    from mxnet_tpu.ops import bd_kernels

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    rows = NamedSharding(mesh, P("data", None, "model"))
    gains = NamedSharding(mesh, P())

    def loss(q, k, v, gq, gk, w):
        out = bd_kernels.bd_flash_attention(q, k, v, gq, gk, num_heads=8,
                                            block_length=4, rope_theta=1e6)
        return (out * w).astype(jnp.float32).sum()
    bf16 = jnp.bfloat16
    shapes = [((2, 2048, 8 * 128), bf16, rows),
              *[((2, 2048, 2 * 128), bf16, rows)] * 2,
              *[((128,), jnp.float32, gains)] * 2,
              ((2, 2048, 8 * 128), bf16, rows)]
    with jax.set_mesh(mesh):
        c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *[jax.ShapeDtypeStruct(s, d, sharding=sh)
              for s, d, sh in shapes]).compile()
    assert _kernels(c) == ["mx_flash_bwd_dkv_bd", "mx_flash_bwd_dq_bd",
                           "mx_flash_fwd_bd"]
    # each device runs the kernels on its own batch row and key head
    text = c.as_text()
    assert re.search(r"%mx_flash_bwd_dkv_bd\S* = \(bf16\[1,2048,128\]", text)
    assert "all-gather" not in text


def _mlp_train_step(ws, ms, x):
    # three residual MLP blocks as JitTrainStep runs a decoder's: float32
    # master weights, bfloat16 products, a momentum update of every weight
    def loss(ws):
        h = x
        for up, down in ws:
            a = jax.nn.silu(jnp.dot(h, up.astype(jnp.bfloat16)))
            h = h + jnp.dot(a, down.astype(jnp.bfloat16))
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    g = jax.grad(loss)(ws)
    ms = jax.tree_util.tree_map(lambda m, g: 0.9 * m + 0.1 * g, ms, g)
    ws = jax.tree_util.tree_map(lambda w, m: w - 1e-3 * m, ws, ms)
    return ws, ms


def _entry(compiled):
    """The entry computation in schedule order: ``[(instruction name, the
    shapes it yields, its whole text)]``."""
    body = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled.as_text(),
                     re.S | re.M).group(1)
    return [(name, re.split(r" (?:fusion|all-reduce)\(", text, 1)[0], text)
            for name, text in re.findall(
                r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", body, re.M)]


@pytest.mark.parametrize("with_options", [False, True],
                         ids=["default", "mesh_options"])
def test_mesh_step_gradient_all_reduces_overlap(topo, with_options):
    # Megatron shardings on data=2 x model=2: the weight gradients are
    # all-reduced over `data`, each shard 16 MiB in bfloat16.  By default
    # they are synchronous, combined into tuples; with the options a mesh
    # step is compiled with (PR 28) each travels alone in a pair of
    # async-collective-start/-done fusions, matmuls of the backward pass
    # between the two.
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    options = mesh_compiler_options(mesh)
    assert options, "a mesh of TPUs gets the overlap options"
    d, f, layers, tokens = 2048, 8192, 3, 2048

    def aval(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    ws = [(aval((d, f), jnp.float32, None, "model"),
           aval((f, d), jnp.float32, "model", None))] * layers
    c = jax.jit(_mlp_train_step, donate_argnums=(0, 1),
                compiler_options=options if with_options else None).lower(
        ws, ws, aval((tokens, d), jnp.bfloat16, "data", None)).compile()
    entry = _entry(c)
    shard = re.compile(r"bf16\[(%d,%d|%d,%d)\]" % (d, f // 2, f // 2, d))
    # instructions that yield a weight's shard: its gradient, all-reduced
    started = [i for i, (name, out, _) in enumerate(entry)
               if name.startswith("async-collective-start")
               and shard.search(out)]
    synchronous = [i for i, (name, out, _) in enumerate(entry)
                   if name.startswith("all-reduce") and shard.search(out)]
    names = [name for name, _, _ in entry]
    if not with_options:
        assert not any("async-collective" in name for name in names)
        assert synchronous
        return
    # at most the last gradients produced stay synchronous
    assert len(started) >= 2 * layers - 2, (started, synchronous)
    assert len(synchronous) <= 2
    # between a start and its done: fusions of the backward pass or the
    # update, each carrying a piece of the exchange
    for i in started:
        j = names.index(names[i].replace("start", "done"))
        assert any("calls=%async_collective_fusion" in text
                   for _, _, text in entry[i + 1:j]), names[i]


# (page_size, kv_heads, heads, head_dim): a 160M decoder's heads at D=64
# (the kernel alone; the serving programs below pad such a head to whole
# lanes), and llama2_7b-like heads at D=128
_GEOMETRIES = {"serve160m": (16, 4, 12, 64), "d128": (16, 8, 32, 128)}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("k1", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_paged_attention(one_chip, geometry, k1, kv_dtype):
    s_page, kv, h, d = _GEOMETRIES[geometry]
    b, pages, maxp = 8, 512, 64
    int8 = kv_dtype == "int8"
    shapes = [((b, k1, h, d), jnp.bfloat16),
              ((pages, kv, s_page, d), jnp.int8 if int8 else jnp.bfloat16),
              ((pages, kv, s_page, d), jnp.int8 if int8 else jnp.bfloat16),
              ((b, maxp), jnp.int32), ((b,), jnp.int32)]
    if int8:
        shapes += [((pages,), jnp.float32)] * 2
    # use_kernel unset: on a TPU the op must pick the kernel by itself
    c = _compile(paged_attention, one_chip, *shapes)
    assert "tpu_custom_call" in c.as_text()
    assert "%mx_paged_attention" in c.as_text()


# -- the serving programs at a size whose decode once did not fit (PR 40) -----

def _smol360m(kv_dtype):
    # SmolLM2-360M's shape at the geometry PERF.md 7.1 names: while the
    # arena was one (L, P, KV, S, D) array a side the decode wanted 19.40
    # of 15.75 GB (two copies of it, turned over for the scatter and back)
    from mxnet_tpu.serve.model import KVGeometry

    return KVGeometry(
        num_layers=32, num_heads=15, num_kv_heads=5, head_dim=64, units=960,
        hidden_size=2560, vocab_size=49152, page_size=16, num_pages=8192,
        max_pages_per_seq=256, max_batch=32, prefill_buckets=(512,),
        dtype="bfloat16", tie_embeddings=True, kv_dtype=kv_dtype, spec_k=4,
        prefill_chunk=64, paged_kernel="1")


@pytest.fixture(scope="module", params=["bfloat16", "int8"])
def serving(request, topo):
    """Geometry and programs; the weights are shapes, so nothing but the
    compiler is needed."""
    from mxnet_tpu.serve.model import serving_programs

    g = _smol360m(request.param)
    return g, serving_programs(g, topo.devices[0])


@pytest.mark.parametrize("name", ["decode", "verify", "chunk", "prefill_512"])
def test_a_serving_program_updates_the_arena_where_it_lies(serving, name):
    g, programs = serving
    fn, avals = programs[name]
    c = jax.jit(fn, donate_argnums=(0,)).lower(*avals).compile()
    m = c.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert held < 15.75e9, held
    # every layer's pages come back in the buffer they came in; a 64-wide
    # head takes 128 lanes there, and the geometry says so
    (k, _), _ = avals[0][0]
    assert k.shape == (8192, 5, 16, 128)
    pages = 2 * g.num_layers * k.size * k.dtype.itemsize
    assert g.arena_bytes(padded=True) >= pages > g.arena_bytes()
    assert m.alias_size_in_bytes >= pages
    # and nothing the size of a layer's pages is made beside them: the
    # temporaries are activations
    assert m.temp_size_in_bytes < 1.25 * pages / g.num_layers, \
        m.temp_size_in_bytes
    text = c.as_text()
    if name != "prefill_512":
        assert text.count("%mx_paged_attention") >= g.num_layers
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= \w+\[%d,%d,%d,%d\]\S* (copy|transpose)\("
                          % k.shape, line)]
    assert not moved, moved[:3]


# -- the Nemotron-H cell's operators at its own sizes (PR 30) -----------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_grouped_ffn_is_the_gmm_kernels_forward_and_backward(one_chip, dtype):
    # 4096 tokens x 6 assignments over 8 held experts of 2688 x 1856 (one
    # lane tile of 1856 is partial): each of the six products, and the two
    # made again in the backward pass, is a grouped Pallas kernel whose
    # grid follows the group sizes (PR 31: ``mx_gmm`` forward and for the
    # rows' gradient, ``mx_gmm_dw`` for the weights'), with a whole
    # expert's matrix held in VMEM, in bfloat16 (under amp) and float32;
    # nothing is left of the compiler's ``ragged-dot-*``, and no product
    # is dense over every expert and the whole bound.  What stands around
    # them walks the landed rows too (PR 33): three ``mx_rows_take`` (the
    # tokens' rows, the same again in the backward pass, the result's
    # gradient with the routing weight), three ``mx_rows_relu2``, two
    # ``mx_rows_combine``, the 4096 x 2688 float32 array they index by
    # token whole in VMEM; and no operation of XLA's has a floating-point
    # result over the bound's 24,576 rows
    dtype = jnp.dtype(dtype)

    def loss(x, idx, w, up, down):
        out, counts = grouped_ffn(x, idx, w, up.astype(dtype),
                                  down.astype(dtype))
        return jnp.sum(out * out), counts
    c = _compile(jax.grad(loss, argnums=(0, 2, 3, 4), has_aux=True),
                 one_chip, ((4096, 2688), dtype),
                 ((4096, 6), jnp.int32), ((4096, 6), jnp.float32),
                 ((8, 1856, 2688), jnp.float32),
                 ((8, 2688, 1856), jnp.float32))
    text = c.as_text()
    kernels = re.findall(r"%(mx_\w+?)(?:\.\d+)* = [^=]+ custom-call\(", text)
    assert sorted(kernels) == (
        ["mx_gmm"] * 6 + ["mx_gmm_dw"] * 2 + ["mx_rows_combine"] * 2
        + ["mx_rows_relu2"] * 3 + ["mx_rows_take"] * 3), kernels
    assert "tpu_custom_call" in text and "ragged-dot" not in text
    assert not re.search(r"\[8,24576,", text)          # no dense fallback
    over_the_bound = [
        line.strip() for line in text.splitlines()
        if re.match(r"\s*(ROOT )?%\S+ = \(?(bf16|f32)\[24576,\d+\]", line)
        and "custom-call(" not in line and "get-tuple-element(" not in line]
    assert not over_the_bound, over_the_bound[:3]


@pytest.mark.parametrize("seq", [2048, 2000])
def test_ssd_scan_forward_and_backward_fit(one_chip, seq):
    # 2 x 2048 tokens, 64 heads x 64, 8 groups x 128 states, chunks of
    # 128 (and a length that is no multiple of the chunk, which pads x, B
    # and C to whole chunks first): the scan is the Pallas kernels (PR 37),
    # a chunk's decays and state in VMEM; what the backward keeps beside the
    # inputs is the state each chunk starts from, 67 MB, and the rest of
    # the temporaries are the cotangents (131 MB in all; 192 MB padded)
    def loss(*args):
        return jnp.sum(jnp.square(ssd_scan(*args)))
    f32 = jnp.float32
    c = _compile(jax.grad(loss, argnums=tuple(range(7))), one_chip,
                 ((2, seq, 64, 64), f32), ((2, seq, 64), f32),
                 ((1, 64), f32), ((2, seq, 8, 128), f32),
                 ((2, seq, 8, 128), f32), ((64,), f32), ((1, 64), f32))
    text = c.as_text()
    assert "mx_ssd_fwd" in text and "mx_ssd_bwd" in text
    assert "tpu_custom_call" in text
    # no decay between every pair of a chunk's tokens outside the kernels
    assert not re.search(r"f32\[[\d,]*128,128\]", text)
    assert c.memory_analysis().temp_size_in_bytes < 256 << 20


# -- the Kimi Linear cell's operators at its own sizes (PR 34) ----------------

def test_kda_scan_forward_and_backward_fit(one_chip):
    # one sequence of 4096 tokens, 32 heads x 128 channels, chunks of 64:
    # what the backward keeps is the inputs; the chunk states, the solve
    # and the products within a chunk are temporaries of one layer
    def loss(*args):
        return jnp.sum(jnp.square(kda_scan(*args, chunk=64)))
    f32 = jnp.float32
    c = _compile(jax.grad(loss, argnums=tuple(range(5))), one_chip,
                 *[((1, 4096, 32, 128), f32)] * 4, ((1, 4096, 32), f32))
    assert c.memory_analysis().temp_size_in_bytes < 3 << 30
    # the scan is the Pallas kernels (PR 35): the forward, the forward again
    # with what the backward reads (the chunks' states, inverses and U:
    # 112 KB a head and chunk, 235 MB for 32 heads), and the backward
    kernels = re.findall(r"%(mx_\w+?)(?:\.\d+)* = [^=]+ custom-call\(",
                         c.as_text())
    assert sorted(kernels) == ["mx_kda_bwd", "mx_kda_fwd", "mx_kda_fwd"]
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("chunk", [16, 32], ids=["chunk16", "chunk32"])
def test_kda_kernels_at_shorter_chunks(one_chip, chunk):
    # a chunk of one sub-chunk and of two take the kernels too (the static
    # test asks for whole sub-chunks): values wider than keys, eight heads
    def loss(*args):
        return jnp.sum(jnp.square(kda_scan(*args, chunk=chunk)))
    f32 = jnp.float32
    c = _compile(jax.grad(loss, argnums=tuple(range(5))), one_chip,
                 *[((1, 512, 8, 128), f32)] * 2, ((1, 512, 8, 256), f32),
                 ((1, 512, 8, 128), f32), ((1, 512, 8), f32))
    assert "mx_kda_bwd" in c.as_text()


def test_grouped_ffn_with_swiglu_experts(one_chip):
    # 4096 tokens x 8 assignments over 8 held experts of 2304 x 1024, gate
    # and up stacked (8, 2048, 2304): the same eight grouped products and
    # the same kernels around them, with ``mx_rows_swiglu`` where the
    # Nemotron cell has ``mx_rows_relu2``
    bf16 = jnp.bfloat16

    def loss(x, idx, w, up, down):
        out, counts = grouped_ffn(x, idx, w, up.astype(bf16),
                                  down.astype(bf16), activation="swiglu")
        return jnp.sum(out * out), counts
    c = _compile(jax.grad(loss, argnums=(0, 2, 3, 4), has_aux=True),
                 one_chip, ((4096, 2304), bf16),
                 ((4096, 8), jnp.int32), ((4096, 8), jnp.float32),
                 ((8, 2048, 2304), jnp.float32),
                 ((8, 2304, 1024), jnp.float32))
    text = c.as_text()
    kernels = re.findall(r"%(mx_\w+?)(?:\.\d+)* = [^=]+ custom-call\(", text)
    assert sorted(kernels) == (
        ["mx_gmm"] * 6 + ["mx_gmm_dw"] * 2 + ["mx_rows_combine"] * 2
        + ["mx_rows_swiglu"] * 3 + ["mx_rows_take"] * 3), kernels
    assert "ragged-dot" not in text


# -- the JoyAI-LLM Flash cell's operators at its own sizes (PR 36) ------------

def test_flash_forward_and_backward_t8192_at_latent_attentions_widths(
        one_chip):
    # 32 heads x 8192 tokens, keys of 192 channels and values of 128: the
    # forward and dq kernels hold a head's K and V in VMEM (12 MiB
    # double-buffered, past the compiler's own 16 MiB with the rest) under
    # a limit of their own (``_flash_params``)
    def loss(q, k, v, w):
        out = flash_attention(q, k, v, scale=192 ** -0.5, causal=True)
        return (out * w).astype(jnp.float32).sum()
    bf16 = jnp.bfloat16
    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                 *[((1, 32, 8192, 192), bf16)] * 2,
                 *[((1, 32, 8192, 128), bf16)] * 2)
    text = c.as_text()
    for name in ("mx_flash_fwd", "mx_flash_bwd_dq", "mx_flash_bwd_dkv"):
        assert "%%%s" % name in text
    assert "pad(" not in text


def _traced(block):
    """``fn(weights, *inputs)``: ``block`` over raw arrays, its parameters
    the ``weights`` handed in (what ``JitTrainStep`` does to a network)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import block as block_mod
    from mxnet_tpu.ndarray import NDArray

    params = list(block.collect_params().values())

    def fn(weights, *inputs):
        st = block_mod._trace_st()
        was = (st.param_map, st.aux_updates, st.active)
        st.param_map = {id(p): NDArray(w) for p, w in zip(params, weights)}
        st.aux_updates, st.active = [], True
        try:
            with autograd.train_mode():
                return block._forward_imperative(
                    *[NDArray(x) for x in inputs]).data()
        finally:
            st.param_map, st.aux_updates, st.active = was
    return fn, params


def _mixer_grads(mixer, one_chip, tokens, layers=(1,)):
    """``mixer`` (an ``MLAMixer`` at 2048 or 2304 units, or a ``KDAMixer``:
    the last parameter is ``o_proj``) under bfloat16 AMP,
    forward and backward over one sequence, compiled for the described
    chip: one executable for each count of ``layers``."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp

    mixer.initialize(mx.init.Zero())
    fn, params = _traced(mixer)
    units = params[-1].shape[0]             # o_proj (units, H * vd)

    def loss(weights, u, w, layers):
        for _ in range(layers):
            u = u + fn(weights, u).astype(jnp.float32)
        return jnp.sum(u * w)
    amp.init("bfloat16")
    try:
        avals = ([jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                       sharding=one_chip) for p in params],
                 jax.ShapeDtypeStruct((1, tokens, units), jnp.float32,
                                      sharding=one_chip),
                 jax.ShapeDtypeStruct((1, tokens, units), jnp.float32,
                                      sharding=one_chip))
        return [jax.jit(jax.grad(functools.partial(loss, layers=n),
                                 argnums=(0, 1))).lower(*avals).compile()
                for n in layers]
    finally:
        amp.turn_off()


def _kernels(compiled):
    return sorted(re.findall(r"%(mx_\w+?)(?:\.\d+)* = [^=]+ custom-call\(",
                             compiled.as_text()))


@pytest.fixture(scope="module")
def joyai_mixer(one_chip):
    """JoyAI-LLM Flash's mixer at its published sizes, compiled once for
    one layer and once for two over 8192 tokens: two tests read the
    first."""
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    mixer = kimi_linear.MLAMixer(2048, 32, 512, 128, 64, 128, 1e-6,
                                 q_lora_rank=1536, rope_theta=32000000)
    return _mixer_grads(mixer, one_chip, 8192, layers=(1, 2))


def test_the_latent_attention_mixer_fits_at_8192_tokens(joyai_mixer):
    # JoyAI-LLM Flash's mixer at its published sizes under bfloat16 AMP,
    # forward and backward over one sequence of 8192 tokens: the three
    # flash kernels once each, nothing made again.  What the first of two
    # layers keeps while the second runs is what the step's five layers are
    # sized on: W_kvb's result as it lies, the two query parts, the rope
    # key, the result and its logsumexp (PR 39; q, k, v and W_kvb's result,
    # 0.69 GB, before it)
    one, two = joyai_mixer
    assert _kernels(one) == ["mx_flash_bwd_dkv_mla", "mx_flash_bwd_dq_mla",
                             "mx_flash_fwd_mla"]
    temp = one.memory_analysis().temp_size_in_bytes
    kept = two.memory_analysis().temp_size_in_bytes - temp
    assert temp < 2 << 30
    assert kept < 640 << 20


@pytest.mark.parametrize("model", ["joyai", "kimi"])
def test_the_latent_attention_mixer_rewrites_no_array_over_the_heads(
        request, one_chip, model):
    # the mixer's path by its shapes (JoyAI-LLM Flash at (1, 8192), a
    # low-rank query and a rotation; Kimi Linear at (1, 4096), neither):
    # the kernels read W_kvb's and W_qb's results where the products wrote
    # them, so under the scope ``mla`` the compiled step holds no transpose
    # and no copy of a bfloat16 array over (heads, tokens) or (tokens,
    # heads)
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    if model == "joyai":
        tokens = 8192
        compiled = request.getfixturevalue("joyai_mixer")[0]
    else:
        tokens = 4096
        compiled, = _mixer_grads(kimi_linear.MLAMixer(
            2304, 32, 512, 128, 64, 128, 1e-5), one_chip, tokens)
    assert _kernels(compiled) == ["mx_flash_bwd_dkv_mla",
                                  "mx_flash_bwd_dq_mla", "mx_flash_fwd_mla"]
    over_heads = re.compile(r"bf16\[(?:1,)?(?:32,%d|%d,32),\d+\]"
                            % (tokens, tokens))
    moved = [line.strip()[:200] for line in compiled.as_text().splitlines()
             if re.search(r" (transpose|copy)\(", line)
             and "mla" in line and over_heads.search(line)]
    assert not moved, moved


def test_grouped_ffn_with_swiglu_experts_of_768_over_8192_tokens(one_chip):
    # 8192 tokens x 8 assignments over 8 held experts of 2048 x 768, gate
    # and up stacked (8, 1536, 2048), a layout of 65,536 rows: the same
    # kernels as the Kimi cell's, and the (8192, 2048) float32 array they
    # index by token is cut along its columns to fit VMEM
    bf16 = jnp.bfloat16

    def loss(x, idx, w, up, down):
        out, counts = grouped_ffn(x, idx, w, up.astype(bf16),
                                  down.astype(bf16), activation="swiglu")
        return jnp.sum(out * out), counts
    c = _compile(jax.grad(loss, argnums=(0, 2, 3, 4), has_aux=True),
                 one_chip, ((8192, 2048), bf16),
                 ((8192, 8), jnp.int32), ((8192, 8), jnp.float32),
                 ((8, 1536, 2048), jnp.float32),
                 ((8, 2048, 768), jnp.float32))
    text = c.as_text()
    kernels = re.findall(r"%(mx_\w+?)(?:\.\d+)* = [^=]+ custom-call\(", text)
    assert sorted(kernels) == (
        ["mx_gmm"] * 6 + ["mx_gmm_dw"] * 2 + ["mx_rows_combine"] * 2
        + ["mx_rows_swiglu"] * 3 + ["mx_rows_take"] * 3), kernels
    assert "ragged-dot" not in text


# -- the Kimi Linear cell's KDA mixer whole in its kernels (PR 41) ------------

def _launches(compiled):
    return sorted(re.findall(r"%(mx_\w+?)(?:\.\d+)* = .* custom-call\(",
                             compiled.as_text()))


def test_the_kda_mixer_is_its_kernels_and_rewrites_no_array_over_the_heads(
        one_chip):
    # Kimi Linear's KDA mixer at its published sizes under bfloat16 AMP,
    # forward and backward over one sequence of 4096 tokens: the kernels
    # read the projections' results where they lie, (1, 4096, 32 x 128), so
    # under ``kda_attention`` nothing transposes, copies or concatenates an
    # array over (heads, tokens) or (head groups, batch, tokens); a layer
    # launches ``mx_kda_fwd`` once a pass (the forward, and the forward
    # again when the cotangent arrives) and ``mx_kda_bwd`` once.  A layer's
    # temporaries were 768 MiB and what it keeps while the next runs 282
    # before PR 41 (548 and 264 since)
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    mixer = kimi_linear.KDAMixer(2304, 32, 128, layer=1)
    one, two = _mixer_grads(mixer, one_chip, 4096, layers=(1, 2))
    assert _launches(one) == ["mx_kda_bwd", "mx_kda_fwd", "mx_kda_fwd"]
    assert _launches(two) == ["mx_kda_bwd"] * 2 + ["mx_kda_fwd"] * 4
    over_heads = re.compile(r"\[(?:\d+,)*(?:32,4096|4096,32),128\]"
                            r"|\[4,1,4096,\d+\]")
    moved = [line.strip()[:200] for line in one.as_text().splitlines()
             if re.search(r" (transpose|copy|concatenate)\(", line)
             and "kda_attention" in line and over_heads.search(line)]
    assert not moved, moved
    temp = one.memory_analysis().temp_size_in_bytes
    kept = two.memory_analysis().temp_size_in_bytes - temp
    assert temp < 640 << 20
    assert kept < 282 << 20


def test_the_mamba2_scan_and_convolution_trace_as_they_did():
    # ``ops/ssd_kernels.py`` takes ``_dot``, ``_column`` and ``_iota`` from
    # ``kda_kernels``, and the KDA mixer no longer calls ``causal_conv1d``:
    # the Nemotron cell's scan and convolution, forward and backward, trace
    # to the jaxpr text they had before PR 41 (sha256 of ``jax.make_jaxpr``
    # at small shapes, recorded from the parent; nothing compiles here)
    import hashlib

    from mxnet_tpu.ops.ssm import causal_conv1d

    f32 = jnp.float32

    def digest(fn, *shapes):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a))),
            argnums=tuple(range(len(shapes)))))(
            *[jnp.zeros(s, f32) for s in shapes]))
        return hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest(ssd_scan, (1, 256, 2, 64), (1, 256, 2), (1, 2),
                  (1, 256, 1, 128), (1, 256, 1, 128), (2,), (1, 2)) \
        == "cc2528a72423d0c9"
    assert digest(causal_conv1d, (1, 64, 256), (256, 4)) \
        == "f33e4420777b9092"


# -- the SDAR cell: block diffusion through the flash kernels ----------------

def test_flash_mla_and_the_sigmoid_router_trace_as_they_did():
    # the block-diffusion mask is a static fact that selects code, never an
    # operand: the six standing cells' attention (causal flash, latent
    # attention) and their sigmoid router, free and forced, forward and
    # backward, trace to the jaxpr text they had before the mask came
    # (sha256 of ``jax.make_jaxpr`` at small shapes, recorded from the tree
    # without it; nothing compiles here).  The mask's own kernels
    # (``ops/bd_kernels.py``) trace as they did while the mask's code lived
    # in ``pallas_kernels``
    import hashlib

    from mxnet_tpu.ops.bd_kernels import _attend
    from mxnet_tpu.ops.mla_kernels import mla_flash_attention
    from mxnet_tpu.ops.moe import router_topk

    f32 = jnp.float32

    def digest(fn, *shapes):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a))),
            argnums=tuple(range(len(shapes)))))(
            *[jnp.zeros(s, f32) for s in shapes]))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def router(seed):
        return lambda x, w: router_topk(x, w, jnp.ones((8,), f32), k=2,
                                        scale=2.5, balance_seed=seed)[1]
    heads = [(1, 2, 256, 128)] * 3
    assert digest(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128), *heads) \
        == "e6dd130cf9e4058c"
    assert digest(lambda q, k, v: flash_attention(q, k, v, causal=True),
                  *[(1, 2, 1024, 128)] * 3) == "5953ee5bec2eeafa"
    assert digest(lambda qn, qr, kv, kr: mla_flash_attention(
        qn, qr, kv, kr, num_heads=2, rope_theta=10000.0),
        (1, 512, 256), (1, 512, 128), (1, 512, 512), (1, 512, 64)) \
        == "4b7ea19e8ec2ed71"
    assert digest(lambda q, k, v, gq, gk: _attend(
        q, k, v, gq, gk, 4, 4, 1e6, 1e-6, 128), (1, 512, 512),
        (1, 512, 256), (1, 512, 256), (128,), (128,)) == "aed79dbe6fca5eb5"
    assert digest(router(None), (16, 32), (8, 32)) == "d4349856736fb737"
    assert digest(router(3), (16, 32), (8, 32)) == "506bff14e050d278"


def test_the_bd_attention_layer_rewrites_no_array_over_the_heads(one_chip):
    # the SDAR cell's attention layer (32 / 4 heads of 128, 2 x 4096
    # positions, width 2048) under bfloat16 AMP, forward and backward: the
    # kernels read the projections' results where the products wrote them,
    # so no array over (heads, positions) exists but the kernels' float32
    # logsumexp and delta rows: no transpose, copy or broadcast of q, of
    # the result or of their cotangents, and no K or V at 32 heads; K and V
    # and their cotangents are (1, 8192, 4 x 128)
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.gluon.model_zoo import sdar_moe

    attn = sdar_moe.BDAttention(2048, 32, 4, 128, 1e6, 4)
    attn.initialize(mx.init.Zero())
    fn, params = _traced(attn)

    def loss(weights, u, w):
        return jnp.sum(fn(weights, u).astype(jnp.float32) * w)
    amp.init("bfloat16")
    try:
        c = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            [jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=one_chip)
             for p in params],
            *[jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32,
                                   sharding=one_chip)] * 2).compile()
    finally:
        amp.turn_off()
    text = c.as_text()
    assert _kernels(c) == ["mx_flash_bwd_dkv_bd", "mx_flash_bwd_dq_bd",
                           "mx_flash_fwd_bd"]
    assert not re.search(r"bf16\[(?:1,)?(?:32,8192|8192,32),128\]", text)
    assert not re.search(r"f32\[(?:(?:1,)?8192,32,128|32,8192,128)\]", text)
    dkv = [line for line in text.splitlines()
           if re.search(r"%mx_flash_bwd_dkv_bd\S* = ", line)]
    assert len(dkv) == 1 and re.search(
        r"= \(bf16\[1,8192,512\]\S*, bf16\[1,8192,512\]", dkv[0])
    assert c.memory_analysis().temp_size_in_bytes < 640 << 20


def test_the_bd_dkv_kernel_walks_only_the_live_visits(one_chip):
    # the SDAR cell's attention (32 / 4 heads of 128, 2 x 4096 positions,
    # 512-token tiles, blocks of 4): the dk/dv kernel's grid is (batch, key
    # head, visit), 8 query heads x 80 live tiles a key head, and not the
    # rectangle's 8 x 16 x 16; lowered for the described chip, the visits
    # prefetched as scalars
    from jax._src import core

    from mxnet_tpu.ops import bd_kernels

    def loss(q, k, v, w):
        ones = jnp.ones((128,), jnp.float32)
        out = bd_kernels._attend(q, k, v, ones, ones, 32, 4, 1e6, 1e-6, 512)
        return (out * w).astype(jnp.float32).sum()
    shapes = [((1, 8192, 32 * 128), jnp.bfloat16),
              ((1, 8192, 4 * 128), jnp.bfloat16),
              ((1, 8192, 4 * 128), jnp.bfloat16),
              ((1, 8192, 32 * 128), jnp.bfloat16)]
    grad = jax.grad(loss, argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(*[jax.ShapeDtypeStruct(s, d)
                                   for s, d in shapes]).jaxpr

    def grids(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"], e.params["grid_mapping"].grid
            for p in e.params.values():
                for x in p if isinstance(p, (tuple, list)) else [p]:
                    if isinstance(x, (core.Jaxpr, core.ClosedJaxpr)):
                        yield from grids(getattr(x, "jaxpr", x))
    # each kernel twice: the TPU branch and the interpreter's
    assert sorted(set(grids(jaxpr))) == [
        ("mx_flash_bwd_dkv_bd", (1, 4, 640)),
        ("mx_flash_bwd_dq_bd", (1, 32, 16)),
        ("mx_flash_fwd_bd", (1, 32, 16))]
    assert "mx_flash_bwd_dkv_bd" in jax.jit(grad).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
          for s, d in shapes]).as_text()


def test_the_sdar_step_fits_one_chip_at_the_cells_shapes(one_chip):
    # the configuration as the cell runs it (4 layers, 16 held experts, an
    # eighth of the vocabulary) under bfloat16 AMP: the model's gradient at
    # one 4096-token sequence (8192 positions through every layer) and an
    # Adam update, the state donated; beside it the state at 20 B a
    # parameter (weights, m and v, and gluon's data and zero-gradient
    # copies) fits 15.75 GB.  At depth 5 it would want 17.20 (PERF.md 4)
    import json
    import sys

    import mxnet_tpu as mx
    from mxnet_tpu import amp

    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "chip")
    sys.path.insert(0, chip)
    import archs

    with open(os.path.join(chip, "configs", "sdar_30b_a3b_e16.json")) as f:
        cfg = json.load(f)
    net = archs.of(cfg).build(cfg, mx.cpu())
    fn, params = _traced(net)
    vocab = cfg["vocab_size"]

    def loss(weights, toks, labels):
        logp = jax.nn.log_softmax(
            fn(weights, toks).reshape(-1, vocab).astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    def step(ws, ms, vs, toks, labels):
        g = jax.grad(loss)(ws, toks, labels)
        ms = [0.9 * m + 0.1 * d for m, d in zip(ms, g)]
        vs = [0.999 * v + 0.001 * d * d for v, d in zip(vs, g)]
        return ([w - 1e-3 * m / (jnp.sqrt(v) + 1e-8)
                 for w, m, v in zip(ws, ms, vs)], ms, vs)
    amp.init("bfloat16")
    try:
        state = [jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                      sharding=one_chip) for p in params]
        c = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            state, state, state,
            jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)) \
            .compile()
    finally:
        amp.turn_off()
    n = sum(p.size for p in state)
    assert n == 456346624
    assert 20 * n + c.memory_analysis().temp_size_in_bytes < 15.75e9
    assert {"mx_flash_fwd_bd", "mx_flash_bwd_dq_bd", "mx_flash_bwd_dkv_bd",
            "mx_gmm", "mx_rows_swiglu"} <= set(_kernels(c))


# -- the Qwen3-Next cell: Gated DeltaNet on the delta-rule kernels -----------

def test_the_kda_mixer_and_scan_trace_as_they_did():
    # the GDN form of ``mx_kda_fwd`` / ``mx_kda_bwd`` is a static choice of
    # code: the Kimi cell's mixer (fused, a padded tail, and the composition
    # at 8 channels) and the scan alone, forward and backward, trace to the
    # jaxpr text they had before the form came (sha256 of ``jax.make_jaxpr``
    # at small shapes, recorded from the tree without it; nothing compiles)
    import hashlib

    from mxnet_tpu.ops.kda import kda_attention

    f32 = jnp.float32

    def digest(fn, *shapes):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.square(fn(*a))),
            argnums=tuple(range(len(shapes)))))(
            *[jnp.zeros(s, f32) for s in shapes]))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def mixer(h, d, t):
        i = h * d
        return [(1, t, i)] * 4 + [(1, t, h), (1, t, i)] + [(i, 4)] * 3 \
            + [(1, h), (h, d), (d,)]
    want = {(2, 128, 128): "b80b9a3726682714", (2, 128, 100): "789e9bc7d1bd98f5",
            (4, 8, 40): "9f60fb3aae87c6de"}
    for shape, hexdigest in want.items():
        assert digest(lambda *a: kda_attention(*a, chunk=64),
                      *mixer(*shape)) == hexdigest, shape
    assert digest(lambda *a: kda_scan(*a, chunk=64),
                  *[(1, 128, 2, 128)] * 4, (1, 128, 2)) == "bbf6de4696f1cc82"


def test_the_qwen3_next_step_fits_one_chip_at_the_cells_shapes(one_chip):
    # the configuration as the cell runs it (GDN, GDN, GDN, gated attention;
    # 16 of 512 experts held, an eighth of the vocabulary) under bfloat16
    # AMP: the model's gradient at one 8192-token sequence and an Adam
    # update, the state donated; beside it the state at 20 B a parameter
    # fits 15.75 GB (PERF.md 4: 4.78 GB of temporaries beside 8.49)
    import json
    import sys

    import mxnet_tpu as mx
    from mxnet_tpu import amp

    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "chip")
    sys.path.insert(0, chip)
    import archs

    with open(os.path.join(chip, "configs",
                           "qwen3_next_80b_p4_e16.json")) as f:
        cfg = json.load(f)
    net = archs.of(cfg).build(cfg, mx.cpu())
    fn, params = _traced(net)
    vocab = cfg["vocab_size"]

    def loss(weights, toks, labels):
        logp = jax.nn.log_softmax(
            fn(weights, toks).reshape(-1, vocab).astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    def step(ws, ms, vs, toks, labels):
        g = jax.grad(loss)(ws, toks, labels)
        ms = [0.9 * m + 0.1 * d for m, d in zip(ms, g)]
        vs = [0.999 * v + 0.001 * d * d for v, d in zip(vs, g)]
        return ([w - 1e-3 * m / (jnp.sqrt(v) + 1e-8)
                 for w, m, v in zip(ws, ms, vs)], ms, vs)
    amp.init("bfloat16")
    try:
        state = [jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                      sharding=one_chip) for p in params]
        c = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            state, state, state,
            jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip)) \
            .compile()
    finally:
        amp.turn_off()
    n = sum(p.size for p in state)
    assert n == 424340544
    assert 20 * n + c.memory_analysis().temp_size_in_bytes < 15.75e9
    # three GDN layers: the forward, the forward again and the backward
    # kernel each; the attention layer's flash kernels
    assert _launches(c).count("mx_kda_fwd") == 6
    assert _launches(c).count("mx_kda_bwd") == 3
    assert {"mx_flash_fwd", "mx_flash_bwd_dq", "mx_flash_bwd_dkv", "mx_gmm",
            "mx_rows_swiglu"} <= set(_launches(c))
