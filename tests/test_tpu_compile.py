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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from mxnet_tpu.ops.paged_attention import paged_attention
from mxnet_tpu.ops.pallas_kernels import _flash_bwd_pallas, flash_attention


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


@pytest.mark.xfail(strict=True, raises=Exception,
                   reason="RESOURCE_EXHAUSTED: Ran out of memory in memory "
                          "space vmem while allocating on stack for ... "
                          "custom_call_target=\"tpu_custom_call\": the dk/dv "
                          "kernel holds whole-T q, dO, lse and delta per "
                          "program (ROADMAP Queue 1)")
def test_flash_backward_t4096(one_chip):
    # the kernel pair called as flash_attention's vjp calls it.  Inside a
    # larger program (jax.grad of the op) XLA still fits T=4096 and gives
    # up at T=8192 with the same message.
    bwd = functools.partial(_flash_bwd_pallas, scale=128 ** -0.5,
                            causal=True, block_q=512, block_k=512)
    _compile(bwd, one_chip, *[((32, 4096, 128), jnp.bfloat16)] * 4,
             *[((32, 4096, 128), jnp.float32)] * 2)


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


# (page_size, kv_heads, heads, head_dim): the 160M decoder chip_smoke.py
# serves, and llama2_7b-like heads at D=128
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
