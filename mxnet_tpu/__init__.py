"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

A from-scratch rebuild of Apache MXNet's capability surface (reference:
kalakuer/incubator-mxnet) designed for TPU hardware: NDArrays are PJRT
buffers, operators are XLA computations (Pallas for the hot fused kernels),
``hybridize()`` lowers a captured graph to a single XLA executable, and the
KVStore runs on XLA collectives over ICI/DCN instead of NCCL/ps-lite.

Usage mirrors MXNet::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()    # mx:setup.import, closed at the end

__version__ = "0.1.0"


def _honor_int64_tensor_size():
    """``MXNET_INT64_TENSOR_SIZE=1`` enables 64-bit index VALUES
    (parity: the reference's ``USE_INT64_TENSOR_SIZE`` compile flag,
    tested by tests/nightly/test_large_array.py — here a runtime flag).

    Array *shapes* are 64-bit regardless (XLA native); this flag lifts
    jax's default int32 truncation so index arithmetic and integer
    reductions past 2^31 are exact too.  Opt-in because it also widens
    numpy-style default promotions, exactly like the reference flag
    changes framework-wide index types.  See docs/large_tensor.md.
    """
    import os

    if os.environ.get("MXNET_INT64_TENSOR_SIZE", "0") not in ("1", "true"):
        return
    try:
        import jax

        jax.config.update("jax_enable_x64", True)
    except Exception as e:
        # an explicit opt-in to exact >2^31 index math must never fail
        # silently — truncation would corrupt numerics downstream
        import warnings

        warnings.warn(
            "MXNET_INT64_TENSOR_SIZE=1 requested but enabling jax x64 "
            "failed (%s): index values past 2^31 will truncate" % (e,))


_honor_int64_tensor_size()


def _honor_compile_cache():
    """Persistent XLA executable cache, ON by default unless the process
    was told to run on CPU; the rule is ``compile_cache.configure``'s.

    The reference pays per-process graph-init cost in milliseconds (its
    kernels are precompiled into libmxnet.so); under XLA a cold llama train
    step is minutes of compile, so without this every NEW process pays it.
    """
    try:
        from . import compile_cache

        compile_cache.configure()
    except Exception:
        pass  # a cache is an optimization; never break import over it


_honor_compile_cache()

from .base import MXNetError  # noqa: F401
from .context import (  # noqa: F401
    Context, cpu, cpu_pinned, gpu, tpu, num_gpus, num_tpus, current_context,
)
from . import engine  # noqa: F401
from . import sharding  # noqa: F401
from . import layout  # noqa: F401
from .layout import layout_scope, set_default_layout  # noqa: F401
from . import random  # noqa: F401
from . import autograd  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray import NDArray  # noqa: F401

from .ndarray import waitall  # noqa: F401

from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import optimizer  # noqa: F401
from . import kvstore  # noqa: F401
from . import registry  # noqa: F401
from . import metric  # noqa: F401
from . import recordio  # noqa: F401
from . import io  # noqa: F401
from . import image  # noqa: F401
from . import parallel  # noqa: F401
from . import gluon  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .symbol import AttrScope  # noqa: F401
from . import model  # noqa: F401
from . import rnn  # noqa: F401
from . import log  # noqa: F401
from . import util  # noqa: F401
from . import name  # noqa: F401
from . import error  # noqa: F401
from . import executor  # noqa: F401
from . import callback  # noqa: F401
from . import module  # noqa: F401
from . import monitor  # noqa: F401
from . import visualization  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import profiler  # noqa: F401
from . import telemetry  # noqa: F401
from . import compile_cache  # noqa: F401
from . import test_utils  # noqa: F401
from . import amp  # noqa: F401
from . import contrib  # noqa: F401
from . import runtime  # noqa: F401
from . import rtc  # noqa: F401
from . import operator  # noqa: F401
from . import deploy  # noqa: F401
from . import serve  # noqa: F401
from . import library  # noqa: F401
from . import numpy as np  # noqa: F401
from . import numpy_extension as npx  # noqa: F401
from .numpy_extension import set_np, reset_np, is_np_shape, is_np_array  # noqa: F401,E501

# the package's own import (and jax's, where the process had not imported it)
# as a set-up stage; the profiler is imported by now, so only the total
profiler._add_setup_seconds("setup.import", _time.perf_counter() - _T_IMPORT)
