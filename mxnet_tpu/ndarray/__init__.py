"""``mx.nd`` — imperative tensor namespace.

Op functions are generated at import from the live registry, mirroring the
reference's codegen-at-import (``python/mxnet/ndarray/register.py``).
"""
from __future__ import annotations

# ensure op modules register before namespace generation
from ..ops import tensor as _t  # noqa: F401
from ..ops import nn as _n  # noqa: F401
from ..ops import random_ops as _r  # noqa: F401
from ..ops import optimizer_ops as _o  # noqa: F401
from ..ops import contrib as _c  # noqa: F401
from ..ops import pallas_kernels as _p  # noqa: F401
from ..ops import paged_attention as _pa  # noqa: F401
from ..ops import ssm as _ssm  # noqa: F401
from ..ops import kda as _kda  # noqa: F401
from ..ops import rotary as _rotary  # noqa: F401
from ..ops import mla_kernels as _mla  # noqa: F401
from ..ops import bd_kernels as _bdk  # noqa: F401
from ..ops import moe as _moe  # noqa: F401
from ..ops import block_diffusion as _bd  # noqa: F401
from ..ops import misc as _m  # noqa: F401
from ..ops import vision as _v  # noqa: F401
from ..ops import quantized_ops as _q  # noqa: F401
from ..ops import npi as _npi  # noqa: F401
from ..ops import control_flow as _cf  # noqa: F401

from .ndarray import (  # noqa: F401
    NDArray, array, empty, zeros, ones, full, arange, zeros_like, ones_like,
    concatenate, moveaxis, save, load, waitall, shard,
    from_dlpack, to_dlpack_for_read, to_dlpack_for_write,
)
from . import random  # noqa: F401
from . import sparse  # noqa: F401
from .register import populate as _populate

_populate(globals())

# imperative cast_storage returns REAL sparse views (the registry op is
# the dense/graph rendering; parity: mx.nd.cast_storage returning
# CSRNDArray/RowSparseNDArray objects)
_graph_cast_storage = cast_storage  # noqa: F821  (registry-generated)


def cast_storage(data, stype="default"):  # noqa: F811
    if getattr(data, "stype", "default") != "default":
        return sparse.cast_storage(data, stype)
    if stype != "default" and not getattr(data, "_in_graph", False):
        # eager dense -> sparse view; in-graph (taped/jitted) arrays stay
        # on the registry op, whose dense rendering is differentiable
        return sparse.cast_storage(data, stype)
    return _graph_cast_storage(data, stype=stype)

# control-flow operators (lax.scan/while/cond lowering; ops/control_flow.py)
from ..ops.control_flow import (  # noqa: E402
    foreach as _contrib_foreach,
    while_loop as _contrib_while_loop,
    cond as _contrib_cond,
)

# user-defined ops (mx.operator registry; parity: mx.nd.Custom)
from ..operator import custom as Custom  # noqa: E402,F401

# contrib sub-namespace: ops named _contrib_* surface as nd.contrib.<name>
class _ContribNS:
    def __getattr__(self, item):
        fn = globals().get("_contrib_" + item)
        if fn is None:
            raise AttributeError("nd.contrib.%s" % item)
        return fn

    def __dir__(self):
        return sorted(n[len("_contrib_"):] for n in globals()
                      if n.startswith("_contrib_"))


contrib = _ContribNS()
