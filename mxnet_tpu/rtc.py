"""``mx.rtc`` — user-supplied device kernels.

Reference: ``mx.rtc.CudaModule`` (``include/mxnet/rtc.h:39``,
``src/common/rtc.cc:49``) — NVRTC-compiled CUDA source launchable on
NDArrays.  TPU-native replacement: the kernel language is **Pallas**
(the TPU kernel DSL) instead of CUDA C; ``PallasModule`` wraps a Pallas
kernel function into an NDArray-callable with tape integration, running
interpreted on CPU for tests and compiled on TPU.
"""
from __future__ import annotations

import jax

from .base import MXNetError
from .ops import registry as _reg


class PallasKernel:
    """A launchable kernel (parity: CudaModule.get_kernel result)."""

    def __init__(self, kernel_fn, out_shape, in_specs=None, out_specs=None,
                 grid=None, name=None, interpret=None, **pallas_kwargs):
        self._kernel_fn = kernel_fn
        self._out_shape = out_shape
        self._name = name or getattr(kernel_fn, "__name__", "pallas_kernel")
        self._kwargs = dict(pallas_kwargs)
        if in_specs is not None:
            self._kwargs["in_specs"] = in_specs
        if out_specs is not None:
            self._kwargs["out_specs"] = out_specs
        if grid is not None:
            self._kwargs["grid"] = grid
        self._interpret = interpret

    def _interp(self):
        if self._interpret is not None:
            return self._interpret
        return jax.default_backend() != "tpu"

    def launch(self, *arrays):
        """Run on NDArrays; differentiable if the kernel is (via jax.vjp
        over the pallas_call, which Pallas supports for simple kernels)."""
        from jax.experimental import pallas as pl

        def fn(*raw):
            out = pl.pallas_call(
                self._kernel_fn,
                out_shape=self._out_shape,
                interpret=self._interp(),
                **self._kwargs,
            )(*raw)
            return out if isinstance(out, tuple) else (out,)

        results = _reg.invoke_fn(fn, list(arrays), op_name=self._name)
        return results[0] if len(results) == 1 else results

    __call__ = launch


class PallasModule:
    """Named collection of Pallas kernels (parity: CudaModule)."""

    def __init__(self, **kernels):
        self._kernels = dict(kernels)

    def get_kernel(self, name, *args, **kwargs):
        k = self._kernels.get(name)
        if k is None:
            raise MXNetError("no kernel %r in module" % name)
        return k


class CudaModule:
    def __init__(self, *a, **kw):
        raise MXNetError(
            "CUDA RTC does not exist on TPU; write the kernel in Pallas "
            "and wrap it with mx.rtc.PallasKernel (same launch-on-NDArray "
            "contract)")
