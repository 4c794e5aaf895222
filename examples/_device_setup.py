"""Shared example helper: run an example on virtual CPU devices.

The examples are small and meant to run anywhere, so they ask for the
CPU backend with enough virtual devices unless the user opts into the
machine's own backend with MXNET_EXAMPLE_PLATFORM=ambient.
"""
from __future__ import annotations

import os


def ensure_devices(n_needed=1):
    import jax

    if os.environ.get("MXNET_EXAMPLE_PLATFORM") == "ambient":
        return
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", max(8, n_needed))
