#!/usr/bin/env python
"""Diagnose the runtime environment
(parity: reference tools/diagnose.py — python/pip/OS/hardware/framework
checks; the network-reachability checks become backend/device checks,
since the TPU build's critical dependency is the XLA backend, not a
download mirror).

Usage: python tools/diagnose.py
"""
from __future__ import annotations

import os
import platform
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip

        print("Version      :", pip.__version__)
    except ImportError:
        print("No corresponding pip install for current python.")


def check_mxnet():
    print("----------MXNet-TPU Info-----------")
    t0 = time.time()
    try:
        import mxnet_tpu as mx

        print("Imported in  : %.2fs" % (time.time() - t0))
        print("Directory    :", os.path.dirname(mx.__file__))
        from mxnet_tpu.runtime import Features

        feats = Features()
        on = [k for k in feats.keys() if feats.is_enabled(k)]
        print("Features     :", ", ".join(on) if on else "(none)")
    except Exception as e:  # keep diagnosing even on failure
        print("mxnet_tpu import FAILED:", e)


def check_backend():
    print("----------Backend Info---------")
    try:
        import jax

        print("jax          :", jax.__version__)
        t0 = time.time()
        devs = jax.devices()
        print("Devices      : %s (init %.2fs)" % (devs, time.time() - t0))
        print("Default      :", jax.default_backend())
    except Exception as e:
        print("jax backend FAILED:", e)


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_hardware():
    print("----------Hardware Info----------")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor())
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.run(["lscpu"], capture_output=True,
                                 text=True, timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Model name", "CPU(s)",
                                           "Thread", "Socket")):
                    print(line)
        except Exception:
            pass


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "DMLC_", "OMP_")):
            if "SECRET" in k:
                v = "<redacted>"
            print("%s=\"%s\"" % (k, v))


if __name__ == "__main__":
    check_python()
    check_pip()
    check_mxnet()
    check_backend()
    check_os()
    check_hardware()
    check_environment()
