#!/usr/bin/env python
"""All-reduce bandwidth microbenchmark.

Parity: reference ``tools/bandwidth/measure.py`` (KVStore allreduce
bandwidth; its README reports ~4.5 GB/s/GPU over PCIe at 8 GPUs).
Here the collective is an XLA ``psum`` over the device mesh — ICI on a
real pod, shared-memory on the virtual CPU mesh — which is the rebuild's
actual gradient-aggregation path (compiled into the train step).

``--dist`` instead measures the DCN tier: push+pull round-trip
throughput of the typed dist-kvstore wire against an in-process
DistServer over loopback TCP (upper bound of the protocol + framing
stack; real DCN adds the network itself).

Usage:
    python tools/bandwidth/measure.py [--size-mb 64] [--runs 10]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bandwidth/measure.py   # 8 virtual devices
    python tools/bandwidth/measure.py --dist  # dist-kvstore TCP wire
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def measure_dist(size_mb, runs):
    """Loopback push+pull throughput of the typed dist-kvstore wire.

    The server runs in a SUBPROCESS: an in-process server thread shares
    the GIL and the measurement then reports Python contention, not the
    protocol (measured ~0.6 GB/s in-process vs the subprocess number).
    """
    import subprocess
    import time as _t

    import numpy as np

    from mxnet_tpu import nd
    from mxnet_tpu.parallel.dist_kvstore import (
        DistKVStore, _server_port)

    root_port = 23450
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    server = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from mxnet_tpu.parallel.dist_kvstore import DistServer, _server_port\n"
         "DistServer(_server_port(%d, 0), num_workers=1, sync=True).run()\n"
         % (os.path.dirname(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__)))), root_port)],
        env=env)
    _t.sleep(3.0)
    os.environ["DMLC_PS_ROOT_PORT"] = str(root_port)
    os.environ["DMLC_NUM_WORKER"] = "1"
    os.environ["DMLC_NUM_SERVER"] = "1"
    kv = DistKVStore("dist_sync")
    elems = int(size_mb * 1e6 / 4)
    val = nd.array(np.ones((elems,), np.float32))
    kv.init("bw", val)
    out = nd.zeros((elems,))
    kv.push("bw", val)
    kv.pull("bw", out=out)
    t0 = _t.perf_counter()
    for _ in range(runs):
        kv.push("bw", val)
        kv.pull("bw", out=out)
    dt = (_t.perf_counter() - t0) / runs
    moved = elems * 4 * 2  # push + pull payloads
    print("dist wire: payload=%.1fMB round-trip=%.1fms throughput=%.2f GB/s"
          % (elems * 4 / 1e6, dt * 1e3, moved / dt / 1e9))
    kv.stop()
    server.wait(timeout=30)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--dist", action="store_true",
                    help="measure the dist-kvstore TCP wire instead")
    args = ap.parse_args()

    if args.dist:
        measure_dist(args.size_mb, args.runs)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        print("single device (%s): nothing to all-reduce; use the "
              "virtual CPU mesh (see --help)" % devs)
        return
    mesh = Mesh(np.array(devs), ("d",))
    elems = int(args.size_mb * 1e6 / 4)
    x = jnp.ones((n, elems), jnp.float32)
    x = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("d", None)))

    @jax.jit
    def allreduce(v):
        return jax.shard_map(
            lambda s: jax.lax.psum(s, "d"),
            mesh=mesh, in_specs=P("d", None), out_specs=P("d", None),
        )(v)

    out = allreduce(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(args.runs):
        out = allreduce(out)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / args.runs
    # ring all-reduce moves 2*(n-1)/n of the payload per device
    payload = elems * 4
    algo_bw = payload * 2 * (n - 1) / n / dt / 1e9
    print("devices=%d payload=%.1fMB time=%.3fms alg_bandwidth=%.2f GB/s"
          % (n, payload / 1e6, dt * 1e3, algo_bw))


if __name__ == "__main__":
    main()
