#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; never part of a
benchmark run.

    python3 benchmark/chip/probe.py --workload <cell> --seeds 1,2,3 \
        --what program,control,half --out chiprun_out/probe.jsonl

For every seed, in one process, at the cell's own size:

- ``program``: the cell's driver builds the program, drives its first steps
  and is freed; its readings against the float32 reference (the LOWER
  reading of each number);
- ``control``: the reference computed in fp8 (``reference.py``), the step
  below the configuration's bfloat16, put in the program's place (the UPPER
  reading);
- ``half``: the reference with half of the batch left out and the mean
  taken over the rest, put in the program's place (on a mesh this is also
  what one data shard computes when the gradient exchange over ``data`` is
  left out);
- ``unchanged``: the reference whose step returns its state unchanged.

One JSON line a seed and kind is appended to ``--out`` as it is read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402  (puts the benchmark and the program on sys.path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,half")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    cell, cfg, workload, _, _ = harness.load_cell(a.workload, a.rehearse)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_" \
                                      "count=%d" % cell["chips"]
    devs, peak = harness.find_devices(cell, a.rehearse)
    from mxnet_tpu import compile_cache

    compile_cache.configure()
    import compare
    import reference
    import traffic

    drv_mod = harness._module("drivers", workload["driver"])
    what = a.what.split(",")
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)

    def emit(seed, kind, numbers, extra=None):
        doc = {"workload": a.workload, "seed": seed, "kind": kind,
               "numbers": numbers, "device": devs[0].device_kind}
        doc.update(extra or {})
        with open(a.out, "a") as f:
            f.write(json.dumps(doc) + "\n")
        print(json.dumps(doc), flush=True)

    for seed in [int(s) for s in a.seeds.split(",")]:
        batches = None
        if "program" in what:
            run = {"cell": cell, "cfg": cfg, "workload": workload,
                   "seed": seed, "chips": cell["chips"], "peak": peak,
                   "rehearse": a.rehearse, "trace": None}
            drv = drv_mod.Driver(run)
            drv.setup()
            batches = drv.followed
            drv.release()
            left = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                       for d in devs)
            numbers = drv.check()
            emit(seed, "program", numbers,
                 {"ours": drv.ours, "reference": run["reference"],
                  "bytes_left_after_release": left})
            ref = run["reference"]
        else:
            ref = None
        if batches is None:
            feed = traffic.token_batches(seed, workload["batch"],
                                         workload["seq"], cfg["vocab_size"])
            batches = [next(feed) for _ in range(workload["follow_steps"])]
        batches = [(t, lab.astype("int32")) for t, lab in batches]
        import jax

        shard = drv_mod.reference_shardings(jax, cfg)

        def readings(**kw):
            return reference.train_readings(cfg, cfg["optimizer"], seed,
                                            batches, shardings=shard, **kw)
        if ref is None:
            ref = readings()
        if "control" in what:
            emit(seed, "control", compare.train_numbers(
                readings(precision="fp8"), ref))
        if "half" in what:
            emit(seed, "half", compare.train_numbers(
                readings(rows=workload["batch"] // 2), ref))
        if "unchanged" in what:
            emit(seed, "unchanged", compare.train_numbers(
                readings(skip_update=True), ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
