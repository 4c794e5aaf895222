"""Set-up seen from inside the program: the parts of ``setup_s``.

The program keeps a ledger of every program it builds
(``mxnet_tpu.compile_cache.programs()``: ``trace_s``, ``lower_s``,
``backend_s``, ``cache``, the set-up stage it was built ``under``, and
``requests``, the cache's count of requests when the entry closed) and the
seconds of its own set-up stages (``mxnet_setup_seconds_total{stage}``:
``setup.import``, ``train_step.init``, ``train_step.build``).  ``run.py``
takes ``compile_cache.stats()`` at the window's start
(``run["cache_at_window"]``); the entries whose ``requests`` is at most that
snapshot's are set-up's, the later ones the reference's, built in
``check()``.  The parts, none counted twice::

    import          the stage setup.import
    step_init       the stage train_step.init, whole (its small programs too)
    step_trace      \\
    step_lower       > the costliest entry under train_step.build: the step
    step_backend    /
    other_programs  the entries under neither stage (the benchmark's eager
                    programs: weights, readings)
    outside_program ``setup_s`` less all of the above: the interpreter, jax
                    and the runtime's start, the benchmark's own work, the
                    first steps' run time

A program without the ledger (any tree before it) gives ``None`` for all.
"""
from __future__ import annotations

INIT, BUILD, IMPORT = "train_step.init", "train_step.build", "setup.import"


def total_s(entry):
    return entry["trace_s"] + entry["lower_s"] + entry["backend_s"]


def stage_seconds(families, stage):
    """``mxnet_setup_seconds_total{stage}`` of a snapshot, or None."""
    for s in families.get("mxnet_setup_seconds_total", {}).get("series", []):
        if s["labels"].get("stage") == stage:
            return s["value"]
    return None


def reduce(programs, families, at_window, setup_s):
    """``{name: number or None}`` for the nine readers, from the ledger, a
    telemetry snapshot, ``compile_cache.stats()`` at the window's start and
    ``setup_s``."""
    entries = [e for e in programs if e["requests"] <= at_window["requests"]]
    step = max((e for e in entries if e["under"] == BUILD), key=total_s,
               default=None)
    out = {"setup_part_s.import": stage_seconds(families, IMPORT),
           "setup_part_s.step_init": stage_seconds(families, INIT),
           "setup_part_s.step_trace": step and step["trace_s"],
           "setup_part_s.step_lower": step and step["lower_s"],
           "setup_part_s.step_backend": step and step["backend_s"],
           "setup_part_s.other_programs": sum(
               total_s(e) for e in entries if e["under"] not in (INIT, BUILD))}
    out["setup_part_s.outside_program"] = setup_s - sum(
        v for v in out.values() if v is not None)
    out["setup_programs_built"] = len(entries)
    out["cache_misses_setup"] = at_window["misses"]
    return out


def read(run, name):
    """What a reader under ``metrics/`` returns.  The nine share one
    reduction, kept on the run."""
    if "setup_reduced" not in run:
        from mxnet_tpu import compile_cache
        from mxnet_tpu.telemetry import metrics

        at_window = run.get("cache_at_window")
        if at_window is None or not hasattr(compile_cache, "programs"):
            run["setup_reduced"] = {}
        else:
            run["setup_reduced"] = reduce(
                compile_cache.programs(), metrics.snapshot(), at_window,
                run["setup_s"])
    return run["setup_reduced"].get(name)
