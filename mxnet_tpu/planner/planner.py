"""Score candidates, pick a plan, explain it.

``plan()`` is pure and deterministic: same param tree + same mesh axes
→ the same chosen specs, byte for byte (the CI determinism contract —
``tools/mxplan.py`` run twice must diff clean).  Scoring is the
uncalibrated α=1 heuristic by default::

    score = resident bytes/device (params + grads + optimizer slots
            + activation estimate)
          + comm_weight × collective bytes/device/step

with ``comm_weight`` overridable through a ``spmd_cost.Calibration``
(fed from measured telemetry).  A candidate over the capacity is
infeasible; if NONE fits, the smallest-footprint candidate is chosen
and the plan says so (``Plan.feasible``) — the same prediction mxlint
SP1001 makes statically.
"""
from __future__ import annotations

import os
import time

from ..analysis import spmd_cost as _cost
from ..base import MXNetError
from .candidates import enumerate_candidates

__all__ = ["ENV_CAPACITY", "ENV_DRYRUN", "Plan", "default_capacity_bytes",
           "plan", "plan_for_net", "plan_serving"]

ENV_CAPACITY = "MXNET_PLANNER_CAPACITY_BYTES"
ENV_DRYRUN = "MXNET_PLANNER_DRYRUN"


def default_capacity_bytes():
    """Per-device memory budget: ``MXNET_PLANNER_CAPACITY_BYTES`` wins;
    otherwise the accelerator's reported limit; None = unconstrained
    (CPU dryruns report no limit)."""
    env = os.environ.get(ENV_CAPACITY)
    if env:
        try:
            return int(env)
        except ValueError:
            raise MXNetError("%s=%r is not an integer byte count"
                             % (ENV_CAPACITY, env))
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        return int(limit) if limit else None
    except Exception:
        return None


def dryrun_enabled():
    v = os.environ.get(ENV_DRYRUN, "")
    return v not in ("", "0", "false", "False")


def _human(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return ("%d%s" % (n, unit) if unit == "B"
                    else "%.1f%s" % (n, unit))
        n /= 1024.0
    return "%d" % n


class Plan:
    """The planner's decision: chosen specs + the predictions behind it.

    ``param_rule`` is the ``fn(name, shape) -> PartitionSpec|None``
    JitTrainStep consumes — a lookup into the chosen spec map, so the
    executed shardings ARE the scored ones.
    """

    __slots__ = ("candidate", "description", "specs", "report", "score",
                 "mesh_axes", "data_axis", "capacity_bytes", "feasible",
                 "alternatives", "plan_seconds")

    def __init__(self, candidate, description, specs, report, score,
                 mesh_axes, data_axis, capacity_bytes, feasible,
                 alternatives, plan_seconds):
        self.candidate = candidate
        self.description = description
        self.specs = specs                  # name -> entries tuple
        self.report = report                # the chosen CostReport
        self.score = score
        self.mesh_axes = dict(mesh_axes)
        self.data_axis = data_axis
        self.capacity_bytes = capacity_bytes
        self.feasible = feasible
        self.alternatives = alternatives    # [(name, score, feasible)]
        self.plan_seconds = plan_seconds

    def param_rule(self, name, shape):
        """The chosen rule-set as a JitTrainStep ``param_rule``."""
        from jax.sharding import PartitionSpec

        entries = self.specs.get(name)
        if not entries:
            return None
        return PartitionSpec(*entries)

    def explain(self):
        """The dry-run report: chosen spec per parameter + predictions."""
        r = self.report
        mesh = "x".join("%s=%d" % kv for kv in self.mesh_axes.items())
        cap = (_human(self.capacity_bytes) if self.capacity_bytes
               else "unconstrained")
        lines = [
            "mxplan: mesh %s (data axis %r), capacity %s"
            % (mesh, self.data_axis, cap),
            "",
            "  %-38s %12s %9s  %s" % ("candidate", "resident/dev",
                                      "comms/step", "verdict"),
        ]
        for name, score, feasible, rep in self.alternatives:
            verdict = "chosen" if name == self.candidate else (
                "ok" if feasible else "over capacity")
            lines.append("  %-38s %12s %9s  %s"
                         % (name, _human(rep.total_bytes_per_device),
                            _human(rep.collective_bytes), verdict))
        lines += ["", "chosen: %s — %s" % (self.candidate,
                                           self.description)]
        if not self.feasible:
            lines.append("WARNING: no candidate fits the %s capacity — "
                         "predicted per-device OOM (SP1001)" % cap)
        lines.append("")
        lines.append("  %-28s %-18s %-22s %s"
                     % ("parameter", "shape", "spec", "bytes/device"))
        for pc in r.params:
            lines.append("  %-28s %-18s %-22s %s"
                         % (pc.name, "x".join(map(str, pc.shape)),
                            pc.spec_str(), _human(pc.per_device_bytes)))
        lines += [
            "",
            "predicted per device: params %s, grads %s, opt state %s, "
            "activations %s" % (_human(r.param_bytes_per_device),
                                _human(r.grad_bytes_per_device),
                                _human(r.opt_bytes_per_device),
                                _human(r.activation_bytes_per_device)),
            "predicted collectives per step: all-reduce %s, all-gather "
            "%s, reduce-scatter %s" % (_human(r.allreduce_bytes),
                                       _human(r.allgather_bytes),
                                       _human(r.reducescatter_bytes)),
            "compile signatures: %d" % r.compile_signatures,
        ]
        return "\n".join(lines)

    def as_dict(self):
        """JSON-stable form (bundle meta, mxplan --format json)."""
        return {
            "candidate": self.candidate,
            "description": self.description,
            "mesh_axes": dict(self.mesh_axes),
            "data_axis": self.data_axis,
            "capacity_bytes": self.capacity_bytes,
            "feasible": self.feasible,
            "score": self.score,
            "specs": {name: [list(e) if isinstance(e, tuple) else e
                             for e in entries]
                      for name, entries in self.specs.items()},
            "report": self.report.as_dict(),
            "alternatives": [
                {"candidate": name, "score": score, "feasible": feasible,
                 "total_bytes_per_device": rep.total_bytes_per_device,
                 "collective_bytes": rep.collective_bytes}
                for name, score, feasible, rep in self.alternatives],
        }


def plan(params, mesh, data_axis="data", capacity_bytes=None,
         step_tokens=None, optimizer_slots=0, candidates=None,
         calibration=None, trainable=None):
    """Choose a rule-set for ``params`` on ``mesh``.  Deterministic.

    ``capacity_bytes=None`` reads :func:`default_capacity_bytes`; pass
    ``0``/negative to force unconstrained.  See ``spmd_cost.
    analyze_params`` for the remaining knobs.
    """
    t0 = time.perf_counter()
    axes = _cost.mesh_axes(mesh)
    norm = _cost._norm_params(params)
    if capacity_bytes is None:
        capacity_bytes = default_capacity_bytes()
    if capacity_bytes is not None and capacity_bytes <= 0:
        capacity_bytes = None
    comm_weight = calibration.comm_weight if calibration else 1.0
    cands = list(candidates) if candidates is not None \
        else enumerate_candidates(axes, data_axis)
    if not cands:
        raise MXNetError("planner needs at least one candidate rule-set")

    scored, seen_specs = [], {}
    for cand in cands:
        specs = cand.specs(norm, axes)
        key = tuple(sorted(specs.items()))
        if key in seen_specs:
            continue        # spec-identical to an earlier candidate
        seen_specs[key] = cand.name
        rep = _cost.analyze_params(
            norm, axes, specs=specs, data_axis=data_axis,
            optimizer_slots=optimizer_slots, step_tokens=step_tokens,
            trainable=trainable)
        score = int(rep.total_bytes_per_device
                    + comm_weight * rep.collective_bytes)
        feasible = (capacity_bytes is None
                    or rep.total_bytes_per_device <= capacity_bytes)
        scored.append((cand, specs, rep, score, feasible))

    pool = [s for s in scored if s[4]]
    any_feasible = bool(pool)
    if not pool:
        # nothing fits: pick the smallest footprint and say so
        pool = sorted(scored,
                      key=lambda s: s[2].total_bytes_per_device)[:1]
    best = min(pool, key=lambda s: (s[3], cands.index(s[0])))
    cand, specs, rep, score, _ = best
    return Plan(
        candidate=cand.name, description=cand.description, specs=specs,
        report=rep, score=score, mesh_axes=axes, data_axis=data_axis,
        capacity_bytes=capacity_bytes,
        feasible=any_feasible,
        alternatives=[(c.name, sc, fe, rp)
                      for c, _sp, rp, sc, fe in scored],
        plan_seconds=time.perf_counter() - t0)


def _net_params(net, sample=None):
    """``[(name, shape, dtype)]`` from a gluon net; a sample batch
    resolves deferred shapes with one throwaway forward."""
    ps = list(net.collect_params().values())
    if any(0 in tuple(p.shape or (0,)) for p in ps) and sample is not None:
        net(*sample) if isinstance(sample, (tuple, list)) else net(sample)
        ps = list(net.collect_params().values())
    return [(p.name, tuple(p.shape),
             str(getattr(p, "dtype", "float32") or "float32"))
            for p in ps]


def plan_for_net(net, mesh, sample=None, **kwargs):
    """:func:`plan` over a gluon net's parameter tree."""
    return plan(_net_params(net, sample), mesh, **kwargs)


def plan_serving(net, geometry, mesh, data_axis="data", **kwargs):
    """The serving-export hook: plan the weight specs AND suggest a KV
    arena spec (KV-heads dim on the first tensor-parallel axis that
    divides them — the canonical placement ``PagedKVArena`` takes).

    Returns a JSON-able dict stored in the bundle meta (``"planner"``
    key), so a sharded server can be brought up with zero live jits AND
    zero hand-written specs.
    """
    pl = plan_for_net(net, mesh, data_axis=data_axis, **kwargs)
    axes = pl.mesh_axes
    kv_spec = [None, None, None, None]
    for axis, size in axes.items():
        if axis != data_axis and size > 1 \
                and geometry.num_kv_heads % size == 0:
            kv_spec[1] = axis        # a layer's (P, KV-heads, page, head-dim)
            break
    doc = pl.as_dict()
    doc["kv_spec"] = kv_spec
    return doc
