"""Driver ``train_batches``: ``parallel.JitTrainStep`` fed a fresh batch
every step.

Set-up builds ONE object, the compiled step with its state, exactly as
``chip_smoke.py:child_train`` does (``with mx.tpu():``, ``amp.init``, the
``LM`` wrapper, AdamW, ``SoftmaxCrossEntropyLoss``; a mesh and the Megatron
rules where the configuration names them) around the network that the
configuration's architecture module builds (``archs/<model_type>.py``),
hands it the benchmark's own weights from ``--seed``, drives it through
its first steps with the window's own call and feed, reads what
``correct`` compares, and hands the same object to the window.

Parameters (``workloads/<cell>.json``): ``batch``, ``seq``, ``log_every``
(a loss is fetched every so many steps, as a trainer's log line does),
``log_lag`` (the loss fetched is that of so many log lines before: a
trainer that logs asynchronously never drains the device's queue, and a
stall of the host shorter than the queue costs nothing), ``follow_steps``
(how many first steps the reference follows).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import sys
import time

import archs
import compare
import reference
import traffic


class Driver:
    def __init__(self, run):
        self.run = run
        self._stack = contextlib.ExitStack()

    # -- set-up -------------------------------------------------------------
    def setup(self):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu import amp, gluon, parallel
        from mxnet_tpu.telemetry import metrics

        run, cfg, wl = self.run, self.run["cfg"], self.run["workload"]
        stamps = [("imports", time.perf_counter())]
        self._jax = jax
        self._metrics = metrics
        vocab = cfg["vocab_size"]
        ctx = mx.tpu()
        self._stack.enter_context(ctx)
        mesh = parallel.make_mesh(cfg["mesh"]) if cfg.get("mesh") else None
        net = archs.of(cfg).build(cfg, ctx)
        params = list(net.collect_params().values())
        specs = reference.leaf_specs(cfg)
        if [tuple(p.shape) for p in params] != [s for _, s in specs]:
            raise RuntimeError(
                "the program's parameters %s are not the reference's "
                "leaves %s" % ([(p.name, p.shape) for p in params], specs))
        weights = reference.make_weights(cfg, run["seed"])
        for p, w in zip(params, weights):
            p.set_data(w)        # aliases: the gluon copy IS this array
        del weights
        stamps.append(("model and weights", time.perf_counter()))
        amp.init(cfg["dtype"])

        class LM(gluon.HybridBlock):
            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def hybrid_forward(self, F, toks):
                return F.reshape(self.inner(toks), shape=(-1, vocab))

        opt = cfg["optimizer"]
        self.step = parallel.JitTrainStep(
            LM(net), gluon.loss.SoftmaxCrossEntropyLoss(), opt["name"],
            {"learning_rate": opt["learning_rate"], "beta1": opt["beta1"],
             "beta2": opt["beta2"], "epsilon": opt["epsilon"],
             "wd": opt["wd"]},
            mesh=mesh, rules=cfg.get("rules"))
        self.net = net
        self.tokens_per_step = wl["batch"] * wl["seq"]
        self.feed = traffic.token_batches(run["seed"], wl["batch"],
                                          wl["seq"], vocab)
        self.followed = []
        self.ours = self._first_steps(wl["follow_steps"], opt)
        stamps.append(("first steps and readings", time.perf_counter()))
        # two more: every layout has settled and nothing compiles after
        for _ in range(2):
            float(self._one_step())
        stamps.append(("warm-up", time.perf_counter()))
        run["setup_parts"] = [(k, b - a) for (_, a), (k, b)
                              in zip(stamps, stamps[1:])]
        print("[chipbench] set-up: " + ", ".join(
            "%s %.1fs" % kv for kv in run["setup_parts"]), file=sys.stderr)

    def _one_step(self, keep=False):
        batch = next(self.feed)
        if keep:
            self.followed.append(batch)
        return self.step.step(*batch)

    def _first_steps(self, n, opt):
        """The first ``n`` steps through the window's own call and feed,
        and the program's side of what ``correct`` compares."""
        jax = self._jax
        jnp = jax.numpy
        losses, grad_norms = [], None
        for t in range(1, n + 1):
            losses.append(float(self._one_step(keep=True)))
            if t == 1:
                # the gradient as the optimizer got it: m_1 = (1-b1) g
                means = [st[0] for st in self.step._opt_state]
                scale = 1.0 / (1.0 - opt["beta1"])
                grad_norms = [float(x) * scale for x in jax.jit(
                    lambda ms: [jnp.sqrt(jnp.sum(jnp.square(
                        m.astype(jnp.float32)))) for m in ms])(means)]
                del means
        deltas = [float(x) for x in reference.delta_norms(
            self.run["cfg"], self.run["seed"], self.step._weights)]
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": deltas}

    def _compiles(self):
        fam = self._metrics.snapshot().get("mxnet_compiles_total", {})
        return (int(sum(s["value"] for s in fam.get("series", []))),
                int(self.step._step_fn._cache_size()))

    # -- the window -----------------------------------------------------------
    def window(self, seconds):
        from jax.profiler import TraceAnnotation as Span

        run, wl = self.run, self.run["workload"]
        every, lag = wl["log_every"], wl.get("log_lag", 0)
        compiles0 = self._compiles()
        steps, fetched, stamps = 0, [], []
        pending = collections.deque()
        batch = next(self.feed)
        t0 = time.perf_counter()
        with Span("bench:window"):
            while True:
                with Span("bench:step.dispatch"):
                    loss = self.step.step(*batch)
                steps += 1
                with Span("bench:input"):
                    batch = next(self.feed)
                if steps % every == 0:
                    pending.append(loss)
                    if len(pending) > lag:
                        with Span("bench:loss_fetch"):
                            fetched.append(float(pending.popleft()))
                        stamps.append(time.perf_counter())
                    if time.perf_counter() - t0 >= seconds:
                        break
            with Span("bench:loss_fetch"):      # every step dispatched ends
                fetched.extend(float(x) for x in pending)
            t1 = time.perf_counter()
        run["window_s"] = t1 - t0
        run["fetch_stamps"] = [s - t0 for s in stamps]
        run["steps"] = steps
        run["tokens"] = steps * self.tokens_per_step
        run["attempted"], run["failed"] = steps, 0
        run["losses_logged"] = fetched
        run["compiles"] = (compiles0, self._compiles())

    # -- after the window -------------------------------------------------------
    def release(self):
        """Free the program's state, so that the reference fits."""
        self.step = self.net = self.feed = None
        self._stack.close()
        gc.collect()

    def check(self):
        """Reference readings over the steps followed, and the numbers."""
        run, cfg = self.run, self.run["cfg"]
        ref = reference.train_readings(
            cfg, cfg["optimizer"], run["seed"],
            [(t, lab.astype("int32")) for t, lab in self.followed],
            shardings=reference_shardings(self._jax, cfg))
        run["reference"] = ref
        run["ours"] = self.ours
        return compare.train_numbers(self.ours, ref)


def reference_shardings(jax, cfg):
    """On several chips the float32 reference (16 bytes a parameter) is
    spread over all of them, rows of each matrix apart; GSPMD does the
    rest.  One chip: no sharding."""
    devs = jax.devices()
    if len(devs) == 1 or not cfg.get("mesh"):
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(devs, ("ref",))
    return [NamedSharding(mesh, P("ref", None) if len(s) == 2
                          and s[0] % len(devs) == 0 else P())
            for _, s in reference.leaf_specs(cfg)]
