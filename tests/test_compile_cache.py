"""Framework-level persistent compile cache (MXNET_COMPILE_CACHE).

The cache is a framework default — a second process importing mxnet_tpu
gets cache HITS for executables a first process compiled.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUN = r"""
import jax
from jax._src import monitoring
hits = []
monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if "compilation_cache" in name and "hit" in name else None)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd
a = nd.array(np.ones((96, 96), np.float32))
b = nd.array(np.ones((96, 96), np.float32))
out = nd.dot(a, b)
out.wait_to_read()
print("HITS=%d" % len(hits))
"""


def _run(tmp_cache, extra_env=None):
    env = dict(os.environ)
    env["MXNET_COMPILE_CACHE_DIR"] = tmp_cache
    env["MXNET_COMPILE_CACHE_MIN_SECS"] = "0"
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", _RUN], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=240)


def test_cache_populates_and_hits_across_processes(tmp_path):
    cache = str(tmp_path / "xla_cache")
    r1 = _run(cache)
    assert r1.returncode == 0, r1.stderr
    entries = os.listdir(cache)
    assert entries, "first process wrote no cache entries"
    assert "HITS=0" in r1.stdout  # cold

    r2 = _run(cache)
    assert r2.returncode == 0, r2.stderr
    hits = int(r2.stdout.strip().rsplit("HITS=", 1)[1])
    assert hits >= 1, "second process did not hit the persistent cache:\n" \
        + r2.stdout + r2.stderr
    # no new entries were written for the same executable
    assert set(os.listdir(cache)) == set(entries)


def test_cache_disable_env(tmp_path):
    cache = str(tmp_path / "xla_cache_off")
    r = _run(cache, {"MXNET_COMPILE_CACHE": "0"})
    assert r.returncode == 0, r.stderr
    assert not os.path.exists(cache) or not os.listdir(cache)


def test_path_valued_env_picks_dir_and_forces_on(tmp_path):
    # ISSUE 7: MXNET_COMPILE_CACHE=<path> is shorthand for =1 plus
    # _DIR=<path> — and it opts even a pure-CPU process in
    cache = str(tmp_path / "by_value")
    env = dict(os.environ)
    env.pop("MXNET_COMPILE_CACHE_DIR", None)
    env["MXNET_COMPILE_CACHE"] = cache
    env["MXNET_COMPILE_CACHE_MIN_SECS"] = "0"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _RUN], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    assert os.path.isdir(cache) and os.listdir(cache), \
        "path-valued MXNET_COMPILE_CACHE did not populate its directory"


def test_budget_eviction_is_pair_aware(tmp_path, monkeypatch):
    """LRU eviction removes whole <key>-cache/<key>-atime pairs oldest
    first, never orphaning an atime file, and counts what it evicted."""
    import time as _time

    from mxnet_tpu import compile_cache as cc

    d = str(tmp_path / "budget")
    os.makedirs(d)
    now = _time.time()
    for key, age, size in (("old", 500, 600 * 1024),
                           ("mid", 250, 600 * 1024),
                           ("new", 0, 600 * 1024)):
        with open(os.path.join(d, key + "-cache"), "wb") as f:
            f.write(b"\0" * size)
        with open(os.path.join(d, key + "-atime"), "wb") as f:
            f.write(b"\0")
        for suffix in ("-cache", "-atime"):
            os.utime(os.path.join(d, key + suffix),
                     (now - age, now - age))
    monkeypatch.setitem(cc._state, "dir", d)
    before = cc.stats()["evictions"]
    # 1 MB budget: three 600K entries -> the two oldest pairs must go
    evicted = cc.enforce_budget(budget_mb=1)
    assert evicted == 2
    left = sorted(os.listdir(d))
    assert left == ["new-atime", "new-cache"], left
    assert cc.stats()["evictions"] - before == 2
    # under budget now: another pass is a no-op
    assert cc.enforce_budget(budget_mb=1) == 0


_WARM_TRAIN = r"""
import json, os
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, autograd

net = gluon.nn.HybridSequential()
net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
net.initialize(mx.init.Xavier())
net.hybridize()
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.05})
for step in range(4):
    x = nd.array(np.ones((8, 8), np.float32) * (step + 1))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(8)
a = nd.ones((8, 8))
for i in range(10):
    a = (a + 1.0) if i % 2 else (a * 0.5)
a.wait_to_read()
loss.asnumpy()

from mxnet_tpu.telemetry import metrics
snap = metrics.snapshot()

def total(name):
    fam = snap.get(name)
    if not fam:
        return 0.0
    return sum(s.get("value", s.get("sum", 0.0)) for s in fam["series"])

def hsum(name):
    fam = snap.get(name)
    if not fam:
        return 0.0
    return sum(s.get("sum", 0.0) for s in fam["series"])

print("RESULT=%s" % json.dumps({
    "compiles": total("mxnet_compiles_total"),
    "compile_seconds": hsum("mxnet_compile_seconds"),
    "cc_hits": total("mxnet_compile_cache_hits_total"),
    "seg_disk_hits": total("mxnet_engine_segment_cache_disk_hits_total"),
}))
"""


def test_warm_process_records_disk_hits_not_compiles(tmp_path):
    """Satellite 6: a warm start must show up as cache hits, NOT as
    compiles — so it neither pollutes mxnet_compile_seconds nor trips
    MXNET_RETRACE_WARN_THRESHOLD."""
    import json

    cache = str(tmp_path / "warm_cache")

    def run():
        env = dict(os.environ)
        env.update({"MXNET_COMPILE_CACHE": "1",
                    "MXNET_COMPILE_CACHE_DIR": cache,
                    "MXNET_COMPILE_CACHE_MIN_SECS": "0",
                    "JAX_PLATFORMS": "cpu"})
        r = subprocess.run([sys.executable, "-c", _WARM_TRAIN], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        return json.loads(r.stdout.rsplit("RESULT=", 1)[1])

    cold = run()
    warm = run()
    assert cold["compiles"] > 0, cold
    assert warm["cc_hits"] > 0, warm
    assert warm["compiles"] == 0, \
        "warm start mis-counted as real compiles: %s" % warm
    assert warm["compile_seconds"] == 0.0, warm
    if os.environ.get("MXNET_ENGINE_TYPE", "") != "NaiveEngine":
        # BulkEngine: the imperative chain's segment came from disk and
        # was counted on its own counter, not as a retrace
        assert warm["seg_disk_hits"] > 0, warm


_CHAIN = r"""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, autograd

record = %r
x = nd.array(np.ones((32, 32), np.float32))
if record:
    x.attach_grad()
    with autograd.record():
        a = x
        for i in range(8):
            a = (a + 1.0) if i %% 2 else (a * 0.5)
        loss = a.sum()
    loss.backward()
    x.grad.wait_to_read()
else:
    a = x
    for i in range(8):
        a = (a + 1.0) if i %% 2 else (a * 0.5)
    a.wait_to_read()
print("DONE")
"""


def test_exact_and_fused_artifacts_never_cross_hit(tmp_path):
    """The exact taped path (no fusion across ops) and the default fused
    segment path must key DIFFERENT disk entries: an exact request served
    a fused artifact would silently change gradient-replay semantics."""
    cache = str(tmp_path / "o_cache")

    def run(record):
        env = dict(os.environ)
        env.update({"MXNET_COMPILE_CACHE": "1",
                    "MXNET_COMPILE_CACHE_DIR": cache,
                    "MXNET_COMPILE_CACHE_MIN_SECS": "0",
                    "JAX_PLATFORMS": "cpu"})
        r = subprocess.run([sys.executable, "-c", _CHAIN % record],
                           cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr

    run(record=False)                      # fused segment entries
    after_fused = set(os.listdir(cache))
    assert after_fused
    run(record=True)                       # recorded chain: exact/backward
    after_exact = set(os.listdir(cache))
    assert after_exact - after_fused, \
        "recorded (exact) chain wrote no new entries — it was served " \
        "the fused artifact"
    run(record=True)                       # same recorded chain again
    assert set(os.listdir(cache)) == after_exact, \
        "third process re-wrote entries instead of hitting the cache"


# -- the one cache rule (ISSUE 22) ---------------------------------------

def _configure_recording(monkeypatch, env):
    """configure(env) with jax.config.update recorded, not applied."""
    import jax
    from mxnet_tpu import compile_cache as cc

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(cc.os, "makedirs", lambda *a, **k: None)
    monkeypatch.setattr(cc, "_state", dict(cc._state, atexit=True))
    return cc.configure(env), cc.cache_dir(), updates


def test_jax_cache_dir_env_is_left_to_jax(monkeypatch, tmp_path):
    want = str(tmp_path / "from_jax")
    on, where, updates = _configure_recording(monkeypatch, {
        "JAX_COMPILATION_CACHE_DIR": want,
        "MXNET_COMPILE_CACHE_DIR": str(tmp_path / "ignored")})
    assert on and where == want   # recorded for counters and budget
    assert "jax_compilation_cache_dir" not in updates
    # MXNET_COMPILE_CACHE=0 keeps its meaning below that rule
    on, _, updates = _configure_recording(monkeypatch, {
        "JAX_COMPILATION_CACHE_DIR": want, "MXNET_COMPILE_CACHE": "0"})
    assert not on and updates == {"jax_enable_compilation_cache": False}


def test_default_cache_dir_is_the_checkout(monkeypatch):
    on, where, updates = _configure_recording(
        monkeypatch, {"MXNET_COMPILE_CACHE": "1"})
    assert on and where == os.path.join(REPO, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == where
    # a CPU process (this one) stays off unless it opts in
    on, _, updates = _configure_recording(monkeypatch, {})
    assert not on and not updates


def test_unset_platform_is_not_read_as_cpu():
    # a stock TPU host sets no JAX_PLATFORMS: the cache must come on, at
    # import, without initialising a backend to find out
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
           and not k.startswith("MXNET_COMPILE_CACHE")}
    code = ("import jax, mxnet_tpu\n"
            "from mxnet_tpu import compile_cache as cc\n"
            "from jax._src import xla_bridge as xb\n"
            "print(cc.enabled(), cc.cache_dir(),"
            " jax.config.jax_compilation_cache_dir,"
            " xb.backends_are_initialized())")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    where = os.path.join(REPO, ".jax_cache")
    assert r.stdout.split() == ["True", where, where, "False"]
