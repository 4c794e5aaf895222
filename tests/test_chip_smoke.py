"""chip_smoke.py rehearsed on CPU: the launcher's control flow, the HTTP
round trip and the exit-non-zero-on-a-failed-phase rule.  What the script
proves, it proves on the chip; here only its own plumbing is checked."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(env, *flags):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_runs_every_phase_and_prints_no_result_line(one_device_env):
    r = _smoke(one_device_env, "--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = r.stdout
    for line in ("train: done", "train again: done", "serve export: done",
                 "8/8 requests", "server exited 0 after SIGTERM",
                 "serve check: done", "all phases passed"):
        assert line in out, line
    assert "persistent-cache hits 0 then" in out
    # a rehearsal is never mistaken for a chip run
    assert '"ok"' not in out
    assert out.strip().splitlines()[-1].startswith("[chip_smoke] rehearsal")


def test_without_the_switch_it_fails_off_the_chip(one_device_env):
    r = _smoke(one_device_env)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    # the first failed phase ends the run: nothing after train started
    assert "FAILED train: child exited" in r.stdout
    assert "serve" not in r.stdout and '"ok"' not in r.stdout
