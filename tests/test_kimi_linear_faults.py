"""Faults planted in the program's delta-rule, latent-attention and routed
layers come out not ``correct``: through the benchmark's own rehearsal of
the Kimi Linear cell at its tiny size (``run.py`` -> driver ->
``compare.train_numbers`` -> the cell's ``tiny.limits``), the fault planted
under it (``benchmark/chip/tests/faults_kimi_linear.py``).  A file of its
own, so that the rehearsals run beside ``test_kimi_linear.py`` and not
after it.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
NEVER = 1e30        # compare.NEVER: what a missing reading counts as


@pytest.mark.parametrize("fault", ["no_carry", "decay_per_head", "no_beta",
                                   "ninth_expert", "not_normalised"])
def test_a_planted_fault_comes_out_not_correct(fault, rehearsal_env):
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tests", "faults_kimi_linear.py"),
         fault, "--workload", "train_kimilinear_p5_b1s4096", "--seed", "11",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=rehearsal_env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < NEVER for v, _ in doc["check"].values())
