"""Faults planted in the program's latent-attention and routed layers come
out not ``correct``: through the benchmark's own rehearsal of the JoyAI-LLM
Flash cell at its tiny size (``run.py`` -> driver ->
``compare.train_numbers`` -> the cell's ``tiny.limits``), the fault planted
under it (``benchmark/chip/tests/faults_joyai_llm_flash.py``).  A file of
its own, so that the rehearsals run beside ``test_joyai_llm_flash.py`` and
not after it.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
NEVER = 1e30        # compare.NEVER: what a missing reading counts as


def _rehearse(env, *script_and_fault):
    proc = subprocess.run(
        [sys.executable, *script_and_fault, "--workload",
         "train_joyai_p5_b1s8192", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_correct(rehearsal_env):
    doc = _rehearse(rehearsal_env, os.path.join(CHIP, "run.py"))
    assert doc["correct"] is True and doc["failed"] == 0


@pytest.mark.parametrize("fault", ["no_query_norm", "no_kv_norm",
                                   "key_a_head", "ninth_expert",
                                   "not_normalised", "scale_nope"])
def test_a_planted_fault_comes_out_not_correct(fault, rehearsal_env):
    doc = _rehearse(rehearsal_env, os.path.join(
        CHIP, "tests", "faults_joyai_llm_flash.py"), fault)
    assert doc["correct"] is False
    assert [k for k, (v, lim) in doc["check"].items() if v > lim]
    assert all(v < NEVER for v, _ in doc["check"].values())
