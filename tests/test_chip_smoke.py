"""chip_smoke.py on the CPU: it refuses to report without a TPU, and its
phases run end to end at small sizes with Pallas in interpret mode (where
the kernels are fp32 and bit-exact, so every comparison is exact)."""
import dataclasses
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs
from repro import configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_lm():
    return dataclasses.replace(configs.get_smoke("stablelm-3b"), n_layers=2)


LM_KW = dict(prompt_lens=(4, 12), max_new=3, max_len=32)


def test_no_tpu_no_result(capsys):
    assert cs.main([]) == 1
    assert capsys.readouterr().out == ""


def test_alone_without_the_repo_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_ecg_phase(capsys):
    found = cs.ecg_phase(batches=(1, 4))
    for b in (1, 4):
        assert found[b]["dispatches"] == 1
        assert found[b]["max_code_diff"] == 0.0
        assert found[b]["class_agree"] == 1.0
    flt = found["float_forward"]
    assert flt["mean_code_diff"] > cs.ECG_MEAN_CODE_TOL
    assert flt["max_code_diff"] > cs.ECG_MAX_CODE_TOL
    assert flt["class_agree"] < cs.ECG_MIN_CLASS_AGREE
    assert capsys.readouterr().out.count("ecg: B=") == 2


def test_lm_phase(tiny_lm):
    found = cs.lm_phase(tiny_lm, **LM_KW)
    assert found["prefill_rel_l2"] == 0.0
    assert found["new_tokens"] == 4 * LM_KW["max_new"]
    assert found["float_forward_rel_l2"] > cs.LM_REL_TOL


def test_sharded_lm_phase(tiny_lm):
    found = cs.sharded_lm_phase(tiny_lm, n_devices=len(jax.devices()),
                                **LM_KW)
    assert found["prefill_rel_l2"] == 0.0
    assert found["same_tokens_share"] == 1.0

