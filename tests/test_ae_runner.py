"""AE runner CI leg (reference scripts/osdi22ae/*.sh +
tests/python_interface_test.sh): the one-command runner trains a zoo model
in both AE modes on the virtual mesh, prints machine-readable results, and
enforces the MNIST accuracy gate."""

import os
import sys

# scripts/ lies beside tests/, and only tests/ is on pytest's path
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def test_ae_runner_mlp_both_modes():
    from scripts.run_ae import run_one

    dp = run_one("mlp", "dp", batch=64, epochs=2)
    assert dp["samples_per_sec"] > 0
    assert dp["accuracy"] >= 0.90  # python_interface_test.sh's gate
    assert dp["mesh"]["data"] == 8  # all 8 virtual devices, pure DP

    unity = run_one("mlp", "unity", batch=64, epochs=2)
    n = 1
    for v in unity["mesh"].values():
        n *= v
    assert n == 8  # the searched factorization still uses every device
    assert unity["accuracy"] >= 0.90


def test_ae_runner_rejects_unknown_model():
    import subprocess

    p = subprocess.run(
        [sys.executable, "scripts/run_ae.py", "--models", "nope"],
        capture_output=True, text=True, cwd=ROOT)
    assert p.returncode != 0
    assert "unknown model" in p.stderr
