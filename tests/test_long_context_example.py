"""Smoke the long-context LM example end-to-end on the CPU mesh.

examples/long_context_lm.py is the sequence-parallel flagship (ring /
Ulysses CP + single-chip flash); until now only manual runs covered it.
Tiny shapes, few steps: the assertion is that each attention mode trains
(loss decreases) through the real example code path.
"""

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(attention: str, extra=()):
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": str(REPO),
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    out = subprocess.run(
        [sys.executable, str(REPO / "examples" / "long_context_lm.py"),
         "--attention", attention, "--seq-len", "64", "--batch-size", "2",
         "--d-model", "32", "--num-layers", "1", "--num-heads", "8",
         "--vocab", "32", "--steps", "6", *extra],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_cp_example_trains(attention):
    stdout = _run(attention)
    losses = [float(line.rsplit("loss ", 1)[1])
              for line in stdout.splitlines() if "loss " in line]
    assert len(losses) >= 2 and losses[-1] < losses[0], stdout


@pytest.mark.slow  # interpret-mode flash is the slow path on CPU
def test_flash_example_trains():
    stdout = _run("flash", extra=("--interpret",))
    assert "full-sequence on one chip" in stdout
    losses = [float(line.rsplit("loss ", 1)[1])
              for line in stdout.splitlines() if "loss " in line]
    assert len(losses) >= 2 and losses[-1] < losses[0], stdout
