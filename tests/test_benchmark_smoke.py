"""Benchmark-harness smoke on the 8-device CPU mesh.

Runs examples/benchmark.py end to end (mlp model, tiny batch) through the
``bfrun --simulate`` launch path, so collective-overhead regressions in the
fused optimizer step show up in CI rather than only on hardware. The analog
of running the reference's examples/pytorch_benchmark.py under mpirun.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _scrubbed_env():
    env = os.environ.copy()
    # BLUEFOG_CP_FAULT: a fault spec leaked from the operator's shell must
    # never poison a benchmark run — throughput under injected connection
    # drops is not a benchmark (asserted below)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "BLUEFOG_TIMELINE",
              "BLUEFOG_CP_FAULT"):
        env.pop(k, None)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    # CI smoke runs on the simulated CPU mesh; children never open an
    # accelerator
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_fault_injection_disarmed_in_benchmark_env(monkeypatch):
    """Fault injection stays OFF in benchmark runs by default: the bench
    harness env scrubs any inherited BLUEFOG_CP_FAULT spec, and the native
    injector in THIS process is disarmed unless a test armed it."""
    monkeypatch.setenv("BLUEFOG_CP_FAULT", "drop_after=5,seed=1")
    env = _scrubbed_env()
    assert "BLUEFOG_CP_FAULT" not in env
    from bluefog_tpu.runtime import native

    if native.load() is not None:
        native.fault_disarm()
        assert native.fault_stats() == {"ops": 0, "drops": 0}


@pytest.mark.slow
@pytest.mark.parametrize("dist_opt", ["neighbor_allreduce", "win_put"])
def test_benchmark_mlp_smoke(dist_opt):
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "--simulate", "8", "--",
         sys.executable, str(REPO / "examples" / "benchmark.py"),
         "--model", "mlp", "--batch-size", "8",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2", "--dist-optimizer", dist_opt],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    # the harness prints "Total img/sec on N chip(s): <mean> +-<ci>" like
    # the reference (:118-124); a parseable positive number means a full run
    m = re.search(r"Total img/sec on \d+ chip\(s\):\s*([0-9.]+)", out.stdout)
    assert m, f"no throughput line in:\n{out.stdout}"
    assert float(m.group(1)) > 0


@pytest.mark.slow
def test_win_microbench_quick():
    """scripts/win_microbench.py --quick: the 4-controller hosted-plane
    drain/get pipeline (put, accumulate, pipelined update drain, win_get,
    fold-vs-stream probe) runs end to end at tiny sizes — the new drain
    paths are CI-exercised, not hand-run only. The r7 raw-ceiling probe
    rows (raw put/get at the full striped pool AND pinned to one stream)
    must be present with positive throughput, so a striped-transport
    regression surfaces in-tree rather than only in manual PERF.md runs."""
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "win_microbench.py"),
         "--quick"],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "WIN_MICROBENCH_OK" in out.stdout, out.stdout + out.stderr
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    ops = {r["op"] for r in rows}
    assert {"win_put", "win_update", "win_get", "drain_stream",
            "drain_fold", "raw_put_bytes", "raw_get_bytes",
            "raw_put_bytes_1s", "raw_get_bytes_1s"} <= ops, out.stdout
    for r in rows:
        if r["op"].startswith("raw_"):
            assert r["mbps"] and r["mbps"] > 0, r


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["win_put", "sharded_allreduce"])
def test_opt_matrix_bench_quick(mode):
    """scripts/opt_matrix_bench.py --quick on the two modes the r6
    acceptance compares: a parseable throughput JSON line per mode."""
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "opt_matrix_bench.py"),
         "--quick", "--modes", mode],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["mode"] == mode and res.get("img_per_sec", 0) > 0, res
