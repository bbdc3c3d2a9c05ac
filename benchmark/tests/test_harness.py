"""Toy sizes of both families through the harness's functions on the CPU, the
four-rank reference under the one-peer schedule, the checks that must fail, and
the result line. ``run.py`` itself refuses a CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from benchmark import harness, reference
from benchmark.manifest import Manifest

from conftest import ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_stages(manifest, name, seconds=0.3, trace_dir=None):
    """``harness.run_cell`` without what only a TPU has (peaks, memory_stats)."""
    cell = harness.Cell.load(manifest, name)
    devices = jax.devices()[:cell.entry["chips"]]
    run = harness.Run(cell, harness.Spans(),
                      peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    compiles = harness.CompileCounter()
    trainer = harness.set_up(cell, 7, devices, run)
    harness.window(trainer, seconds, run, compiles)
    if trace_dir:
        harness.traced_steps(trainer, run, trace_dir)
    trainer.close()
    return run, harness.check(trainer, run, devices)


@pytest.mark.parametrize("name", ["toy-lm-1", "toy-resnet-1", "toy-lm-onepeer-4"])
def test_toy_cell_end_to_end(toy_root, name, tmp_path):
    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, name, trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    # float32 on one backend: the optimizer and the plain reference agree closely
    assert verdict["steps"]["loss_rel_err"] < 1e-6 and verdict["steps"]["print_err"] < 1e-4
    assert verdict["forward"]["logits_rel_err"] < 1e-5
    assert run.attempted > 0 and run.failed == 0 and run.window_compiles == 0
    assert run.attempted == len(run.spans.seconds["host_step_s"])
    assert run.check_losses.shape == (harness.CHECK_STEPS, run.cell.entry["chips"])

    values = harness.end_to_end(run, 1.0, 2 ** 30)
    assert values["step_ms"] > 0 and values["peak_hbm_gib"] == 1.0
    assert values[run.cell.family.THROUGHPUT_METRIC] == pytest.approx(
        run.cell.family.units_per_step(run.cell.traffic["batch"]) / run.step_seconds)
    # a CPU trace has no device plane: the trace readers find nothing and are
    # left out, the span readers report
    layers = harness.per_layer(manifest, run)
    assert run.trace.chips == [] and set(layers) == {"host_step_ms", "init_s", "first_step_s"}
    harness.report(run, 1.0, verdict)


def test_one_peer_schedule_uses_both_shift_sets(toy_root):
    run, verdict = run_stages(Manifest(toy_root), "toy-lm-onepeer-4")
    assert verdict["ok"], verdict
    shifts = []
    for W in run.check_weights:
        assert np.allclose(W.sum(axis=0), 1.0) and np.allclose(np.diag(W), 0.5)
        (src,) = [s for s in range(4) if s != 0 and W[s, 0] > 0]
        shifts.append((0 - src) % 4)
    assert shifts == [1, 2, 1]  # rank 0 receives from 3, 2, 3: shifts 1 and 2 in turn


def _with_traffic(root, base, name, **changes):
    """A traffic mix and a cell added to a copy: new files, new entries."""
    with open(os.path.join(root, "benchmark", "traffic", base + ".json")) as f:
        traffic = json.load(f)
    traffic.update(changes)
    with open(os.path.join(root, "benchmark", "traffic", name + ".json"), "w") as f:
        json.dump(traffic, f)
    manifest = Manifest(root)
    manifest.doc["workloads"].append(
        {"name": name, "config": "toy-lm", "traffic": name, "chips": 4, "why": "test"})
    for metric in manifest.doc["end_to_end"] + manifest.doc["per_layer"]:
        if "toy-lm-1" in metric.get("workloads", []):
            metric["workloads"].append(name)
    return manifest


def test_check_fails_when_the_combine_is_skipped(toy_root):
    """The optimizer never communicates ("local", as examples/benchmark.py has
    it) while the schedule reports the one-peer weights: losses and parameters
    both leave the reference by far more than the tolerances."""
    manifest = _with_traffic(toy_root, "toy-s32-onepeer", "toy-skip",
                             optimizer_args={"num_steps_per_communication": 10 ** 9})
    _, verdict = run_stages(manifest, "toy-skip")
    assert not verdict["ok"] and verdict["forward"]["ok"]
    assert verdict["steps"]["loss_rel_err"] > 3 * reference.LOSS_RTOL
    assert verdict["steps"]["print_err"] > 2 * reference.PRINT_TOL


def test_check_fails_on_the_wrong_peer(toy_root, monkeypatch):
    """Mixing with another peer than the schedule says moves the parameters
    as far as not mixing at all."""
    manifest = Manifest(toy_root)
    cell = harness.Cell.load(manifest, "toy-lm-onepeer-4")
    honest = cell.schedule.Schedule.before_step

    def next_peer(self):
        """Every rank's one source replaced by the rank after it (or two after,
        past the receiver itself): the same weights on the wrong edges."""
        W = honest(self)
        wrong = np.diag(np.diag(W))
        for s, r in zip(*np.nonzero(W * (1 - np.eye(4)))):
            t = (s + 1) % 4 if (s + 1) % 4 != r else (s + 2) % 4
            wrong[t, r] = W[s, r]
        return wrong

    monkeypatch.setattr(cell.schedule.Schedule, "before_step", next_peer)
    monkeypatch.setattr(harness.Cell, "load", classmethod(lambda cls, m, n: cell))
    _, verdict = run_stages(manifest, "toy-lm-onepeer-4")
    assert not verdict["ok"] and verdict["steps"]["print_err"] > 2 * reference.PRINT_TOL


def test_check_fails_on_bfloat16_parameters():
    """Parameters that went through bfloat16 are caught by what they are, not
    by how far they moved: every element is one a bfloat16 holds exactly."""
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 33), jnp.float32)
    kept = jax.device_get(reference.fingerprint_stacked({"w": x}))["w"]
    rounded = jax.device_get(reference.fingerprint_stacked(
        {"w": x.astype(jnp.bfloat16).astype(jnp.float32)}))["w"]
    assert kept[0, 1] / x.size < 0.01 and rounded[0, 1] == x.size
    ref = {"losses": np.ones((1, 1)), "elements": x.size,
           "prints": [{"w": np.array([rounded[0, 0], 1.0, 10.0])}]}
    verdict = reference.compare_steps(np.ones((1, 1)), {"w": rounded}, ref, "float32")
    assert not verdict["ok"] and verdict["bf16_exact_share"] == 1.0
    assert reference.compare_steps(np.ones((1, 1)), {"w": rounded}, ref, "bfloat16")["ok"]


def test_a_non_finite_loss_is_a_failed_step(toy_root):
    manifest = _with_traffic(toy_root, "toy-s32", "toy-nan", schedule="static")
    manifest.doc["workloads"][-1]["chips"] = 1
    cell = harness.Cell.load(manifest, "toy-nan")
    cell.config["optimizer"] = {"name": "sgd", "args": {"learning_rate": float("inf")}}
    devices = jax.devices()[:1]
    run = harness.Run(cell, harness.Spans())
    trainer = harness.set_up(cell, 1, devices, run)
    harness.window(trainer, 0.1, run, harness.CompileCounter())
    assert run.failed == run.attempted > 0


def test_result_line_has_exactly_the_contract_keys(toy_root, monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(harness, "peaks", lambda device: {"bf16_flops": 1e12,
                                                          "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "peak_bytes", lambda devices: 5 * 2 ** 30)
    manifest = Manifest(toy_root)
    for trace_dir, section in ((None, "end_to_end"), (str(tmp_path), "per_layer")):
        result = harness.run_cell(manifest, "toy-lm-1", 3, 0.2, trace_dir,
                                  jax.devices()[:1], 0.0)
        line = json.loads(json.dumps(result))
        assert set(line) == CONTRACT_KEYS  # no breakdown without a device plane
        assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
        assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
        declared = {m["name"]: m["unit"] for m in manifest.metrics(section, "toy-lm-1")}
        assert line["metrics"] and set(line["metrics"]) <= set(declared)
        for name, metric in line["metrics"].items():
            assert set(metric) == {"value", "unit"} and metric["unit"] == declared[name]
            assert isinstance(metric["value"], float)
        if section == "end_to_end":
            assert set(line["metrics"]) == {"step_ms", "tokens_per_s_per_chip",
                                            "peak_hbm_gib", "setup_s"}
    assert "reference:" in capsys.readouterr().out


def test_run_py_refuses_a_cpu_and_an_unknown_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for workload in ("pythia-s8192-1chip", "no-such-cell"):
        done = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", workload,
             "--seed", "0", "--seconds", "1", "--trace", "0"],
            env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode != 0 and done.stdout.strip() == "", (done.stdout, done.stderr)
    assert "TPU" in done.stderr or "no workloads entry" in done.stderr
