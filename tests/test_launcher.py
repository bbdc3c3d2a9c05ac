"""Launcher (bfrun) tests: env export, --simulate, and the 2-process smoke.

The reference's launcher path (run/run.py:257-280, mpirun assembly) is
covered in this stack by env export + jax.distributed bootstrap; the
2-process test is the analog of the reference's smallest mpirun job —
two controller processes on localhost stitched into one size-4 device mesh,
with cross-process collectives riding gloo.
"""

import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bluefog_tpu import launcher

TESTS = Path(__file__).resolve().parent


def _scrubbed_env():
    env = os.environ.copy()
    # children pick their own platform/device forcing; drop the conftest's
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "BLUEFOG_TIMELINE",
              "BLUEFOG_CP_HOST", "BLUEFOG_CP_PORT"):
        env.pop(k, None)
    repo = str(TESTS.parent)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # every launch here targets the simulated CPU mesh; children never open
    # an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_parser_env_export(monkeypatch):
    """--timeline-filename/--verbose/--simulate export the documented env."""
    captured = {}

    def fake_exec(prog, args, env):
        captured.update(env=env, prog=prog, args=args)

    monkeypatch.setattr(os, "execvpe", fake_exec)
    launcher.main(["--timeline-filename", "/tmp/tl_", "--verbose",
                   "--simulate", "4", "--", "prog", "a1"])
    env = captured["env"]
    assert env["BLUEFOG_TIMELINE"] == "/tmp/tl_"
    assert env["BLUEFOG_LOG_LEVEL"] == "debug"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert captured["prog"] == "prog" and captured["args"] == ["prog", "a1"]


def test_multiproc_requires_coordinator():
    assert launcher.main(["-np", "2", "--", "prog"]) == 1
    assert launcher.main([]) == 1


def test_simulate_single_host():
    """bfrun --simulate N boots a usable N-device CPU job."""
    code = ("import jax, bluefog_tpu as bf; bf.init(); "
            "assert bf.size() == 4, bf.size(); "
            "assert bf.rank() == 0 and bf.local_rank() == 0; "
            "print('SIM_OK')")
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "--simulate", "4",
         "--", sys.executable, "-c", code],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SIM_OK" in out.stdout


@pytest.mark.slow
def test_simulate_16_ranks():
    """A deeper mesh than the 8-device fixture: log2(16)=4 Expo-2 shifts
    and a 4x4 machine-by-local hierarchy, through the bfrun path."""
    code = (
        "import numpy as np, jax, bluefog_tpu as bf; "
        "bf.init(local_size=4); "
        "assert bf.size() == 16 and bf.num_machines() == 4; "
        "x = bf.shard_rank_stacked(bf.mesh(), "
        "np.arange(16, dtype=np.float32).reshape(16, 1)); "
        "y = x\n"
        "for _ in range(40): y = bf.neighbor_allreduce(y)\n"
        "np.testing.assert_allclose(np.asarray(y), 7.5, atol=1e-3); "
        "h = bf.hierarchical_neighbor_allreduce(x); "
        "assert h.shape == (16, 1); "
        "print('RANKS16_OK')"
    )
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "--simulate", "16",
         "--", sys.executable, "-c", code],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "RANKS16_OK" in out.stdout


def _launch_n(child_script: str, env, nproc: int, timeout: int = 300,
              simulate: int = 2):
    """Run an nproc-process bfrun job of ``child_script`` (``simulate``
    devices each); return (procs, outs)."""
    port = _free_port()

    def cmd(i):
        return [sys.executable, "-m", "bluefog_tpu.launcher",
                "-np", str(nproc),
                "--coordinator", f"127.0.0.1:{port}", "--process-id", str(i),
                "--simulate", str(simulate),
                "--", sys.executable, str(TESTS / child_script)]

    procs = [subprocess.Popen(cmd(i), env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def _launch_pair(child_script: str, env):
    """Run a 2-process bfrun job of ``child_script``; return (procs, outs)."""
    # 420 s: the child imports torch for the live-frontend phase (~10 s
    # cold each) and slow CI boxes run several of these harnesses back to
    # back on one core
    return _launch_n(child_script, env, 2, timeout=420)


@pytest.mark.slow
def test_two_process_launch_smoke(tmp_path):
    """bfrun -np 2 --coordinator: the full multi-controller bootstrap.

    Asserts (in the children, tests/_launch_child.py): distributed init,
    size/rank/local_size/local_rank truthfulness, cross-process allreduce +
    ring neighbor_allreduce + hierarchical correctness, windows on global
    arrays, a coordinated orbax checkpoint round-trip, and control-plane
    fetch_add/barrier.
    """
    env = _scrubbed_env()
    env["SMOKE_CKPT_DIR"] = str(tmp_path / "ck")
    env["KERAS_BACKEND"] = "jax"  # opt into the keras frontend phase
    # fast heartbeat cadence so the coordinated-shutdown observation at the
    # end of the child doesn't wait out the default 5 s interval
    env["BLUEFOG_HEARTBEAT_INTERVAL"] = "0.3"
    procs, outs = _launch_pair("_launch_child.py", env)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert f"CHILD_OK {i}" in out
        # live-torch frontend across 2 controllers (skipped if no torch)
        assert (f"TORCH_MC_OK {i}" in out or f"TORCH_MC_SKIP {i}" in out)
        # keras frontend across 2 controllers (skipped if no keras)
        assert (f"KERAS_MC_OK {i}" in out or f"KERAS_MC_SKIP {i}" in out)


def test_parse_hosts_formats(tmp_path):
    from bluefog_tpu.launcher import parse_hosts
    assert parse_hosts("h1:2,h2:2") == [("h1", 2), ("h2", 2)]
    assert parse_hosts("h1, h2:3") == [("h1", 1), ("h2", 3)]
    hf = tmp_path / "hosts"
    hf.write_text("# cluster\nh1 slots=4\nh2:2\nh3\n\n")
    assert parse_hosts(hostfile=str(hf)) == [("h1", 4), ("h2", 2), ("h3", 1)]
    with pytest.raises(ValueError):
        parse_hosts("h1:0")


@pytest.mark.slow
def test_hostfile_fanout_two_processes():
    """VERDICT-r2 #3: ONE bfrun command drives the whole 2-process job —
    automatic process ids + coordinator, aggregated exit codes. Runs the
    same full multi-controller child as the manual smoke."""
    env = _scrubbed_env()
    env["BLUEFOG_HEARTBEAT_INTERVAL"] = "0.3"
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:2", "--simulate", "2",
         "--", sys.executable, str(TESTS / "_launch_child.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "CHILD_OK 0" in out.stdout and "CHILD_OK 1" in out.stdout


@pytest.mark.slow
def test_fanout_aggregates_failure():
    """A failing process makes the driver kill the job and report nonzero."""
    env = _scrubbed_env()
    code = ("import os, sys, time; "
            "sys.exit(7) if os.environ['JAX_PROCESS_ID'] == '1' "
            "else time.sleep(60)")
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:2", "--", sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 7, (out.returncode, out.stdout + out.stderr)
    # the survivor slept 60s; first-failure kill must not wait it out
    assert time.monotonic() - t0 < 45


def test_fanout_rejects_np_slot_mismatch():
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "-np", "3",
         "-H", "localhost:2", "--", "true"],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 1
    assert "does not match" in out.stderr


@pytest.mark.slow
def test_one_sided_windows_across_controllers():
    """VERDICT-r2 #1: window gossip is truly one-sided across controllers.

    Process 1 sleeps inside its step while process 0 completes win_put +
    win_update in bounded time (phase A); then a push-sum run with
    deliberately skewed controller speeds conserves total mass and p mass
    after a final drain (phase B). See tests/_onesided_child.py.
    """
    env = _scrubbed_env()
    procs, outs = _launch_pair("_onesided_child.py", env)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert f"CHILD_OK {i}" in out
    assert "PHASE_A_BOUNDED" in outs[0]
    assert "PHASE_B_UNCOUPLED" in outs[0]
    assert "PHASE_B_INVARIANT" in outs[0]


@pytest.mark.slow
def test_cross_controller_topo_check():
    """VERDICT-r2 #7: divergent dynamic edge sets across controllers raise
    (hash rendezvous over the control plane) instead of silently producing
    garbage ppermutes. See tests/_topocheck_child.py."""
    procs, outs = _launch_pair("_topocheck_child.py", _scrubbed_env())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert f"AGREED_OK {i}" in out
        assert f"DIVERGENT_RAISED {i}" in out
        assert f"CHILD_OK {i}" in out


@pytest.mark.slow
def test_peer_crash_detected():
    """Fault injection: a controller dies silently; the survivor's heartbeat
    monitor reports it as a DEAD peer (bf.dead_controllers()) instead of a
    coordinated shutdown, within the configured timeout. SURVEY §5.3: the
    reference only *warns* about missing ranks; this asserts the detection
    end-to-end across real processes."""
    env = _scrubbed_env()
    env["BLUEFOG_HEARTBEAT_INTERVAL"] = "0.2"
    env["BLUEFOG_HEARTBEAT_TIMEOUT"] = "1.5"
    procs, outs = _launch_pair("_fault_child.py", env)
    assert procs[1].returncode == 17, f"faulty process:\n{outs[1]}"
    assert procs[0].returncode == 0, f"survivor failed:\n{outs[0]}"
    assert "SURVIVOR_DETECTED 1" in outs[0]
    # VERDICT-r2 #8: the survivor's bounded synchronize raises within the
    # deadline, naming the dead peer, instead of hanging on the corpse
    assert "SURVIVOR_SYNC_RAISED 1" in outs[0]
    assert "HEALTHY 0" in outs[0] and "HEALTHY 1" in outs[1]


# ---------------------------------------------------------------------------
# 4-controller harness (VERDICT r3 #4; reference CI ran np=4, Makefile:1)
# ---------------------------------------------------------------------------

_QUAD_MARKERS = [
    "PHASE_A_OK", "PHASE_D_AGREED", "PHASE_D_DIVERGENT_RAISED",
    "PHASE_E_FENCE_OK", "CHILD_OK",
]


def _assert_quad_outputs(procs, outs):
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        for marker in _QUAD_MARKERS:
            assert f"{marker} {i}" in out, f"missing {marker} {i}:\n{out}"
    assert "PHASE_B_MASS" in outs[0]
    assert "PHASE_C_UNCOUPLED" in outs[0]
    assert "PHASE_C_INVARIANT" in outs[0]


@pytest.mark.slow
def test_four_controllers_windows_mutex_pushsum_topocheck():
    """4 controllers x 2 devices: hosted-window exact values with 4 owners,
    4-client mutex contention under strict mode, skewed push-sum mass
    conservation, 4-way topo-check divergence, and cross-controller
    win_fence. See tests/_quad_child.py."""
    procs, outs = _launch_n("_quad_child.py", _scrubbed_env(), 4,
                            timeout=420)
    _assert_quad_outputs(procs, outs)


@pytest.mark.slow
def test_eight_controller_high_degree_windows():
    """8 controllers x 1 device: hosted windows at high/ragged degrees
    (expo2 d=3, star d=7), chunked cross-controller deposits
    (BLUEFOG_MAX_WIN_SENT_LENGTH=64Ki), and the server mailbox byte cap
    engaging under real contention with exact mass accounting afterwards.
    See tests/_degree_child.py (VERDICT r4 #5)."""
    env = _scrubbed_env()
    env["BLUEFOG_CP_MAILBOX_MAX_MB"] = "1"  # phase D: cap engages fast
    procs, outs = _launch_n("_degree_child.py", env, 8, timeout=600,
                            simulate=1)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        for marker in ("PHASE_A_OK", "PHASE_B_OK", "PHASE_C_OK",
                       "CHILD_OK"):
            assert f"{marker} {i}" in out, f"missing {marker} {i}:\n{out}"
        if i != 0:
            assert f"PHASE_D_CAP {i}" in out, out
    assert "PHASE_D_MASS_OK" in outs[0]


@pytest.mark.slow
def test_four_process_fanout_one_command():
    """The same 4-controller job through ONE `bfrun -H localhost:4`
    command: fan-out assigns ids/coordinator and mints the control-plane
    secret for all four processes."""
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:4", "--simulate", "2",
         "--", sys.executable, str(TESTS / "_quad_child.py")],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    for i in range(4):
        assert f"CHILD_OK {i}" in out.stdout, out.stdout
    assert "PHASE_C_INVARIANT" in out.stdout


@pytest.mark.slow
def test_one_of_four_crash_detected_by_all_survivors():
    """Controller 3 of 4 dies silently; EVERY survivor's heartbeat monitor
    reports it dead and the bounded-wait synchronize raises naming it.
    See tests/_quad_fault_child.py."""
    env = _scrubbed_env()
    env["BLUEFOG_HEARTBEAT_INTERVAL"] = "0.2"
    env["BLUEFOG_HEARTBEAT_TIMEOUT"] = "1.5"
    procs, outs = _launch_n("_quad_fault_child.py", env, 4, timeout=300)
    assert procs[3].returncode == 17, f"faulty process:\n{outs[3]}"
    for i in range(3):
        assert procs[i].returncode == 0, f"survivor {i} failed:\n{outs[i]}"
        assert f"SURVIVOR_DETECTED {i}" in outs[i]
        assert f"SURVIVOR_SYNC_RAISED {i}" in outs[i]
    for i in range(4):
        assert f"HEALTHY {i}" in outs[i]


@pytest.mark.slow
def test_torch_frontend_example():
    """The live-torch-loop consensus example through bfrun --simulate 8."""
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "--simulate", "8",
         "--", sys.executable,
         str(TESTS.parent / "examples" / "torch_average_consensus.py")],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "TORCH CONSENSUS OK" in out.stdout


@pytest.mark.slow
def test_keras_frontend_example():
    """The keras data-parallel training example through bfrun --simulate 8."""
    env = _scrubbed_env()
    env["KERAS_BACKEND"] = "jax"
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher", "--simulate", "8",
         "--", sys.executable,
         str(TESTS.parent / "examples" / "keras_mnist.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "KERAS TRAIN OK" in out.stdout


# ---------------------------------------------------------------------------
# bfrun --elastic: incarnation-bumped respawn supervision (ISSUE r9)
# ---------------------------------------------------------------------------

def test_elastic_parser_forms():
    p = launcher.build_parser()
    a = p.parse_args(["--elastic", "--", "prog"])
    assert a.elastic == 3  # bare flag: default budget
    a = p.parse_args(["--elastic=5", "--min-world", "2", "--", "prog"])
    assert a.elastic == 5 and a.min_world == 2
    a = p.parse_args(["--", "prog"])
    assert a.elastic is None


def test_elastic_respawns_with_bumped_incarnation(tmp_path):
    """A rank that crashes is respawned with BLUEFOG_INCARNATION bumped;
    the job succeeds once the respawn does (the probe exits 0 only at
    incarnation >= 1) — the crash is absorbed, not propagated."""
    probe = tmp_path / "probe.py"
    probe.write_text(
        "import os, sys\n"
        "inc = int(os.environ.get('BLUEFOG_INCARNATION', '0'))\n"
        "print(f'probe pid={os.environ.get(\"JAX_PROCESS_ID\")} "
        "inc={inc}', flush=True)\n"
        "sys.exit(0 if inc >= 1 else 9)\n")
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:2", "--elastic=2", "--",
         sys.executable, str(probe)],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "respawning as incarnation 1" in out.stderr
    assert "inc=1" in out.stdout


def test_elastic_budget_exhaustion_is_terminal(tmp_path):
    """A rank that keeps crashing past its restart budget propagates a
    terminal failure (nonzero job exit), with the budget respected."""
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:1", "--elastic=1", "--",
         sys.executable, "-c", "import sys; sys.exit(9)"],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=180)
    assert out.returncode == 9, out.stdout + out.stderr
    assert "exhausted its restart budget" in out.stderr
    assert out.stderr.count("respawning") == 1  # budget=1: exactly one


def test_elastic_min_world_teardown(tmp_path):
    """With --min-world equal to the full world, losing one rank for good
    tears the whole job down instead of limping along under-replicated."""
    out = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.launcher",
         "-H", "localhost:2", "--elastic=0", "--min-world", "2", "--",
         sys.executable, "-c",
         "import os, sys, time\n"
         "if os.environ.get('JAX_PROCESS_ID') == '1':\n"
         "    sys.exit(9)\n"
         "time.sleep(60)\n"],
        env=_scrubbed_env(), capture_output=True, text=True, timeout=180)
    assert out.returncode == 9, out.stdout + out.stderr
    assert "dropped below --min-world" in out.stderr
