"""Test harness: 8 virtual CPU devices standing in for an 8-chip mesh.

The reference runs every test as a real multiprocess job under mpirun
(Makefile:9, test strategy in SURVEY.md §4). The TPU-native analog is an
8-device CPU-simulated mesh via --xla_force_host_platform_device_count:
the same SPMD programs, shardings, and collectives that run on a pod,
executed by the CPU backend. Must configure the env BEFORE jax is imported.
"""

import os

# 16 forced devices: suites mostly slice 8 of them, but the odd/non-power-
# of-2 world-size sweep (test_odd_world_sizes.py) also needs 12 — the
# reference ran at arbitrary np (its Makefile used np=2/np=4), so neighbor
# math must not silently assume power-of-2 sizes. Any caller-provided
# force flag (e.g. the Makefile's =8) is stripped so 16 actually wins.
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=16"])
# The suite is a CPU suite on any machine: an accelerator belongs to one
# process at a time, and this process and its many children would all ask
# for it. chip_smoke.py is what runs on the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

# Flight-recorder dumps default to the cwd; tests that deliberately stall
# handles or crash optimizer steps would litter the repo root, so the
# suite's automatic dumps land in a throwaway dir instead (tests that
# assert on dump files monkeypatch their own BLUEFOG_FLIGHT_DIR).
if "BLUEFOG_FLIGHT_DIR" not in os.environ:
    import tempfile

    os.environ["BLUEFOG_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="bf_flight_tests_")

import jax  # noqa: E402
import pytest  # noqa: E402

# Exact-value assertions: keep MXU matmuls in full f32 (the default TPU
# precision rounds operands to bf16, which breaks 1e-5-level oracles).
jax.config.update("jax_default_matmul_precision", "highest")

import bluefog_tpu as bf  # noqa: E402


def cpu_devices(n=8):
    devs = jax.devices("cpu")
    assert len(devs) >= n, f"need {n} cpu devices, got {len(devs)}"
    return devs[:n]


# The BLUEFOG_FLIGHT_DIR redirect above keeps this process's dumps out of
# the tree, but subprocess-spawning tests that scrub or rebuild their env
# could still let a crashing child dump into its cwd — the repo root. Any
# new bf_flight_*.json at the root after the run is a harness regression
# (and `make check`'s litter analyzer would flag the file as debris), so
# fail loudly here with the responsible pattern named.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _root_flight_dumps():
    import glob

    return set(glob.glob(os.path.join(_REPO_ROOT, "bf_flight_*.json")))


_flight_dumps_before = _root_flight_dumps()


def pytest_sessionfinish(session, exitstatus):
    leaked = _root_flight_dumps() - _flight_dumps_before
    if leaked:
        raise pytest.UsageError(
            "test run littered the repository root with flight-recorder "
            f"dump(s): {sorted(os.path.basename(p) for p in leaked)} — "
            "point the responsible test's BLUEFOG_FLIGHT_DIR at a temp "
            "dir (see conftest.py)")


@pytest.fixture()
def bf8():
    """bluefog_tpu initialized over 8 virtual devices, default Expo-2 topo."""
    bf.init(devices=cpu_devices(8), local_size=4)
    yield bf
    bf.shutdown()
