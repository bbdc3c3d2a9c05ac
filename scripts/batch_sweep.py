"""Batch-size sweep for the ResNet-50 benchmark step (real-chip probe).

Imports bench.setup() so the probe measures EXACTLY the benchmarked step
(same model, optimizer, data placement and completion barrier),
printing img/s per batch size. Used to pick bench.py's BATCH_PER_CHIP
(PERF.md: B=128 adopted in round 2).

Run from the repo root: ``python scripts/batch_sweep.py [batch ...]``. Every
point runs in this one process (the chip belongs to one process at a time).
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Flight dumps from a bench run land in a tempdir instead of littering
# the CWD (conftest's default for the test suite); an explicit
# BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))

import jax  # noqa: E402

import bluefog_tpu as bf  # noqa: E402
import bench  # noqa: E402

WARMUP = 5
STEPS = 30


def measure(batch: int) -> float:
    # bench.setup() re-inits in place; no per-point shutdown — announcing
    # coordinated shutdown between points would latch every peer's
    # shutdown_requested() in a multi-controller job (see state.py re-init
    # note).
    opt, state, data = bench.setup(batch)
    for _ in range(WARMUP):
        state, metrics = opt.step(state, data)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, metrics = opt.step(state, data)
    jax.block_until_ready(metrics["loss"])
    return batch * STEPS / (time.perf_counter() - t0)


if __name__ == "__main__":
    batches = [int(b) for b in sys.argv[1:]] or [96, 128, 192, 256]
    try:
        for b in batches:
            rate = measure(b)
            print(f"B={b:4d}: {rate:8.1f} img/s/chip  "
                  f"({1000*b/rate:.1f} ms/step)", flush=True)
    finally:
        bf.shutdown()
