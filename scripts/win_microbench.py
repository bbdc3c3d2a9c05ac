"""Hosted-window data-plane microbenchmark (VERDICT r4 #1).

Launches 4 controller processes (1 simulated CPU device each) through the
real ``bfrun`` fan-out with control-plane authentication ON, runs
scripts/_win_microbench_child.py in each, and relays controller 0's JSON
result lines. Measures per-op latency and MB/s for win_put /
win_accumulate / win_update / win_get on ResNet-sized (102 MB), small
(1 MB), and bf16 windows, plus the raw put_bytes/get_bytes transport
ceiling the numbers should be judged against — measured BOTH at the full
striped connection pool (``raw_put_bytes``/``raw_get_bytes``, the default
client) and pinned to one stream (``raw_put_bytes_1s``/``raw_get_bytes_1s``),
so the striping win and either regime's regressions are visible in the
same run. Every timed series is preceded by explicit warmup rounds
(excluded from the medians): the first ops of a kind pay allocator +
page-cache + pool-connect costs that otherwise masquerade as transport
time (r6's win_put run-to-run swing).

Also prints a fold-vs-stream isolation line per config: the same drained
bytes timed as (a) the socket take alone and (b) the numpy fold alone, so
the drain pipeline's overlap headroom is a measured number, not a guess.

Usage:  python scripts/win_microbench.py [--quick] [--codec LIST]
                                         [--sharded LIST]
  --quick: tiny windows, 2 rounds, 1 warmup — seconds instead of minutes;
           exercised by the CI smoke test (tests/test_benchmark_smoke.py),
           numbers are NOT meaningful for PERF.md.
  --codec: comma-separated wire codecs (e.g. ``int8,fp8,topk:0.01``) to
           additionally sweep on the headline config's win_put/win_update
           series (docs/compression.md). ``mbps`` in codec rows is the
           EFFECTIVE rate — app-level payload bytes over wall time — so
           the compressed-vs-raw comparison reads off directly (the int8
           ``>= 2x win_update`` acceptance bar, PERF.md r15).
  --sharded: comma-separated shard factors (e.g. ``2,4``): replays
           win_put on shard-row-sized windows and counter-delta-verifies
           (``win.deposit_bytes``) that per-op wire bytes drop by
           ``>= 0.9*S`` — the sharded-window acceptance bar
           (docs/sharded_windows.md); the child ASSERTS it.
"""

import argparse
import os
import secrets
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Flight dumps from a bench run (deliberate fault probes included) land in
# a tempdir instead of littering the CWD, the same default the test
# suite's conftest applies; an explicit BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--codec", type=str, default=None,
                    help="comma-separated wire codecs to sweep "
                         "(int8,fp8,topk:<frac>) on the headline config")
    ap.add_argument("--sharded", type=str, default=None,
                    help="comma-separated shard factors (e.g. 2,4) to "
                         "sweep: shard-row windows replay win_put and the "
                         "per-op wire bytes are counter-delta verified to "
                         "drop ≥ 0.9*S (docs/sharded_windows.md)")
    args = ap.parse_args()
    env = os.environ.copy()
    if args.quick:
        env["BLUEFOG_WB_QUICK"] = "1"
    if args.codec:
        env["BLUEFOG_WB_CODECS"] = args.codec
    if args.sharded:
        env["BLUEFOG_WB_SHARD"] = args.sharded
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "BLUEFOG_TIMELINE",
              "BLUEFOG_CP_HOST", "BLUEFOG_CP_PORT", "BLUEFOG_WIN_CODEC"):
        env.pop(k, None)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    # host-plane bench on a simulated mesh: the controllers never open an
    # accelerator (it belongs to one process at a time)
    env["JAX_PLATFORMS"] = "cpu"
    env["BLUEFOG_CP_SECRET"] = secrets.token_hex(16)  # auth ON (VERDICT r4)
    port = free_port()
    child = str(REPO / "scripts" / "_win_microbench_child.py")

    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "bluefog_tpu.launcher", "-np", "4",
             "--coordinator", f"127.0.0.1:{port}", "--process-id", str(i),
             "--simulate", "1", "--", sys.executable, child],
            env=env,
            stdout=None if i == 0 else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if i == 0 else subprocess.DEVNULL)
        for i in range(4)
    ]
    rc = 0
    for p in procs:
        p.wait(timeout=1800)
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
