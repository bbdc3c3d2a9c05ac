"""Benchmark the full distributed-optimizer matrix (VERDICT r4 #3).

Runs examples/benchmark.py across every ``--dist-optimizer`` mode on the
8-device CPU-simulated mesh (relative step cost, same tiny MLP model), the
comparison the reference published as its own benchmark harness
(examples/pytorch_benchmark.py:52-60). Results go to stdout as one JSON
line per mode; PERF.md records the table.

Usage:  python scripts/opt_matrix_bench.py [--chip] [--quick] [--modes ...]
  --chip:  additionally run the single-chip-meaningful modes on the real
           TPU (resnet50, batch 64) — at n=1 collectives are degenerate, so
           this isolates per-mode dispatch overhead on the real device.
  --quick: 1 warmup / 2 batches / 1 iter per mode — the CI smoke setting
           (tests/test_benchmark_smoke.py); exercises every mode's full
           launch+step path in seconds, numbers NOT meaningful for PERF.md.
  --hybrid: sweep the window-plane policy x overlap matrix (ISSUE r13) on
           the single-host multi-controller harness (world-1 control plane,
           forced-hosted window, static exp2 topology — every edge
           compiled-eligible under `auto`): `hosted` is the mailbox-plane
           baseline, `auto` the per-edge hybrid plane, `auto`+overlap the
           double-buffered residual. Auto rows report `speedup_vs_hosted`;
           the acceptance bar is >= 1.5x. Then replays the plane
           equivalence suite (tests/test_win_planes.py) so the speedup and
           the bit-exactness/mass-conservation proofs come from one run.
  --sharded: sweep BLUEFOG_WIN_SHARD x BLUEFOG_WIN_CODEC (SHARD_SWEEP)
           over the win_put optimizer on the world-1 hosted harness with
           the LM-shaped model (--model lm: embedding + attention-block +
           norm leaves), so the partition rules are exercised on
           realistic shapes. NOTE the world-1 harness has no
           cross-controller wire, so `speedup_vs_s1` < 1 isolates the
           HOST-SIDE rotation cost (pack/scatter + smaller-buffer op
           overhead); the wire win itself is win_microbench --sharded's
           counter-delta-verified 4-process measurement
           (docs/sharded_windows.md).
  --codec: sweep BLUEFOG_WIN_CODEC (none, int8, fp8, topk:0.01) over the
           win_put optimizer on the same world-1 hosted-window harness
           (plane pinned to `hosted`). NOTE the world-1 harness has no
           cross-controller wire — every deposit folds locally — so this
           sweep isolates the HOST-SIDE codec cost (encode + decode per
           gossip step, `speedup_vs_none` < 1 by construction); the wire
           win itself is win_microbench --codec's 4-process measurement
           (docs/compression.md).
"""

import argparse
import json
import os
import re
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

MODES = [
    "neighbor_allreduce", "allreduce", "gradient_allreduce",
    "hierarchical_neighbor_allreduce", "sharded_allreduce",
    "win_put", "push_sum", "pull_get", "local",
]
# window modes drive the hosted plane through a control plane even in one
# process; at n=1-chip they still exercise the full op path
CHIP_MODES = ["gradient_allreduce", "neighbor_allreduce", "win_put"]

RATE_RE = re.compile(r"Total img/sec on \d+ chip\(s\): ([0-9.]+) \+-([0-9.]+)")


def run_mode(mode: str, simulate: int, extra=(), quick: bool = False) -> dict:
    # this parent never imports jax, so a chip row's child (no --simulate)
    # is the one process that holds the chip; --simulate pins its own
    # children to the CPU
    cmd = [sys.executable, "-m", "bluefog_tpu.launcher"]
    if simulate:
        cmd += ["--simulate", str(simulate)]
    reps = ("1", "2", "1") if quick else ("3", "5", "3")
    cmd += ["--", sys.executable, str(REPO / "examples" / "benchmark.py"),
            "--model", "mlp", "--batch-size", "8",
            "--num-warmup-batches", reps[0], "--num-batches-per-iter",
            reps[1], "--num-iters", reps[2], "--dist-optimizer", mode,
            *extra]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       cwd=REPO)
    m = RATE_RE.search(r.stdout)
    if r.returncode != 0 or not m:
        return {"mode": mode, "error": (r.stdout + r.stderr)[-500:]}
    return {"mode": mode, "img_per_sec": float(m.group(1)),
            "ci": float(m.group(2))}


# (plane, overlap) sweep of the hybrid harness; "hosted"/ov0 is the baseline
HYBRID_SWEEP = [("hosted", "0"), ("auto", "0"), ("auto", "1")]

# wire-codec sweep on the forced-hosted harness; "none" is the baseline
CODEC_SWEEP = ["none", "int8", "fp8", "topk:0.01"]

# sharded-window sweep (ISSUE r17): shard factor x codec, on the
# LM-shaped param tree fixture (examples/benchmark.py --model lm:
# embedding + attention-block + norm leaves) so the partition rules are
# exercised on realistic shapes; S=1 is the per-codec baseline
SHARD_SWEEP = [(1, "none"), (2, "none"), (4, "none"),
               (1, "int8"), (4, "int8")]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_hybrid_mode(mode: str, plane: str, overlap: str,
                    quick: bool = False) -> dict:
    """One benchmark child on the world-1 control-plane harness with the
    window plane pinned: the hosted window is forced (legacy knob) so the
    same mailbox machinery serves as baseline (`hosted`) and as the hybrid
    residual (`auto`) — only the plane policy and overlap knob move."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        BLUEFOG_CP_HOST="127.0.0.1", BLUEFOG_CP_PORT=str(_free_port()),
        BLUEFOG_CP_WORLD="1", BLUEFOG_CP_RANK="0",
        BLUEFOG_WIN_HOST_PLANE="1", BLUEFOG_WIN_PLANE=plane,
        BLUEFOG_WIN_OVERLAP=overlap)
    env.pop("BLUEFOG_CP_FAULT", None)  # never bench under fault injection
    cmd = [sys.executable, "-m", "bluefog_tpu.launcher",
           "--simulate", "8", "--"]
    reps = ("1", "2", "1") if quick else ("3", "5", "3")
    cmd += [sys.executable, str(REPO / "examples" / "benchmark.py"),
            "--model", "mlp", "--batch-size", "8",
            "--num-warmup-batches", reps[0], "--num-batches-per-iter",
            reps[1], "--num-iters", reps[2], "--dist-optimizer", mode,
            "--disable-dynamic-topology"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       cwd=REPO, env=env)
    m = RATE_RE.search(r.stdout)
    base = {"mode": mode, "plane": plane, "overlap": int(overlap)}
    if r.returncode != 0 or not m:
        return {**base, "error": (r.stdout + r.stderr)[-500:]}
    return {**base, "img_per_sec": float(m.group(1)),
            "ci": float(m.group(2))}


def run_hybrid(modes, quick: bool) -> int:
    rc = 0
    for mode in modes:
        baseline = None
        for plane, overlap in HYBRID_SWEEP:
            res = run_hybrid_mode(mode, plane, overlap, quick=quick)
            res["where"] = "cpu-mesh-8dev-mlp-b8-cp1-hosted-win"
            if "error" in res:
                rc = 1
            elif plane == "hosted":
                baseline = res["img_per_sec"]
            elif baseline:
                res["speedup_vs_hosted"] = round(
                    res["img_per_sec"] / baseline, 2)
            print(json.dumps(res), flush=True)
    # the acceptance criterion couples the speedup to the equivalence
    # proofs: replay the plane suite in the same run
    t = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_win_planes.py", "-q"],
        capture_output=True, text=True, timeout=1200, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    print(json.dumps({
        "mode": "win_planes_equivalence",
        "passed": t.returncode == 0,
        "tail": t.stdout.strip().splitlines()[-1] if t.stdout else ""}),
        flush=True)
    return rc or int(t.returncode != 0)


def run_codec_mode(mode: str, codec: str, quick: bool = False) -> dict:
    """One benchmark child on the world-1 hosted-window harness with the
    wire codec pinned: the plane is forced `hosted` so every gossip byte
    rides the mailbox wire the codec compresses (the plane policy stays
    out of the comparison)."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        BLUEFOG_CP_HOST="127.0.0.1", BLUEFOG_CP_PORT=str(_free_port()),
        BLUEFOG_CP_WORLD="1", BLUEFOG_CP_RANK="0",
        BLUEFOG_WIN_PLANE="hosted")
    if codec != "none":
        env["BLUEFOG_WIN_CODEC"] = codec
    else:
        env.pop("BLUEFOG_WIN_CODEC", None)
    env.pop("BLUEFOG_CP_FAULT", None)  # never bench under fault injection
    cmd = [sys.executable, "-m", "bluefog_tpu.launcher",
           "--simulate", "8", "--"]
    reps = ("1", "2", "1") if quick else ("3", "5", "3")
    cmd += [sys.executable, str(REPO / "examples" / "benchmark.py"),
            "--model", "mlp", "--batch-size", "8",
            "--num-warmup-batches", reps[0], "--num-batches-per-iter",
            reps[1], "--num-iters", reps[2], "--dist-optimizer", mode,
            "--disable-dynamic-topology"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       cwd=REPO, env=env)
    m = RATE_RE.search(r.stdout)
    base = {"mode": mode, "codec": codec}
    if r.returncode != 0 or not m:
        return {**base, "error": (r.stdout + r.stderr)[-500:]}
    return {**base, "img_per_sec": float(m.group(1)),
            "ci": float(m.group(2))}


def run_codecs(modes, quick: bool) -> int:
    rc = 0
    for mode in modes:
        baseline = None
        for codec in CODEC_SWEEP:
            res = run_codec_mode(mode, codec, quick=quick)
            res["where"] = "cpu-mesh-8dev-mlp-b8-cp1-hosted-win"
            if "error" in res:
                rc = 1
            elif codec == "none":
                baseline = res["img_per_sec"]
            elif baseline:
                res["speedup_vs_none"] = round(
                    res["img_per_sec"] / baseline, 2)
            print(json.dumps(res), flush=True)
    return rc


def run_sharded_mode(mode: str, shard: int, codec: str,
                     quick: bool = False) -> dict:
    """One benchmark child on the world-1 hosted-window harness with the
    shard factor (and optionally the wire codec) pinned, over the
    LM-shaped model so the partition rules cut realistic leaves
    (embedding rows, qkv/mlp matrices, whole norm scales)."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        BLUEFOG_CP_HOST="127.0.0.1", BLUEFOG_CP_PORT=str(_free_port()),
        BLUEFOG_CP_WORLD="1", BLUEFOG_CP_RANK="0",
        BLUEFOG_WIN_PLANE="hosted")
    if shard > 1:
        env["BLUEFOG_WIN_SHARD"] = str(shard)
    else:
        env.pop("BLUEFOG_WIN_SHARD", None)
    if codec != "none":
        env["BLUEFOG_WIN_CODEC"] = codec
    else:
        env.pop("BLUEFOG_WIN_CODEC", None)
    env.pop("BLUEFOG_CP_FAULT", None)  # never bench under fault injection
    cmd = [sys.executable, "-m", "bluefog_tpu.launcher",
           "--simulate", "8", "--"]
    reps = ("1", "2", "1") if quick else ("3", "5", "3")
    cmd += [sys.executable, str(REPO / "examples" / "benchmark.py"),
            "--model", "lm", "--batch-size", "8",
            "--num-warmup-batches", reps[0], "--num-batches-per-iter",
            reps[1], "--num-iters", reps[2], "--dist-optimizer", mode,
            "--disable-dynamic-topology"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       cwd=REPO, env=env)
    m = RATE_RE.search(r.stdout)
    base = {"mode": mode, "shard": shard, "codec": codec}
    if r.returncode != 0 or not m:
        return {**base, "error": (r.stdout + r.stderr)[-500:]}
    return {**base, "img_per_sec": float(m.group(1)),
            "ci": float(m.group(2))}


def run_sharded(modes, quick: bool) -> int:
    rc = 0
    for mode in modes:
        baselines = {}
        for shard, codec in SHARD_SWEEP:
            res = run_sharded_mode(mode, shard, codec, quick=quick)
            res["where"] = "cpu-mesh-8dev-lm-b8-cp1-hosted-win"
            if "error" in res:
                rc = 1
            elif shard == 1:
                baselines[codec] = res["img_per_sec"]
            elif baselines.get(codec):
                res["speedup_vs_s1"] = round(
                    res["img_per_sec"] / baselines[codec], 2)
            print(json.dumps(res), flush=True)
    return rc


def run_chip_mode(mode: str) -> dict:
    cmd = [sys.executable, str(REPO / "examples" / "benchmark.py"),
           "--model", "resnet50", "--batch-size", "64",
           "--num-warmup-batches", "5", "--num-batches-per-iter", "5",
           "--num-iters", "3", "--dist-optimizer", mode]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       cwd=REPO)
    m = RATE_RE.search(r.stdout)
    if r.returncode != 0 or not m:
        return {"mode": mode, "error": (r.stdout + r.stderr)[-500:]}
    return {"mode": mode, "img_per_sec": float(m.group(1)),
            "ci": float(m.group(2))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--hybrid", action="store_true")
    ap.add_argument("--codec", action="store_true")
    ap.add_argument("--sharded", action="store_true")
    ap.add_argument("--modes", nargs="*", default=None)
    args = ap.parse_args()
    rc = 0
    if args.sharded:
        return run_sharded(args.modes or ["win_put"], quick=args.quick)
    if args.codec:
        return run_codecs(args.modes or ["win_put"], quick=args.quick)
    if args.hybrid:
        return run_hybrid(args.modes or ["win_put"], quick=args.quick)
    if args.chip:
        for mode in (args.modes or CHIP_MODES):
            res = run_chip_mode(mode)
            res["where"] = "tpu-1chip-resnet50-b64"
            print(json.dumps(res), flush=True)
            rc = rc or ("error" in res)
    else:
        for mode in (args.modes or MODES):
            extra = ()
            if mode != "neighbor_allreduce":
                # dynamic Expo-2 applies only to neighbor_allreduce; keep
                # the others on their natural static path
                extra = ("--disable-dynamic-topology",)
            res = run_mode(mode, simulate=8, extra=extra, quick=args.quick)
            res["where"] = "cpu-mesh-8dev-mlp-b8"
            print(json.dumps(res), flush=True)
            rc = rc or ("error" in res)
    return int(rc)


if __name__ == "__main__":
    sys.exit(main())
