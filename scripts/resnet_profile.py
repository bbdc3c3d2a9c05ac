"""Capture + categorize a device trace of the benchmarked ResNet-50 step.

Answers the VERDICT-r3 question behind "push ResNet MFU": WHERE do the
46-49 ms of device time go — MXU-limited convolutions, HBM-limited
fusions, or scheduling gaps? Writes a jax.profiler trace (xplane + chrome
json) under ``traces/<name>/`` and prints a per-category duration table
parsed from the chrome trace, which is the evidence the PERF.md roofline
section cites.

Run on the real chip: ``python scripts/resnet_profile.py``.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def categorize(name: str) -> str:
    n = name.lower()
    if ("convolution" in n or "conv" in n) and "fusion" not in n:
        return "convolution"
    if "fusion" in n:
        return "fusion (elementwise/BN/pool)"
    if "copy" in n or "transpose" in n:
        return "copy/transpose"
    if "reduce" in n:
        return "reduce"
    if "dot" in n or "matmul" in n:
        return "matmul"
    if "dynamic" in n or "slice" in n or "concatenate" in n:
        return "slice/concat"
    return "other"


def iter_device_op_events(trace_dir: str):
    """Yield (name, args, dur_us) for XLA-op rows on device lanes.

    These are the ONLY rows safe to sum: the steps/modules lanes of the
    same device pid re-cover the identical time spans and would double-
    count. Shared by parse_trace and scripts/convgrad_probe.py."""
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz")))
    if not files:
        raise RuntimeError(f"no chrome trace found under {trace_dir}")
    with gzip.open(files[-1], "rt") as f:
        events = json.load(f)["traceEvents"]
    # device lanes: pid whose process_name mentions TPU/device; fall back to
    # lanes that carry XLA op events (args with 'long_name'/hlo)
    pid_names = {e["pid"]: e["args"].get("name", "")
                 for e in events if e.get("name") == "process_name"}
    device_pids = {p for p, n in pid_names.items()
                   if "TPU" in n or "/device" in n.lower()}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        dur = float(e.get("dur", 0.0))
        name = e.get("name", "")
        args = e.get("args") or {}
        if not (args.get("long_name") or args.get("hlo_category")
                or name.startswith(("%", "fusion", "convolution", "copy"))):
            continue
        yield name, args, dur


def device_op_seconds(trace_dir: str) -> float:
    """Total device XLA-op time in seconds (double-count-safe)."""
    return sum(d for _, _, d in iter_device_op_events(trace_dir)) / 1e6


def parse_trace(trace_dir: str) -> None:
    per_cat = collections.Counter()
    per_op = collections.Counter()
    total = 0.0
    try:
        for name, args, dur in iter_device_op_events(trace_dir):
            cat = args.get("hlo_category") or categorize(name)
            per_cat[cat] += dur
            per_op[name.split(".")[0]] += dur
            total += dur
    except RuntimeError as exc:
        print(exc)
        return
    print(f"\ndevice op time by category ({os.path.basename(trace_dir)}):")
    for cat, dur in per_cat.most_common():
        print(f"  {cat:32s} {dur / 1e3:8.2f} ms  {100 * dur / total:5.1f} %")
    print(f"  {'TOTAL':32s} {total / 1e3:8.2f} ms")
    print("\ntop 12 ops:")
    for op, dur in per_op.most_common(12):
        print(f"  {op:48s} {dur / 1e3:8.2f} ms")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--name", default="resnet50_r4")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--parse-only", action="store_true",
                   help="only re-parse an existing trace directory")
    args = p.parse_args()
    trace_dir = os.path.join(REPO, "traces", args.name)

    if not args.parse_only:
        import jax

        import bench

        opt, state, batch = bench.setup()
        for _ in range(3):  # compile + warm
            state, m = opt.step(state, batch)
        jax.block_until_ready(m["loss"])
        with jax.profiler.trace(trace_dir):
            for _ in range(args.steps):
                state, m = opt.step(state, batch)
            jax.block_until_ready(m["loss"])
        import bluefog_tpu as bf
        bf.shutdown()
        print("trace written to", trace_dir)

    parse_trace(trace_dir)


if __name__ == "__main__":
    main()
