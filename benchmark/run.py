"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for (no CPU fallback: any other platform, or fewer chips, is exit code 1 and
no result). The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``): with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else worth reading is on the
lines before it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args()

    # flight-recorder dumps of the program, and the profiler's trace, go to a
    # directory of this run's own
    scratch = tempfile.mkdtemp(prefix="bf_bench_")
    os.environ.setdefault("BLUEFOG_FLIGHT_DIR", scratch)
    try:
        import jax

        import bluefog_tpu as bf

        if os.path.dirname(os.path.dirname(os.path.realpath(bf.__file__))) != ROOT:
            raise SystemExit(f"bluefog_tpu was imported from {bf.__file__}, not from the "
                             f"checkout this benchmark is in ({ROOT})")
        # every program of a run goes to the persistent cache bf.init() sets up,
        # the sub-second ones too: a second run in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

        from benchmark import harness
        from benchmark.manifest import Manifest

        spans = harness.Spans()
        spans.add("import_s", time.perf_counter() - T_PROCESS)
        manifest = Manifest(ROOT)
        chips = manifest.cell(args.workload)["chips"]
        with spans.timed("reach_chip_s"):
            devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < chips:
            raise SystemExit(
                f"cell {args.workload} needs {chips} TPU chip(s); JAX found {len(devices)} "
                f"device(s) of platform {devices[0].platform!r} ({devices[0].device_kind})")
        result = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                                  scratch if args.trace else None, devices[:chips],
                                  T_PROCESS, spans)
        bf.shutdown()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
