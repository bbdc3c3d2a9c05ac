"""Per-op dispatch/execution microbenchmark.

Analog of the reference's scripts/single_ops_test.py: time each op family
on the current mesh so dispatch-path regressions (e.g. a collective
accidentally re-tracing per call) are visible in isolation. Run on the
default devices, or an 8-device CPU mesh via
``bfrun --simulate 8 -- python scripts/op_microbench.py``.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

import jax


# Flight dumps from a bench run land in a tempdir instead of littering
# the CWD (conftest's default for the test suite); an explicit
# BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))

import bluefog_tpu as bf


def timeit(fn, iters):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--size", type=int, default=1 << 16,
                   help="elements per rank")
    p.add_argument("--iters", type=int, default=50)
    args = p.parse_args()

    bf.init()
    n = bf.size()
    print(f"mesh: {n} rank(s) on {bf.mesh().devices.flat[0].platform}, "
          f"{args.size} f32/rank, {args.iters} iters")

    x = bf.shard_rank_stacked(
        bf.mesh(), np.ones((n, args.size), np.float32))
    bf.win_create(x, name="mb.win", zero_init=True)
    peers = {r: r ^ 1 for r in range(n)} if n % 2 == 0 else None

    ops = [
        ("allreduce", lambda: bf.synchronize(bf.allreduce_nonblocking(x))),
        ("broadcast", lambda: bf.broadcast(x, 0)),
        ("allgather", lambda: bf.allgather(x)),
        ("neighbor_allreduce", lambda: bf.neighbor_allreduce(x)),
        ("neighbor_allgather", lambda: bf.neighbor_allgather(x)),
        ("barrier", lambda: bf.barrier()),
        ("win_put", lambda: bf.win_put(x, "mb.win")),
        ("win_accumulate", lambda: bf.win_accumulate(x, "mb.win")),
        ("win_update", lambda: bf.win_update(name="mb.win")),
    ]
    if peers:
        ops.append(("pair_gossip", lambda: bf.pair_gossip(x, peers)))

    for name, fn in ops:
        dt = timeit(fn, args.iters)
        print(f"{name:22s} {dt * 1e3:8.3f} ms/call")

    # Dynamic one-peer schedule, per-position host cost across cycles.
    # Cycle 1 builds (and caches) each step's CombinePlan; later cycles
    # must be flat and cheap — the per-step O(n^2) W rebuild the r3 review
    # flagged is gone (plan cache keyed on the step's edge set + weights).
    # A 1-rank mesh has no one-peer schedule to cycle.
    if n >= 2:
        topo = bf.load_topology()
        gens = [bf.topology_util.GetDynamicSendRecvRanks(topo, r)
                for r in range(n)]

        def dyn_step():
            sends, recv_from = {}, {r: [] for r in range(n)}
            for r, g in enumerate(gens):
                to, _ = next(g)
                sends[r] = to
            for s, dsts in sends.items():
                for d in dsts:
                    recv_from[d].append(s)
            sw = {r: 1.0 / (len(recv_from[r]) + 1) for r in range(n)}
            nw = {r: {s: sw[r] for s in recv_from[r]} for r in range(n)}
            return bf.neighbor_allreduce(
                x, self_weight=sw, neighbor_weights=nw,
                send_neighbors=sends)

        cycle = max(int(np.log2(n)), 1)
        for label in ("cold", "warm", "warm"):
            t0 = time.perf_counter()
            for _ in range(cycle):
                out = dyn_step()
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / cycle
            print(f"neighbor_allreduce_dyn {dt * 1e3:8.3f} ms/step ({label} "
                  f"cycle of {cycle})")

    bf.win_free("mb.win")
    bf.shutdown()


if __name__ == "__main__":
    main()
