#!/usr/bin/env python
"""Serving-plane benchmark: sharded snapshot fan-out + batched inference.

Three phases over a real control plane (no jax anywhere — the serving
path is numpy-only by contract):

1. **Pull-bandwidth scaling.** Publish a ``--model-mb`` snapshot
   (default 102 MB) and time pinned parallel pulls against 1 and then 4
   control-plane shard servers. Wire bytes are VERIFIED against the
   native transport counters (``client_stats()['bytes_in']`` deltas), so
   the reported bandwidth is what crossed the sockets, not what the
   Python layer believes. The acceptance bar is >= 1.6x from 1 -> 4.

2. **Codec wire savings.** Publish the same model raw and int8-quantized
   and compare the EXACT per-pull wire-byte counter deltas. Bar: int8
   moves >= 3x fewer bytes.

3. **Open-loop serving latency under churn.** A trainer-side publisher
   keeps committing versions whose every element equals the version
   number (torn reads become value mismatches); a :class:`ServeClient`
   serves an open-loop arrival stream (fixed rate, no backpressure from
   completions) while the harness injects a straggling model batch every
   ``--straggle-every`` batches, SIGKILLs a replicated control-plane
   shard mid-run, and rejoins it ON A NEW PORT. Reported: p50/p99
   request latency, shed count, and the two invariants that must be
   ZERO: torn reads and stale-beyond-keep-window serving at settle.

Prints one machine-readable line -- ``BF_SERVE_BENCH {json}`` -- that
``perf_gate.py`` collects as INFO-ONLY ``serve.*`` metrics.

Invocations:
    python scripts/serve_bench.py            # full: 102 MB, 30 s churn
    python scripts/serve_bench.py --quick    # perf-gate preset (~30 s)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_ROOT, "bluefog_tpu")
sys.path.insert(0, _ROOT)
for _name in ("bluefog_tpu", "bluefog_tpu.runtime", "bluefog_tpu.ops",
              "bluefog_tpu.serving"):
    if _name not in sys.modules:
        _mod = types.ModuleType(_name)
        _mod.__path__ = [os.path.join(_PKG, *_name.split(".")[1:])]
        sys.modules[_name] = _mod

import numpy as np  # noqa: E402

from bluefog_tpu.ops import codec as codec_mod  # noqa: E402
from bluefog_tpu.runtime import native  # noqa: E402
from bluefog_tpu.runtime.router import ShardRouter  # noqa: E402
from bluefog_tpu.serving import snapshot as snap  # noqa: E402
from bluefog_tpu.serving.client import ServeClient, RequestShed  # noqa: E402

SHARD_SERVER = os.path.join(_PKG, "runtime", "shard_server.py")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model-mb", type=float, default=102.0,
                   help="snapshot size for the bandwidth/codec phases")
    p.add_argument("--snap-shards", type=int, default=16,
                   help="snapshot stripe count (pull units)")
    p.add_argument("--trials", type=int, default=5,
                   help="timed pulls per configuration (best-of)")
    p.add_argument("--rate", type=float, default=150.0,
                   help="open-loop arrival rate (requests/s)")
    p.add_argument("--duration", type=float, default=30.0,
                   help="seconds of open-loop serving load")
    p.add_argument("--straggle-every", type=int, default=23,
                   help="every Nth model batch sleeps --straggle-ms")
    p.add_argument("--straggle-ms", type=float, default=25.0)
    p.add_argument("--net-mbps", type=float, default=300.0,
                   help="modeled per-endpoint link capacity (MB/s) for "
                        "the paced scaling pass; 0 disables it. On a "
                        "single-core host the UNCONSTRAINED pass cannot "
                        "exceed 1x (everything shares the core); the "
                        "paced pass shows the fan-out overlap the way "
                        "NIC-bound production pulls experience it")
    p.add_argument("--skip-latency", action="store_true",
                   help="bandwidth + codec phases only")
    p.add_argument("--quick", action="store_true",
                   help="perf-gate preset: 16 MB model, 3 trials, "
                        "10 s of churned serving load")
    args = p.parse_args(argv)
    if args.quick:
        args.model_mb = min(args.model_mb, 16.0)
        args.trials = min(args.trials, 3)
        args.duration = min(args.duration, 10.0)
        args.rate = min(args.rate, 80.0)
    return args


# ---------------------------------------------------------------------------
# control-plane process helpers (same two-phase spawn as cp_soak)
# ---------------------------------------------------------------------------

def spawn_shard(index, world, replicate, port=0, rejoin=False):
    cmd = [sys.executable, SHARD_SERVER, "--port", str(port),
           "--world", str(world), "--shard", str(index)]
    if replicate:
        cmd.append("--expect-peers")
    if rejoin:
        cmd.append("--rejoin")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stdin=subprocess.PIPE if replicate else None,
                            text=True)
    marker = "BF_SHARD_PORT" if replicate else "BF_SHARD_READY"
    line = proc.stdout.readline()
    if not line.startswith(marker):
        raise RuntimeError(f"shard {index} failed to start: {line!r}")
    return proc, int(line.split()[1])


def finish_shard_spawn(servers, ring=None):
    ring = ring or ",".join(f"127.0.0.1:{p}" for _, p in servers)
    for proc, _ in servers:
        proc.stdin.write(f"BF_SHARD_PEERS {ring}\n")
        proc.stdin.flush()
    for i, (proc, _) in enumerate(servers):
        line = proc.stdout.readline()
        if not line.startswith("BF_SHARD_READY"):
            raise RuntimeError(f"shard {i} failed to wire peers: {line!r}")


def stop_shards(servers):
    for proc, _ in servers:
        if proc.poll() is None:
            proc.terminate()
    for proc, _ in servers:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def attach(endpoints):
    if len(endpoints) == 1:
        return native.ControlPlaneClient(endpoints[0][0], endpoints[0][1], 0,
                                         streams=1)
    return ShardRouter(endpoints, 0, streams=1, lenient=True)


def wire_in_total():
    st = native.client_stats()
    return sum(st.get("bytes_in", {}).values())


def model_leaves(total_mb, fill=None, seed=0):
    """A few unequal f32 leaves totalling ~total_mb (like a real tree)."""
    total = int(total_mb * 2 ** 20 / 4)
    splits = [total // 2, total // 3, total - total // 2 - total // 3]
    if fill is not None:
        return [np.full(n, float(fill), np.float32) for n in splits]
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(n).astype(np.float32) for n in splits]


# ---------------------------------------------------------------------------
# phase 1+2: pull-bandwidth scaling and codec wire savings
# ---------------------------------------------------------------------------

def bench_pull(args, nshards, leaves, codec=None, pace_mbps=0.0):
    """Publish once against ``nshards`` servers; return the best timed
    parallel pull (counter-verified wire bytes)."""
    servers = [spawn_shard(i, 1, False) for i in range(nshards)]
    endpoints = [("127.0.0.1", p) for _, p in servers]
    cl = attach(endpoints)
    sc = ServeClient(endpoints, register=False, start=False)
    sc._pace_mbps = pace_mbps
    try:
        pub = snap.SnapshotPublisher(cl, shards=args.snap_shards,
                                     codec=codec, keep=8)
        pub.publish(leaves, 1)
        meta = snap.fetch_meta(cl)
        keys = snap.snap_keys(meta, 1)
        best_dt, wire = float("inf"), 0
        c0 = wire_in_total()
        for _ in range(args.trials):
            t0 = time.perf_counter()
            blobs = sc.pull_blobs(keys)
            best_dt = min(best_dt, time.perf_counter() - t0)
            wire = sum(len(b) for b in blobs)
        counted = wire_in_total() - c0
        # the transport counter must agree with what we think we pulled
        # (headers/framing allow a small envelope)
        verified = abs(counted - args.trials * wire) <= \
            0.05 * args.trials * wire + 4096
        # decode correctness once per configuration
        out, ver, _ = snap.fetch_snapshot(cl, meta=meta, ver=1,
                                          pull=sc.pull_blobs)
        assert ver == 1
        tol = 0.0 if codec is None else 0.05
        for a, b in zip(leaves, out):
            np.testing.assert_allclose(a, b, atol=tol)
        return {"mbps": wire / best_dt / 1e6, "wire_bytes": wire,
                "counter_verified": bool(verified), "dt_s": best_dt}
    finally:
        sc.close()
        try:
            cl.close()
        except (OSError, RuntimeError):
            pass
        stop_shards(servers)


# ---------------------------------------------------------------------------
# phase 3: open-loop serving under straggler + kill/rejoin churn
# ---------------------------------------------------------------------------

class Publisher(threading.Thread):
    """Trainer stand-in: commits a version every ``period`` whose every
    element equals the version (torn reads become value mismatches)."""

    def __init__(self, cl, elems, period=0.4, keep=3):
        super().__init__(daemon=True, name="bench-pub")
        self.cl = cl
        self.elems = elems
        self.period = period
        self.pub = snap.SnapshotPublisher(cl, shards=8, keep=keep)
        self.ver = 0
        self.committed = 0
        self.failed = 0
        self.stop = threading.Event()

    def run(self):
        while not self.stop.is_set():
            # poll EVERY tick (what the trainer's heartbeat loop does):
            # a writer that only discovers churn on failure would keep
            # natively-redirected fence writes on the ring successor
            # after the shard rejoined — readers re-point to the
            # rejoined shard and would never see a fence move again
            if hasattr(self.cl, "poll_shard_health"):
                try:
                    self.cl.poll_shard_health()
                except (OSError, RuntimeError):
                    pass
            nxt = self.ver + 1
            leaves = [np.full(self.elems, float(nxt), np.float32),
                      np.full(self.elems // 4 + 1, float(nxt), np.float32)]
            try:
                self.pub.publish(leaves, nxt, step=nxt)
                self.ver = nxt
                self.committed += 1
            except (OSError, RuntimeError):
                self.failed += 1  # shard outage window: fence unmoved
            self.stop.wait(self.period)


def bench_latency(args):
    os.environ.setdefault("BLUEFOG_CP_BACKOFF_MS", "20")
    os.environ["BLUEFOG_SERVE_POLL_S"] = "0.1"
    # r21: the churn run doubles as the request-path attribution bench —
    # tracing + a declared SLO produce the phase p50/p99 and slo.* rows
    # that perf_gate collects INFO-ONLY (docs/slo.md)
    os.environ["BLUEFOG_TRACE_SERVE"] = "1"
    os.environ.setdefault("BLUEFOG_SLO", "serve_p99:50ms@1m,serve_avail:99@1m")
    keep = 3
    servers = [spawn_shard(i, 1, True) for i in range(2)]
    finish_shard_spawn(servers)
    endpoints = [("127.0.0.1", p) for _, p in servers]
    pub_cl = attach(endpoints)
    publisher = Publisher(pub_cl, elems=200_000, keep=keep)
    publisher.start()

    state = {"batches": 0}

    def model_fn(params, xs):
        state["batches"] += 1
        if args.straggle_every > 0 and \
                state["batches"] % args.straggle_every == 0:
            time.sleep(args.straggle_ms / 1e3)  # injected straggler
        return xs + params[0][0]

    sc = ServeClient(endpoints, model_fn=model_fn, register=True)
    torn = [0]
    verify_stop = threading.Event()

    def verifier():
        # the serving-side torn-read probe: whatever (params, version)
        # pair is swapped in, every element must equal the version
        while not verify_stop.is_set():
            with sc._mu:
                p, v = sc._params, sc._version
            if p is not None:
                for leaf in p:
                    if leaf[0] != float(v) or leaf[-1] != float(v) or \
                            not bool((leaf == float(v)).all()):
                        torn[0] += 1
                        break
            verify_stop.wait(0.05)

    vt = threading.Thread(target=verifier, daemon=True, name="bench-verify")
    vt.start()

    if not sc.wait_ready(timeout=20):
        raise RuntimeError("serve client never pulled a first snapshot")

    lat_ms, shed = [], [0]
    lat_mu = threading.Lock()

    def arrival(t_sched):
        try:
            fut = sc.submit(np.zeros(4, np.float32))
        except RequestShed:
            shed[0] += 1
            return
        fut.add_done_callback(
            lambda f: (lat_mu.acquire(),
                       lat_ms.append((time.perf_counter() - t_sched) * 1e3)
                       if f.exception() is None else None,
                       lat_mu.release()))

    t_start = time.perf_counter()
    t_kill = t_start + 0.4 * args.duration
    t_rejoin = t_start + 0.6 * args.duration
    t_end = t_start + args.duration
    killed = rejoined = False
    next_t = t_start
    old_port = servers[1][1]
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        if now >= next_t:
            arrival(next_t)  # open loop: scheduled arrival, no waiting
            next_t += 1.0 / args.rate
        if not killed and now >= t_kill:
            servers[1][0].send_signal(signal.SIGKILL)
            servers[1][0].wait()
            killed = True
            print(f"serve_bench: SIGKILLed shard 1 at "
                  f"t+{now - t_start:.1f}s")
        if killed and not rejoined and now >= t_rejoin:
            proc, nport = spawn_shard(1, 1, True, port=0, rejoin=True)
            ring = f"127.0.0.1:{servers[0][1]},127.0.0.1:{old_port}"
            finish_shard_spawn([(proc, nport)], ring=ring)
            servers[1] = (proc, nport)
            rejoined = True
            print(f"serve_bench: shard 1 REJOINED on new port {nport} "
                  f"(was {old_port}) at t+{now - t_start:.1f}s")
        time.sleep(min(0.002, max(0.0, next_t - time.perf_counter())))

    # settle: the client must catch back up to within the keep window
    stale_beyond_keep = 1
    settle_deadline = time.monotonic() + 15.0
    while time.monotonic() < settle_deadline:
        if publisher.ver and publisher.ver - sc.version() <= keep:
            stale_beyond_keep = 0
            break
        time.sleep(0.2)

    publisher.stop.set()
    publisher.join(timeout=10)
    verify_stop.set()
    vt.join(timeout=5)
    st = sc.stats()
    # request-path attribution: replay the flight ring's request spans
    # (client + in-process publisher share one ring here) into the
    # per-phase percentile table
    from bluefog_tpu.runtime import flight as flight_mod
    from bluefog_tpu.runtime import metrics as metrics_mod
    trace_rows: dict = {}
    rep = flight_mod.serve_report()
    if rep:
        trace_rows["trace.requests"] = rep["requests"]
        for p, prow in sorted(rep["phases"].items()):
            trace_rows[f"trace.phase.{p}.p50_us"] = round(prow["p50_us"], 1)
            trace_rows[f"trace.phase.{p}.p99_us"] = round(prow["p99_us"], 1)
        attr = "  ".join(f"{p} {prow['p50_us']:.0f}/{prow['p99_us']:.0f}"
                         for p, prow in sorted(rep["phases"].items()))
        print(f"serve_bench: phase attribution over {rep['requests']} "
              f"traced request(s), p50/p99 us: {attr}")
    for name in ("slo.requests", "slo.shed", "slo.breach.serve_p99",
                 "slo.breach.serve_avail"):
        c = metrics_mod._REGISTRY._counters.get(name)
        if c is not None:
            trace_rows[name] = c.value
    sc.close()
    try:
        pub_cl.close()
    except (OSError, RuntimeError):
        pass
    stop_shards(servers)

    with lat_mu:
        lats = sorted(lat_ms)
    pct = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))] \
        if lats else float("nan")  # noqa: E731
    out = {
        "p50_ms": round(pct(0.50), 3), "p99_ms": round(pct(0.99), 3),
        "completed": len(lats), "shed": shed[0] + int(st["shed"]),
        "swaps": st["swaps"], "pull_failures": st["pull_failures"],
        "published": publisher.committed, "publish_failed": publisher.failed,
        "torn_reads": torn[0], "stale_beyond_keep": stale_beyond_keep,
        "rejoined_new_port": rejoined,
    }
    out.update(trace_rows)
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    if native.load() is None:
        print("serve_bench: native runtime unavailable", file=sys.stderr)
        return 1
    t0 = time.time()
    result: dict = {"model_mb": args.model_mb}
    failures = []

    # phase 1: pull-bandwidth scaling 1 -> 4 control-plane shards
    leaves = model_leaves(args.model_mb)
    r1 = bench_pull(args, 1, leaves)
    r4 = bench_pull(args, 4, leaves)
    scaling = r4["mbps"] / max(1e-9, r1["mbps"])
    result.update({
        "pull_mbps_1shard": round(r1["mbps"], 1),
        "pull_mbps_4shard": round(r4["mbps"], 1),
        "pull_scaling_x": round(scaling, 2),
        "counter_verified": r1["counter_verified"] and
        r4["counter_verified"],
    })
    result["cores"] = os.cpu_count() or 1
    print(f"serve_bench: pull {args.model_mb:.0f} MB: "
          f"1 shard {r1['mbps']:.0f} MB/s, 4 shards {r4['mbps']:.0f} MB/s "
          f"({scaling:.2f}x unconstrained on {result['cores']} core(s), "
          f"counters "
          f"{'verified' if result['counter_verified'] else 'MISMATCH'})")
    if not result["counter_verified"]:
        failures.append("wire-byte counter deltas disagree with pulled "
                        "payload sizes")

    # paced pass: per-endpoint link capacity modeled, so the fan-out
    # overlap is visible even when one core serializes the local copies
    if args.net_mbps > 0:
        p1 = bench_pull(args, 1, leaves, pace_mbps=args.net_mbps)
        p4 = bench_pull(args, 4, leaves, pace_mbps=args.net_mbps)
        net_scaling = p4["mbps"] / max(1e-9, p1["mbps"])
        result.update({
            "net_mbps_model": args.net_mbps,
            "pull_mbps_1shard_net": round(p1["mbps"], 1),
            "pull_mbps_4shard_net": round(p4["mbps"], 1),
            "pull_scaling_x_net": round(net_scaling, 2),
        })
        print(f"serve_bench: pull at a {args.net_mbps:.0f} MB/s/endpoint "
              f"link model: 1 shard {p1['mbps']:.0f} MB/s, 4 shards "
              f"{p4['mbps']:.0f} MB/s ({net_scaling:.2f}x)")
        if net_scaling < 1.6:
            failures.append(
                f"paced pull scaling {net_scaling:.2f}x < 1.6x — the "
                "endpoint fan-out is not overlapping pulls")

    # phase 2: int8 vs raw wire bytes (exact, from the same counters)
    int8 = codec_mod.state_codec_for(codec_mod.resolve("int8"))
    ri = bench_pull(args, 4, leaves, codec=int8)
    ratio = r4["wire_bytes"] / max(1, ri["wire_bytes"])
    result.update({"int8_wire_ratio": round(ratio, 2),
                   "raw_wire_bytes": r4["wire_bytes"],
                   "int8_wire_bytes": ri["wire_bytes"]})
    print(f"serve_bench: codec: raw {r4['wire_bytes']} B vs int8 "
          f"{ri['wire_bytes']} B per pull = {ratio:.2f}x fewer bytes")
    if not ri["counter_verified"]:
        failures.append("int8 wire-byte counter deltas disagree")

    # phase 3: open-loop latency under straggler + kill/rejoin churn
    if not args.skip_latency:
        lat = bench_latency(args)
        result.update(lat)
        print(f"serve_bench: open loop {args.rate:.0f} req/s x "
              f"{args.duration:.0f}s under churn: p50 {lat['p50_ms']:.1f} ms"
              f" p99 {lat['p99_ms']:.1f} ms, {lat['completed']} completed, "
              f"{lat['shed']} shed, {lat['swaps']} hot-swaps, "
              f"{lat['published']} versions published "
              f"({lat['publish_failed']} publish attempts hit the outage)")
        if lat["torn_reads"]:
            failures.append(f"{lat['torn_reads']} TORN reads")
        if lat["stale_beyond_keep"]:
            failures.append("client stale beyond the keep window after "
                            "churn settled")
        if not lat["rejoined_new_port"]:
            failures.append("rejoin-on-new-port never executed")
        if lat["completed"] == 0:
            failures.append("no request ever completed")

    result["wall_s"] = round(time.time() - t0, 1)
    print("BF_SERVE_BENCH " + json.dumps(result), flush=True)
    if failures:
        print("serve_bench: FAIL", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"serve_bench: PASS ({result['wall_s']}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
