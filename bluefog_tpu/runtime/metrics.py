"""Cluster telemetry plane: metrics registry + health aggregation (r10).

The paper's neighbor-averaging design trades one easy-to-observe collective
for many loosely-coupled asynchronous flows (window deposits, mailbox
drains, push-sum mass movement, heartbeat transitions), and since r8/r9 the
system changes *shape* at runtime (healed combine tables, incarnation
fencing, elastic respawn). The reference's answer was a per-process
timeline (common/timeline.{h,cc}); this module is the layer above it:
quantitative, cluster-wide, always-on telemetry that answers "is the gossip
converging, is mass conserved, which rank is the straggler, how many
retries/replays/force-releases happened" without attaching a tracer.

Three pieces:

* **Registry** — process-global counters / gauges / fixed-bucket
  histograms. The hot path is allocation-free: a counter increment is one
  attribute add on a ``__slots__`` object (< 100 ns, microbenched by
  ``make metrics-smoke``); cross-thread races can at worst drop a rare
  increment, which is the right trade for telemetry. Native-transport
  counters (bytes per op class, redials, dedup replays, stale frames —
  ``csrc/bf_runtime.cc``'s relaxed-atomic counter block) are merged into
  every snapshot as deltas against the registry's baseline.

* **Cluster health** — each controller publishes a compact packed snapshot
  to the control-plane KV under ``bf.metrics.<rank>`` on a
  ``BLUEFOG_METRICS_INTERVAL`` cadence, piggybacking the heartbeat thread
  (no new per-step RTT). :func:`cluster_health` merges the per-rank views:
  staleness, straggler detection via step-counter spread, and a global
  push-sum mass-conservation check across live ranks. ``bfrun --status``
  prints the same view from outside the job.

* **Prometheus** — ``BLUEFOG_METRICS_PROM=<path>`` dumps the text
  exposition format on the same cadence (atomic rename), ready for a
  node-exporter textfile collector or a sidecar scraper.

Collection is ALWAYS on (it is too cheap to gate); only *publication* is
gated by the env knobs, so enabling telemetry changes no training-path
behavior.
"""

from __future__ import annotations

import bisect
import os
import struct
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .logging import logger

# -- instruments -------------------------------------------------------------

# Default latency buckets (seconds): spans window-op dispatch (sub-ms) to a
# wedged-transport drain (tens of seconds).
DEFAULT_BUCKETS = (0.0005, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1.0, 3.0,
                   10.0, 30.0)


class Counter:
    """Monotonic counter. ``inc`` is the hot path: one attribute add, no
    lock, no allocation (a lost increment under a cross-thread race is an
    acceptable telemetry error; every call site is per-op or rarer)."""

    __slots__ = ("name", "_v")

    def __init__(self, name: str) -> None:
        self.name = name
        self._v = 0

    def inc(self, n: int = 1) -> None:
        self._v += n

    @property
    def value(self) -> int:
        return self._v

    def _reset(self) -> None:
        self._v = 0


class Gauge:
    """Last-write-wins scalar (step counters, mass, queue depths)."""

    __slots__ = ("name", "_v")

    def __init__(self, name: str) -> None:
        self.name = name
        self._v = 0.0

    def set(self, v: float) -> None:
        self._v = float(v)

    def add(self, v: float) -> None:
        self._v += float(v)

    @property
    def value(self) -> float:
        return self._v

    def _reset(self) -> None:
        self._v = 0.0


class Histogram:
    """Fixed-bucket histogram (cumulative counts computed at export).

    ``observe`` costs one bisect + two adds; bounds are immutable after
    creation so pack/merge never have to reconcile bucket layouts."""

    __slots__ = ("name", "bounds", "counts", "sum", "count")

    def __init__(self, name: str, bounds=DEFAULT_BUCKETS) -> None:
        self.name = name
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError(f"histogram {name}: bounds must be strictly "
                             "increasing")
        self.counts = [0] * (len(self.bounds) + 1)  # +1 overflow bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.sum += v
        self.count += 1

    def _reset(self) -> None:
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0


class _Timed:
    __slots__ = ("_h", "_t0")

    def __init__(self, h: Histogram) -> None:
        self._h = h

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._h.observe(time.perf_counter() - self._t0)
        return False


# -- registry ----------------------------------------------------------------

class Registry:
    """Process-global instrument registry.

    Instrument *creation* takes a lock; the returned instruments are
    lock-free. ``reset()`` zeroes values in place (instrument identity is
    preserved, so call sites may cache bound methods across ``bf.init``
    cycles) and re-baselines the native counter block."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}
        self._docs: Dict[str, str] = {}
        self._native_base: Dict[str, float] = {}

    def _register_doc(self, name: str, doc: Optional[str]) -> None:
        if doc:
            self._docs[name] = doc

    def counter(self, name: str, doc: Optional[str] = None) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._mu:
                c = self._counters.setdefault(name, Counter(name))
                self._register_doc(name, doc)
        return c

    def gauge(self, name: str, doc: Optional[str] = None) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._mu:
                g = self._gauges.setdefault(name, Gauge(name))
                self._register_doc(name, doc)
        return g

    def histogram(self, name: str, bounds=DEFAULT_BUCKETS,
                  doc: Optional[str] = None) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            with self._mu:
                h = self._hists.setdefault(name, Histogram(name, bounds))
                self._register_doc(name, doc)
        return h

    def timed(self, name: str, bounds=DEFAULT_BUCKETS) -> _Timed:
        """Context manager observing the block's wall time in seconds."""
        return _Timed(self.histogram(name, bounds))

    def reset(self) -> None:
        """Zero every instrument in place and re-baseline native counters
        (each ``bf.init`` starts a fresh job's telemetry epoch)."""
        with self._mu:
            for c in self._counters.values():
                c._reset()
            for g in self._gauges.values():
                g._reset()
            for h in self._hists.values():
                h._reset()
            self._native_base = _native_counters()

    # -- snapshot ---------------------------------------------------------

    def snapshot(self, include_native: bool = True) -> dict:
        """Point-in-time view of every instrument, native transport
        counters merged in as deltas against the last ``reset()``."""
        from . import control_plane as _cp

        meta = {"schema": 1, "ts": time.time(), "rank": _process_index(),
                "inc": _cp.incarnation()}
        counters = {n: float(c._v) for n, c in self._counters.items()}
        gauges = {n: float(g._v) for n, g in self._gauges.items()}
        hists = {
            n: {"bounds": list(h.bounds), "counts": list(h.counts),
                "sum": h.sum, "count": h.count}
            for n, h in self._hists.items()
        }
        if include_native:
            base = self._native_base
            for name, v in _native_counters().items():
                # fault-injector counters reset on every arm — report them
                # raw; a baseline delta could go negative across an arm
                if name.startswith("cp.fault."):
                    counters[name] = v
                else:
                    counters[name] = v - base.get(name, 0.0)
            for name, v in _server_stats_flat().items():
                # live aggregates (depth/bytes/connections) are gauges;
                # event counts are counters
                if name.rsplit(".", 1)[-1] in _SERVER_GAUGE_FIELDS:
                    gauges[name] = v
                else:
                    counters[name] = v
        return {"meta": meta, "counters": counters, "gauges": gauges,
                "hists": hists}


_REGISTRY = Registry()


def registry() -> Registry:
    return _REGISTRY


# module-level conveniences (the instrumented subsystems' entry points)

def counter(name: str) -> Counter:
    return _REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return _REGISTRY.gauge(name)


def histogram(name: str, bounds=DEFAULT_BUCKETS) -> Histogram:
    return _REGISTRY.histogram(name, bounds)


def timed(name: str, bounds=DEFAULT_BUCKETS) -> _Timed:
    return _REGISTRY.timed(name, bounds)


def snapshot(include_native: bool = True) -> dict:
    return _REGISTRY.snapshot(include_native)


def reset_for_job() -> None:
    _REGISTRY.reset()


def _process_index() -> int:
    from .state import _global_state

    st = _global_state()
    return st.process_index if st.initialized else 0


# -- native counter merge ----------------------------------------------------

_SERVER_GAUGE_FIELDS = {"live_connections", "mailbox_records",
                        "mailbox_bytes", "locks_held", "kv_entries",
                        "bytes_slots", "bytes_slot_bytes"}


def _native_counters() -> Dict[str, float]:
    """Flattened native client + fault-injector counters (cumulative)."""
    from . import native as _native

    out: Dict[str, float] = {}
    stats = _native.client_stats()
    for group in ("ops", "bytes_out", "bytes_in"):
        for op, v in stats.get(group, {}).items():
            out[f"cp.client.{group}.{op}"] = float(v)
    for k in ("redials", "redial_attempts", "stale_frames",
              "striped_transfers"):
        if k in stats:
            out[f"cp.client.{k}"] = float(stats[k])
    fault = _native.fault_stats()
    out["cp.fault.ops"] = float(fault.get("ops", 0))
    out["cp.fault.drops"] = float(fault.get("drops", 0))
    return out


def _server_stats_flat() -> Dict[str, float]:
    """Flattened control-plane server stats (only on the serving rank)."""
    from . import control_plane as _cp

    srv = getattr(_cp, "_server", None)
    if srv is None:
        return {}
    try:
        stats = srv.stats()
    except Exception:  # noqa: BLE001 — telemetry must not raise
        return {}
    out: Dict[str, float] = {}
    for op, v in stats.get("ops", {}).items():
        out[f"cp.server.ops.{op}"] = float(v)
    for k, v in stats.items():
        if k != "ops":
            out[f"cp.server.{k}"] = float(v)
    return out


# -- packed snapshot wire format --------------------------------------------
#
#   magic "BFM1" | u16 schema | i32 rank | i64 inc | f64 ts
#   | u32 n_counters | (u16 len, name, f64 value)*
#   | u32 n_gauges   | (u16 len, name, f64 value)*
#   | u32 n_hists    | (u16 len, name, u16 nbounds, f64*nbounds bounds,
#                       u64*(nbounds+1) counts, f64 sum, u64 count)*
#
# Compact enough for the KV (a typical snapshot is a few KB), stable enough
# to read from an external process (bfrun --status) without importing jax.

_MAGIC = b"BFM1"


def _pack_kv(out: bytearray, items: Dict[str, float]) -> None:
    out += struct.pack("<I", len(items))
    for name in sorted(items):
        nb = name.encode()
        out += struct.pack("<H", len(nb)) + nb
        out += struct.pack("<d", float(items[name]))


def pack_snapshot(snap: dict) -> bytes:
    meta = snap["meta"]
    out = bytearray()
    out += _MAGIC
    out += struct.pack("<HiqD".replace("D", "d"), meta.get("schema", 1),
                       int(meta.get("rank", 0)), int(meta.get("inc", 0)),
                       float(meta.get("ts", 0.0)))
    _pack_kv(out, snap.get("counters", {}))
    _pack_kv(out, snap.get("gauges", {}))
    hists = snap.get("hists", {})
    out += struct.pack("<I", len(hists))
    for name in sorted(hists):
        h = hists[name]
        nb = name.encode()
        bounds = h["bounds"]
        out += struct.pack("<H", len(nb)) + nb
        out += struct.pack("<H", len(bounds))
        out += struct.pack(f"<{len(bounds)}d", *bounds)
        out += struct.pack(f"<{len(bounds) + 1}Q", *h["counts"])
        out += struct.pack("<dQ", float(h["sum"]), int(h["count"]))
    return bytes(out)


def _unpack_kv(buf: bytes, off: int):
    (n,) = struct.unpack_from("<I", buf, off)
    off += 4
    items: Dict[str, float] = {}
    for _ in range(n):
        (ln,) = struct.unpack_from("<H", buf, off)
        off += 2
        name = buf[off:off + ln].decode()
        off += ln
        (v,) = struct.unpack_from("<d", buf, off)
        off += 8
        items[name] = v
    return items, off


def unpack_snapshot(blob: bytes) -> dict:
    if len(blob) < 26 or blob[:4] != _MAGIC:
        raise ValueError("not a bluefog metrics snapshot (bad magic)")
    schema, rank, inc, ts = struct.unpack_from("<Hiqd", blob, 4)
    off = 4 + struct.calcsize("<Hiqd")
    counters, off = _unpack_kv(blob, off)
    gauges, off = _unpack_kv(blob, off)
    (nh,) = struct.unpack_from("<I", blob, off)
    off += 4
    hists: Dict[str, dict] = {}
    for _ in range(nh):
        (ln,) = struct.unpack_from("<H", blob, off)
        off += 2
        name = blob[off:off + ln].decode()
        off += ln
        (nb,) = struct.unpack_from("<H", blob, off)
        off += 2
        bounds = list(struct.unpack_from(f"<{nb}d", blob, off))
        off += 8 * nb
        counts = list(struct.unpack_from(f"<{nb + 1}Q", blob, off))
        off += 8 * (nb + 1)
        s, c = struct.unpack_from("<dQ", blob, off)
        off += 16
        hists[name] = {"bounds": bounds, "counts": counts, "sum": s,
                       "count": c}
    return {"meta": {"schema": schema, "rank": rank, "inc": inc, "ts": ts},
            "counters": counters, "gauges": gauges, "hists": hists}


# -- Prometheus text exposition ----------------------------------------------

# HELP text registry: instrument creation sites may pass ``doc=`` (stored
# per-registry); this curated table covers the fleet of implicitly-created
# names (subsystems create instruments by name on their hot paths, where a
# doc string per call would be noise). Prefix rules catch the generated
# families (per-op-class transport counters). Scrapes are self-describing:
# every sample gets a ``# HELP`` line (prom-lint asserts it).
_HELP_EXACT: Dict[str, str] = {
    "serve.publishes": "serving-plane snapshots committed behind the "
                       "version fence by this trainer (docs/serving.md)",
    "serve.publish_wire_bytes": "encoded snapshot bytes written to the "
                                "control plane by the serving publisher",
    "serve.version": "latest committed serving snapshot version "
                     "(bf.serve.ver fence value)",
    "serve.publish_sec": "wall seconds of the last serving snapshot "
                         "publish (encode + stripe writes + fence)",
    "opt.step": "optimizer step counter of this rank",
    "opt.step_sec": "host seconds dispatching one optimizer step's program "
                    "(asynchronous: the device runs on after it; not the "
                    "step's duration)",
    "opt.step_cache_misses": "step programs this job built: optimizer "
                             "steps whose plan had no cached program "
                             "(StepProgram.build has each one's cost; "
                             "docs/timeline.md, Set-up)",
    "opt.build_cache_hits": "of those builds, the ones whose every compile "
                            "request the persistent compile cache answered",
    "opt.step_cache_size": "step programs the optimizer that built last "
                           "holds in its cache",
    "opt.build_trace_sec": "seconds tracing losses to jaxprs inside "
                           "<optimizer>.BUILD spans, summed over the job's "
                           "builds (outermost traces only)",
    "opt.build_lower_sec": "seconds lowering step jaxprs to modules inside "
                           "BUILD spans, summed over the job's builds",
    "opt.build_compile_sec": "seconds in the backend inside BUILD spans, "
                             "summed over the job's builds: compilation, or "
                             "the persistent cache's load on a hit",
    "opt.init_sec": "wall seconds of the last <optimizer>.INIT span: "
                    "opt.init() to its state in place on the devices",
    "opt.init_hbm_peak_bytes": "largest peak_bytes_in_use over the mesh's "
                               "local devices at the end of the last "
                               "opt.init() (absent where the backend keeps "
                               "no memory_stats)",
    "import.total_sec": "seconds `import bluefog_tpu` took in this process "
                        "(stamped by the package's __init__, rewritten at "
                        "every bf.init())",
    "opt.pack_sec": "seconds packing the fusion buffer per gossip step",
    "opt.gossip_sec": "seconds in window gossip ops per step",
    "opt.unpack_sec": "seconds unpacking the fusion buffer per step",
    "opt.healed_rebuilds": "healed edge-table rebuilds after membership "
                           "changes",
    "opt.gossip_retries": "gossip steps retried once on a self-healed "
                          "topology after PeerLostError",
    "opt.consensus_dist": "neighborhood consensus distance: L2 from this "
                          "rank's params to the combine-weighted neighbor "
                          "mean (RMS over owned ranks; decays toward 0 as "
                          "the gossip converges — docs/observability.md)",
    "opt.mixing_rate": "effective per-second mixing rate fit from the "
                       "consensus-distance decay (< 1 = converging; ~1 = "
                       "stalled)",
    "alert.fired": "rank-local alert rules fired (sustained threshold "
                   "breaches; docs/observability.md)",
    "tune.decisions": "self-tuner lever actuations applied (codec "
                      "escalations, in-degree moves, plane re-plans; "
                      "docs/self_tuning.md)",
    "tune.deferred": "self-tuner decisions deferred by the membership-"
                     "epoch fence (re-derived on the next tick)",
    "cp.shards": "control-plane shards this process routes over",
    "cp.dead_shards": "control-plane shards currently failed over",
    "cp.shard_failovers": "shard keyspace failovers this client observed",
    "cp.shard_promotions": "times this server was promoted failover "
                           "primary for a dead shard's keyspace",
    "cp.shard_rejoins": "shard rejoin (snapshot catch-up) completions "
                        "observed",
    "cp.repl_lag": "max WAL records enqueued-but-unacked across live "
                   "shards (replication lag)",
    "cp.under_replicated": "shards serving DEGRADED (successor lagging "
                           "or absent — acked writes live nowhere else)",
    "cp.quorum_lost": "shards below their commit quorum (alive, serving "
                      "reads, rejecting mutating ops with "
                      "QuorumLostError)",
    "cp.partitions": "mutating control-plane ops rejected below quorum "
                     "(grows while a partition or correlated replica "
                     "loss is in effect)",
    "pushsum.mass": "this rank's share of global push-sum de-bias mass",
    "pushsum.minted": "push-sum mass minted (created, not transferred) by "
                      "this rank",
    "pushsum.debias_drift": "max |p - 1| over owned ranks (de-bias scalar "
                            "wander)",
    "membership.epoch": "membership epoch mirror (bumps on join/leave/"
                        "re-admission)",
    "hb.dead_peers": "controllers currently considered dead",
    "hb.suspect_peers": "resumed-but-unfenced controllers (still out of "
                        "membership)",
    "hb.dead_transitions": "live->dead membership transitions observed",
    "hb.suspect_transitions": "dead->suspect transitions (heartbeat "
                              "resumed without re-attach)",
    "hb.readmissions": "suspects re-admitted after fenced rejoin + "
                       "quarantine",
    "hb.quarantine_entries": "times this rank entered rejoin quarantine",
    "hb.quarantine_sec": "seconds spent in rejoin quarantine",
    "watchdog.stalls": "ops flagged stalled by the watchdog",
    "win.deposits_sent": "remote window deposits sent",
    "win.deposits_drained": "window deposits folded by this owner",
    "win.deposits_rejected": "deposits rejected by the server mailbox cap",
    "win.drain_records": "mailbox records drained",
    "win.drain_bytes": "mailbox bytes drained",
    "win.drain_orphans": "orphaned deposit chunks discarded",
    "win.plan_rebuilds": "per-edge plane partitions recomputed (membership "
                         "epoch / dead-set changes)",
    "win.compiled_edges": "edges on the compiled ppermute plane in the "
                          "latest partition",
    "win.hosted_edges": "edges on the hosted mailbox residual in the "
                        "latest partition",
    "cp.client.redials": "successful transparent control-plane reconnects",
    "cp.client.redial_attempts": "control-plane reconnect dials attempted",
    "cp.client.stale_frames": "incarnation-fence verdicts observed",
    "cp.client.striped_transfers": "whole striped put/get transfers",
    "cp.fault.ops": "client ops seen by the fault injector since arm",
    "cp.fault.drops": "connections killed by the fault injector since arm",
    "slo.requests": "serve requests submitted (admitted + shed) — the "
                    "burn-rate denominator (docs/slo.md)",
    "slo.shed": "serve requests refused by the admission gate — the "
                "availability-SLO error numerator",
    "slo.request_us": "end-to-end serve request latency (microseconds, "
                      "submit to reply)",
    "slo.staleness_ver": "snapshot versions between the fence and the "
                         "version that answered each request",
    "flash.dead_steps_fetching": "copies the causal flash kernels issue "
                                 "for dead grid steps alone, one (batch, "
                                 "head) and kernel of the last "
                                 "flash_attention traced "
                                 "(flash.causal_schedule)",
    "flash.chunks_computed": "[tq, chunk] score chunks the live grid steps "
                             "of one (batch, head) and kernel work on, last "
                             "flash_attention traced",
    "flash.chunks_needed": "score chunks of that call that hold an allowed "
                           "(row, column) pair: the causal floor of "
                           "flash.chunks_computed",
    "flash.bwd_fused": "1 when the backward of the last flash_attention "
                       "traced is one call of the kernel, a head's dq "
                       "resident in VMEM; 0 when the shape rule "
                       "(flash._dq_rows) walks q in row blocks",
    "flash.window": "sliding window of the last flash_attention traced: "
                    "row t sees the columns s with 0 <= t - s < window; 0 "
                    "without one. The three schedule gauges count both of "
                    "the band's edges",
    "flash.kv_group": "query heads a k/v head of the last flash_attention "
                      "traced (Hq / Hkv; 1 without grouped-query heads): "
                      "k and v are read through the block specs, never "
                      "repeated",
    "moe.buffer_tiles": "128-row tiles of the held experts' buffer of the "
                        "last RoutedExperts layer traced "
                        "(expert.buffer_rows / ROW_TILE)",
    "moe.chunk_tiles": "tiles a trip of that layer's walks over the buffer "
                       "takes (expert.chunk_tiles; rows_in, rows_out's "
                       "gradient): they stop after the chunk that holds "
                       "tile tiles_in_use - 1",
    "loop.passes": "times the last ConfigLM traced runs its one stack of "
                   "layers on the same weights (LMConfig.total_ut_steps; 1 "
                   "for a model that is not looped)",
    "loop.layer_applications": "layer applications of one forward pass of "
                               "that model: passes x layers, where its "
                               "parameters are of the layers alone",
    "loop.recomputed": "1 when every layer application of that model runs "
                       "under jax.checkpoint (LMConfig.remat_layers: its "
                       "input is kept, the rest made again in the backward "
                       "pass), else 0",
    "loop.kept_residual_bytes": "bytes a forward pass of that model keeps "
                                "of its recomputed applications' attention "
                                "kernels (flash.RESIDUAL_NAMES: q, k, v, "
                                "the output and the f32 row max and sum an "
                                "application), so the forward kernel does "
                                "not run again; 0 where nothing is kept",
    "attn.gated_layers": "layers of the last ConfigLM traced whose attention "
                         "output is gated, o * sigmoid(h W_gate) under "
                         "bf.attn.gate (LMConfig.attn_output_gate; MTP "
                         "blocks counted), else 0",
    "attn.qk_normed_layers": "layers of that model with an RMSNorm over "
                             "head_dim on q and on k before rotary "
                             "(LMConfig.qk_norm), else 0",
    "loss.compare_heads": "head cross-entropies of the last ConfigLM loss "
                          "traced (next_token_loss: 1 + MTP modules; "
                          "looped_exit_loss: passes), each "
                          "models.label_cross_entropy: the label's logit "
                          "picked by comparison, no gather or scatter of a "
                          "[tokens, vocab] array",
    "trace.requests": "serve requests traced into the flight ring "
                      "(BLUEFOG_TRACE_SERVE; docs/slo.md)",
}

_HELP_PREFIX = (
    ("cp.client.ops.", "control-plane client requests sent, by op class"),
    ("cp.client.bytes_out.", "control-plane client request bytes, by op "
                             "class"),
    ("cp.client.bytes_in.", "control-plane client reply bytes, by op "
                            "class"),
    ("cp.server.ops.", "control-plane server dispatches, by op class"),
    ("cp.server.", "control-plane server state/event counter"),
    ("win.", "hosted window data-plane op latency (seconds)"),
    ("slo.breach.", "serve requests that violated this SLO kind's "
                    "target, by objective (docs/slo.md)"),
    ("slo.burn.", "SLO error-budget burn rate over the fast/slow window, "
                  "by objective (docs/slo.md)"),
    ("slo.budget.", "fraction of the slow-window SLO error budget "
                    "remaining, by objective (<= 0 = exhausted)"),
    ("slo.phase.", "per-phase serve request latency percentile from the "
                   "trace analyzer (microseconds)"),
    ("slo.", "serving-plane SLO series (docs/slo.md)"),
    ("trace.", "serve request-path tracing series (docs/slo.md)"),
    ("import.", "seconds of `import bluefog_tpu` spent in this import group "
                "of the package's __init__ (the groups sum to "
                "import.total_sec)"),
)

# Instrument-name prefix families the tree may create (first dotted
# segment). The bfcheck [metrics] analyzer enforces this plus HELP
# resolution for every creation site in the package — a new family must
# be added here (with curated HELP coverage) before it can ship.
_PREFIX_FAMILIES = ("alert", "attn", "cp", "flash", "hb", "import", "loop", "loss", "membership", "moe", "opt",
                    "pushsum", "serve", "slo", "trace", "tune", "watchdog", "win")


def help_for(name: str) -> str:
    """HELP text for a metric: the creating site's ``doc=`` wins, then the
    curated table, then the prefix rules, then a generic fallback — every
    scraped sample is self-describing either way."""
    doc = _REGISTRY._docs.get(name) or _HELP_EXACT.get(name)
    if doc:
        return doc
    for prefix, text in _HELP_PREFIX:
        if name.startswith(prefix):
            return text
    return f"bluefog metric {name}"


def _prom_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    base = "".join(out)
    if base and base[0].isdigit():
        base = "_" + base
    return "bluefog_" + base


def _prom_value(v: float) -> str:
    if v == int(v) and abs(v) < 2 ** 53:
        return str(int(v))
    return repr(float(v))


def prometheus_text(snap: Optional[dict] = None) -> str:
    """Render a snapshot in the Prometheus text exposition format v0.0.4
    (counters, gauges, and classic ``_bucket``/``_sum``/``_count``
    histograms, labeled with the publishing rank)."""
    if snap is None:
        snap = _REGISTRY.snapshot()
    rank = snap["meta"].get("rank", 0)
    label = f'{{rank="{rank}"}}'
    lines: List[str] = []
    for name in sorted(snap.get("counters", {})):
        m = _prom_name(name)
        lines.append(f"# HELP {m} {_prom_help(help_for(name))}")
        lines.append(f"# TYPE {m} counter")
        lines.append(f"{m}{label} "
                     f"{_prom_value(snap['counters'][name])}")
    for name in sorted(snap.get("gauges", {})):
        m = _prom_name(name)
        lines.append(f"# HELP {m} {_prom_help(help_for(name))}")
        lines.append(f"# TYPE {m} gauge")
        lines.append(f"{m}{label} {_prom_value(snap['gauges'][name])}")
    for name in sorted(snap.get("hists", {})):
        h = snap["hists"][name]
        m = _prom_name(name)
        lines.append(f"# HELP {m} {_prom_help(help_for(name))}")
        lines.append(f"# TYPE {m} histogram")
        cum = 0
        for bound, cnt in zip(h["bounds"], h["counts"]):
            cum += cnt
            lines.append(f'{m}_bucket{{rank="{rank}",le="{bound:g}"}} {cum}')
        cum += h["counts"][len(h["bounds"])]
        lines.append(f'{m}_bucket{{rank="{rank}",le="+Inf"}} {cum}')
        lines.append(f"{m}_sum{label} {_prom_value(h['sum'])}")
        lines.append(f"{m}_count{label} {h['count']}")
    return "\n".join(lines) + "\n"


# -- publication -------------------------------------------------------------

_WORLD_KEY = "bf.metrics.world"


def _metrics_key(rank: int) -> str:
    return f"bf.metrics.{rank}"


def publish_interval() -> float:
    """Seconds between snapshot publications; 0 = publication disabled.
    ``BLUEFOG_METRICS_PROM`` alone implies a 10 s default cadence."""
    raw = os.environ.get("BLUEFOG_METRICS_INTERVAL")
    if raw:
        try:
            return max(0.0, float(raw))
        except ValueError:
            logger.warning("BLUEFOG_METRICS_INTERVAL=%r is not a number; "
                           "metrics publication disabled", raw)
            return 0.0
    return 10.0 if os.environ.get("BLUEFOG_METRICS_PROM") else 0.0


def publication_enabled() -> bool:
    return publish_interval() > 0


_pub_mu = threading.Lock()
_last_publish = 0.0


def _write_prom_file(snap: dict) -> None:
    path = os.environ.get("BLUEFOG_METRICS_PROM")
    if not path:
        return
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(prometheus_text(snap))
        os.replace(tmp, path)  # atomic: scrapers never see a torn file
    except OSError as exc:
        logger.warning("metrics: prometheus dump to %s failed (%s)",
                       path, exc)


def publish_now(cl=None) -> Optional[dict]:
    """Publish one snapshot unconditionally (KV + prometheus file).
    Returns the snapshot, or None when nothing could be published."""
    return _publish(cl, force=True)


def maybe_publish(cl=None) -> None:
    """Interval-gated publish — the heartbeat tick calls this every cycle,
    so multi-controller jobs pay zero extra threads and no per-step RTT."""
    _publish(cl, force=False)


def _publish(cl, force: bool) -> Optional[dict]:
    global _last_publish
    interval = publish_interval()
    if not force and interval <= 0:
        return None
    now = time.monotonic()
    with _pub_mu:
        if not force and now - _last_publish < interval:
            return None
        _last_publish = now
    snap = _REGISTRY.snapshot()
    _emit_timeline_counters(snap)
    _write_prom_file(snap)
    from . import control_plane as _cp

    if cl is None and _cp.active():
        cl = _cp.client()
    if cl is not None:
        try:
            from .state import _global_state

            st = _global_state()
            cl.put_bytes(_metrics_key(snap["meta"]["rank"]),
                         pack_snapshot(snap))
            cl.put(_WORLD_KEY, st.process_count if st.initialized else 1)
        except Exception as exc:  # noqa: BLE001 — telemetry must not raise
            logger.debug("metrics publish failed (%s)", exc)
    return snap


def _emit_timeline_counters(snap: dict) -> None:
    """Mirror the gauges onto chrome counter tracks (mailbox depth, mass,
    epoch...) so traces and metrics share one vocabulary."""
    from .timeline import _timeline

    tl = _timeline()
    if tl is None:
        return
    for name, v in snap.get("gauges", {}).items():
        tl.counter(name, int(v))


class _Publisher:
    """Standalone cadence thread for deployments without a heartbeat
    monitor (single-controller jobs): the multi-controller path piggybacks
    :func:`maybe_publish` on the heartbeat tick instead."""

    def __init__(self) -> None:
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name="bf-metrics-publisher", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.wait(max(0.2, publish_interval() / 2.0)):
            try:
                maybe_publish()
                # the live time-series plane samples on the same cadence
                # (heartbeat jobs piggyback the monitor tick instead)
                from . import timeseries as _ts

                _ts.maybe_sample()
            except Exception as exc:  # noqa: BLE001 — observability thread
                logger.debug("metrics publisher tick failed (%s)", exc)


_publisher: Optional[_Publisher] = None


def start_publisher_if_needed(has_heartbeat: bool) -> None:
    """Called by ``bf.init``: start the cadence thread only when enabled
    AND no heartbeat monitor exists to piggyback on."""
    global _publisher
    if not publication_enabled() or has_heartbeat:
        return
    if _publisher is None:
        _publisher = _Publisher()
    _publisher.start()


def stop_publisher() -> None:
    global _publisher
    if _publisher is not None:
        _publisher.stop()
        _publisher = None


# -- cluster health ----------------------------------------------------------

def _straggler_threshold() -> int:
    try:
        return max(1, int(os.environ.get("BLUEFOG_STRAGGLER_STEPS", "3")))
    except ValueError:
        return 3


def health_from_snapshots(snaps: Dict[int, dict], world: int,
                          interval: Optional[float] = None,
                          now: Optional[float] = None) -> dict:
    """Merge per-rank snapshots into the cluster health view.

    * per-rank staleness (wall seconds since that rank published) and an
      ``alive`` verdict (stale past 3 publish intervals = presumed dead);
    * stragglers: ranks whose ``opt.step`` gauge trails the fleet maximum
      by at least ``BLUEFOG_STRAGGLER_STEPS`` (default 3) — the
      step-counter-spread detector;
    * push-sum mass conservation: sum of live ranks' ``pushsum.mass``
      gauges vs the mass they minted, within an ulp-scaled tolerance
      (conservation is exact in the protocol — r8 renormalization, r9
      mass split — so drift beyond rounding means lost deposits).
    """
    if interval is None:
        interval = publish_interval() or 10.0
    if now is None:
        now = time.time()
    stale_after = max(3.0 * interval, 15.0)
    ranks: Dict[int, dict] = {}
    steps: Dict[int, float] = {}
    epoch = 0
    repl_lag = under_repl = 0.0
    have_repl = False
    for pid, s in sorted(snaps.items()):
        staleness = max(0.0, now - s["meta"]["ts"])
        step = s["gauges"].get("opt.step")
        ranks[pid] = {
            "staleness_sec": staleness,
            "alive": staleness < stale_after,
            "incarnation": s["meta"].get("inc", 0),
            "step": None if step is None else int(step),
            # r17 rotation-drift signal: deposits dropped because the
            # origin's shard rotation disagreed with this owner's
            "shard_drops": int(s["counters"].get(
                "win.shard_stale_drops", 0)),
        }
        if step is not None:
            steps[pid] = step
        epoch = max(epoch, int(s["gauges"].get("membership.epoch", 0)))
        # r16 durability gauges (published by the heartbeat tick): the
        # single-endpoint probe's view of the sharded plane's health
        if "cp.repl_lag" in s["gauges"] or \
                "cp.under_replicated" in s["gauges"]:
            have_repl = True
            repl_lag = max(repl_lag, s["gauges"].get("cp.repl_lag", 0.0))
            under_repl = max(under_repl,
                             s["gauges"].get("cp.under_replicated", 0.0))
    missing = sorted(set(range(world)) - set(snaps))
    stragglers: List[int] = []
    if steps:
        mx = max(steps.values())
        thr = _straggler_threshold()
        stragglers = sorted(p for p, v in steps.items() if mx - v >= thr)
        # a rank too stale to publish is behind by definition
        stragglers = sorted(set(stragglers) | {
            p for p, r in ranks.items()
            if not r["alive"] and p in steps})
    live = {p: s for p, s in snaps.items() if ranks[p]["alive"]}
    mass = None
    if any("pushsum.mass" in s["gauges"] for s in live.values()):
        total = sum(s["gauges"].get("pushsum.mass", 0.0)
                    for s in live.values())
        minted = sum(s["gauges"].get("pushsum.minted", 0.0)
                     for s in live.values())
        drift = total - minted
        tol = max(1e-12,
                  float(np.spacing(max(1.0, abs(minted)))) * max(1, world))
        mass = {"total": total, "minted": minted, "drift": drift,
                "tolerance": tol, "conserved": abs(drift) <= tol}
    return {"world": world, "ranks": ranks, "missing": missing,
            "stragglers": stragglers, "mass": mass,
            "membership_epoch": epoch,
            "repl": ({"lag": repl_lag, "under_replicated": int(under_repl)}
                     if have_repl else None)}


def read_cluster_health(cl, world: Optional[int] = None) -> dict:
    """Build the health view from a raw control-plane client — usable from
    OUTSIDE the job (``bfrun --status``) as well as from within."""
    if world is None:
        world = int(cl.get(_WORLD_KEY)) or 1
    snaps: Dict[int, dict] = {}
    for r in range(world):
        try:
            blob = cl.get_bytes(_metrics_key(r))
        except OSError:
            continue
        if not blob:
            continue
        try:
            snaps[r] = unpack_snapshot(blob)
        except (ValueError, struct.error) as exc:
            logger.warning("metrics: snapshot for rank %d unreadable (%s)",
                           r, exc)
    return health_from_snapshots(snaps, world)


def cluster_health() -> dict:
    """The merged cluster health view (see :func:`health_from_snapshots`).

    Multi-controller jobs read every rank's published snapshot from the
    control-plane KV; without a control plane the view is built from this
    process's live registry (single-controller: local IS global). Publish
    cadence is ``BLUEFOG_METRICS_INTERVAL``; a rank that never published
    shows up in ``missing``.
    """
    from . import control_plane as _cp
    from .state import _global_state

    st = _global_state()
    world = st.process_count if st.initialized else 1
    if _cp.active():
        # Read peers from the KV, but use the LIVE registry for this
        # process: our own KV copy can be a full publish interval old (or
        # absent entirely when publication is disabled), and self-freshness
        # costs nothing.
        snaps = {_process_index(): _REGISTRY.snapshot()}
        cl = _cp.client()
        for r in set(range(world)) - {_process_index()}:
            try:
                blob = cl.get_bytes(_metrics_key(r))
                if blob:
                    snaps[r] = unpack_snapshot(blob)
            except (OSError, ValueError, struct.error):
                pass
        return health_from_snapshots(snaps, world)
    return health_from_snapshots({_process_index(): _REGISTRY.snapshot()},
                                 world)


def format_health(health: dict) -> str:
    """Human-readable rendering (the ``bfrun --status`` output)."""
    lines = [f"cluster health — world {health['world']}, membership epoch "
             f"{health['membership_epoch']}"]
    for pid in sorted(health["ranks"]):
        r = health["ranks"][pid]
        step = "-" if r["step"] is None else str(r["step"])
        flags = []
        if not r["alive"]:
            flags.append("STALE")
        if pid in health["stragglers"]:
            flags.append("STRAGGLER")
        drops = r.get("shard_drops", 0)
        lines.append(
            f"  rank {pid}: step {step}, inc {r['incarnation']}, "
            f"published {r['staleness_sec']:.1f}s ago"
            + (f", shard_drops {drops}" if drops else "")
            + (f"  [{' '.join(flags)}]" if flags else ""))
    for pid in health["missing"]:
        lines.append(f"  rank {pid}: no snapshot published")
    m = health["mass"]
    if m is not None:
        verdict = "conserved" if m["conserved"] else "DRIFTING"
        lines.append(
            f"  push-sum mass: total {m['total']:.12g} vs minted "
            f"{m['minted']:.12g} (drift {m['drift']:.3g}) — {verdict}")
    repl = health.get("repl")
    if repl is not None:
        state = (f"{repl['under_replicated']} shard(s) UNDER-REPLICATED"
                 if repl["under_replicated"] else "replicating")
        lines.append(f"  control-plane replication: max WAL lag "
                     f"{repl['lag']:.0f} — {state}")
    if health["stragglers"]:
        lines.append(f"  stragglers: {health['stragglers']}")
    return "\n".join(lines)
