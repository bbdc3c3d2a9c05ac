"""Environment-variable configuration surface.

BlueFog configures itself exclusively through ``BLUEFOG_*`` environment
variables and function arguments (reference: docs/env_variable.rst,
operations.cc:42-47). We keep the same names where the concept survives the
move to TPU and document the ones XLA subsumes.

Knobs kept:
  BLUEFOG_LOG_LEVEL        trace/debug/info/warn/error/fatal (logging.h:56-80)
  BLUEFOG_LOG_HIDE_TIME    hide timestamps in log lines
  BLUEFOG_TIMELINE         path prefix -> enable the chrome-tracing timeline
  BLUEFOG_FUSION_THRESHOLD bytes; leaf-batching threshold for pytree fusion
                           (analog of the fusion buffer, tensor_queue.cc:127-155)
  BLUEFOG_CYCLE_TIME       ms; poll cadence of the host watchdog thread (the
                           background-loop cadence in operations.cc:459-464)
  BLUEFOG_STALL_WARNING_TIME seconds between stall warnings (operations.cc:46)
  BLUEFOG_SKIP_NEGOTIATE   '1' skips eager cross-rank validation (the analog
                           of bf.set_skip_negotiate_stage, basics.py:293-306;
                           under jit there is never a negotiation stage)
  BLUEFOG_SIMULATE_DEVICES N -> init() ranks over N forced-CPU devices even
                           when an accelerator is present (bfrun --simulate)
  BLUEFOG_WIN_PLANE        auto|compiled|hosted — window gossip plane
                           policy; 'auto' (default) runs the per-edge
                           plane planner over hosted windows
                           (docs/window_planes.md). The legacy
                           BLUEFOG_WIN_HOST_PLANE '1'/'0' is a deprecated
                           alias of hosted/compiled (MIGRATION.md)
  BLUEFOG_CP_HOST/PORT/RANK/WORLD/DISABLE/SERVE/CONNECT_TIMEOUT
                           control-plane wiring (runtime/control_plane.py);
                           auto-derived from the jax.distributed coordinator
                           in multi-controller jobs

Knobs with no TPU meaning (accepted, ignored, logged once at init):
  BLUEFOG_*_BY_MPI routing, BLUEFOG_OPS_ON_CPU, BLUEFOG_WIN_ON_GPU,
  BLUEFOG_NUM_FINALIZER_THREADS, BLUEFOG_SLEEP_USEC_FOR_WIN_PASSIVE,
  BLUEFOG_MPI_THREAD_LEVEL — all are MPI/NCCL/CUDA transport details; XLA
  owns transport on TPU. (BLUEFOG_MAX_WIN_SENT_LENGTH is LIVE since r5: it
  sizes hosted-window deposit chunks, ops/windows.py.)
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

_IGNORED_KNOBS = (
    "BLUEFOG_ALLREDUCE_BY_MPI",
    "BLUEFOG_BROADCAST_BY_MPI",
    "BLUEFOG_ALLGATHER_BY_MPI",
    "BLUEFOG_NEIGHBOR_ALLREDUCE_BY_MPI",
    "BLUEFOG_NEIGHBOR_ALLGATHER_BY_MPI",
    "BLUEFOG_WIN_OPS_BY_MPI",
    "BLUEFOG_OPS_ON_CPU",
    "BLUEFOG_WIN_ON_GPU",
    "BLUEFOG_NUM_FINALIZER_THREADS",
    "BLUEFOG_SLEEP_USEC_FOR_WIN_PASSIVE",
    "BLUEFOG_MPI_THREAD_LEVEL",
)


# ---------------------------------------------------------------------------
# Declarative knob registry
# ---------------------------------------------------------------------------
#
# Every LIVE ``BLUEFOG_*`` environment variable the tree reads is declared
# here exactly once: name, type, authoritative default, and the doc line
# rendered into docs/env_variables.md ("Live knobs" table, regenerated by
# ``python scripts/bfcheck --write-docs``). ``scripts/bfcheck``'s ``knobs``
# analyzer AST-walks the repository for ``BLUEFOG_*`` reads and fails on
#   * a read of a knob not declared here,
#   * a per-site literal default that contradicts the registry default,
#   * a declared knob missing from docs/env_variables.md.
# To add a knob: declare it here (doc text included), read it through
# :func:`knob_env` (or keep a local read whose literal default matches),
# and run ``make check``.

@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``type`` is one of ``int``/``float``/``str``/``bool``/``path``/``spec``
    (``bool`` knobs are the ``"0"``/``"1"`` convention; ``spec`` is a
    structured grammar documented elsewhere). ``default`` is the canonical
    default as a TYPED value (None = unset/auto — the site decides).
    ``scope`` records which layer reads it: ``python``, ``native``
    (csrc/bf_runtime.cc via EnvInt/EnvSeconds), or ``both``.
    """

    name: str
    type: str
    default: object
    doc: str
    scope: str = "python"


KNOBS: Tuple[Knob, ...] = (
    Knob("BLUEFOG_LOG_LEVEL", "str", "warn",
         "trace / debug / info / warn / error / fatal"),
    Knob("BLUEFOG_LOG_HIDE_TIME", "bool", False,
         "`1` hides timestamps in log lines"),
    Knob("BLUEFOG_TIMELINE", "path", None,
         "path prefix → enable the chrome-tracing timeline (one file per "
         "controller)"),
    Knob("BLUEFOG_FUSION_THRESHOLD", "int", 8 * 1024 * 1024,
         "bytes; pytree-leaf batching threshold for window-optimizer fusion "
         "groups (reference default kept; raise on high-latency links — "
         "window-mode dispatch cost is linear in group count, see PERF.md "
         "r5 optimizer matrix)"),
    Knob("BLUEFOG_CYCLE_TIME", "float", 0.5,
         "ms; poll cadence of the host watchdog thread"),
    Knob("BLUEFOG_STALL_WARNING_TIME", "float", 60.0,
         "seconds between stall warnings for outstanding handles"),
    Knob("BLUEFOG_SKIP_NEGOTIATE", "bool", False,
         "`1` skips eager cross-rank validation (the reference's "
         "`skip_negotiate_stage`; also settable at runtime via "
         "`bf.set_skip_negotiate_stage`)"),
    Knob("BLUEFOG_SIMULATE_DEVICES", "int", 0,
         "N → rank over N forced-CPU devices (what `bfrun --simulate N` "
         "sets)"),
    Knob("BLUEFOG_HEARTBEAT_INTERVAL", "float", 5.0,
         "seconds between controller heartbeats (multi-controller jobs)"),
    Knob("BLUEFOG_HEARTBEAT_TIMEOUT", "float", 30.0,
         "silent-peer threshold: after this many seconds a controller lands "
         "in `bf.dead_controllers()`"),
    Knob("BLUEFOG_SHUTDOWN_GRACE", "float", None,
         "how long `bf.shutdown()` waits for peers to ack the "
         "coordinated-shutdown flag (default 3×heartbeat interval)"),
    Knob("BLUEFOG_SYNC_TIMEOUT", "float", None,
         "default deadline (seconds) for `bf.synchronize`/`bf.wait`: on "
         "expiry raise with the failure detector's diagnosis (dead peers "
         "named) instead of hanging"),
    Knob("BLUEFOG_TOPO_CHECK_TIMEOUT", "float", 30.0,
         "seconds the cross-controller dynamic-topology hash rendezvous "
         "waits before declaring divergence"),
    Knob("BLUEFOG_TOPO_CHECK_REARM", "int", 50,
         "every K topo-checked dynamic steps the rendezvous re-runs at a "
         "server-assigned round (shared ticket counter, fixed "
         "per-controller keys — no key growth), re-detecting controllers "
         "de-synced inside one cyclic schedule (`0` disables re-arm). Must "
         "be set identically on every controller; a mismatch surfaces as a "
         "re-check timeout naming this variable"),
    Knob("BLUEFOG_WIN_HOST_PLANE", "bool", None,
         "**deprecated alias** of `BLUEFOG_WIN_PLANE` (`1` ≙ `hosted`, "
         "`0` ≙ `compiled`, with a one-time warning; see MIGRATION.md). "
         "When set NEXT TO `BLUEFOG_WIN_PLANE=auto` it still forces "
         "window hostedness while the per-edge planner stays on — the "
         "single-controller hybrid harness shape (docs/window_planes.md)"),
    Knob("BLUEFOG_WIN_PLANE", "str", None,
         "window gossip plane policy: `auto` (default — hosted window for "
         "multi-controller jobs with the PER-EDGE plane planner choosing "
         "compiled ppermute vs mailbox per edge), `compiled` (collective "
         "plane forced), `hosted` (mailbox plane forced, planner off: the "
         "r6/r7 wire bit for bit). docs/window_planes.md"),
    Knob("BLUEFOG_WIN_OVERLAP", "bool", False,
         "`1`: hybrid gossip double-buffers the hosted residual — step "
         "t's deposit/drain runs on a worker thread and folds into step "
         "t+1 (one-step-stale neighbor contributions), overlapping the "
         "residual wire with the next step's backprop. Default off until "
         "the bench validates it; push-sum ignores it (exact p "
         "conservation needs the synchronous residual)"),
    Knob("BLUEFOG_WIN_PLAN_MIN_MB", "float", 0.0,
         "per-edge wire-byte floor (MB) below which the plane planner "
         "keeps an otherwise compiled-eligible edge on the hosted "
         "residual — a partition change re-jits the fused program, and "
         "for tiny windows hosted latency beats that re-jit. `0` "
         "disables the floor"),
    Knob("BLUEFOG_WIN_CODEC", "str", None,
         "wire codec for hosted-window gossip deposits "
         "(docs/compression.md): `none` (default — the legacy wire, byte "
         "for byte), `int8` / `fp8` (per-block-scaled quantization, ~4x "
         "fewer wire bytes for f32 windows; also applied to the compiled "
         "plane's mail-dtype blend), or `topk:<frac>` (top-k "
         "sparsification by magnitude with an error-feedback residual — "
         "dropped mass is sent on later steps, never lost; `topk` alone "
         "means `topk:0.01`). Per-edge overrides append "
         "`;<src>><dst>=<spec>` terms (docs/self_tuning.md), e.g. "
         "`none;0>1=int8;2>3=topk:0.05` — the base applies everywhere "
         "else, and the self-tuner actuates the same per-edge lever at "
         "runtime via `Window.set_edge_codec`. Push-sum ships the "
         "associated-p channel exact under every codec, so "
         "mass-conservation gauges stay green. Read once per window at "
         "`win_create`"),
    Knob("BLUEFOG_WIN_CODEC_BLOCK", "int", 4096,
         "elements per quantization block for the `int8`/`fp8` wire "
         "codecs (one f32 scale per block rides the payload, so the "
         "block size is self-describing on the wire — origin and owner "
         "may disagree safely). Smaller blocks track outliers better at "
         "more scale overhead"),
    Knob("BLUEFOG_WIN_SHARD", "int", 1,
         "shard factor S for FSDP-style sharded window gossip "
         "(docs/sharded_windows.md): the fused window optimizer's row "
         "becomes ONE SHARD of the param tree (partition rules cut each "
         "leaf's chosen axis into S pieces) and gossip rotates the "
         "active shard each step — win-op wire bytes, mailbox slots, "
         "and published rows all shrink by ~S, so a model whose "
         "replicated window plane would not fit trains on the same "
         "wire. Requires the fused window (BLUEFOG_FUSION_THRESHOLD > "
         "0). `1` (default) keeps the unsharded plane byte for byte"),
    Knob("BLUEFOG_WIN_SHARD_RULES", "str", None,
         "ordered partition rules for sharded windows: comma-separated "
         "`regex=axis` terms matched against `/`-joined leaf path names "
         "(first match wins; axis is an integer, `largest`, or `none`), "
         "e.g. `embedding=0,qkv=1,.*=largest`. Unset = the auto rule "
         "(shard every eligible leaf's largest axis). Must resolve "
         "identically on every controller — the rules are pure input to "
         "the piece table both ends of the wire derive from "
         "(ops/partition.py)"),
    Knob("BLUEFOG_WIN_SHARD_FLOOR_KB", "float", 0.0,
         "per-leaf size floor (KB) below which sharded-window partition "
         "rules never cut a leaf: tiny biases/norm scales stay whole "
         "and are greedily balanced across shards instead of "
         "fragmenting into sub-cacheline wire pieces. `0` disables the "
         "floor"),
    Knob("BLUEFOG_WIN_SHARD_DONATE", "bool", True,
         "`1` (default): a sharded gossip step DONATES the param leaves "
         "to the shard scatter program so XLA writes the touched pieces "
         "in place instead of double-buffering the full model (the "
         "memory contract docs/sharded_windows.md describes — arrays "
         "reached through a TrainState retained from BEFORE the step "
         "are invalidated). `0` opts out for callers that keep aliases "
         "of the pre-step params (eval/checkpoint copies), at the cost "
         "of a transient full-model copy per gossip step"),
    Knob("BLUEFOG_WIN_STRICT", "bool", False,
         "`1`: a `require_mutex` `win_update` raises if it drains a deposit "
         "whose version counter is 0 (an origin skipped the mutex protocol) "
         "instead of folding it silently"),
    Knob("BLUEFOG_CP_HOST", "str", None,
         "control-plane server host; auto-derived from the jax.distributed "
         "coordinator in multi-controller jobs"),
    Knob("BLUEFOG_CP_PORT", "int", None,
         "control-plane server port (default: coordinator port + 17)"),
    Knob("BLUEFOG_CP_RANK", "int", 0,
         "this controller's control-plane rank (auto-wired by `bfrun`)"),
    Knob("BLUEFOG_CP_WORLD", "int", 0,
         "number of controller processes in the job (0 = not configured)"),
    Knob("BLUEFOG_CP_SHARDS", "int", 1,
         "shard the control plane across N server processes "
         "(docs/fault_tolerance.md \"Control-plane sharding & failover\"): "
         "clients route each key to its owning shard with a stable hash "
         "and fail over along the shard ring when one dies; "
         "membership-critical keys (epoch, incarnations, quarantine, "
         "shutdown flags) are replicated on every shard. Over a single "
         "BLUEFOG_CP_HOST/PORT, N derives ports PORT..PORT+N-1 and rank 0 "
         "serves all N in-process; `bfrun --cp-shards N` launches real "
         "server processes and exports `BLUEFOG_CP_HOSTS` instead. `1` "
         "(default) keeps the single-server plane, byte for byte"),
    Knob("BLUEFOG_CP_HOSTS", "str", None,
         "explicit comma-separated shard endpoints "
         "(`HOST:PORT,HOST:PORT,...`) naming external shard server "
         "processes (`python bluefog_tpu/runtime/shard_server.py`); "
         "overrides BLUEFOG_CP_HOST/PORT/SHARDS and disables in-process "
         "serving. Exported automatically by `bfrun --cp-shards N`"),
    Knob("BLUEFOG_CP_SERVE", "bool", True,
         "`0` stops rank 0 from serving (an external actor owns the "
         "server)"),
    Knob("BLUEFOG_CP_DISABLE", "bool", False,
         "`1` turns the control-plane subsystem off entirely — window "
         "scalar state stays controller-local"),
    Knob("BLUEFOG_CP_CONNECT_TIMEOUT", "float", 30.0,
         "seconds `bf.init` retries the control-plane connect before a "
         "multi-controller job fails loudly"),
    Knob("BLUEFOG_CP_SECRET", "str", None,
         "shared secret for the control plane's mutual HMAC-SHA256 "
         "connection handshake; minted per job by `bfrun` fan-out "
         "(reference analog: HMAC-signed driver/task messages). Unset = "
         "unauthenticated (single-host dev)"),
    Knob("BLUEFOG_CP_RETRIES", "int", 3, scope="native", doc=(
         "reconnect attempts per control-plane op after a wire failure "
         "(dropped connection, truncated frame, lost reply). Each rebuilt "
         "stream re-runs the HMAC handshake; non-idempotent ops (the "
         "`idempotent=False` rows of `runtime/protocol.py`: fetch_add, "
         "append, take, unlock, barrier, striped-put parts) ride a "
         "per-client op-sequence dedup table server-side, so a retry is "
         "never double-applied. `0` restores the strict pre-r8 fail-fast "
         "wire (and the zero-copy take path — dedup'd drains cost one "
         "extra server memcpy). See docs/fault_tolerance.md")),
    Knob("BLUEFOG_CP_BACKOFF_MS", "int", 50, scope="native", doc=(
         "initial reconnect backoff, doubling per attempt, capped at 2 s")),
    Knob("BLUEFOG_CP_LOCK_LEASE", "float", 60.0, scope="native", doc=(
         "seconds a control-plane lock (window mutexes included) may be "
         "held before a waiter may force-release it (epoch bump + "
         "`PeerLostError` wake). Locks also force-release the instant the "
         "holder's connection closes, so the lease is the backstop for "
         "wedged-but-connected holders; `0` disables lease expiry. Set it "
         "above the longest legitimate critical section (a full gossip "
         "step under the hoisted mutex). Read by the SERVER at startup")),
    Knob("BLUEFOG_CP_BARRIER_TIMEOUT", "float", 600.0, scope="native", doc=(
         "seconds a control-plane barrier waits for stragglers before "
         "waking every waiter with `PeerLostError` (arrival withdrawn, so "
         "a later full barrier still works). Read by the server at "
         "startup")),
    Knob("BLUEFOG_CP_REPLICATION", "int", 1,
         "replication factor R of the sharded control plane's durable "
         "WAL, counted in COPIES of every acked record "
         "(docs/fault_tolerance.md \"Replication rule\" and \"Partitions "
         "& quorum\"): `0` restores the r14 unreplicated sharding (the "
         "documented one-drain-cycle loss window); `1` (default) is the "
         "legacy on-switch, an alias for the two-copy r16 ring-successor "
         "chain — as is `2`, byte-identical wire; `R>=3` keeps min(R, "
         "nshards) copies via R-1 successor WAL streams and arms QUORUM "
         "mode: writes commit on ack-from-majority, any R-1 simultaneous "
         "shard deaths lose nothing, and a shard cut below its commit "
         "quorum (a partition minority) degrades to READ-ONLY — mutating "
         "ops raise the typed `bf.QuorumLostError` until the cut heals. "
         "Read by `bfrun --cp-shards`, the in-process sharded attach, "
         "the router's failover-chain depth, and the shard server's peer "
         "wiring — a single-endpoint (unsharded) plane never replicates "
         "regardless"),
    Knob("BLUEFOG_CP_REPL_TIMEOUT", "float", 30.0, scope="native", doc=(
         "seconds a shard server's handler waits for the ring successor "
         "to ack a WAL record before DEGRADING to unreplicated (the "
         "chain-commit bound). A DEAD successor degrades fast through "
         "the replicator's send failure regardless — this deadline only "
         "guards against a wedged-but-connected successor, and it must "
         "comfortably cover a rejoining successor's snapshot-load gate, "
         "so it defaults generous (consistency over availability). A "
         "degraded shard is reported under-replicated by `bfrun --status "
         "--strict` and only resumes replicating at the next snapshot "
         "cut. Read by the server when the successor is configured")),
    Knob("BLUEFOG_CP_WAL_DEPTH", "int", 65536, scope="native", doc=(
         "record cap of a shard server's in-memory WAL replication queue; "
         "a wedged successor that lets the queue hit the cap degrades "
         "the plane instead of growing server memory without bound. Read "
         "by the server when the successor is configured")),
    Knob("BLUEFOG_CP_REJOIN_TIMEOUT", "float", 30.0,
         "seconds a restarted shard server (`shard_server.py --rejoin`) "
         "retries the snapshot catch-up pull from its ring successor "
         "before giving up and exiting (the spawner may retry the whole "
         "rejoin)"),
    Knob("BLUEFOG_CP_FAULT", "spec", None,
         "deterministic fault injection spec, e.g. "
         "`drop_after=37,delay_ms=50,trunc=1,seed=7`, the per-edge "
         "asymmetric form `delay_edges=0>1:80;2>3:40` (milliseconds "
         "added before shipping deposits on those directed edges), or "
         "the r20 partition clause "
         "`partition=0,1|2,3[,part_after=S][,heal_after=S]` — `|`-"
         "separated sides of SHARD INDICES (resolved to ports by the "
         "shard server's peer ring), cut activating `part_after` seconds "
         "after arming and self-healing `heal_after` seconds later (0 = "
         "immediately / never); full grammar in docs/fault_tolerance.md. "
         "**Testing only — OFF by default and "
         "asserted so by the chaos suite** (the benchmark harness "
         "additionally scrubs it from child envs); `bfrun --chaos SPEC` "
         "exports it job-wide. `make chaos` replays the suite's armed drop "
         "points at two seed offsets via `BLUEFOG_CHAOS_SEED`"),
    Knob("BLUEFOG_CHAOS_SEED", "int", 0,
         "offset added to every armed fault-injection drop point by the "
         "chaos suite (`make chaos` replays at two offsets so "
         "reconnect/dedup/fencing face different drop sites)"),
    Knob("BLUEFOG_INCARNATION", "int", 0,
         "this process's membership incarnation, registered with the "
         "control-plane server at attach (elastic membership, "
         "docs/fault_tolerance.md \"Rejoin & fencing\"). A respawned rank "
         "must attach with the previous value + 1 — the server then fences "
         "the crash's zombie connections with typed "
         "`StaleIncarnationError` replies and garbage-collects its "
         "dedup/mailbox/lock state. `bfrun --elastic` bumps it "
         "automatically on every respawn; never set it by hand on a "
         "healthy job"),
    Knob("BLUEFOG_CP_QUARANTINE_TIMEOUT", "float", 120.0,
         "seconds a quarantined rejoiner waits for neighbor state transfer "
         "(including the push-sum donor mass-split handshake) before "
         "falling back to the newest checkpoint under "
         "`BLUEFOG_CHECKPOINT_DIR` (and then to fresh parameters, "
         "ERROR-logged)"),
    Knob("BLUEFOG_CHECKPOINT_DIR", "path", None,
         "directory of checkpoint directories; the elastic-rejoin fallback "
         "restores the newest one (`bluefog_tpu.checkpoint.latest_path`) "
         "when no live in-neighbor serves state transfer"),
    Knob("BLUEFOG_CP_MAILBOX_MAX_MB", "float", 256.0,
         "per-mailbox byte cap on the control-plane server; a full mailbox "
         "(owner dead / not draining) rejects deposits with a targeted "
         "error instead of growing server memory"),
    Knob("BLUEFOG_CP_STREAMS", "int", 4,
         "width of the striped connection pool each control-plane client "
         "holds per server (clamped to 1–16). Large bulk bodies — "
         "deposits, publishes, raw `put_bytes`/`get_bytes`, win_get pulls "
         "— split across the pool and transfer concurrently; the "
         "win_update drain also sweeps its mailbox keys over the pool. "
         "Every pool connection runs the full mutual HMAC handshake. "
         "**`BLUEFOG_CP_STREAMS=1` is the strict fallback**: no extra "
         "connections are opened and every byte rides the single "
         "connection with exactly the pre-r7 wire behavior, bit for bit "
         "(the striped record reordering and ranged part-ops only engage "
         "at width > 1)"),
    Knob("BLUEFOG_CP_STRIPE_MIN_MB", "float", 4.0,
         "stripe threshold: bulk payloads below this many MB stay on one "
         "pipelined connection (striping latency-bound small transfers "
         "costs more than it parallelizes); at or above it they fan out "
         "across the pool. The win_update drain applies the same "
         "threshold per sweep round, adapting between pooled and "
         "single-connection sweeps on the previous round's byte count"),
    Knob("BLUEFOG_CP_SOCKBUF_MB", "float", 0.0,
         "explicit `SO_SNDBUF`/`SO_RCVBUF` for control-plane sockets, "
         "applied at BOTH ends (server listen socket → inherited by "
         "accepts; clients before connect). `0` keeps the kernel's "
         "auto-tuned buffers — measured faster on loopback, where pinning "
         "a size disables Linux's buffer auto-grow (PERF.md r7); set it on "
         "cross-host DCN paths whose bandwidth-delay product outruns the "
         "auto-tuner"),
    Knob("BLUEFOG_MAX_WIN_SENT_LENGTH", "int", 16 * 1024 * 1024,
         "hosted-window deposit chunk size **in bytes** (reference knob, "
         "mpi_controller.cc:41-46, live again since r5): large window "
         "tensors ship as bounded pipelined wire records instead of one "
         "message. **Unit change vs the reference**: the reference counted "
         "*elements* (default 20000); here the knob counts *bytes* and "
         "values are clamped to a 64 KiB floor. A migrated element-count "
         "config (anything below 65536) triggers a one-time runtime "
         "warning — multiply the old value by the element size instead "
         "(see MIGRATION.md)"),
    Knob("BLUEFOG_WIN_DRAIN_TIMEOUT", "float", 60.0,
         "seconds a win_update drain waits for the continuation chunks of "
         "a concurrently-written deposit before raising (origin died "
         "mid-deposit / mailbox cap hit)"),
    Knob("BLUEFOG_METRICS_INTERVAL", "float", None,
         "seconds between cluster-telemetry snapshot publications to the "
         "control-plane KV (`bf.metrics.<rank>`, read by "
         "`bf.cluster_health()` and `bfrun --status`). Multi-controller "
         "jobs piggyback the heartbeat thread; single-controller jobs "
         "start a small cadence thread. Unset/0 disables publication — "
         "metric *collection* is always on and costs < 100 ns per counter "
         "increment (docs/metrics.md)"),
    Knob("BLUEFOG_METRICS_PROM", "path", None,
         "path for a Prometheus text-exposition dump, rewritten atomically "
         "on the metrics cadence (implies a 10 s cadence when "
         "`BLUEFOG_METRICS_INTERVAL` is unset); point a node-exporter "
         "textfile collector at it"),
    Knob("BLUEFOG_STRAGGLER_STEPS", "int", 3,
         "step-counter spread at which `bf.cluster_health()` flags a rank "
         "as a straggler"),
    Knob("BLUEFOG_TS_INTERVAL", "float", None,
         "seconds between live time-series delta publications to the "
         "control-plane KV (`bf.ts.<rank>`, read by `bfrun --top`, "
         "`scripts/ts_export.py`, and `step_attribution --live`; "
         "docs/observability.md). Unset: the `BLUEFOG_METRICS_INTERVAL` "
         "cadence, else 5 s. Sampling itself is always on and rides the "
         "heartbeat tick regardless; `0` publishes never"),
    Knob("BLUEFOG_TS_DISABLE", "bool", False,
         "`1` turns the live time-series plane (ring history, per-edge "
         "estimators, convergence gauges, alert rules) off entirely; the "
         "point-in-time metrics registry keeps working"),
    Knob("BLUEFOG_ALERT_RULES", "spec", None,
         "override/extend the rank-local alert rules, comma-separated "
         "`name:series>value:for=SEC` terms (`name:off` disables a "
         "default; grammar and the default rule table in "
         "docs/observability.md). Rules fire flight instants "
         "(`alert.<name>`) and publish under `bf.alerts.<rank>`"),
    Knob("BLUEFOG_TUNE", "bool", False,
         "`1` turns on the rank-local online performance controller "
         "(docs/self_tuning.md): it consumes the live time-series plane "
         "and actuates the per-edge plane floor, per-edge wire codec "
         "(`none`->`int8`->`topk` escalation on sustained-slow edges), "
         "and per-rank in-degree (straggler demotion with "
         "total-preserving column renormalization). Every actuation is "
         "epoch-fenced on the membership epoch and hysteresis-gated; "
         "`0` (default) takes zero KV reads and leaves the wire "
         "byte-identical to the untuned build"),
    Knob("BLUEFOG_TUNE_INTERVAL", "float", 5.0,
         "seconds between controller ticks when `BLUEFOG_TUNE=1` (the "
         "tick rides the heartbeat/sampler funnel, so the effective "
         "floor is the heartbeat interval)"),
    Knob("BLUEFOG_TUNE_RULES", "spec", None,
         "override the self-tuner's decision thresholds, "
         "comma-separated `key=value` terms over the defaults "
         "`slow_ratio=0.5,min_bps=0,transit_p99_ms=0,slow_for=10,"
         "straggler_for=10,dwell=30,deesc_norm=0,keep_in=1` (decision "
         "table in docs/self_tuning.md); unknown keys warn and are "
         "skipped"),
    Knob("BLUEFOG_SERVE_PUBLISH_EVERY", "int", 0,
         "snapshot-publish cadence of the serving plane "
         "(docs/serving.md): every N-th COMMUNICATING optimizer step, "
         "controller 0 publishes the post-gossip model as a versioned, "
         "immutable, codec-compressed snapshot "
         "(`bf.serve.snap.<ver>.<shard>` + the monotone `bf.serve.ver` "
         "commit fence). `0` (default) disables publishing — the wire "
         "stays byte-identical to a non-serving job"),
    Knob("BLUEFOG_SERVE_SHARDS", "int", 0,
         "snapshot pull-unit count S: the flat model vector is cut into "
         "S contiguous segments published under independent keys that "
         "hash across the control-plane shards, so serve clients pull "
         "them in parallel. `0` (default) follows BLUEFOG_WIN_SHARD so "
         "the serving plane stripes the way the gossip wire does"),
    Knob("BLUEFOG_SERVE_KEEP", "int", 2,
         "committed snapshot versions retained before the publisher GCs "
         "the oldest (empty-bytes overwrite; `bf.serve.gc_floor` names "
         "the oldest survivor). A reader that loses the GC race re-reads "
         "the fence and retries at the current version — raise the keep "
         "window if slow clients report `SnapshotGone`"),
    Knob("BLUEFOG_SERVE_CODEC", "str", None,
         "snapshot wire codec override (`none`/`int8`/`fp8`); unset "
         "follows the trainer's BLUEFOG_WIN_CODEC through "
         "`state_codec_for` (absolute state never ships top-k — sparse "
         "specs fall back to int8, exactly like published window rows)"),
    Knob("BLUEFOG_SERVE_POLL_S", "float", 0.5,
         "serve-client poll interval (seconds): how often the puller "
         "thread re-reads the `bf.serve.ver` fence, heartbeats "
         "`bf.serve.client.<id>`, and refreshes admission-control "
         "telemetry"),
    Knob("BLUEFOG_SERVE_BATCH", "int", 8,
         "max examples the serve client stacks into one `model_fn` "
         "call"),
    Knob("BLUEFOG_SERVE_BATCH_WAIT_MS", "float", 5.0,
         "batching linger (ms): after the first queued example, how long "
         "the batcher waits for the batch to fill before running a "
         "partial one"),
    Knob("BLUEFOG_SERVE_QUEUE_MAX", "int", 256,
         "serve-client request queue capacity; at depth >= max the "
         "admission gate sheds (`RequestShed`) instead of queueing"),
    Knob("BLUEFOG_SERVE_QUEUE_SOFT", "int", 0,
         "soft queue-depth threshold above which admitted requests are "
         "counted as `queued` (degraded) rather than `accepted`; `0` "
         "(default) = half of BLUEFOG_SERVE_QUEUE_MAX"),
    Knob("BLUEFOG_SERVE_STALE_S", "float", 30.0,
         "publish-lag threshold (seconds) past which the serve client "
         "degrades admission to `queue` and `bfrun --status --strict` "
         "reports a stale-snapshot finding"),
    Knob("BLUEFOG_SERVE_CLIENT_TTL_S", "float", 30.0,
         "serve-client heartbeat TTL (seconds): a `bf.serve.client.<id>` "
         "slot whose heartbeat is older than this is considered dead and "
         "is reclaimed by the next client that registers, so client ids "
         "and heartbeat keys stay bounded under client churn instead of "
         "growing forever. `0` disables reuse (every client gets a fresh "
         "id)"),
    Knob("BLUEFOG_TRACE_SERVE", "bool", False,
         "`1` turns on request-path tracing of the serving plane "
         "(docs/slo.md): every served request gets a 64-bit trace id and "
         "per-phase spans (admit/queue/linger/decode, plus the poller's "
         "stripe-group pulls and failovers) in the flight ring, the "
         "publisher stamps `bf.serve.lineage.<ver>` provenance records "
         "and emits per-stripe flow events that `merge_dumps` binds into "
         "one cross-process chrome trace. Off (default) the serve wire "
         "and flight ring are byte-identical to an untraced job"),
    Knob("BLUEFOG_SLO", "spec", None,
         "serving SLO declarations, comma-separated `kind:target@window` "
         "terms (docs/slo.md): `serve_p99:50ms@5m` / `serve_p50:10ms@5m` "
         "(request latency), `serve_avail:99.9@1h` (non-shed fraction), "
         "`serve_staleness:3ver@5m` (answered-version lag). The serve "
         "client records per-request latency/staleness histograms and "
         "the time-series plane evaluates multi-window burn rates over "
         "them, firing `alert.slo.<kind>` alerts and error-budget "
         "gauges. Unset (default) disables the SLO engine entirely"),
    Knob("BLUEFOG_SLO_BURN", "float", 2.0,
         "burn-rate alert threshold: an SLO alert fires when BOTH the "
         "fast window (the declared SLO window) and the slow window "
         "(12x) burn error budget faster than this multiple of the "
         "sustainable rate, and clears when the fast window recovers "
         "below it"),
    Knob("BLUEFOG_FLIGHT_CAPACITY", "int", 8192,
         "event capacity of the always-on flight-recorder ring "
         "(docs/flight_recorder.md); rounded up to a power of two. The "
         "ring is preallocated numpy columns, so memory is ~40 bytes per "
         "slot"),
    Knob("BLUEFOG_FLIGHT_DIR", "path", None,
         "directory flight-recorder dumps (`bf_flight_<rank>.json`) are "
         "written to; default: the current working directory"),
    Knob("BLUEFOG_FLIGHT_DISABLE", "bool", False,
         "`1` turns flight recording off entirely (the ring is ALWAYS on "
         "otherwise — recording costs well under a microsecond per event "
         "and dumping only happens on failure or explicit request)"),
    Knob("BLUEFOG_FLIGHT_MIN_INTERVAL", "float", 30.0,
         "rate limit (seconds) between AUTOMATIC flight dumps (fatal "
         "optimizer-step exceptions, watchdog stalls) so a failure storm "
         "cannot spam the disk/KV; explicit `bf.flight_dump()` and "
         "`bfrun --dump` bypass it"),
    Knob("BLUEFOG_PERF_GATE_DELAY_MS", "float", 0.0,
         "testing only — injects an artificial per-op delay (ms) into the "
         "hosted window plane and per-step delay into optimizer steps, so "
         "`make perf-gate`'s red path can be exercised deterministically "
         "(`BLUEFOG_PERF_GATE_DELAY_MS=50 make perf-gate` must FAIL). "
         "Never set on a real job or benchmark run"),
    Knob("BLUEFOG_NATIVE_SO", "path", None,
         "override path of the native runtime shared library; how `make "
         "tsan`/`make asan` point the whole Python runtime at a "
         "sanitizer-instrumented build (docs/static_analysis.md)"),
    Knob("BLUEFOG_WB_QUICK", "bool", False,
         "`1`: win_microbench children run the quick sweep (smoke-test "
         "mode)"),
    Knob("BLUEFOG_WB_CODECS", "str", None,
         "comma-separated wire codecs the win_microbench children "
         "additionally sweep on the headline config (what "
         "`win_microbench --codec` exports; docs/compression.md)"),
    Knob("BLUEFOG_WB_SHARD", "str", None,
         "comma-separated shard factors the win_microbench children "
         "additionally sweep (what `win_microbench --sharded` exports): "
         "per factor, a shard-row-sized window replays the win_put / "
         "win_update series and the per-op wire bytes are counter-delta "
         "verified to drop by ≥ 0.9·S (docs/sharded_windows.md)"),
    Knob("BLUEFOG_TB_CONFIG", "str", None,
         "row label the tune_bench children stamp on their JSON result "
         "(set by scripts/tune_bench.py per config: `static-none` / "
         "`static-int8` / `tuned`; docs/self_tuning.md)"),
    Knob("BLUEFOG_TB_SECONDS", "float", 12.0,
         "timed free-running gossip window (seconds) per tune_bench "
         "config"),
    Knob("BLUEFOG_TB_STRAGGLER", "int", 3,
         "rank the tune_bench children inject the per-round sleep into "
         "(the sustained straggler the in-degree lever must demote)"),
    Knob("BLUEFOG_TB_STRAGGLE_MS", "float", 150.0,
         "per-round sleep (ms) of the injected tune_bench straggler"),
)

_KNOB_INDEX = {k.name: k for k in KNOBS}


def knob(name: str) -> Knob:
    """The declared :class:`Knob` for ``name`` (KeyError if undeclared)."""
    return _KNOB_INDEX[name]


def knob_default(name: str):
    """The registry's authoritative default for ``name``."""
    return _KNOB_INDEX[name].default


def knob_env(name: str):
    """Read ``name`` from the environment, typed per its declaration.

    Returns the registry default when unset (None for unset/auto knobs);
    raises KeyError for an undeclared name — new knobs must be declared in
    :data:`KNOBS` first (bfcheck enforces the same rule statically).
    A malformed value falls back to the default rather than raising, the
    prevailing convention across the tree's env readers.
    """
    k = _KNOB_INDEX[name]
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return k.default
    try:
        if k.type == "int":
            return int(raw)
        if k.type == "float":
            return float(raw)
        if k.type == "bool":
            return raw == "1"
    except ValueError:
        return k.default
    return raw


@dataclasses.dataclass
class Config:
    log_level: str = "warn"
    log_hide_time: bool = False
    timeline_prefix: Optional[str] = None
    fusion_threshold_bytes: int = 8 * 1024 * 1024
    cycle_time_ms: float = 0.5
    stall_warning_sec: float = 60.0
    skip_negotiate: bool = False
    simulate_devices: int = 0
    ignored_set: tuple = ()

    @classmethod
    def from_env(cls) -> "Config":
        env = os.environ
        cfg = cls(
            log_level=env.get("BLUEFOG_LOG_LEVEL", "warn").lower(),
            log_hide_time=env.get("BLUEFOG_LOG_HIDE_TIME", "0") == "1",
            timeline_prefix=env.get("BLUEFOG_TIMELINE") or None,
            fusion_threshold_bytes=int(
                env.get("BLUEFOG_FUSION_THRESHOLD", 8 * 1024 * 1024)
            ),
            cycle_time_ms=float(env.get("BLUEFOG_CYCLE_TIME", 0.5)),
            stall_warning_sec=float(env.get("BLUEFOG_STALL_WARNING_TIME", 60.0)),
            skip_negotiate=env.get("BLUEFOG_SKIP_NEGOTIATE", "0") == "1",
            simulate_devices=int(env.get("BLUEFOG_SIMULATE_DEVICES", 0)),
            ignored_set=tuple(k for k in _IGNORED_KNOBS if k in env),
        )
        return cfg


_CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def compile_cache_dir() -> Optional[str]:
    """Directory of XLA's persistent compile cache, configured on first use.

    ``JAX_COMPILATION_CACHE_DIR`` wins when the environment sets it: JAX reads
    that variable itself and nothing is set in code. Otherwise the cache lives
    in ``<checkout>/.jax_cache`` — one fixed place, because a cache that moves
    between runs (a temp dir, a pid, a timestamp) is never hit. A CPU backend
    gets no cache (None): XLA:CPU executables are bound to the CPU features of
    the host that compiled them, and a checkout travels between hosts. Called
    from ``bf.init()`` before anything compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
