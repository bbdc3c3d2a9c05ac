"""Global runtime state: device mesh, topology, windows.

This is the TPU-native analog of BlueFog's ``BluefogGlobalState`` +
``bluefog_init``/``bluefog_set_topology`` C API (reference: common/global_state.h:44-100,
operations.cc:1165-1304, basics.py:47-65). The big design departure: there is
no background communication thread and no rank-0 negotiation. Ranks are
*devices in a jax Mesh* driven by one SPMD program, so op ordering is static
at compile time — which is exactly the fast path BlueFog exposes as
``skip_negotiate_stage`` (operations.cc:1113-1135). Validation that the
negotiation stage performed (shape/dtype/name consistency across ranks) is
done eagerly in Python in the ops layer instead.

Topology changes are a host-side re-plan followed by fresh jit traces — the
analog of the reference's 3-flag epoch handshake pausing the background loop
(operations.cc:1273-1283) is simply cache invalidation here.
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, Dict, List, Optional

import networkx as nx
import numpy as np

import jax
from jax.sharding import Mesh

from .. import topology as topology_util
from . import handles
from .config import Config, compile_cache_dir
from .logging import logger


class BluefogTPUState:
    """Singleton process state. One per Python process (controller)."""

    def __init__(self) -> None:
        self.initialized = False
        self.config: Config = Config()
        self.devices: List[Any] = []
        self.size: int = 0
        self.local_size: int = 1
        self.local_rank: int = 0
        self.process_index: int = 0
        self.process_count: int = 1
        self.mesh: Optional[Mesh] = None
        self.machine_mesh: Optional[Mesh] = None
        self.topology: Optional[nx.DiGraph] = None
        self.is_topo_weighted: bool = False
        # Window registry: name -> bluefog_tpu.ops.windows.Window
        self.windows: Dict[str, Any] = {}
        self.win_mutex_lock = threading.RLock()
        # Window gossip plane policy (policy, hosted_forced), resolved once
        # per init from BLUEFOG_WIN_PLANE / the legacy alias — every window
        # created in this job sees one consistent verdict even if the env
        # mutates mid-run (ops/windows._plane_policy).
        self.win_plane = None
        # Global toggle: win ops also move the associated push-sum scalar p
        # (reference: mpi_ops.py:1339-1363).
        self.win_ops_with_associated_p = False
        self.skip_negotiate: bool = False
        self.timeline = None  # runtime.timeline.Timeline when enabled
        self.watchdog = None  # runtime.watchdog.StallWatchdog when enabled
        self.peer_monitor = None  # runtime.heartbeat.PeerMonitor (multi-ctrl)
        self._plan_cache: Dict[Any, Any] = {}  # compiled combine plans
        # combine-matrix hashes every controller has agreed on
        # (ops.neighbors.cross_controller_topo_check)
        self._topo_check_agreed: set = set()
        self._topo_check_calls: int = 0  # re-arm cadence counter

    # -- lifecycle ---------------------------------------------------------

    def check_initialized(self) -> None:
        if not self.initialized:
            raise RuntimeError(
                "bluefog_tpu is not initialized; call bluefog_tpu.init() first."
            )


_state = BluefogTPUState()


def _global_state() -> BluefogTPUState:
    return _state


_distributed_initialized = False


def _maybe_init_distributed() -> None:
    """Join the multi-host job when the launcher exported coordinator env.

    The analog of the reference's MPI_Init across ranks (operations.cc
    :1165-1182): ``bfrun -np K --coordinator host:port --process-id i``
    exports JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID
    (launcher.py), and jax.distributed stitches the hosts into one global
    device set. On TPU pods with the runtime's own metadata, argument-free
    initialize() also works; we only force it when the env is present so
    single-host usage stays zero-config.
    """
    global _distributed_initialized
    import os

    if _distributed_initialized or "JAX_COORDINATOR_ADDRESS" not in os.environ:
        return
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["JAX_PROCESS_ID"]),
    )
    _distributed_initialized = True
    logger.info(
        "joined distributed job: process %d/%d",
        jax.process_index(), jax.process_count(),
    )


def _publish_import_seconds() -> None:
    """``import bluefog_tpu`` as the package's ``__init__`` stamped it, by
    import group, as gauges ``import.<group>_sec`` and ``import.total_sec``:
    written at every ``init()`` because each zeroes the registry."""
    import bluefog_tpu
    from . import metrics as _metrics

    for group, seconds in getattr(bluefog_tpu, "IMPORT_SECONDS", {}).items():
        _metrics.gauge(f"import.{group}_sec").set(seconds)


def init(
    topology_fn=None,
    is_weighted: bool = False,
    devices: Optional[List[Any]] = None,
    local_size: Optional[int] = None,
) -> None:
    """Initialize the runtime over the available TPU devices.

    Analog of ``bf.init(topology_fn, is_weighted)`` (reference: basics.py:47-65).
    Rather than MPI_Init across processes, this builds a 1-D rank mesh (and a
    2-D machine × local mesh for hierarchical ops) over ``jax.devices()``.

    Args:
      topology_fn: size -> nx.DiGraph; defaults to ExponentialTwoGraph, the
        reference default (basics.py:59-65).
      is_weighted: use the graph's edge weights for averaging instead of
        uniform 1/(indegree+1).
      devices: explicit device list (default jax.devices()).
      local_size: devices per "machine" for hierarchical ops; defaults to
        jax.local_device_count() (all devices of this host).
    """
    st = _state
    if st.initialized:
        # Re-init: tear down locally WITHOUT announcing coordinated shutdown
        # — the job is not ending, and the flag would spuriously (and
        # permanently) trip every peer's shutdown_requested().
        shutdown(_announce=False)

    st.config = Config.from_env()
    for knob in st.config.ignored_set:
        logger.info("env %s has no effect on TPU (transport is XLA-managed)", knob)

    _maybe_init_distributed()
    compile_cache_dir()
    # Multi-controller scalar coordination (window mutexes/versions/p,
    # cross-controller barrier). No-op unless the job is multi-process or
    # BLUEFOG_CP_HOST is set (runtime/control_plane.py).
    from . import control_plane as _cp
    _cp.attach()
    # Fresh telemetry epoch for the job: instruments zero in place (cached
    # bound methods in subsystems stay valid) and the native transport
    # counter block re-baselines, so snapshots report this job's deltas.
    from . import metrics as _metrics
    _metrics.reset_for_job()
    _publish_import_seconds()
    # Fresh live time-series plane (ring history, per-edge estimators,
    # alert-rule state; re-reads BLUEFOG_ALERT_RULES/TS_* knobs).
    from . import timeseries as _timeseries
    _timeseries.reset_for_job()
    # Fresh self-tuning controller state (hysteresis clocks, codec
    # levels, demotion view; re-reads BLUEFOG_TUNE* knobs).
    from . import tuner as _tuner
    _tuner.reset_for_job()
    # Fresh flight-recorder ring + wall-clock anchor (a postmortem dump
    # belongs to THIS job), and the abnormal-exit hook so an uncaught
    # exception leaves a dump behind (docs/flight_recorder.md).
    from . import flight as _flight
    _flight.reset_for_job()
    _flight.install_excepthook()
    if _cp.active():
        # eager remote-trigger latch: bumps AFTER this point fire even if
        # they land before the first heartbeat/watchdog poll tick
        _flight.latch_trigger(_cp.client())
    if devices is None and st.config.simulate_devices > 0:
        # bfrun --simulate N: rank over forced-CPU devices even when an
        # accelerator backend registered (launcher.py:62-68). N counts
        # devices PER PROCESS; a multi-controller simulate job ranks over
        # the whole aggregated CPU device set.
        want = st.config.simulate_devices * jax.process_count("cpu")
        devices = jax.devices("cpu")[:want]
        if len(devices) < want:
            raise RuntimeError(
                f"BLUEFOG_SIMULATE_DEVICES={st.config.simulate_devices} but "
                f"only {len(devices)} CPU devices exist; set XLA_FLAGS="
                "--xla_force_host_platform_device_count (bfrun does this)"
            )
    st.devices = list(devices if devices is not None else jax.devices())
    st.size = len(st.devices)
    # Process identity of the backend the mesh actually lives on. The
    # argless jax.process_index()/process_count() read the DEFAULT backend,
    # which can be a different (single-process) platform than the mesh —
    # e.g. ranks on a multi-process CPU job while an accelerator plugin is
    # the default. Reference analog: rank comes from the communicator the
    # job runs on, not from the environment at large.
    platform = getattr(st.devices[0], "platform", None)
    try:
        st.process_index = jax.process_index(platform)
        st.process_count = jax.process_count(platform)
    except RuntimeError:
        st.process_index = jax.process_index()
        st.process_count = jax.process_count()
    if local_size:
        st.local_size = int(local_size)
    else:
        mine = [
            d for d in st.devices
            if getattr(d, "process_index", 0) == st.process_index
        ]
        st.local_size = max(1, len(mine))
    if st.size % st.local_size != 0:
        # Heterogeneous layout: hierarchical ops will refuse to run
        # (reference requires homogeneity too, mpi_ops.py:693-741).
        logger.warning(
            "size %d not divisible by local_size %d; hierarchical ops disabled",
            st.size, st.local_size,
        )
        st.machine_mesh = None
    st.mesh = Mesh(np.array(st.devices), ("rank",))
    if st.size % st.local_size == 0 and st.size >= st.local_size:
        st.machine_mesh = Mesh(
            np.array(st.devices).reshape(st.size // st.local_size, st.local_size),
            ("machine", "local"),
        )
    st.local_rank = _compute_local_rank()
    # Elastic rejoin: a respawned rank (BLUEFOG_INCARNATION > 0, exported
    # by bfrun --elastic) attached with a bumped incarnation above — the
    # server fenced its zombie predecessor and GC'd its state. It now
    # enters QUARANTINE: registered in membership but excluded from
    # averaging until a window optimizer completes state transfer
    # (runtime/heartbeat.py, docs/fault_tolerance.md "Rejoin & fencing").
    from .heartbeat import enter_quarantine

    enter_quarantine(st.process_index)
    st.skip_negotiate = st.config.skip_negotiate
    st.windows = {}
    # One plane-policy verdict per job (ISSUE r13): windows consult this
    # instead of re-reading the env per creation, so a mid-job env change
    # can't give two windows of one optimizer different planes.
    from ..ops.windows import _plane_policy

    st.win_plane = _plane_policy()
    if st.win_plane[0] != "auto" or st.win_plane[1] is not None:
        logger.info("window plane policy: %s (hosted forced: %s)",
                    st.win_plane[0], st.win_plane[1])
    st.win_ops_with_associated_p = False
    st._plan_cache = {}
    st._topo_check_agreed = set()
    st._topo_check_calls = 0
    st.initialized = True

    if topology_fn is not None:
        topo = topology_fn(st.size)
    else:
        topo = topology_util.ExponentialTwoGraph(st.size)
        is_weighted = False
    if not set_topology(topo, is_weighted=is_weighted):
        raise RuntimeError("failed to set initial topology")

    if st.config.timeline_prefix:
        from .timeline import Timeline

        # st.process_index, not the Timeline default (argless
        # jax.process_index() reads the DEFAULT backend): co-hosted
        # controllers must not clobber each other's trace file.
        st.timeline = Timeline(st.config.timeline_prefix,
                               process_index=st.process_index)

    from .watchdog import StallWatchdog

    st.watchdog = StallWatchdog(
        warning_sec=st.config.stall_warning_sec,
        cycle_ms=st.config.cycle_time_ms,
    )
    st.watchdog.start()

    # Cross-controller failure detection + coordinated shutdown (reference:
    # stall check operations.cc:387-432, SHUTDOWN broadcast :1074-1095).
    if st.process_count > 1:
        from .heartbeat import PeerMonitor

        st.peer_monitor = PeerMonitor(st.process_index, st.process_count)
        st.peer_monitor.start()

    # Telemetry publication (BLUEFOG_METRICS_INTERVAL / _PROM): the
    # heartbeat tick carries it in multi-controller jobs; single-controller
    # jobs get a dedicated cadence thread (runtime/metrics.py).
    _metrics.start_publisher_if_needed(
        has_heartbeat=st.peer_monitor is not None)

    logger.info(
        "bluefog_tpu initialized: %d rank(s) on %s, local_size=%d",
        st.size, st.devices[0].platform, st.local_size,
    )
    if st.devices[0].platform != jax.default_backend():
        logger.warning(
            "ranks are %d %s device(s) (%s), not the default JAX backend %s",
            st.size, st.devices[0].platform, st.devices[0].device_kind,
            jax.default_backend(),
        )


def shutdown(_announce: bool = True) -> None:
    """Tear down runtime state; analog of ``bf.shutdown`` (operations.cc:1205-1215).

    Outstanding window state is dropped; the stall watchdog, heartbeat
    monitor, and timeline writer threads are joined. In multi-controller
    jobs the coordinated-shutdown flag is published first (the analog of
    the reference's SHUTDOWN broadcast, operations.cc:1074-1095) so peers
    can exit before hanging on a collective with this process's devices.
    """
    st = _state
    if not st.initialized:
        return
    from . import control_plane as _cp
    from .heartbeat import announce_shutdown
    if _announce and st.process_count > 1:
        # Coordinated: peers learn the job is ending BEFORE this process
        # (possibly the control-plane server host) tears anything down.
        announce_shutdown(st.process_index, st.process_count)
    from . import metrics as _metrics
    if _metrics.publication_enabled():
        # final flush: short jobs (and clean exits generally) leave a
        # current scrape + KV snapshot even if no cadence tick ever fired
        try:
            _metrics.publish_now()
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass
    from . import timeseries as _timeseries
    try:
        # same final flush for the live series: one last sample + delta
        _timeseries.maybe_sample(force=True, publish=True)
    except Exception:  # noqa: BLE001 — teardown must not raise
        pass
    _metrics.stop_publisher()
    if st.peer_monitor is not None:
        st.peer_monitor.stop()
        st.peer_monitor = None
    # Release hosted-plane server state (published tensors, pending
    # deposits) BEFORE detaching the client it needs. Best-effort and
    # unaligned: peers may already be gone, so no close-time barriers —
    # an externally shared control-plane server must not keep dead
    # windows' bytes for its lifetime (ADVICE r3).
    for win in list(st.windows.values()):
        try:
            win.close(aligned=False)
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass
    _cp.detach()
    if st.watchdog is not None:
        st.watchdog.stop()
        st.watchdog = None
    # close open per-op spans BEFORE the timeline so the trace stays
    # balanced (every B gets its E edge)
    handles.close_all_spans()
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None
    st.windows.clear()
    st._plan_cache.clear()
    handles.clear()
    st.mesh = None
    st.machine_mesh = None
    st.topology = None
    st.initialized = False


atexit.register(shutdown)


# -- introspection (parity: basics.py:120-186) -----------------------------

def size() -> int:
    _state.check_initialized()
    return _state.size


def local_size() -> int:
    _state.check_initialized()
    return _state.local_size


def num_machines() -> int:
    _state.check_initialized()
    return _state.size // _state.local_size


def machine_size() -> int:
    return num_machines()


def rank() -> int:
    """Index of this controller process.

    In the reference each process is one rank; on TPU one controller drives
    many devices, so per-device rank only exists inside SPMD code (as the
    rank-axis index). This returns the process index of the mesh's backend
    for launcher parity.
    """
    _state.check_initialized()
    return _state.process_index


def _compute_local_rank() -> int:
    """Index of this controller among controllers on the same physical host.

    The reference reads this off MPI's LOCAL communicator
    (mpi_context.cc local comm split). Multi-controller jobs here register
    their hostname in the control-plane KV and count lower-indexed
    co-hosted processes; single-controller jobs are trivially 0.
    """
    from . import control_plane as _cp

    st = _state
    if st.process_count <= 1 or not _cp.active():
        return 0
    import socket
    import zlib

    cl = _cp.client()
    me = st.process_index
    h = zlib.crc32(socket.gethostname().encode())
    cl.put(f"bf.host.{me}", h)
    if _cp.incarnation() == 0:
        cl.barrier("bf.local_rank")
    # A rejoining incarnation must NOT barrier: the surviving peers are deep
    # in their training loops and would never arrive — their host keys from
    # the original launch are already published, which is all we read.
    return sum(
        1 for i in range(st.process_count)
        if i < me and cl.get(f"bf.host.{i}") == h
    )


def local_rank() -> int:
    """This controller's index among co-hosted controllers (see
    :func:`_compute_local_rank`); 0 in single-controller deployments."""
    _state.check_initialized()
    return _state.local_rank


def is_homogeneous() -> bool:
    """All machines have the same device count (reference: mpi_controller.cc:71-96)."""
    _state.check_initialized()
    return _state.size % _state.local_size == 0


def mesh() -> Mesh:
    _state.check_initialized()
    return _state.mesh


def machine_mesh() -> Mesh:
    _state.check_initialized()
    if _state.machine_mesh is None:
        raise RuntimeError("hierarchical mesh unavailable (heterogeneous layout)")
    return _state.machine_mesh


# -- topology management (parity: basics.py:188-291) -----------------------

def set_topology(topology: Optional[nx.DiGraph] = None, is_weighted: bool = False) -> bool:
    """Install a new virtual topology; returns False if rejected.

    Mirrors ``bf.set_topology`` semantics (basics.py:188-271): rejected with a
    warning when windows exist (torch_basics_test.py:63-78 relies on this) or
    when the node count mismatches; equivalent topology is a cheap no-op.
    """
    st = _state
    st.check_initialized()
    if topology is None:
        topology = topology_util.ExponentialTwoGraph(st.size)
        is_weighted = False
    if not isinstance(topology, nx.DiGraph):
        logger.error("set_topology requires a networkx.DiGraph")
        return False
    if topology.number_of_nodes() != st.size:
        logger.error(
            "topology has %d nodes but runtime has %d ranks",
            topology.number_of_nodes(), st.size,
        )
        return False
    if st.windows:
        logger.error(
            "cannot change topology while windows exist; call win_free first"
        )
        return False
    if (
        st.topology is not None
        and topology_util.IsTopologyEquivalent(topology, st.topology)
        and is_weighted == st.is_topo_weighted
    ):
        logger.debug("topology unchanged; skipping re-plan")
        return True
    st.topology = topology
    st.is_topo_weighted = is_weighted
    st._plan_cache.clear()  # new graph -> new combine plans / jit traces
    st._topo_check_agreed.clear()
    st._topo_check_calls = 0
    return True


def load_topology() -> nx.DiGraph:
    _state.check_initialized()
    return _state.topology


def is_topo_weighted() -> bool:
    _state.check_initialized()
    return _state.is_topo_weighted


def in_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    """Sorted in-neighbors of ``rank_`` (default: rank 0 for parity calls)."""
    _state.check_initialized()
    r = 0 if rank_ is None else rank_
    return topology_util.in_neighbor_ranks(_state.topology, r)


def out_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    _state.check_initialized()
    r = 0 if rank_ is None else rank_
    return topology_util.out_neighbor_ranks(_state.topology, r)


def set_skip_negotiate_stage(value: bool) -> None:
    """Disable eager cross-rank validation in the ops layer.

    Under jit there is never a negotiation stage (op order is compiled); this
    only controls the eager debug checks (reference: basics.py:293-306).
    """
    _state.check_initialized()
    _state.skip_negotiate = bool(value)


def get_skip_negotiate_stage() -> bool:
    """Whether eager cross-rank validation is skipped (basics.py:304-306)."""
    _state.check_initialized()
    return _state.skip_negotiate


def unified_mpi_window_model_supported() -> bool:
    """Always True: the mailbox window model has one coherent store per
    rank by construction — the property the reference probes MPI for
    (basics.py:119-128, MPI_WIN_UNIFIED) before allowing win ops."""
    return True


def mpi_threads_supported() -> bool:
    """Always True: op dispatch is plain thread-safe Python/XLA calls, the
    guarantee the reference asks MPI_THREAD_MULTIPLE for (basics.py
    :129-143). (The name keeps the reference's spelling; there is no MPI.)"""
    return True


def nccl_built() -> bool:
    """Always False: there is no NCCL transport — collectives ride XLA over
    ICI/DCN (basics.py:285-292's probe, answered honestly)."""
    return False
