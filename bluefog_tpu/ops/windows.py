"""One-sided "window" ops: the asynchronous gossip subsystem.

TPU-native redesign of BlueFog's MPI-RMA windows (reference API:
torch/mpi_ops.py:890-1363; CPU transport mpi_controller.cc:796-1393; GPU
emulation nccl_controller.cc:1113-1238). True one-sided RMA does not exist on
TPU, and the reference itself proves emulation is acceptable — its NCCL path
is a two-sided protocol with a passive-recv thread. Here the emulation is a
**mailbox model**: every window keeps, per rank, one receive slot per
in-neighbor — exactly the clone-per-in-neighbor layout of
WinTorchStorageManager (mpi_win_ops.cc:83-105) — plus the rank's own window
tensor.

Execution model: one window op = ONE compiled SPMD program over the rank
mesh. The mailbox is a rank-sharded array ``mail[n, d_max, ...]`` (slot k of
rank r belongs to its k-th sorted in-neighbor, the MPI_Dist_graph ordering
contract); put/get/accumulate decompose the active edge set into circulant
shifts, move data with one ``ppermute`` per shift, and blend it into the
destination slot. Per-call weights and active-edge masks are *traced*
operands, so dynamic partial-destination puts reuse the same compiled
program. ``win_update`` is a second one-program combine:
``out[r] = sw[r]*self[r] + sum_k nw[r,k]*mail[r,k]``
(DoWinSync's Sum/AvgWithNeighbor, mpi_win_ops.cc:185-238).

Semantics preserved from the reference:
  * ``self_weight`` on put/accumulate rescales the locally stored window
    tensor after the send (the push-sum "self down-weighting").
  * per-edge version counters: bumped on put/get/accumulate, cleared when
    win_update reads the buffer (mpi_controller.cc:1281-1393). Advisory, as
    in the reference. On the hosted plane origins bump BEFORE depositing
    (one batched round-trip), so a mutex-protected drain never consumes a
    deposit at version 0; the residual non-mutex race is an origin's bump
    landing before an owner's reset while its deposit lands after — the
    deposit then sits pending with version 0 until the next update folds
    it (a version poller misses that one write). Use ``require_mutex`` on
    every participant (optionally with ``BLUEFOG_WIN_STRICT=1`` to turn
    violations into errors) or ``win_fence`` where exact write/read
    ordering matters, exactly as the reference prescribes.
  * per-rank mutexes with host-side lock tables (the MPI_Fetch_and_op
    spin-lock, mpi_controller.cc:1532-1602, owned by the controller).
  * associated-p scalars: optional parallel channel carrying the push-sum
    weight, toggled globally (mpi_ops.py:1339-1363); tiny host-side numpy
    mirror of the same edge algebra.
"""

from __future__ import annotations

import os
import struct
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import topology as topology_util
from . import codec as _wire_codec
from ..runtime import control_plane as _cp
from ..runtime import flight as _flight
from ..runtime import handles as _handles
from ..runtime import metrics as _metrics
from ..runtime import native as _native
from ..runtime.config import knob_env
from ..runtime.logging import logger
from ..runtime.state import _global_state
from ..runtime.timeline import (timeline_context, timeline_counter,
                                timeline_flow_finish, timeline_flow_start)
from .neighbors import _check_rank_stacked, _per_rank

Weights = Union[float, Dict[int, float], Dict[int, Dict[int, float]]]


def _op_timer(activity: str):
    """Step-phase latency histogram for one window op ('WIN_PUT' ->
    ``win.put_sec``): the quantitative complement of the timeline span
    emitted next to it (docs/metrics.md)."""
    ms = knob_env("BLUEFOG_PERF_GATE_DELAY_MS")
    if ms:
        # testing-only seeded slowdown: scripts/perf_gate.py's red path
        time.sleep(float(ms) / 1e3)
    return _metrics.timed(f"win.{activity[4:].lower()}_sec")


# Flow-event name binding a deposit on the origin to its drain at the owner
# (chrome flow id = the deposit tag's 39-bit (origin << 32 | counter)
# sequence, identical on both sides of the wire).
_FLOW_DEPOSIT = "WIN_DEPOSIT"


class _LocalWinHost:
    """Controller-local scalar state: versions, push-sum p, rank mutexes.

    Single-controller deployments keep the reference's cross-process
    protocols (version windows mpi_controller.cc:1281-1393, fetch-and-op
    mutexes mpi_controller.cc:1532-1602) as plain host memory — every rank
    lives in this process, so process-local IS globally consistent.
    """

    def __init__(self, name: str, n: int, d_max: int) -> None:
        self.n = n
        self.d_max = d_max
        self.version = np.zeros((n, d_max), np.int64)
        self.p = np.ones(n, np.float64)
        self.p_mail = np.zeros((n, d_max), np.float64)
        self.mutexes = [threading.RLock() for _ in range(n)]

    def bump_version(self, dst: int, k: int, force: bool = False) -> None:
        self.version[dst, k] += 1

    def bump_versions(self, pairs, force: bool = False,
                      delta: int = 1) -> None:
        for dst, k in pairs:
            self.version[dst, k] += delta

    def reset_versions(self, pairs) -> None:
        for dst, k in pairs:
            self.version[dst, k] = 0

    def get_version(self, dst: int, k: int) -> int:
        return int(self.version[dst, k])

    def get_versions(self, pairs) -> List[int]:
        return [int(self.version[dst, k]) for dst, k in pairs]

    def read_p(self) -> np.ndarray:
        return self.p.copy()

    def read_p_owned(self) -> Dict[int, float]:
        return {r: float(self.p[r]) for r in range(self.n)}

    def read_p_mail_owned(self) -> Dict[int, np.ndarray]:
        return {r: self.p_mail[r].copy() for r in range(self.n)}

    def write_p_entries(self, entries: Dict[int, float]) -> None:
        for r, v in entries.items():
            self.p[r] = v

    def write_p_mail_rows(self, rows: Dict[int, np.ndarray]) -> None:
        for r, v in rows.items():
            self.p_mail[r] = np.asarray(v, np.float64)

    def write_p(self, values: np.ndarray) -> None:
        self.p = np.asarray(values, np.float64).copy()

    def read_p_mail(self) -> np.ndarray:
        return self.p_mail.copy()

    def write_p_mail(self, values: np.ndarray) -> None:
        self.p_mail = np.asarray(values, np.float64).copy()

    def add_p_mail(self, dst: int, k: int, v: float) -> None:
        self.p_mail[dst, k] += v

    def set_p_mail(self, dst: int, k: int, v: float) -> None:
        self.p_mail[dst, k] = v

    def mutex_acquire(self, rank: int) -> None:
        self.mutexes[rank].acquire()

    def mutex_release(self, rank: int) -> None:
        self.mutexes[rank].release()

    def op_mutex_ranks(self, touched) -> List[int]:
        """Which of the touched ranks' mutexes THIS controller takes for an op."""
        return sorted(set(touched))

    def flush(self) -> None:
        pass


class _ControlPlaneWinHost:
    """Shared scalar state over the native TCP control plane.

    Multi-controller deployments (one process per host) keep window versions,
    push-sum p scalars, and rank mutexes in the job-wide control-plane server
    (csrc/bf_runtime.cc) — the analog of the reference's MPI RMA windows for
    these scalars. Writes are ownership-partitioned: only the controller
    hosting rank r's shard writes r's scalars (all controllers execute the
    same SPMD op sequence, so owner-writes gives exactly-once updates);
    ``flush`` barriers all controllers so reads after an op are consistent.
    """

    def __init__(self, name: str, n: int, d_max: int, owned: Sequence[int]) -> None:
        self.n = n
        self.d_max = d_max
        self.owned = set(owned)
        # May be a plain ControlPlaneClient or (sharded deployments) a
        # ShardRouter — the whole window plane is deliberately routing-
        # agnostic: scalars, mutexes, deposits, and drains address keys,
        # and the router owns key -> shard placement + failover
        # (docs/fault_tolerance.md, "Control-plane sharding & failover").
        self._cl = _cp.client()
        self._pre = f"w.{name}"
        # A quarantined rejoiner starts with ZERO push-sum mass: its old
        # mass died with its previous incarnation, and minting a fresh p=1
        # here would inflate the job's total — the donor mass split
        # (optimizers._PushSumRejoin) installs its share instead. It also
        # must not barrier (the aligned-creation flush below): survivors
        # are mid-loop and will never arrive.
        from ..runtime.heartbeat import quarantine_pending

        rejoining = quarantine_pending()
        p_init = 0.0 if rejoining else 1.0
        # The server lock is re-entrant per client rank but NOT
        # recursion-counted (first unlock fully releases, csrc/bf_runtime.cc
        # kUnlock). Count recursion locally so a require_mutex op nested in a
        # user win_mutex cannot release the user's lock mid-context. Each
        # rank's depth transitions AND its server lock/unlock happen under
        # one per-rank gate: a second local thread must not treat depth>0 as
        # "held" while the first is still blocked in the server lock call,
        # and must not start a fresh server acquire while a release is
        # between its depth write and its server unlock (ADVICE r3, medium).
        self._mu_depth: Dict[int, int] = {}
        self._mu_gates: Dict[int, threading.Lock] = {}
        self._mu_depth_lock = threading.Lock()
        for dst in self.owned:
            _cp.put_float(self._cl, f"{self._pre}.p.{dst}", p_init)
            for k in range(d_max):
                self._cl.put(f"{self._pre}.v.{dst}.{k}", 0)
                _cp.put_float(self._cl, f"{self._pre}.m.{dst}.{k}", 0.0)
        if not rejoining:
            self.flush()

    def bump_version(self, dst: int, k: int, force: bool = False) -> None:
        # ``force``: origin-side bump in the hosted (one-sided) plane — slot
        # (dst, k) maps 1:1 to a source rank, so the origin may bump a
        # non-owned destination's counter without write contention.
        if force or dst in self.owned:
            self._cl.fetch_add(f"{self._pre}.v.{dst}.{k}", 1)

    def bump_versions(self, pairs, force: bool = False,
                      delta: int = 1) -> None:
        """Batched bump: n touched edges, ONE pipelined round-trip (ADVICE
        r3: the per-edge fetch_add re-introduced n-scaling latency on the
        hosted hot path). ``delta=-1`` is the rollback path for deposits
        that never landed."""
        keys = [f"{self._pre}.v.{dst}.{k}" for dst, k in pairs
                if force or dst in self.owned]
        if keys:
            self._cl.fetch_add_many(keys, deltas=[delta] * len(keys))

    def reset_versions(self, pairs) -> None:
        keys = [f"{self._pre}.v.{dst}.{k}" for dst, k in pairs
                if dst in self.owned]
        if keys:
            self._cl.put_many(keys, [0] * len(keys))

    def get_version(self, dst: int, k: int) -> int:
        return int(self._cl.get(f"{self._pre}.v.{dst}.{k}"))

    def get_versions(self, pairs) -> List[int]:
        return [int(v) for v in self._cl.get_many(
            [f"{self._pre}.v.{dst}.{k}" for dst, k in pairs])]

    @staticmethod
    def _bits_to_float(v: int) -> float:
        import struct as _st
        return _st.unpack("<d", _st.pack("<q", v))[0]

    @staticmethod
    def _float_to_bits(v: float) -> int:
        import struct as _st
        return _st.unpack("<q", _st.pack("<d", float(v)))[0]

    def read_p(self) -> np.ndarray:
        vals = self._cl.get_many(
            [f"{self._pre}.p.{r}" for r in range(self.n)])
        return np.array([self._bits_to_float(v) for v in vals])

    def read_p_owned(self) -> Dict[int, float]:
        """Batched read of only this controller's ranks (the hosted hot
        path: one pipelined round-trip, no n-scaling)."""
        owned = sorted(self.owned)
        vals = self._cl.get_many([f"{self._pre}.p.{r}" for r in owned])
        return {r: self._bits_to_float(v) for r, v in zip(owned, vals)}

    def read_p_mail_owned(self) -> Dict[int, np.ndarray]:
        owned = sorted(self.owned)
        keys = [f"{self._pre}.m.{r}.{k}"
                for r in owned for k in range(self.d_max)]
        vals = self._cl.get_many(keys)
        out: Dict[int, np.ndarray] = {}
        i = 0
        for r in owned:
            out[r] = np.array([self._bits_to_float(v)
                               for v in vals[i:i + self.d_max]])
            i += self.d_max
        return out

    def write_p_entries(self, entries: Dict[int, float]) -> None:
        items = sorted(entries.items())
        self._cl.put_many([f"{self._pre}.p.{r}" for r, _ in items],
                          [self._float_to_bits(v) for _, v in items])

    def write_p_mail_rows(self, rows: Dict[int, np.ndarray]) -> None:
        keys, vals = [], []
        for r in sorted(rows):
            for k in range(self.d_max):
                keys.append(f"{self._pre}.m.{r}.{k}")
                vals.append(self._float_to_bits(float(rows[r][k])))
        self._cl.put_many(keys, vals)

    def write_p(self, values: np.ndarray) -> None:
        for r in self.owned:
            _cp.put_float(self._cl, f"{self._pre}.p.{r}", float(values[r]))

    def read_p_mail(self) -> np.ndarray:
        out = np.zeros((self.n, self.d_max), np.float64)
        for r in range(self.n):
            for k in range(self.d_max):
                out[r, k] = _cp.get_float(self._cl, f"{self._pre}.m.{r}.{k}")
        return out

    def write_p_mail(self, values: np.ndarray) -> None:
        for r in self.owned:
            for k in range(self.d_max):
                _cp.put_float(self._cl, f"{self._pre}.m.{r}.{k}",
                              float(values[r, k]))

    def add_p_mail(self, dst: int, k: int, v: float) -> None:
        if dst in self.owned:
            key = f"{self._pre}.m.{dst}.{k}"
            _cp.put_float(self._cl, key, _cp.get_float(self._cl, key) + v)

    def set_p_mail(self, dst: int, k: int, v: float) -> None:
        if dst in self.owned:
            _cp.put_float(self._cl, f"{self._pre}.m.{dst}.{k}", v)

    def _mu_gate(self, rank: int) -> threading.Lock:
        with self._mu_depth_lock:
            gate = self._mu_gates.get(rank)
            if gate is None:
                gate = self._mu_gates[rank] = threading.Lock()
            return gate

    def mutex_acquire(self, rank: int) -> None:
        # The gate is held ACROSS the blocking server call: a second local
        # thread arriving mid-acquire waits here (equivalent to waiting on
        # the server) instead of seeing depth>0 and entering the
        # "mutex-protected" region before the lock is actually granted.
        from ..runtime.native import PeerLostError

        with self._mu_gate(rank):
            depth = self._mu_depth.get(rank, 0)
            if depth == 0:
                try:
                    # bfcheck: ok-blocking-under-lock (the gate exists to
                    # serialize local threads THROUGH this server acquire;
                    # waiting on the gate is equivalent to waiting on the
                    # server, and the gate is per-rank so nothing else
                    # stalls)
                    self._cl.lock(f"{self._pre}.mu.{rank}")
                except PeerLostError as exc:
                    # typed + attributed: the caller (window optimizers'
                    # self-healing retry, or user code) learns WHICH rank's
                    # mutex had a dead holder; the lock itself was left
                    # free, so a retried acquire succeeds.
                    raise PeerLostError(
                        f"window mutex for rank {rank}: holder died "
                        f"mid-hold ({exc.args[0] if exc.args else exc}); "
                        "re-acquire to continue on the shrunken topology",
                        dead=exc.dead) from exc
            self._mu_depth[rank] = depth + 1

    def mutex_release(self, rank: int) -> None:
        # Same gate across the unlock: a fresh acquirer cannot slip in
        # between the depth write and the server unlock (the server lock is
        # re-entrant per controller, so it would be granted instantly and
        # then released out from under the new holder).
        from ..runtime.native import PeerLostError

        with self._mu_gate(rank):
            depth = self._mu_depth.get(rank, 0) - 1
            if depth < 0:
                raise RuntimeError(f"mutex for rank {rank} released more "
                                   "times than acquired")
            self._mu_depth[rank] = depth
            if depth == 0:
                try:
                    self._cl.unlock(f"{self._pre}.mu.{rank}")
                except PeerLostError as exc:
                    # The lock was force-released OUT FROM UNDER this
                    # holder (lease expiry, or our connection dropped and
                    # transparently reconnected mid-hold): the exclusion
                    # this critical section assumed may have been broken.
                    # Release paths run in finally blocks — raising here
                    # would mask the section's actual result — so warn
                    # loudly instead; the data-plane protocols tolerate
                    # the advisory race (module header) and the next
                    # acquire starts a clean epoch.
                    logger.warning(
                        "window mutex for rank %d was force-released while "
                        "held (%s): exclusion may have been broken for the "
                        "section just completed", rank, exc)

    def op_mutex_ranks(self, touched) -> List[int]:
        # Owner-partitioned: each controller locks only the touched ranks it
        # owns. Owned sets are disjoint, so the collective op cannot deadlock
        # between controllers, yet an external mutex holder still excludes it.
        return sorted(set(touched) & self.owned)

    def flush(self) -> None:
        _cp.barrier(self._pre)


def _win_acc_dtype(dtype):
    """Accumulation dtype for weighted mailbox math.

    Fractional edge weights demand float arithmetic even for integer
    windows (the replaced eager implementation got this from JAX's weak
    python-float promotion); low-precision floats accumulate in f32.
    """
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return jnp.float32
    return jnp.float32 if dtype.itemsize < 4 else dtype


class _GraphLayout:
    """Static decomposition of the window's edge set into circulant shifts."""

    def __init__(self, topology, n: int) -> None:
        self.n = n
        self.in_nbrs = {
            r: topology_util.in_neighbor_ranks(topology, r) for r in range(n)
        }
        self.out_nbrs = {
            r: topology_util.out_neighbor_ranks(topology, r) for r in range(n)
        }
        self.d_max = max((len(v) for v in self.in_nbrs.values()), default=0) or 1
        shifts = sorted({
            (dst - src) % n
            for dst, srcs in self.in_nbrs.items() for src in srcs
        })
        self.shifts: Tuple[int, ...] = tuple(shifts)
        self.shift_index = {s: i for i, s in enumerate(shifts)}
        S = max(len(shifts), 1)
        # slot[si, dst] = mailbox slot of src=(dst-si_shift)%n at dst; 0 when
        # the edge doesn't exist (guarded by a zero active mask at runtime).
        self.slot = np.zeros((S, n), np.int32)
        self.has_edge = np.zeros((S, n), bool)
        self.slot_of = {
            r: {src: k for k, src in enumerate(self.in_nbrs[r])}
            for r in range(n)
        }
        for si, s in enumerate(shifts):
            for dst in range(n):
                src = (dst - s) % n
                k = self.slot_of[dst].get(src)
                if k is not None:
                    self.slot[si, dst] = k
                    self.has_edge[si, dst] = True


_legacy_plane_warned = False


def _plane_policy() -> Tuple[str, Optional[bool]]:
    """Resolve the window-plane policy: ``(policy, hosted_forced)``.

    ``policy`` is ``BLUEFOG_WIN_PLANE`` — ``auto`` (per-edge planner over a
    hosted window), ``compiled`` (collective plane forced), or ``hosted``
    (mailbox plane forced, planner off: the r6/r7 wire bit for bit).
    ``hosted_forced`` overrides the window-plane default (hosted iff
    multi-controller): True/False force it, None keeps the default.

    The legacy ``BLUEFOG_WIN_HOST_PLANE`` knob is an alias: ``1`` maps to
    ``hosted`` and ``0`` to ``compiled`` (with a one-time deprecation
    warning), so every existing config keeps its exact pre-planner
    behavior. When BOTH knobs are set, the new knob's policy wins while
    the legacy knob still forces window hostedness — that combination
    (``BLUEFOG_WIN_PLANE=auto BLUEFOG_WIN_HOST_PLANE=1``) is how a
    single-controller harness gets a hosted window WITH the planner, the
    shape the hybrid bench and equivalence tests run (docs/window_planes.md).
    """
    global _legacy_plane_warned
    raw = knob_env("BLUEFOG_WIN_PLANE")
    legacy = knob_env("BLUEFOG_WIN_HOST_PLANE")  # True / False / None
    if raw:
        raw = str(raw).lower()
        if raw not in ("auto", "compiled", "hosted"):
            logger.warning(
                "BLUEFOG_WIN_PLANE=%r is not auto|compiled|hosted; "
                "treating it as auto", raw)
            raw = "auto"
        if raw == "hosted":
            return "hosted", True
        if raw == "compiled":
            return "compiled", False
        return "auto", legacy
    if legacy is not None:
        if not _legacy_plane_warned:
            _legacy_plane_warned = True
            logger.warning(
                "BLUEFOG_WIN_HOST_PLANE is deprecated: use "
                "BLUEFOG_WIN_PLANE=%s instead (see MIGRATION.md; the "
                "legacy knob keeps its exact pre-r13 behavior — it also "
                "pins the per-edge plane planner OFF)",
                "hosted" if legacy else "compiled")
        return ("hosted" if legacy else "compiled"), legacy
    return "auto", None


def _hosted_mode_enabled(policy: Optional[Tuple[str, Optional[bool]]] = None
                         ) -> bool:
    """Whether new windows use the hosted (host-tensor-transport) data plane.

    Default policy: ON for multi-controller jobs with a control plane (the
    deployments where the collective plane's all-controllers-must-dispatch
    contract breaks asynchrony), OFF for single-controller (the compiled
    ppermute plane is strictly faster on-device and the controller owns all
    ranks anyway). ``BLUEFOG_WIN_PLANE`` / the legacy
    ``BLUEFOG_WIN_HOST_PLANE`` force either way (:func:`_plane_policy`).
    """
    if not _cp.active():
        return False
    _, forced = policy if policy is not None else _plane_policy()
    if forced is not None:
        return forced
    return _cp.world() > 1


def _owned_rows(tensor, owned) -> Dict[int, np.ndarray]:
    """Extract this controller's rank rows of a rank-stacked tensor as numpy.

    Works for host arrays, fully-addressable device arrays, and
    multi-controller global arrays (via addressable_shards)."""
    if isinstance(tensor, jax.Array) and not tensor.is_fully_addressable:
        rows: Dict[int, np.ndarray] = {}
        for shard in tensor.addressable_shards:
            idx = shard.index[0]
            r0 = idx.start or 0
            data = np.asarray(shard.data)
            for i in range(data.shape[0]):
                rows[r0 + i] = data[i]
        missing = set(owned) - set(rows)
        if missing:
            raise ValueError(
                f"input tensor is missing addressable rows for owned ranks "
                f"{sorted(missing)}")
        return {r: rows[r] for r in owned}
    host = np.asarray(tensor)
    return {r: np.array(host[r]) for r in owned}


class Window:
    """Mailbox state for one named window over the current topology.

    Two data planes:

    * **collective** (single-controller default): one compiled SPMD program
      per op — ppermute per circulant shift, on-device mailbox blend.
    * **hosted** (multi-controller default; the reference's one-sided
      semantics): tensors move through the control-plane server's bulk-bytes
      mailboxes (csrc/bf_runtime.cc kAppendBytes/kTakeBytes). An origin
      controller deposits into a remote rank's server mailbox and returns —
      the target drains deposits at ITS next win_update, so a slow or
      sleeping controller never blocks a fast one (the property the
      reference gets from passive-target MPI_Win_lock RMA,
      mpi_controller.cc:953-1034, and its NCCL passive-recv thread,
      nccl_controller.cc:1113-1238). Each rank's current window tensor is
      also published to the server (the "exposed window" copy) so win_get
      stays one-sided.
    """

    def __init__(self, name: str, tensor, zero_init: bool) -> None:
        st = _global_state()
        self.name = name
        self.size = st.size
        # Edges are frozen at creation time, like MPI_Win_create against the
        # GRAPH communicator; topology changes are rejected while windows
        # exist (state.set_topology).
        self.layout = _GraphLayout(st.topology, st.size)
        self.in_neighbors = self.layout.in_nbrs
        self.out_neighbors = self.layout.out_nbrs
        d = self.layout.d_max
        # Mailboxes for integer windows store floats: weighted contributions
        # stay exact until win_update casts the combined result back.
        self.dtype = jnp.dtype(tensor.dtype)
        mail_dtype = self.dtype if jnp.issubdtype(self.dtype, jnp.floating) \
            else jnp.dtype(jnp.float32)
        self.mail_dtype = mail_dtype
        self.row_shape = tuple(tensor.shape[1:])
        # Collective-plane mailboxes carry one extra SCRATCH slot (index
        # d_max): the compiled exchange redirects inactive-edge writes there
        # so the put path stays write-only (see _exchange_fn). The hosted
        # plane's host-side rows don't need it.
        mail_shape = (st.size, d + 1) + self.row_shape
        policy = getattr(st, "win_plane", None) or _plane_policy()
        self.plane = policy[0]
        self.hosted = _hosted_mode_enabled(policy)
        # Wire codec (ISSUE r15, docs/compression.md): resolved once per
        # window from the registry knob. On the hosted plane it transforms
        # every deposit payload (and the matching local folds, so a
        # single-controller hosted harness sees the same numerics as a
        # cross-controller wire); on the compiled plane the quantization
        # codecs apply through the mail-dtype blend (codec.quantize_blend)
        # while top-k — index records over a dense exchange — does not.
        # None keeps the legacy wire byte-identical (test-pinned).
        # Per-edge overrides (ISSUE r16, docs/self_tuning.md): the grammar
        # extends to ``<spec>(;<src>><dst>=<spec>)*`` and the tuner mutates
        # the override map at runtime via set_edge_codec; an empty map
        # keeps every path byte-identical to the window-level codec.
        self.codec, _edge_over = _wire_codec.resolve_edge_spec(
            knob_env("BLUEFOG_WIN_CODEC"))
        self._edge_codec: Dict[Tuple[int, int],
                               Optional[_wire_codec.WireCodec]] = \
            dict(_edge_over)
        # Sharded window plane (ISSUE r17, docs/sharded_windows.md): when
        # a window carries rotating shard rows, the optimizer binds the
        # shard factor and advances the active shard index every gossip
        # step. Deposits then carry the shard index on the wire so an
        # owner whose rotation drifted from an origin's NEVER folds a
        # different shard's coordinates into its slots (the value is
        # dropped with a counter; the exact-mass p contribution still
        # folds). factor 1 / shard -1 keeps the legacy wire byte-identical.
        self.shard_factor = 1
        self.active_shard = -1
        # Error-feedback state (top-k): one acc-dtype row per owned source
        # rank, held next to the fused flat window the optimizers pack
        # (optimizers._WindowOptimizer). `_ef_rows` is the residual/unsent
        # gap; `_ef_ref` is the put-mode CHOCO estimate x̂ — seeded below
        # from the creation-time rows so it starts aligned with the
        # mailbox slots' initial copies (zero_init windows start at 0).
        self._ef_rows: Dict[int, np.ndarray] = {}
        self._ef_ref: Dict[int, np.ndarray] = {}
        # Per-edge estimator state (ISSUE r16): edges carrying a codec
        # override keep their OWN residual/reference rows keyed (src, dst)
        # — the shared per-src state above stays byte-identical for every
        # edge still on the window codec. A missing ref for an EF-put edge
        # means "needs rebase": the next send ships the full row through
        # the codec's state fallback as a PUT (see _encode_edge).
        self._ef_edge_rows: Dict[Tuple[int, int], np.ndarray] = {}
        self._ef_edge_ref: Dict[Tuple[int, int], np.ndarray] = {}
        # Scalar protocols (versions / push-sum p / mutexes): controller-local
        # host memory, or the job-wide control plane when one is attached
        # (multi-controller; reference mpi_controller.cc:1281-1393, 1532-1602).
        if _cp.active():
            # st.process_index, not argless jax.process_index(): the mesh's
            # backend may not be the default backend (state.py init).
            owned = _cp.owned_ranks(st.devices, st.process_index)
            self.host = _ControlPlaneWinHost(name, st.size, self.layout.d_max,
                                             owned)
        else:
            owned = list(range(st.size))
            self.host = _LocalWinHost(name, st.size, self.layout.d_max)
        self.owned = sorted(owned)
        # Per-edge plane planner (hosted windows under the auto policy
        # only): decides which frozen edges ride the compiled fast path
        # and which stay on the mailbox residual (ops/plan.py).
        self._planner = None
        self._local_mesh = None
        self._hybrid_cache: Dict[Tuple, object] = {}
        if self.hosted and self.plane == "auto":
            from .plan import PlanePlanner

            min_mb = knob_env("BLUEFOG_WIN_PLAN_MIN_MB") or 0.0
            self._planner = PlanePlanner(
                st.size,
                [(src, dst) for dst, srcs in self.in_neighbors.items()
                 for src in srcs],
                {r: getattr(st.devices[r], "process_index", 0)
                 for r in range(st.size)},
                row_bytes=int(np.prod(self.row_shape, dtype=np.int64))
                * self.dtype.itemsize,
                min_bytes=int(float(min_mb) * (1 << 20)),
                # the codec shrinks every hosted deposit, so the planner's
                # static size floor must judge POST-codec bytes — measured
                # attribution (already on-wire) overrides this estimate
                wire_scale=(self.codec.nominal_ratio
                            if self.codec is not None else 1.0))
            # per-edge overrides shrink (or restore) individual edges: the
            # planner's floor must judge each edge's own on-wire bytes
            for _e, _c in self._edge_codec.items():
                self._planner.set_edge_scale(
                    _e, _c.nominal_ratio if _c is not None else 1.0)

        if self.hosted:
            # defensive: discard any deposit records a crashed predecessor
            # window of the same name left on the server
            cl = _cp.client()
            for r in self.owned:
                for k in range(self.layout.d_max):
                    while cl.take_bytes(self._dep_key(r, k)):
                        pass
            rows = _owned_rows(tensor, self.owned)
            self._rows = {r: v.astype(self.dtype) for r, v in rows.items()}
            if self.codec is not None and self.codec.error_feedback:
                acc_t = np.dtype(_win_acc_dtype(mail_dtype))
                self._ef_ref = {
                    r: (np.zeros(self.row_shape, acc_t) if zero_init
                        else self._rows[r].astype(acc_t))
                    for r in self.owned}
            # grammar-configured EF edges seed their reference exactly like
            # the window-level codec (the mailbox slots start as the same
            # creation-time copies); runtime switches instead start with no
            # ref and rebase on first send
            acc_t = np.dtype(_win_acc_dtype(mail_dtype))
            for (_s, _d), _c in self._edge_codec.items():
                if _c is not None and _c.error_feedback and _s in owned:
                    self._ef_edge_ref[(_s, _d)] = (
                        np.zeros(self.row_shape, acc_t) if zero_init
                        else self._rows[_s].astype(acc_t))
            if zero_init:
                self._mail_rows = {
                    r: np.zeros((d,) + self.row_shape, mail_dtype)
                    for r in self.owned}
            else:
                self._mail_rows = {
                    r: np.broadcast_to(
                        self._rows[r][None], (d,) + self.row_shape
                    ).astype(mail_dtype).copy()
                    for r in self.owned}
            self._publish_selves(self.owned)
            # creation is aligned across controllers (like MPI_Win_create);
            # data-plane OPS afterwards never barrier — that's the point.
            # EXCEPT for a quarantined rejoiner: the survivors are mid-loop
            # and will never arrive at a creation barrier — its window
            # joins one-sidedly and state transfer replaces the rows anyway.
            from ..runtime.heartbeat import quarantine_pending

            if not quarantine_pending():
                self.host.flush()
        else:
            sh = NamedSharding(st.mesh, P("rank"))
            if isinstance(tensor, jax.Array):
                # Device input (possibly a multi-controller global array that
                # CANNOT be materialized on the host): reshard directly, and
                # build the neighbor-buffer copy with eager device ops — every
                # controller executes the same sequence, so this is SPMD-safe.
                self._self_value = jax.device_put(tensor, sh)
                if zero_init:
                    mail = jax.device_put(np.zeros(mail_shape, mail_dtype), sh)
                else:
                    # Neighbor buffers start as a copy of the local tensor
                    # (mpi_ops.py:890-915 zero_init=False default).
                    mail = jnp.broadcast_to(
                        self._self_value[:, None], mail_shape).astype(mail_dtype)
                    mail = jax.device_put(mail, sh)
            else:
                # Host input: stage via numpy so nothing hops through the
                # DEFAULT device, which may be a different backend than the
                # window's mesh (e.g. a remote TPU while the mesh is CPU).
                host = np.asarray(tensor)
                self._self_value = jax.device_put(host, sh)
                if zero_init:
                    mail = np.zeros(mail_shape, mail_dtype)
                else:
                    mail = np.broadcast_to(host[:, None], mail_shape).astype(
                        mail_dtype)
                mail = jax.device_put(mail, sh)
            self.mail = mail
        # Serializes the whole-array read-modify-write of mail/self_value:
        # ops touching disjoint edges hold disjoint rank mutexes yet still
        # reassign the same arrays, so every op takes this lock around its
        # dispatch (the rank mutexes keep their reference semantics of
        # protecting a rank's buffers across ops).
        self.state_mu = threading.RLock()
        self._exchange_cache: Dict[Tuple, object] = {}
        self._update_cache: Dict[Tuple, object] = {}
        # Monotonic deposit sequence for the tagged wire (one counter per
        # window per controller suffices: every mailbox key has exactly one
        # writing controller and state_mu serializes its deposits).
        self._dep_seq = 0

    # -- sharded rotation (ISSUE r17) --------------------------------------

    def bind_shard(self, factor: int, start: int = 0) -> None:
        """Declare this window's rows as rotating shard rows (the window
        optimizer calls this once right after win_create)."""
        self.shard_factor = max(1, int(factor))
        self.active_shard = int(start) if self.shard_factor > 1 else -1
        _metrics.gauge("win.shard_factor").set(float(self.shard_factor))

    def set_active_shard(self, shard: int) -> None:
        """Advance the rotation (called before each sharded gossip step's
        ops; serialized against the drain by state_mu)."""
        with self.state_mu:
            self.active_shard = int(shard) % self.shard_factor

    # -- self_value: a property so both planes share the publish contract ---

    @property
    def self_value(self):
        if not self.hosted:
            return self._self_value
        return _assemble_global(self, self._rows)

    @self_value.setter
    def self_value(self, value) -> None:
        if not self.hosted:
            self._self_value = value
            return
        rows = _owned_rows(value, self.owned)
        with self.state_mu:
            for r in self.owned:
                self._rows[r] = np.asarray(rows[r]).astype(self.dtype)
            self._publish_selves(self.owned)

    # -- hosted-plane internals --------------------------------------------

    def _self_key(self, rank: int) -> str:
        return f"w.{self.name}.self.{rank}"

    def _dep_key(self, dst: int, k: int) -> str:
        return f"w.{self.name}.dep.{dst}.{k}"

    def _sidx_key(self, rank: int) -> str:
        return f"w.{self.name}.sidx.{rank}"

    def read_published_shard(self, rank: int):
        """``(row, shard_index)`` of a rank's published tensor on a
        sharded window (shard_index is None when the owner never
        published or the window is unsharded). The rejoin reassembly
        polls this across a donor's gossip steps until it has collected
        every shard (docs/sharded_windows.md)."""
        sidx = None
        if self.shard_factor > 1:
            try:
                v = int(_cp.client().get(self._sidx_key(rank)))
            except (OSError, RuntimeError):
                v = 0
            sidx = (v - 1) if v > 0 else None
        return self.read_published_row(rank), sidx

    def _publish_self(self, rank: int) -> None:
        """Refresh rank's 'exposed window' copy on the server (win_get)."""
        self._publish_selves([rank])

    def _publish_selves(self, ranks) -> None:
        """Batched publish: all owned rows in one pipelined round-trip.

        Rows go out as uint8 views (always exportable, even for ml_dtypes
        extension floats) through the native scatter-gather write — a
        100 MB publish costs zero Python-side copies, where ``tobytes()``
        duplicated every published byte (this is half the win_update wire
        traffic at ResNet scale).

        Quantization codecs (``state_codec``) compress the published copy
        too — the publish is the OTHER half of win_update's wire bytes
        and the whole of win_get's pull — behind a 4-byte magic + codec
        id header; every reader goes through :meth:`_parse_published`,
        which keeps raw rows (codec ``none``, and top-k windows, whose
        sparse records cannot carry absolute state) byte-identical."""
        ranks = list(ranks)
        if not ranks:
            return
        if self.shard_factor > 1:
            # rotation index published NEXT TO the rows (one pipelined
            # put_many): a donor/rejoiner reading a published row must
            # know WHICH shard's coordinates it carries
            _cp.client().put_many(
                [self._sidx_key(r) for r in ranks],
                [self.active_shard + 1] * len(ranks))
        # Published-state codec: the configured codec itself for the
        # quantizers, the int8 absolute-state fallback for top-k (sparse
        # records cannot carry absolute state — codec.state_codec_for),
        # raw legacy rows when no codec is configured.
        pub = _wire_codec.state_codec_for(self.codec)
        if pub is not None:
            blobs = []
            raw_b = wire_b = 0
            for r in ranks:
                enc = pub.encode(self._rows[r])
                blob = np.empty(_PUB_HDR + enc.nbytes, np.uint8)
                blob[:_PUB_HDR] = np.frombuffer(
                    struct.pack("<IBBH", _PUB_MAGIC, pub.cid, 0, 0),
                    np.uint8)
                blob[_PUB_HDR:] = enc
                blobs.append(blob)
                raw_b += self._rows[r].nbytes
                wire_b += blob.nbytes
            _metrics.counter("win.codec.raw_bytes").inc(raw_b)
            _metrics.counter("win.codec.wire_bytes").inc(wire_b)
            _cp.client().put_bytes_many(
                [self._self_key(r) for r in ranks], blobs)
            return
        _cp.client().put_bytes_many(
            [self._self_key(r) for r in ranks],
            [np.ascontiguousarray(self._rows[r]).reshape(-1).view(
                np.uint8) for r in ranks])

    def _read_remote_self(self, rank: int) -> np.ndarray:
        return self._read_remote_selves([rank])[0]

    def _parse_published(self, rank: int, buf) -> np.ndarray:
        """Published payload -> row array: a raw wire-dtype row (codec
        ``none`` / top-k — byte-identical to the legacy format) or a
        magic-prefixed codec-encoded state row (``_publish_selves``).
        The codec id comes from the PAYLOAD, never this window's env —
        origin and reader may disagree safely."""
        expect = int(np.prod(self.row_shape, dtype=np.int64)) * \
            self.dtype.itemsize
        n = len(buf)
        if n == expect:
            return np.frombuffer(buf, self.dtype).reshape(self.row_shape)
        if n > _PUB_HDR:
            magic, cid = struct.unpack_from("<IB", buf, 0)
            if magic == _PUB_MAGIC:
                count = int(np.prod(self.row_shape, dtype=np.int64))
                flat = _wire_codec.by_id(cid).decode(
                    np.frombuffer(buf, np.uint8)[_PUB_HDR:],
                    self.dtype, count)
                return flat.reshape(self.row_shape)
        raise RuntimeError(
            f"window '{self.name}': published tensor for rank "
            f"{rank} has {n} bytes, expected {expect} (raw) or an "
            "encoded-state payload")

    def _read_remote_selves(self, ranks) -> List[np.ndarray]:
        """Batched read of published tensors: one pipelined round-trip."""
        ranks = list(ranks)
        if not ranks:
            return []
        raws = _cp.client().get_bytes_many(
            [self._self_key(r) for r in ranks])
        return [self._parse_published(rank, raw)
                for rank, raw in zip(ranks, raws)]

    def _read_remote_self_view(self, rank: int):
        """One published row as a zero-copy array over the native reply.

        Returns ``(row, owner)``; the caller folds the row and then
        ``owner.close()``. Large rows arrive as concurrent byte-range
        stripes over the connection pool (``get_bytes_view``); the win_get
        pipeline additionally keeps several sources in flight at once, so
        the pool stays saturated while earlier sources fold. (Encoded
        state rows decode into a fresh array; the owner close stays the
        caller's job either way.)"""
        view, owner = _cp.client().get_bytes_view(self._self_key(rank))
        row = self._parse_published(rank, view)
        return row, owner

    def _fold_record(self, dst: int, k: int, mode: int,
                     contrib: np.ndarray) -> None:
        """Fold one deposit into the local mailbox slot (owner side).

        Same cast discipline as the compiled plane: accumulate in the acc
        dtype, cast back to the mail dtype per record. Wide-enough
        mailboxes (f32/f64 — the mail dtype IS an acc dtype) fold in one
        in-place pass instead of the cast-add-cast-store four."""
        acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
        slot = self._mail_rows[dst][k]
        if mode == _DEP_ACC:
            if np.dtype(self.mail_dtype) == acc_t:
                np.add(slot, contrib.astype(acc_t, copy=False), out=slot)
            else:
                slot[...] = (slot.astype(acc_t) +
                             contrib.astype(acc_t)).astype(self.mail_dtype)
        else:
            np.copyto(slot, contrib, casting="unsafe")

    def ef_residual(self, src: int) -> np.ndarray:
        """The error-feedback residual row for owned source ``src`` (zeros
        until the first compressed send). Held in the acc dtype so
        repeated compensate/subtract cycles never lose mass to rounding
        below the wire's own precision."""
        r = self._ef_rows.get(src)
        if r is None:
            acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
            r = self._ef_rows[src] = np.zeros(self.row_shape, acc_t)
        return r

    def ef_residual_norm(self) -> float:
        """L2 norm over every owned rank's residual (0.0 when EF is off
        or nothing compressed yet) — the ``win.codec.residual_norm``
        gauge's source."""
        if not self._ef_rows and not self._ef_edge_rows:
            return 0.0
        return float(np.sqrt(
            sum(float(np.sum(np.square(r, dtype=np.float64)))
                for r in self._ef_rows.values())
            + sum(float(np.sum(np.square(r, dtype=np.float64)))
                  for r in self._ef_edge_rows.values())))

    def ef_edge_residual_norm(self, src: int, dst: int) -> float:
        """L2 norm of one overridden edge's own residual (0.0 when the
        edge rides the window codec or nothing compressed yet) — the
        tuner's per-edge de-escalation sensor."""
        r = self._ef_edge_rows.get((int(src), int(dst)))
        if r is None:
            return 0.0
        return float(np.sqrt(np.sum(np.square(r, dtype=np.float64))))

    def codec_for(self, src: int, dst: int):
        """Effective wire codec for edge ``src -> dst``: the per-edge
        override when one is set, else the window codec."""
        try:
            return self._edge_codec[(int(src), int(dst))]
        except KeyError:
            return self.codec

    def set_edge_codec(self, src: int, dst: int, spec) -> bool:
        """Switch one edge's wire codec at runtime (the tuner's codec
        lever, ISSUE r16). ``spec`` is the single-codec grammar (``none``
        / ``int8`` / ``fp8`` / ``topk:<frac>``), a WireCodec, or None.

        Switch protocol (docs/self_tuning.md):

        * TO an error-feedback codec in put mode: the per-edge CHOCO
          reference starts absent, so the first post-switch send REBASES —
          it ships the full row through the codec's state fallback (int8)
          as a plain PUT, then both ends agree on x̂ and deltas resume
          (mailbox FIFO ordering makes this race-free).
        * AWAY from error feedback: the put-mode reference is dropped (the
          next full PUT supersedes the unsent gap); any accumulate-mode
          residual is KEPT and folded into the next send's base whatever
          the new codec, so push-sum numerator mass is never lost across
          a switch — the associated-p channel ships exact in the header
          either way.

        Returns True when the effective codec actually changed."""
        edge = (int(src), int(dst))
        new = _wire_codec.resolve(spec) if isinstance(spec, str) or \
            spec is None else spec
        cur = self.codec_for(*edge)

        def _key(c):
            return None if c is None else (c.cid, getattr(c, "frac", None))

        if _key(new) == _key(cur):
            return False
        if _key(new) == _key(self.codec):
            self._edge_codec.pop(edge, None)
        else:
            self._edge_codec[edge] = new
        if new is None or not new.error_feedback:
            self._ef_edge_ref.pop(edge, None)
        if self._planner is not None:
            self._planner.set_edge_scale(
                edge, new.nominal_ratio if new is not None else 1.0)
        _metrics.counter("win.codec.edge_switches").inc()
        return True

    def _edge_residual(self, edge: Tuple[int, int]) -> np.ndarray:
        r = self._ef_edge_rows.get(edge)
        if r is None:
            acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
            r = self._ef_edge_rows[edge] = np.zeros(self.row_shape, acc_t)
        return r

    def _edge_raw_base(self, edge: Tuple[int, int], x: np.ndarray,
                       mode: int) -> np.ndarray:
        """Send base for a raw (codec None) override edge: an accumulate
        folds any residual mass a previous EF codec left behind (exact —
        the uncompressed wire ships it all), a put supersedes it."""
        e = self._ef_edge_rows.pop(edge, None)
        if mode == _DEP_ACC and e is not None:
            return x + e
        return x

    def _encode_edge(self, edge: Tuple[int, int], x: np.ndarray, wire_t,
                     mode: int):
        """Per-edge variant of ``_encode_row`` for an overridden edge:
        ``(payload, estimate, fold_mode, wire_codec)`` against the edge's
        own estimator state. ``wire_codec`` is what actually rides the
        deposit header — normally the override itself, but a rebase send
        (see set_edge_codec) ships through the codec's state fallback."""
        codec = self._edge_codec[edge]
        acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
        fold_mode = mode
        ref = None
        if codec.error_feedback and mode == _DEP_PUT:
            ref = self._ef_edge_ref.get(edge)
            if ref is None:
                # REBASE: resync the receiver slot with a full overwrite
                # through the non-EF state codec, then track its decode as
                # the shared reference — the deltas that follow integrate
                # from exactly what the receiver folded.
                wire = _wire_codec.state_codec_for(codec)
                raw = np.ascontiguousarray(
                    x.astype(wire_t, copy=False)).reshape(-1)
                payload = wire.encode(raw)
                est = wire.decode(payload, wire_t, raw.size).astype(
                    acc_t, copy=False).reshape(self.row_shape)
                self._ef_edge_ref[edge] = est
                self._ef_edge_rows[edge] = x - est
                _metrics.counter("win.codec.edge_rebase").inc()
                _metrics.counter("win.codec.raw_bytes").inc(raw.nbytes)
                _metrics.counter("win.codec.wire_bytes").inc(payload.nbytes)
                return payload, est, _DEP_PUT, wire
            base = x - ref
            fold_mode = _DEP_ACC
        elif codec.error_feedback:
            base = x + self._edge_residual(edge)
        else:
            # non-EF codec: a leftover residual from a pre-switch EF codec
            # still folds into the next accumulate's base (mass carries);
            # its own quantization error keeps being tracked from then on
            # so numerator mass stays exact across the switch
            e = self._ef_edge_rows.get(edge) if mode == _DEP_ACC else None
            base = x if e is None else x + e
        raw = np.ascontiguousarray(
            base.astype(wire_t, copy=False)).reshape(-1)
        payload = codec.encode(raw)
        est = codec.decode(payload, wire_t, raw.size).astype(
            acc_t, copy=False).reshape(self.row_shape)
        if codec.error_feedback:
            if mode == _DEP_PUT:
                self._ef_edge_ref[edge] = ref + est
                self._ef_edge_rows[edge] = x - self._ef_edge_ref[edge]
            else:
                self._ef_edge_rows[edge] = base - est
            _metrics.gauge("win.codec.residual_norm").set(
                self.ef_residual_norm())
        elif mode == _DEP_ACC and edge in self._ef_edge_rows:
            self._ef_edge_rows[edge] = base - est
        _metrics.counter("win.codec.raw_bytes").inc(raw.nbytes)
        _metrics.counter("win.codec.wire_bytes").inc(payload.nbytes)
        _metrics.gauge("win.codec.ratio").set(
            raw.nbytes / payload.nbytes if payload.nbytes else 0.0)
        return payload, est, fold_mode, codec

    def _encode_row(self, src: int, x: np.ndarray, wire_t, mode: int):
        """Encode one source row for the wire:
        ``(payload, estimate, fold_mode)``.

        The codec encodes each row ONCE per op — the same payload feeds
        every out-edge (weights move receiver-side via the extension
        header) and the same decoded ``estimate`` feeds the local folds,
        so a single-controller hosted window and a cross-controller wire
        produce identical numerics.

        Error-feedback codecs split by op mode (docs/compression.md):

        * **put** (overwrite semantics) uses the CHOCO-SGD construction —
          ship ``C(x - x̂)`` against a sender-tracked estimate ``x̂``
          that advances by exactly the decoded increment, and fold it
          ADDITIVELY (``fold_mode`` flips to accumulate), so the mailbox
          slot integrates to the same ``x̂`` both ends agree on. A raw
          ``C(x)`` overwrite would zero the unsent coordinates every
          step — the scheme that does NOT converge for parameter gossip.
        * **accumulate** (push-sum mass) uses classic EF-SGD — ship
          ``C(x + e)``, keep ``e = (x + e) - est``: dropped numerator
          mass is delayed to later deposits, never lost, while the
          associated-p channel ships exact in the header.
        """
        codec = self.codec
        acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
        fold_mode = mode
        if codec.error_feedback and mode == _DEP_PUT:
            ref = self._ef_ref.get(src)
            if ref is None:
                ref = self._ef_ref[src] = np.zeros(self.row_shape, acc_t)
            base = x - ref
            fold_mode = _DEP_ACC
        elif codec.error_feedback:
            base = x + self.ef_residual(src)
        else:
            base = x
        raw = np.ascontiguousarray(base.astype(wire_t, copy=False)).reshape(-1)
        payload = codec.encode(raw)
        est = codec.decode(payload, wire_t, raw.size).astype(
            acc_t, copy=False).reshape(self.row_shape)
        if codec.error_feedback:
            if mode == _DEP_PUT:
                self._ef_ref[src] = ref + est
                self._ef_rows[src] = x - self._ef_ref[src]  # unsent gap
            else:
                self._ef_rows[src] = base - est
            _metrics.gauge("win.codec.residual_norm").set(
                self.ef_residual_norm())
        _metrics.counter("win.codec.raw_bytes").inc(raw.nbytes)
        _metrics.counter("win.codec.wire_bytes").inc(payload.nbytes)
        _metrics.gauge("win.codec.ratio").set(
            raw.nbytes / payload.nbytes if payload.nbytes else 0.0)
        return payload, est, fold_mode

    def _start_deposit(self, pair, rec, expect: int) -> Optional[_PendingDeposit]:
        """Parse a deposit's header record into reassembly state.

        Put-mode deposits stream straight into the mailbox slot: the wire
        dtype always equals the mail dtype (floating windows ship their own
        dtype; integer windows' mailboxes ARE the f32 acc dtype), so a put
        is a pure byte copy with no accumulation pass. Accumulate-mode
        stages into a scratch buffer and folds once complete.

        Codec deposits (mode byte's high nibble non-zero): the encoded
        payload's size differs from the row size — the extension header
        carries it — and both modes must stage (the payload is a codec
        record, not slot bytes); the fold decodes at ``_finish_deposit``.
        ``expect`` is the raw-wire payload byte count (row size in the
        wire dtype), used by legacy deposits."""
        seq = int.from_bytes(rec[:_DEP_TAG], "little") >> 24
        raw_mode, has_p, pc, nchunks = struct.unpack_from(
            "<BBdI", rec, _DEP_TAG)
        codec_id = raw_mode >> _DEP_CODEC_SHIFT
        mode = raw_mode & _DEP_MODE_MASK
        wt = 1.0
        hdr_end = _DEP_TAG + _DEP_HDR
        if codec_id:
            wt, expect = struct.unpack_from("<dQ", rec, hdr_end)
            hdr_end += _DEP_EXT
        shard = -1
        if raw_mode & _DEP_SHARD_FLAG:
            shard, = struct.unpack_from("<i", rec, hdr_end)
            hdr_end += _DEP_SHARD_EXT
        # Rotation-drift guard: a shard-carrying deposit whose index is
        # not THIS owner's active shard holds a different subspace's
        # coordinates — folding it would mix misaligned coordinates. The
        # value is discarded (the slot keeps its last same-shard content,
        # i.e. one-rotation-stale — the per-shard analog of the hosted
        # plane's usual staleness). Accumulate-mode p mass still folds so
        # push-sum conservation survives drift; put-mode p is dropped
        # with the value so the slot's (value, p) pair stays coherent
        # (see _finish_deposit). win.shard_stale_drops counts it:
        # persistent growth means a controller's comm-round counter
        # drifted (see straggler detection, docs/metrics.md).
        discard = shard >= 0 and shard != self.active_shard
        if codec_id or discard:
            staging = np.empty(expect, np.uint8)
            target = staging
        elif mode == _DEP_PUT:
            target = self._mail_rows[pair[0]][pair[1]].reshape(-1).view(
                np.uint8)
            staging = None
        else:
            staging = np.empty(expect, np.uint8)
            target = staging
        pend = _PendingDeposit(mode, has_p, pc, seq, nchunks, target,
                               staging, codec_id=codec_id, wt=wt,
                               expect=int(expect), shard=shard,
                               discard=discard)
        # compact single-record form: a header carrying payload inline
        body = rec[hdr_end:]
        if len(body):
            pend.target[:len(body)] = np.frombuffer(body, np.uint8)
            pend.hdr_len = pend.got = len(body)
        return pend

    def _place_chunk(self, pair, pend: "_PendingDeposit", idx: int,
                     body) -> None:
        """Place one continuation chunk at its deterministic offset.

        Striped senders fan a deposit's chunk records across the
        connection pool, so chunks may arrive in ANY order; the tag index
        pins each one's offset — every chunk except the last is exactly
        the sender's chunk size (learned from whichever non-last chunk
        arrives first), and the last chunk anchors to the tail. In-order
        single-stream arrival degenerates to the same math."""
        expect = pend.expect
        blen = len(body)
        off = -1
        bad = idx < 1 or idx > pend.nchunks or idx in pend.seen
        if not bad:
            if idx == pend.nchunks:
                off = expect - blen
            else:
                if pend.cap is None:
                    pend.cap = blen
                off = pend.hdr_len + (idx - 1) * pend.cap
                bad = blen != pend.cap
        if bad or off < 0 or off + blen > expect:
            raise RuntimeError(
                f"window '{self.name}': deposit chunk {idx} for (rank, "
                f"slot) {pair} of {blen} bytes does not fit the expected "
                f"{expect}-byte payload — wire corruption or a mismatched "
                "window shape across controllers")
        if blen:
            pend.target[off:off + blen] = np.frombuffer(body, np.uint8)
            pend.got += blen
        pend.seen.add(idx)

    def _finish_deposit(self, pair, pend: _PendingDeposit) -> None:
        # close the origin's flow arrow: same id the sender emitted
        # (the 39-bit (origin << 32 | counter) tag sequence)
        timeline_flow_finish(_FLOW_DEPOSIT, pend.seq)
        _metrics.counter("win.deposits_drained").inc()
        fl = _flight.recorder()
        fl.rec(_flight.FLOW_F,
               fl.intern(f"drain.{(pend.seq >> 32) & 0x7F}"),
               pend.got, pend.seq)
        if pend.discard:
            # rotation drift (see _start_deposit): accumulate-mode still
            # folds the exact p mass — push-sum conservation must survive
            # drift even when the value cannot. Put-mode drops the WHOLE
            # (value, p) pair: set_p_mail against the slot's retained
            # previous-rotation value would leave a torn pair (stale
            # value, fresh weight) that biases the combine, whereas
            # keeping both halves from the last same-shard deposit is
            # merely one rotation stale and self-consistent.
            _metrics.counter("win.shard_stale_drops").inc()
            if pend.has_p and pend.mode == _DEP_ACC:
                self.host.add_p_mail(pair[0], pair[1], pend.pc)
            return
        if pend.codec_id:
            # compressed deposit: decode the self-describing payload back
            # to a full wire-dtype row, apply the edge weight the sender
            # moved receiver-side (one encode per source row feeds every
            # out-edge), and fold — put OR accumulate — through the usual
            # acc-dtype discipline (docs/compression.md)
            wire_t = _win_wire_dtype(self.mail_dtype)
            acc_t = np.dtype(_win_acc_dtype(self.mail_dtype))
            n = int(np.prod(self.row_shape, dtype=np.int64))
            codec_obj = _wire_codec.by_id(pend.codec_id)
            # error-feedback put deposits are CHOCO deltas: integrate them
            # (the slot tracks the sender's x̂) instead of overwriting
            fold_mode = _DEP_ACC if (codec_obj.error_feedback
                                     and pend.mode == _DEP_PUT) \
                else pend.mode
            _metrics.counter("win.codec.wire_bytes_in").inc(pend.got)
            slot = self._mail_rows[pair[0]][pair[1]]
            with fl.span("win.fold", a=pend.got):
                if fold_mode == _DEP_PUT and slot.dtype == np.float32:
                    # decode STRAIGHT into the mailbox slot with the edge
                    # weight folded into the per-block scales: two passes
                    # over the row instead of decode + weight + copy
                    codec_obj.decode(pend.staging, np.float32, n,
                                     scale_mul=pend.wt,
                                     out=slot.reshape(-1))
                else:
                    flat = codec_obj.decode(pend.staging, wire_t, n,
                                            scale_mul=pend.wt)
                    contrib = flat.astype(acc_t, copy=False).reshape(
                        self.row_shape)
                    self._fold_record(pair[0], pair[1], fold_mode, contrib)
        elif pend.mode == _DEP_ACC:
            wire_t = _win_wire_dtype(self.mail_dtype)
            contrib = pend.staging.view(wire_t).reshape(self.row_shape)
            with fl.span("win.fold", a=pend.got):
                self._fold_record(pair[0], pair[1], _DEP_ACC, contrib)
        if pend.has_p:
            if pend.mode == _DEP_ACC:
                self.host.add_p_mail(pair[0], pair[1], pend.pc)
            else:
                self.host.set_p_mail(pair[0], pair[1], pend.pc)

    def _drain_deposits(self, strict: bool = False) -> None:
        """Take pending server deposits for every owned rank and fold them
        in deposit order. Called under state_mu (win_update).

        One pipelined multi-take covers every (rank, slot) mailbox per
        round (latency no longer scales with owned x d_max); rounds repeat
        while anything arrived, since the server bounds each key's reply
        (kMaxTakeReply) and chunked deposits may span rounds. A deposit
        whose continuation chunks are still in flight from a concurrently
        writing origin is held as partial state and completed by a bounded
        re-poll — never folded torn.

        **Pipelined fold** (r6): after a round that produced records, the
        NEXT round's take is issued immediately on a prefetch thread, so
        the server-side gather + socket stream of round i+1 overlaps the
        fold of round i (the fold-vs-stream split is measured by
        scripts/win_microbench.py's fold_vs_stream probe). Each record is
        a zero-copy view into the native reply buffer and is copied
        exactly once — into the mailbox slot itself for put-mode deposits
        (wire dtype == mail dtype, no accumulation pass) or an acc-mode
        staging buffer.

        **Striped reassembly + orphan discard** (r7): every record carries
        the server-prefixed deposit tag. Chunks place at their tag-index
        offset, so a striped origin's out-of-order arrivals (chunk records
        fanned across the connection pool) reassemble exactly; pendings
        are keyed per (mailbox key, seq) so interleaved deposits from
        independent origin namespaces coexist. Orphans — the tail a
        win_free/win_fence clear raced past — are recognized two ways:
        a chunk with no drained header (senders append the header before
        any chunk, so a missing header was eaten, not late), and a pending
        superseded by a newer deposit counter in its own origin namespace
        (deposits are fully appended before their successor starts).

        ``strict`` (caller holds the rank mutexes AND the job opted in via
        ``BLUEFOG_WIN_STRICT=1``): verify the write/read exclusion actually
        held — every slot with a pending deposit must show version >= 1,
        because origins bump BEFORE depositing inside their mutex-held
        region (_hosted_exchange) and the owner resets only inside its own.
        A version-0 deposit means some participant skipped
        ``require_mutex``; raising turns the silent one-update-late consume
        into a diagnosable error (reference: the version-window protocol,
        mpi_controller.cc:1281-1393, whose strict mode is MPI_Win_lock
        exclusion). Opt-in because mixed usage is legal per the reference:
        a mutex-holding updater coexisting with advisory non-mutex origins
        must not crash (the module header documents that advisory race)."""
        strict = strict and os.environ.get("BLUEFOG_WIN_STRICT") == "1"
        cl = _cp.client()
        pairs = [(r, k) for r in self.owned
                 for k in range(self.layout.d_max)]
        expect = int(np.prod(self.row_shape, dtype=np.int64)) * \
            _win_wire_dtype(self.mail_dtype).itemsize
        touched: set = set()
        # Striped origins fan one deposit's chunk records across the
        # connection pool, so records of ADJACENT deposits (and of
        # interleaved origins, each in its own tag namespace) can arrive
        # interleaved: pendings are keyed per (mailbox key, seq).
        partial: Dict[Tuple[int, int], Dict[int, _PendingDeposit]] = {}
        orphans = 0
        drain_timeout = float(os.environ.get(
            "BLUEFOG_WIN_DRAIN_TIMEOUT", "60"))

        def sweep(poll_pairs, pooled=True):
            poll_names = [self._dep_key(r, k) for r, k in poll_pairs]
            return (_Prefetch(lambda: cl.take_bytes_many_views(
                        poll_names, pooled=pooled)),
                    poll_pairs)

        drained_records = 0
        drained_bytes = 0
        # step-attribution span: the socket-sweep + reassembly leg of
        # the drain; the numpy folds inside carve themselves out via
        # nested win.fold spans (scripts/step_attribution.py subtracts
        # the overlap so the phase buckets stay disjoint)
        _fl = _flight.recorder()
        _fl.begin("win.drain")
        try:
            fetch, fetch_pairs = sweep(pairs)
            while True:
                batches, owner = fetch.result()
                cur_pairs, fetch = fetch_pairs, None
                got = any(batches)
                if got:
                    drained_records += sum(len(recs) for recs in batches)
                    drained_bytes += sum(
                        len(r) for recs in batches for r in recs)
                    # Progress: sweep everything once more, streamed WHILE the
                    # records below fold (an empty extra sweep costs one RTT).
                    # Pool the next sweep only when THIS round hauled bulk
                    # bytes: fat backlogs stripe across the connection pool,
                    # while trickle rounds stay on one pipelined connection —
                    # a pooled sweep's extra round-trips would otherwise let a
                    # fast depositor outrun the drain loop indefinitely.
                    round_bytes = sum(len(r) for recs in batches for r in recs)
                    fetch, fetch_pairs = sweep(
                        pairs,
                        pooled=round_bytes >= getattr(
                            cl, "_stripe_min", 1 << 22))
                try:
                    for pair, records in zip(cur_pairs, batches):
                        if not records:
                            continue
                        touched.add(pair)
                        pend_map = partial.get(pair)
                        if pend_map is None:
                            pend_map = partial[pair] = {}
                        # newest deposit counter seen per origin namespace this
                        # round — anything older it supersedes is orphaned
                        ns_max: Dict[int, int] = {}
                        for rec in records:
                            tag = int.from_bytes(rec[:_DEP_TAG], "little")
                            seq, idx = tag >> 24, tag & 0xFFFFFF
                            ns, ctr = seq >> 32, seq & 0xFFFFFFFF
                            prev = ns_max.get(ns)
                            if prev is None or _seq_newer(ctr, prev):
                                ns_max[ns] = ctr
                            if idx == 0:
                                if seq in pend_map:
                                    # duplicate header: impossible from the
                                    # clear race; belt-and-braces for a
                                    # corrupted peer
                                    orphans += 1
                                pend = pend_map[seq] = self._start_deposit(
                                    pair, rec, expect)
                            else:
                                pend = pend_map.get(seq)
                                if pend is None:
                                    # Orphaned continuation: every sender
                                    # appends a deposit's header before any of
                                    # its chunks reach the server (the striped
                                    # append's phase split pins this), so a
                                    # chunk whose header we never drained means
                                    # a win_free/win_fence clear ate the
                                    # deposit's prefix — discard the tail.
                                    orphans += 1
                                    continue
                                self._place_chunk(pair, pend,
                                                  idx, rec[_DEP_TAG:])
                            if pend.got == pend.expect:
                                self._finish_deposit(pair, pend)
                                del pend_map[seq]
                        # GC: per-origin deposit counters are monotonic and a
                        # deposit is fully appended before its successor starts,
                        # so a pending superseded by a NEWER counter in its own
                        # namespace can never complete — its missing records
                        # were consumed by a concurrent clear.
                        for seq_o in list(pend_map):
                            m = ns_max.get(seq_o >> 32)
                            if m is not None and _seq_newer(m, seq_o & 0xFFFFFFFF):
                                del pend_map[seq_o]
                                orphans += 1
                        if not pend_map:
                            del partial[pair]
                finally:
                    owner.close()
                if not partial:
                    if not got:
                        break  # no prefetch outstanding (got False issued none)
                    continue
                # Per-PARTIAL deadline, anchored when that chunk sequence first
                # appeared: progress on unrelated keys must not keep a torn
                # deposit alive forever (healthy gossip traffic would otherwise
                # reset a shared clock on every round).
                now = time.monotonic()
                stale = sorted({p for p, pmap in partial.items()
                                for pend in pmap.values()
                                if now - pend.t0 > drain_timeout})
                if stale:
                    raise RuntimeError(
                        f"window '{self.name}': deposit chunk sequence for "
                        f"(rank, slot) {stale} never completed within "
                        f"{drain_timeout:.0f}s — the origin died mid-deposit "
                        "(BLUEFOG_WIN_DRAIN_TIMEOUT)")
                if not got:
                    # only the keys holding partial chunk sequences can produce
                    # the awaited continuations; don't sweep owned x d_max keys
                    # 200x/s while waiting on one slow origin
                    time.sleep(0.005)
                    fetch, fetch_pairs = sweep(sorted(partial), pooled=False)
        finally:
            _fl.end("win.drain", a=drained_bytes)
        if drained_records:
            _metrics.counter("win.drain_records").inc(drained_records)
            _metrics.counter("win.drain_bytes").inc(drained_bytes)
            # counter track next to the WIN_UPDATE span that did the drain
            timeline_counter("win.drained_records", drained_records)
        if orphans:
            _metrics.counter("win.drain_orphans").inc(orphans)
            logger.debug(
                "window '%s': discarded %d orphaned deposit chunk(s) left "
                "by a concurrent clear", self.name, orphans)
        if strict and touched:
            stale = sorted(touched)
            vers = self.host.get_versions(stale)
            bad = [pair for pair, v in zip(stale, vers) if v == 0]
            if bad:
                raise RuntimeError(
                    f"window '{self.name}': deposits consumed at version 0 "
                    f"for (rank, slot) {bad} — an origin wrote without "
                    "require_mutex while this update held the rank mutex; "
                    "strict window consistency requires every participant "
                    "to pass require_mutex=True")

    def close(self, aligned: bool = True) -> None:
        """Release hosted-plane server state (win_free).

        Like MPI_Win_free, freeing is collective: the first barrier aligns
        every controller past its last data op on this window, then each
        owner discards its ranks' pending deposits and published tensors so
        a later window under the same name starts clean; the second barrier
        keeps any controller from re-creating the name mid-cleanup.

        ``aligned=False`` (the shutdown path) skips both barriers: peers may
        already be gone, and a barrier would hang teardown — the one-sided
        server cleanup (drain + clear published bytes) still runs so an
        externally shared server does not accumulate dead windows' memory."""
        if not self.hosted:
            return
        if aligned:
            self.host.flush()
        cl = _cp.client()
        names = [self._dep_key(r, k) for r in self.owned
                 for k in range(self.layout.d_max)]
        while any(cl.take_bytes_many(names)):
            pass
        cl.put_bytes_many([self._self_key(r) for r in self.owned],
                          [b""] * len(self.owned))
        if aligned:
            self.host.flush()

    # -- elastic rejoin support (hosted plane; ISSUE r9) -------------------

    def read_published_row(self, rank: int):
        """One rank's published window tensor, or None when absent or
        mis-sized (its controller never published, or is itself dead and
        its slot was cleared). The rejoin state transfer reads a donor's
        row through this — the same striped get_bytes transport win_get
        rides, reused as-is. Under a state codec the adopted row is the
        donor's quantized copy (bounded per-block error —
        docs/compression.md documents the rejoin tradeoff)."""
        raw = _cp.client().get_bytes(self._self_key(rank))
        try:
            return self._parse_published(rank, raw).copy()
        except RuntimeError:
            return None

    def install_row(self, rank: int, row) -> None:
        """Owner-write one OWNED rank's window row and publish it (the
        rejoiner installing transferred state; also the donor's half after
        a push-sum mass split)."""
        if rank not in self.owned:
            raise ValueError(f"install_row: rank {rank} is not owned here")
        with self.state_mu:
            self._rows[rank] = np.ascontiguousarray(row).astype(
                self.dtype, copy=False).copy()
            self._publish_selves([rank])

    # -- per-edge plane planner (hybrid gossip; ISSUE r13) -----------------

    def plane_partition(self, dead=frozenset(), epoch=None):
        """The planner's per-edge plane split for the current membership,
        or None when no planner is active (collective plane, forced-hosted
        plane, or a pre-``auto`` legacy config). Cached keyed on
        (edge set, dead set, membership epoch) inside the planner, so a
        gossip step pays a dict lookup, and r9's epoch fences are exactly
        the re-plan trigger."""
        if self._planner is None:
            return None
        if epoch is None:
            from ..runtime.heartbeat import membership_epoch

            epoch = membership_epoch()
        before = self._planner.rebuilds
        part = self._planner.partition(frozenset(dead), epoch)
        if self._planner.rebuilds != before:
            _metrics.counter("win.plan_rebuilds").inc()
            _metrics.gauge("win.compiled_edges").set(len(part.compiled))
            _metrics.gauge("win.hosted_edges").set(len(part.hosted))
        return part

    # -- compiled programs -------------------------------------------------

    def _exchange_fn(self, accumulate: bool, donate_source: bool = False,
                     identity_self: bool = False):
        """One-program put/get/accumulate: ppermute per shift + slot write.

        The mailbox carries one extra SCRATCH slot (index ``d_max``) so the
        put path can be pure write-only dynamic updates: an inactive edge
        redirects its write to the scratch slot instead of select-blending
        against the current slot value. Measured on the CPU mesh, any read
        of the donated mailbox inside the program (a ``jnp.where`` against
        ``cur``, a static-slice add) forces XLA into a defensive full-buffer
        copy per shift — 3-4x the whole op's cost at optimizer scale — while
        write-only updates alias in place even with a traced slot index.
        Accumulate must read the current slot by definition; it keeps the
        read-add-write per shift (and still benefits from the scratch
        redirect replacing the select).

        ``identity_self``: compile-time specialization for the all-ones
        self-weight the window optimizers pass on every put — the new self
        value IS the input, so the program skips a full window-sized
        multiply + materialize (with ``donate_source`` it aliases
        outright). ``donate_source``: the caller relinquishes the input
        buffer (the optimizer's packed fusion buffer is dead after the
        put), letting XLA reuse it instead of allocating a fresh self
        tensor.
        """
        # Quantization codecs apply to the compiled plane through the
        # mail-dtype blend (the value each edge materializes): the moved
        # payload rides the same int8/fp8 grid the hosted wire ships, so a
        # hybrid partition's two planes agree numerically. Top-k has no
        # dense-exchange analog (blend id 0 = exact legacy program).
        blend = self.codec.cid if self.codec is not None and \
            self.codec.cid in (_wire_codec.CODEC_INT8,
                               _wire_codec.CODEC_FP8) else 0
        key = ("xchg", accumulate, donate_source, identity_self, blend)
        fn = self._exchange_cache.get(key)
        if fn is not None:
            return fn
        st = _global_state()
        lay = self.layout
        n, shifts = lay.n, lay.shifts
        d_max = lay.d_max
        slot_c = np.asarray(lay.slot)  # [S, n] compile-time const

        def per_rank(x, mail, w, active, self_w):
            me = lax.axis_index("rank")
            xb = x[0]
            mb = mail[0]  # [d_max + 1, ...]; row d_max is scratch
            acc_t = _win_acc_dtype(xb.dtype)
            for si, s in enumerate(shifts):
                perm = [(i, (i + s) % n) for i in range(n)]
                moved = lax.ppermute(xb, "rank", perm)  # from (me - s) % n
                if blend:
                    moved = _wire_codec.quantize_blend(moved, blend)
                ak = active[si, me]
                # effective weight carries the active mask: an inactive
                # shift's write is redirected to the scratch slot AND its
                # payload is zeroed, so the scratch row stays finite and
                # win_update can contract the full buffer with a zero-padded
                # weight vector instead of slicing the scratch off (a partial
                # read would force the defensive copy documented above)
                wk = (w[si, me] * ak).astype(acc_t)
                k = jnp.where(ak > 0, jnp.asarray(slot_c)[si, me], d_max)
                contrib = moved.astype(acc_t) * wk
                if accumulate:
                    # accumulate in acc_t: bf16 mailboxes would otherwise
                    # round small contributions away (256 + 0.5 -> 256)
                    cur = lax.dynamic_index_in_dim(mb, k, axis=0,
                                                   keepdims=False)
                    val = (cur.astype(acc_t) + contrib).astype(mb.dtype)
                else:
                    val = contrib.astype(mb.dtype)
                mb = lax.dynamic_update_index_in_dim(mb, val, k, axis=0)
            if identity_self:
                new_self = xb
            else:
                new_self = (xb.astype(acc_t)
                            * self_w[me].astype(acc_t)).astype(xb.dtype)
            return new_self[None], mb[None]

        mapped = shard_map(
            per_rank,
            mesh=st.mesh,
            in_specs=(P("rank"), P("rank"), P(), P(), P()),
            out_specs=(P("rank"), P("rank")),
        )
        # Donate the mailbox: every caller rebinds win.mail to the output,
        # and without donation each per-shift dynamic_update materializes a
        # full mailbox copy (d_max x window bytes x shifts of pure memcpy —
        # the dominant cost of a collective-plane win_put at optimizer
        # scale). With donation XLA updates the buffer in place.
        donate = (0, 1) if donate_source else (1,)
        fn = jax.jit(mapped, donate_argnums=donate)
        self._exchange_cache[key] = fn
        return fn

    def _update_fn(self, reset: bool = False):
        """One-program combine: out = sw*self + nw . mail, + slot reset.

        Specialized on ``reset``: the no-reset variant returns the mailbox
        STRUCTURALLY unchanged, which — with the mailbox donated — lets XLA
        alias the output to the input (zero mailbox traffic) instead of
        multiplying every slot by a traced all-ones keep mask.
        """
        key = ("upd", reset)
        fn = self._update_cache.get(key)
        if fn is not None:
            return fn
        st = _global_state()

        def per_rank(self_v, mail, sw, nw, reset_mask):
            me = lax.axis_index("rank")
            mb = mail[0]          # [d_max + 1, ...]; row d_max is scratch
            sv = self_v[0]
            acc_t = _win_acc_dtype(sv.dtype)
            # Contract the FULL buffer with a zero-padded weight vector: the
            # scratch row is guaranteed finite (_exchange_fn zeroes inactive
            # payloads), and slicing it off ([:d_max]) would be a partial
            # read of the donated buffer — the defensive-copy pathology
            # _exchange_fn documents.
            w_me = jnp.concatenate(
                [nw[me], jnp.zeros((1,), nw.dtype)]).astype(acc_t)
            combined = sw[me].astype(acc_t) * sv.astype(acc_t) + jnp.tensordot(
                w_me, mb.astype(acc_t), axes=(0, 0))
            if reset:
                keep = jnp.concatenate(
                    [1.0 - reset_mask[me], jnp.ones((1,), reset_mask.dtype)]
                ).reshape((mb.shape[0],) + (1,) * (mb.ndim - 1))
                mail_new = (mb.astype(acc_t) * keep).astype(mb.dtype)
            else:
                mail_new = mb
            return combined.astype(sv.dtype)[None], mail_new[None]

        mapped = shard_map(
            per_rank,
            mesh=st.mesh,
            in_specs=(P("rank"), P("rank"), P(), P(), P()),
            out_specs=(P("rank"), P("rank")),
        )
        fn = jax.jit(mapped, donate_argnums=(1,))
        self._update_cache[key] = fn
        return fn


# ---------------------------------------------------------------------------
# Hybrid gossip: the compiled partition's fused program (ISSUE r13)
# ---------------------------------------------------------------------------
#
# One gossip step over a hybrid window splits its frozen edge set by the
# planner's verdict (Window.plane_partition): the COMPILED partition runs as
# ONE fused shard_map/ppermute program below — the in-neighbor exchange idiom
# of ops/neighbors.py:_gather_exchange_fn, with the mailbox-slot blend and
# weighted combine of _exchange_fn/_update_fn inlined behind it — while the
# HOSTED residual keeps the mailbox deposit/drain semantics via
# _residual_update. The fused program replicates the collective plane's op
# sequence exactly (same per-shift contributions cast through the mail
# dtype, same slot-ordered tensordot combine, same self term), so an
# all-compiled partition is bit-exact against the pure collective plane —
# the equivalence tests/test_win_planes.py pins.
#
# The program runs on the controller's LOCAL mesh (its owned devices): a
# compiled edge is mesh-local by planner construction, so dispatch is
# unilateral — no cross-controller lockstep, the asynchrony the hosted plane
# exists for survives. Static inputs (perms, slots) come from the partition;
# weights stay traced, so healed re-weights never re-jit — only a partition
# change does (the BLUEFOG_WIN_PLAN_MIN_MB floor exists because that re-jit
# is the cost hosted latency is traded against).


def _hybrid_meta(win: Window, part) -> dict:
    """Static tables for one partition's fused program: the local mesh,
    global→local index map, per-shift local permutation lists (naming ONLY
    live compiled edges — no compiled program may name a dead rank), and
    the local slot table."""
    key = ("meta", part.key)
    meta = win._hybrid_cache.get(key)
    if meta is not None:
        return meta
    st = _global_state()
    owned = win.owned
    k = len(owned)
    li = {r: i for i, r in enumerate(owned)}
    lay = win.layout
    by_shift: Dict[int, list] = {}
    for (src, dst) in sorted(part.compiled):
        by_shift.setdefault((dst - src) % lay.n, []).append(
            (li[src], li[dst]))
    shifts = tuple(sorted(by_shift))
    S = max(len(shifts), 1)
    slot = np.zeros((S, k), np.int32)
    perms = []
    for si, s in enumerate(shifts):
        perms.append(tuple(sorted(by_shift[s])))
        for (ls, ld) in by_shift[s]:
            slot[si, ld] = lay.slot_of[owned[ld]][owned[ls]]
    if k == st.size:
        mesh = st.mesh
    else:
        if win._local_mesh is None:
            win._local_mesh = Mesh(
                np.array([st.devices[r] for r in owned]), ("rank",))
        mesh = win._local_mesh
    meta = {"mesh": mesh, "li": li, "shifts": shifts,
            "perms": tuple(perms), "slot": slot, "k": k}
    if len(win._hybrid_cache) > 64:
        win._hybrid_cache.clear()
    win._hybrid_cache[key] = meta
    return meta


def _hybrid_fn(win: Window, meta: dict, accumulate: bool):
    """The fused compiled-partition program, cached per (mode, perms).

    Body = _exchange_fn's per-shift mailbox blend over a FRESH zero mailbox
    + _update_fn's slot-ordered weighted combine, chained in one jit. The
    intermediate mail values round-trip through the mail dtype exactly as
    the two-program collective pair materializes them, which is what makes
    the all-compiled case bit-exact against that plane.
    """
    blend = win.codec.cid if win.codec is not None and \
        win.codec.cid in (_wire_codec.CODEC_INT8,
                          _wire_codec.CODEC_FP8) else 0
    key = ("fn", accumulate, meta["perms"], meta["k"], blend)
    fn = win._hybrid_cache.get(key)
    if fn is not None:
        return fn
    d_max = win.layout.d_max
    mail_dtype = win.mail_dtype
    slot_c = np.asarray(meta["slot"])
    perms = meta["perms"]

    def per_rank(x, w, active, sw_put, sw_upd, nw):
        me = lax.axis_index("rank")
        xb = x[0]
        acc_t = _win_acc_dtype(xb.dtype)
        mb = jnp.zeros((d_max + 1,) + xb.shape, mail_dtype)
        for si in range(len(perms)):
            moved = lax.ppermute(xb, "rank", list(perms[si]))
            if blend:
                # the compiled partition's mail-dtype blend rides the same
                # quantized grid as the hosted wire (docs/compression.md)
                moved = _wire_codec.quantize_blend(moved, blend)
            ak = active[si, me]
            wk = (w[si, me] * ak).astype(acc_t)
            # inactive (no compiled edge on this shift for me): redirect the
            # zero payload to the scratch row so real slots are write-only,
            # the same discipline as _exchange_fn
            kk = jnp.where(ak > 0, jnp.asarray(slot_c)[si, me], d_max)
            contrib = moved.astype(acc_t) * wk
            if accumulate:
                cur = lax.dynamic_index_in_dim(mb, kk, axis=0,
                                               keepdims=False)
                val = (cur.astype(acc_t) + contrib).astype(mb.dtype)
            else:
                val = contrib.astype(mb.dtype)
            mb = lax.dynamic_update_index_in_dim(mb, val, kk, axis=0)
        new_self = (xb.astype(acc_t)
                    * sw_put[me].astype(acc_t)).astype(xb.dtype)
        w_me = jnp.concatenate(
            [nw[me], jnp.zeros((1,), nw.dtype)]).astype(acc_t)
        combined = sw_upd[me].astype(acc_t) * new_self.astype(acc_t) + \
            jnp.tensordot(w_me, mb.astype(acc_t), axes=(0, 0))
        return combined.astype(xb.dtype)[None]

    mapped = shard_map(
        per_rank,
        mesh=meta["mesh"],
        in_specs=(P("rank"), P(), P(), P(), P(), P()),
        out_specs=P("rank"),
    )
    fn = jax.jit(mapped)
    win._hybrid_cache[key] = fn
    return fn


def _local_view(win: Window, meta: dict, x):
    """The rank-stacked buffer's owned rows as a local-mesh array (the
    identity when this controller owns the whole mesh)."""
    if meta["k"] == win.size:
        return x
    shards = {s.index[0].start or 0: s.data for s in x.addressable_shards}
    sh = NamedSharding(meta["mesh"], P("rank"))
    return jax.make_array_from_single_device_arrays(
        (meta["k"],) + tuple(x.shape[1:]), sh,
        [shards[r] for r in win.owned])


def _globalize(win: Window, meta: dict, local):
    """Local-mesh combined rows back to the global rank-stacked array
    (metadata-only: each controller contributes its addressable shards)."""
    st = _global_state()
    if meta["k"] == st.size:
        return local
    sh = NamedSharding(st.mesh, P("rank"))
    shards = sorted(((s.index[0].start or 0, s.data)
                     for s in local.addressable_shards), key=lambda p: p[0])
    # local row i is global rank owned[i]; reorder by global rank
    per_rank = [d for _, d in shards]
    return jax.make_array_from_single_device_arrays(
        (st.size,) + tuple(local.shape[1:]), sh, per_rank)


def _run_compiled_partition(win: Window, x, part, put_table, sw_put,
                            sw_upd, nw_table, accumulate: bool = False):
    """Run the compiled partition's fused program over the rank-stacked
    buffer ``x``. Weight inputs are global-rank keyed (the same tables the
    hosted ops take); only compiled edges contribute. Returns the combined
    per-owned-rank rows as a local-mesh device array (``_globalize`` lifts
    it back)."""
    meta = _hybrid_meta(win, part)
    li, k = meta["li"], meta["k"]
    lay = win.layout
    S = max(len(meta["perms"]), 1)
    w = np.zeros((S, k), np.float32)
    active = np.zeros((S, k), np.float32)
    shift_index = {s: i for i, s in enumerate(meta["shifts"])}
    nw_arr = np.zeros((k, lay.d_max), np.float32)
    for (src, dst) in part.compiled:
        wt = put_table.get(src, {}).get(dst)
        uw = nw_table.get(dst, {}).get(src)
        if wt is None or uw is None:
            continue  # edge dropped by the (healed) weight tables
        si = shift_index[(dst - src) % lay.n]
        w[si, li[dst]] = wt
        active[si, li[dst]] = 1.0
        nw_arr[li[dst], lay.slot_of[dst][src]] = uw
    sw_put_arr = np.asarray([sw_put[r] for r in win.owned], np.float32)
    sw_upd_arr = np.asarray([sw_upd[r] for r in win.owned], np.float32)
    fn = _hybrid_fn(win, meta, accumulate)
    fl = _flight.recorder()
    with timeline_context(win.name, "WIN_COMPILED"), \
            fl.span("win.compiled"):
        out = fn(_local_view(win, meta, x), w, active, sw_put_arr,
                 sw_upd_arr, nw_arr)
    return out, meta


def _combine_with_residual(win: Window, meta: dict, comp, rows):
    """comp (local-mesh device rows) + the hosted residual's folded rows
    (numpy per owned rank, or None when the residual contributed nothing).
    Adding exactly 0.0 would still be bit-transparent, but skipping the add
    keeps the all-compiled fast path a single program."""
    if rows is None:
        return comp
    stacked = np.stack([np.asarray(rows[r]) for r in win.owned])
    dev = jax.device_put(stacked.astype(np.dtype(comp.dtype), copy=False),
                         NamedSharding(meta["mesh"], P("rank")))
    return comp + dev


def _residual_update(win: Window, nw_table, reset: bool = False,
                     require_mutex: bool = True):
    """The hosted residual's combine leg: drain + fold pending deposits,
    then contract ONLY the residual in-edges' mailbox slots (no self term
    — the compiled program owns it). Returns ``(rows, p_sums)``: the
    per-owned-rank weighted residual contribution (numpy) and, when
    associated-p is on, the matching p-mailbox contraction. Window rows
    stay untouched (clone semantics) — the put leg's publish is the
    step's visible state."""
    st = _global_state()
    n = st.size
    lay = win.layout
    nw = np.zeros((n, lay.d_max), np.float32)
    read_mask = np.zeros((n, lay.d_max), np.float32)
    for r, wmap in nw_table.items():
        for src, wt in wmap.items():
            kslot = lay.slot_of[r][src]
            nw[r, kslot] = wt
            read_mask[r, kslot] = 1.0
    return _hosted_update(win, [0.0] * n, nw_table, nw, read_mask,
                          reset=reset, clone=True,
                          require_mutex=require_mutex, return_rows=True)


# Deposit record (hosted plane wire format):
#   i64 tag | u8 mode | u8 has_p | f64 p_contrib | u32 nchunks | payload chunk
# followed by nchunks-1 ``i64 tag | raw chunk`` continuation records on the
# same mailbox key. The tag — ``seq << 24 | record_index`` — is supplied to
# the server per record (kAppendBytesTagged) and prefixed server-side, so
# the drain can tell a deposit's first record (index 0, carries the header)
# from a continuation chunk STRUCTURALLY: after win_free/win_fence clears a
# mailbox mid-deposit, the orphaned continuation chunks that land afterwards
# are discarded by tag instead of being misparsed as headers (spurious "wire
# corruption" / 60 s drain timeouts — ADVICE r5 medium).
# Payload dtype is the WINDOW's own dtype for floating windows (VERDICT r4
# #1: acc-dtype deposits shipped 2x the bytes for bf16 windows; the
# reference's wire also carries the tensor's own dtype). Integer windows
# keep the f32 acc dtype: fractional edge weights make the weighted
# contribution non-integral, and truncating per-deposit would change the
# accumulate semantics the compiled plane defines. Chunking (size from
# BLUEFOG_MAX_WIN_SENT_LENGTH, reference mpi_controller.cc:41-46) bounds
# every control-plane message and lets a drain move in bounded rounds.
# Chunk contiguity per key is structural: a mailbox key (dst, slot) maps
# 1:1 to one source rank, whose controller serializes its deposits under
# the window state lock.
_DEP_PUT = 0
_DEP_ACC = 1
_DEP_HDR = struct.calcsize("<BBdI")
_DEP_TAG = 8  # server-prefixed i64 tag bytes per stored record
_DEFAULT_MAX_SENT = 16 << 20
# Compressed-wire extension (ISSUE r15, docs/compression.md): a codec id
# rides the HIGH NIBBLE of the header's mode byte (the legacy wire's mode
# byte is 0/1, so BLUEFOG_WIN_CODEC=none stays byte-identical — pinned).
# When the nibble is non-zero, an extension header follows the base one:
#   f64 edge weight | u64 encoded payload bytes
# The weight moves receiver-side because the codec encodes each source ROW
# once (one encode feeds every out-edge — and, for top-k, one
# error-feedback residual per row); the payload itself is the codec's
# self-describing record (ops/codec.py), so its length differs from the
# row size and the drain completes it by the header's byte count.
_DEP_MODE_MASK = 0x07
_DEP_CODEC_SHIFT = 4
_DEP_EXT = struct.calcsize("<dQ")
# Sharded-rotation extension (ISSUE r17, docs/sharded_windows.md): bit 3
# of the mode byte's low nibble flags a shard-carrying deposit; an i32
# shard index follows the base header (after the codec extension when
# both ride). The legacy wire never sets the bit (mode byte low nibble is
# 0/1 there), so unsharded windows stay byte-identical.
_DEP_SHARD_FLAG = 0x08
_DEP_SHARD_EXT = struct.calcsize("<i")
# Published-row ("exposed window") state-codec framing: raw rows have no
# header (the legacy format, length == row bytes); encoded rows carry
# u32 magic | u8 codec id | 3 reserved bytes, then the self-describing
# codec payload. Readers dispatch on length + magic (_parse_published).
_PUB_MAGIC = 0x43575642  # "BVWC"
_PUB_HDR = struct.calcsize("<IBBH")


def _deposit_tags(seq: int, nrec: int, origin: int = 0) -> List[int]:
    """Per-record int64 tags for one deposit: ``seq << 24 | record_index``.

    The 39-bit seq field namespaces a 32-bit per-origin deposit counter
    under a 7-bit origin id (``origin << 32 | counter``): the drain's
    supersession GC compares counters only within one origin's namespace,
    so interleaved writers (one per controller in the multi-origin stress
    shape) cannot orphan each other's in-flight deposits. The counter
    wraps modularly (uniqueness only matters between ADJACENT deposits on
    one key); 24 index bits cover rows up to ~1 PB at the 64 KiB chunk
    floor."""
    base = (((origin & 0x7F) << 32) | (seq & 0xFFFFFFFF)) << 24
    return [base | (i & 0xFFFFFF) for i in range(nrec)]


def _seq_newer(a: int, b: int) -> bool:
    """Modular 32-bit counter comparison: is ``a`` strictly newer than
    ``b``? (Wrap-safe for the per-origin deposit counters.)"""
    return a != b and ((a - b) & 0xFFFFFFFF) < (1 << 31)


class _Prefetch:
    """Run ``fn()`` on a worker thread; ``result()`` joins and re-raises.

    The drain/get pipelines use it to stream the NEXT server reply while
    the current one folds — ctypes releases the GIL inside the native
    call and numpy releases it for bulk copies, so the overlap is real."""

    __slots__ = ("_t", "_r", "_e")

    def __init__(self, fn) -> None:
        self._r = self._e = None

        def run():
            try:
                self._r = fn()
            except BaseException as exc:  # noqa: BLE001 — re-raised in result
                self._e = exc

        self._t = threading.Thread(target=run, name="bf-win-prefetch",
                                   daemon=True)
        self._t.start()

    def result(self):
        self._t.join()
        if self._e is not None:
            raise self._e
        return self._r


class _PendingDeposit:
    """Reassembly state for one in-flight deposit on one mailbox key.

    Chunks copy straight into ``target`` as they arrive (a flat uint8 view
    of the destination): the mailbox slot itself for put-mode deposits —
    the wire dtype IS the mail dtype, so a put needs no accumulation pass
    at all — or a staging buffer for accumulate-mode, folded once complete.
    This replaces the r5 join-then-frombuffer-then-cast fold (three full
    copies of every drained byte) with one copy per byte. Chunks land at
    their tag-index offset (``_place_chunk``), so a striped origin's
    out-of-order arrivals reassemble exactly; completion is by byte count."""

    __slots__ = ("mode", "has_p", "pc", "seq", "nchunks", "cap", "hdr_len",
                 "got", "seen", "staging", "target", "t0", "codec_id", "wt",
                 "expect", "shard", "discard")

    def __init__(self, mode: int, has_p: int, pc: float, seq: int,
                 nchunks: int, target: np.ndarray, staging,
                 codec_id: int = 0, wt: float = 1.0,
                 expect: int = 0, shard: int = -1,
                 discard: bool = False) -> None:
        self.mode = mode
        self.has_p = has_p
        self.pc = pc
        self.seq = seq
        self.nchunks = nchunks
        self.cap = None      # sender chunk size, learned from any non-last
        self.hdr_len = 0     # bytes carried inline by the header record
        self.got = 0
        self.seen: set = set()  # chunk indices already placed
        self.target = target    # flat uint8 view, len == expected bytes
        self.staging = staging  # acc/codec staging array (None for put)
        self.codec_id = codec_id  # wire codec (0 = legacy raw payload)
        self.wt = wt            # receiver-side edge weight (codec wire)
        self.expect = expect    # this deposit's payload byte count
        self.shard = shard      # rotation index on the wire (-1 = none)
        self.discard = discard  # shard mismatch: drop value, keep p
        self.t0 = time.monotonic()


def _win_wire_dtype(mail_dtype):
    # jnp.issubdtype: numpy's own issubdtype does not recognize the
    # ml_dtypes extension floats (bfloat16, float8_*) as np.floating
    d = jnp.dtype(mail_dtype)
    return np.dtype(d) if jnp.issubdtype(d, jnp.floating) else np.dtype(
        _win_acc_dtype(mail_dtype))


_sent_clamp_warned = False


def _max_sent_bytes() -> int:
    raw = os.environ.get("BLUEFOG_MAX_WIN_SENT_LENGTH")
    if raw is None:
        return _DEFAULT_MAX_SENT
    v = int(raw)
    if v < (1 << 16):
        # Unit change vs the reference (mpi_controller.cc:41-46): there the
        # knob counted ELEMENTS, here it counts BYTES. A sub-64 KiB value is
        # almost certainly a migrated element-count config (e.g. the
        # reference default 20000); warn once instead of silently chunking
        # at the clamp floor (docs/env_variables.md, MIGRATION.md).
        global _sent_clamp_warned
        if not _sent_clamp_warned:
            _sent_clamp_warned = True
            logger.warning(
                "BLUEFOG_MAX_WIN_SENT_LENGTH=%d is below the 64 KiB clamp "
                "floor and will be clamped. Note the unit changed vs the "
                "reference BlueFog: this knob now counts BYTES per wire "
                "chunk, not elements — a migrated element-count config "
                "should be multiplied by the element size (see "
                "MIGRATION.md).", v)
    return max(1 << 16, v)


def _pack_deposit(mode: int, has_p: int, pc: float, payload,
                  codec_id: int = 0, wt: float = 1.0,
                  shard: int = -1) -> List:
    """Split one deposit into its wire records: a header record followed by
    bounded payload chunks.

    ``payload`` may be ``bytes`` or any C-contiguous buffer (a numpy
    array): chunks are zero-copy memoryview slices, and the native
    scatter-gather write streams them straight from the source buffer — a
    100 MB deposit is chunked without a single Python-side copy. The drain
    completes a deposit by BYTE COUNT (the row size is known to both
    ends), so a header record carrying its payload inline (the compact
    single-record form) reassembles identically.

    ``codec_id``/``wt`` (compressed wire): the codec id joins the mode
    byte's high nibble and the extension header carries the edge weight
    plus the encoded byte count (the drain cannot derive it from the row
    size). ``codec_id=0`` emits exactly the legacy record layout.

    ``shard`` >= 0 (sharded rotation, ISSUE r17): sets the mode byte's
    shard flag and appends the i32 shard index so the owner's drain can
    reject a drifted rotation's coordinates (``shard=-1`` emits the
    legacy layout bit for bit)."""
    cap = _max_sent_bytes()
    if isinstance(payload, np.ndarray):
        # extension dtypes (ml_dtypes bf16/f8) lack the buffer protocol;
        # a uint8 view is always exportable and stays zero-copy
        payload = payload.reshape(-1).view(np.uint8)
    mv = memoryview(payload).cast("B")
    chunks = [mv[i:i + cap] for i in range(0, mv.nbytes, cap)]
    mode_byte = mode | (codec_id << _DEP_CODEC_SHIFT)
    if shard >= 0:
        mode_byte |= _DEP_SHARD_FLAG
    hdr = struct.pack("<BBdI", mode_byte, has_p, pc, len(chunks))
    if codec_id:
        hdr += struct.pack("<dQ", float(wt), mv.nbytes)
    if shard >= 0:
        hdr += struct.pack("<i", int(shard))
    return [hdr, *chunks]


def _blen(b) -> int:
    return len(b) if isinstance(b, (bytes, bytearray)) else \
        memoryview(b).nbytes


def _precheck_mailbox_cap(win: Window, dep_names, dep_blobs,
                          dep_edge_of) -> set:
    """Edges whose deposits would overflow the server mailbox byte cap.

    The cap check must happen at DEPOSIT granularity, not record
    granularity: a deposit is a header record plus payload chunks, and a
    server-side -2 in the middle of that sequence would leave a torn
    deposit the owner's drain can only time out on. The pre-check is
    race-free because each mailbox key has exactly ONE writer (slot (dst,
    k) maps 1:1 to a source rank owned by this controller) and the owner's
    drain only shrinks the box — a stale read is always conservative in
    the safe direction (pending can only have gone DOWN since).

    The cap value comes from the SERVING process (published at server
    startup under a well-known kv key) rather than this origin's local
    env: a cross-host ``BLUEFOG_CP_MAILBOX_MAX_MB`` mismatch would
    otherwise let the origin's pre-check pass while the server's real cap
    tears a multi-record deposit mid-sequence (ADVICE r5 low)."""
    cap = _cp.mailbox_cap_bytes()
    if cap <= 0:
        return set()
    sizes: Dict[str, int] = {}
    edge_of: Dict[str, Tuple[int, int, int]] = {}
    for nm, blob, edge in zip(dep_names, dep_blobs, dep_edge_of):
        # + _DEP_TAG: the server stores the tag prefix in the same box
        sizes[nm] = sizes.get(nm, 0) + _blen(blob) + _DEP_TAG
        edge_of[nm] = edge
    # a single deposit larger than the cap can NEVER land, drained or not
    # — that's a configuration error, not a dead-owner symptom; diagnose
    # it as such instead of the misleading "owner has not drained" path
    too_big = {nm: sizes[nm] for nm in sizes if sizes[nm] > cap}
    if too_big:
        worst = max(too_big.values())
        raise ValueError(
            f"window '{win.name}': a single deposit of {worst} bytes "
            f"exceeds the {cap}-byte mailbox cap for edges "
            f"{sorted(edge_of[nm] for nm in too_big)} — raise "
            "BLUEFOG_CP_MAILBOX_MAX_MB (it must exceed one full window "
            "row) or split the window tensor into smaller leaves")
    keys = sorted(sizes)
    pending = dict(zip(keys, _cp.client().box_bytes_many(keys)))
    return {edge_of[nm] for nm in keys
            if pending[nm] + sizes[nm] > cap}


def _assemble_global(win: Window, rows: Dict[int, np.ndarray]):
    """Build the rank-stacked global array from this controller's rows.

    Metadata-only across controllers: each controller contributes exactly its
    addressable shards (jax.make_array_from_single_device_arrays), so no
    cross-controller dispatch happens — the one-sided property survives the
    return path."""
    st = _global_state()
    sh = NamedSharding(st.mesh, P("rank"))
    shape = (st.size,) + win.row_shape
    if len(rows) == st.size:
        stacked = np.stack([rows[r] for r in range(st.size)])
        return jax.device_put(stacked, sh)
    shards = [
        jax.device_put(rows[r][None], st.devices[r]) for r in sorted(rows)
    ]
    return jax.make_array_from_single_device_arrays(shape, sh, shards)


def _get_window(name: str) -> Window:
    st = _global_state()
    st.check_initialized()
    win = st.windows.get(name)
    if win is None:
        raise ValueError(f"window '{name}' does not exist; call win_create first")
    return win


def _edge_weights(
    weights: Optional[Weights],
    neighbors: Dict[int, List[int]],
    default: float,
    what: str,
    size: int,
) -> Dict[int, Dict[int, float]]:
    """Normalize {peer: w} / nested / None into per-rank {rank: {peer: w}}."""
    if weights is None:
        return {r: {p: default for p in neighbors[r]} for r in range(size)}
    first = next(iter(weights.values()), None)
    if isinstance(first, dict):
        table = {r: dict(weights.get(r, {})) for r in range(size)}
        for r, wmap in table.items():
            extra = set(wmap) - set(neighbors[r])
            if extra:
                raise ValueError(
                    f"{what} for rank {r} references non-neighbor ranks "
                    f"{sorted(extra)}"
                )
    else:
        # flat {peer: w}: each rank uses the entries that name its neighbors;
        # a key that is nobody's neighbor is a typo, not a no-op (the
        # reference rejects non-neighbor keys, mpi_ops.py:1060-1063).
        all_neighbors = set().union(*neighbors.values()) if neighbors else set()
        extra = set(weights) - all_neighbors
        if extra:
            raise ValueError(
                f"{what} references ranks {sorted(extra)} that are not "
                f"neighbors of any rank under the current topology"
            )
        table = {
            r: {p: w for p, w in weights.items() if p in neighbors[r]}
            for r in range(size)
        }
    return table


def _edge_arrays(win: Window, table: Dict[int, Dict[int, float]]):
    """[S, n] weight + active arrays for an edge-weight table keyed by src."""
    lay = win.layout
    S = max(len(lay.shifts), 1)
    w = np.zeros((S, lay.n), np.float32)
    active = np.zeros((S, lay.n), np.float32)
    for src in range(lay.n):
        for dst, wt in table[src].items():
            si = lay.shift_index[(dst - src) % lay.n]
            w[si, dst] = wt
            active[si, dst] = 1.0
    return w, active


def _bump_host_state(win: Window, table: Dict[int, Dict[int, float]],
                     accumulate: bool) -> None:
    """Mirror version counters and associated-p scalars for touched edges."""
    st = _global_state()
    p = win.host.read_p() if st.win_ops_with_associated_p else None
    win.host.bump_versions(
        [(dst, win.layout.slot_of[dst][src])
         for src in range(win.size) for dst in table[src]])
    if st.win_ops_with_associated_p:
        for src in range(win.size):
            for dst, wt in table[src].items():
                k = win.layout.slot_of[dst][src]
                contrib = p[src] * wt
                if accumulate:
                    win.host.add_p_mail(dst, k, contrib)
                else:
                    win.host.set_p_mail(dst, k, contrib)


def _acquire(win: Window, ranks, require_mutex: bool):
    if require_mutex:
        _acquire_all(win, win.host.op_mutex_ranks(ranks))


def _acquire_all(win: Window, ranks) -> None:
    """Acquire in order, releasing everything on a mid-sequence failure
    (a dead holder's PeerLostError must not leak the earlier mutexes)."""
    acquired = []
    try:
        for r in ranks:
            win.host.mutex_acquire(r)
            acquired.append(r)
    except BaseException:
        for r in reversed(acquired):
            try:
                win.host.mutex_release(r)
            except Exception:  # noqa: BLE001 — unwind must not mask
                pass
        raise


def _release(win: Window, ranks, require_mutex: bool):
    if require_mutex:
        for r in reversed(win.host.op_mutex_ranks(ranks)):
            win.host.mutex_release(r)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def win_create(tensor, name: str, zero_init: bool = False) -> bool:
    """Create a named window from a rank-stacked tensor.

    Reference: mpi_ops.py:890-915 / mpi_controller.cc:796-869. Neighbor
    buffers start as a copy of the local tensor unless ``zero_init``.
    """
    st = _global_state()
    st.check_initialized()
    _check_rank_stacked(tensor, st.size, "win_create")
    if name in st.windows:
        return False
    with timeline_context(name, "WIN_CREATE"):
        st.windows[name] = Window(name, tensor, zero_init)
    return True


def win_free(name: Optional[str] = None) -> bool:
    """Free one window, or all windows when name is None (mpi_ops.py:918-933)."""
    st = _global_state()
    st.check_initialized()
    if name is None:
        for win in st.windows.values():
            win.close()
        st.windows.clear()
        return True
    if name not in st.windows:
        return False
    st.windows[name].close()
    del st.windows[name]
    return True


# ---------------------------------------------------------------------------
# put / accumulate / get
# ---------------------------------------------------------------------------

def _send_deposits_delayed(names, blobs, tags, edge_of, delays):
    """Chaos-only deposit send (BLUEFOG_CP_FAULT ``delay_edges``):
    partition the batch by each record's injected edge delay and ship the
    groups in ascending-delay order, sleeping up to each group's delay
    first — deterministic bandwidth ASYMMETRY (slow edges land late,
    undelayed edges ship immediately), the self-tuning controller's
    slow-edge fixture. Never on the hot path: the caller only reaches
    here when the fault clause is armed."""
    groups: Dict[int, List[int]] = {}
    for i, e in enumerate(edge_of):
        groups.setdefault(int(delays.get((e[0], e[1]), 0)), []).append(i)
    replies = [0] * len(names)
    waited = 0
    for dly in sorted(groups):
        if dly > waited:
            time.sleep((dly - waited) / 1e3)
            waited = dly
        idx = groups[dly]
        sub = _cp.client().append_bytes_tagged_many(
            [names[i] for i in idx], [blobs[i] for i in idx],
            [tags[i] for i in idx])
        for i, r in zip(idx, sub):
            replies[i] = r
    return replies


def _hosted_exchange(win: Window, tensor, table, sw_list, accumulate: bool,
                     require_mutex: bool, activity: str, from_get: bool):
    """One-sided put/accumulate/get over the host tensor transport.

    Only THIS controller's owned source ranks act; contributions to remote
    destinations become server deposits (kAppendBytes) that the owning
    controller folds at its next win_update. Nothing here waits on another
    controller — the reference's passive-target property
    (mpi_controller.cc:953-1034) restated for multi-controller TPU jobs.
    """
    st = _global_state()
    acc_t = np.dtype(_win_acc_dtype(win.mail_dtype))
    owned = set(win.owned)
    if from_get:
        # a get READS the published source tensors: lock the sources
        touched = sorted({src for src in range(win.size)
                          if table[src] and set(table[src]) & owned})
    else:
        touched = sorted({dst for src in owned
                          for dst in table.get(src, {})})
    # Server locks directly (no owner filter): the origin takes the remote
    # target's mutex exactly like MPI_Win_lock on the target window. Sorted
    # order keeps concurrent origins deadlock-free.
    if require_mutex:
        _acquire_all(win, touched)
    try:
        with timeline_context(win.name, activity), _op_timer(activity), \
                win.state_mu:
            use_p = st.win_ops_with_associated_p
            if not from_get:
                # batched owned-only read: the hosted hot path never pays
                # n-scaling server round-trips for ranks it doesn't own
                p_own = win.host.read_p_owned() if use_p else None
                rows = _owned_rows(tensor, win.owned)
                # Version bumps first, ONE pipelined round-trip for every
                # touched edge (ADVICE r3: the per-edge fetch_add in the
                # loop re-introduced n-scaling latency). Bump-before-deposit
                # is also the strict-consistency ordering: a drain that
                # finds a deposit can never observe its version still at 0
                # when both sides hold the rank mutex (VERDICT r3 #7).
                edges = [(src, dst, win.layout.slot_of[dst][src])
                         for src in win.owned
                         for dst in sorted(table.get(src, {}))]
                win.host.bump_versions([(d, k) for _, d, k in edges],
                                       force=True)
                mode = _DEP_ACC if accumulate else _DEP_PUT
                wire_t = _win_wire_dtype(win.mail_dtype)
                # Remote deposits are chunked into bounded wire records and
                # shipped as ONE pipelined batch (latency no longer scales
                # with out-degree; the reference's chunked-put stream,
                # mpi_controller.cc:932-1034). Local folds stay in acc_t.
                dep_names: List[str] = []
                dep_blobs: List = []  # bytes headers + zero-copy np views
                dep_tags: List[int] = []  # (seq, index) per record
                dep_edge_of: List[Tuple[int, int, int]] = []  # per record
                dep_flows: List[Tuple[Tuple[int, int, int], int]] = []
                deposited = set()
                # sharded rotation: every deposit names the active shard
                # so a drifted owner can reject it (ISSUE r17)
                dep_shard = win.active_shard if win.shard_factor > 1 else -1
                fl = _flight.recorder()
                try:
                    for src in win.owned:
                        x = rows[src].astype(acc_t, copy=False)
                        dsts = sorted(table.get(src, {}))
                        # Compressed wire: ONE encode per source row — the
                        # payload feeds every out-edge still on the window
                        # codec (weights move receiver-side) and its
                        # decoded estimate feeds the local folds, so wire
                        # and local numerics agree. Edges carrying a
                        # per-edge override (ISSUE r16) encode separately
                        # below against their own estimator state.
                        enc = est = None
                        fold_mode = mode
                        if win.codec is not None and dsts and (
                                not win._edge_codec
                                or any((src, d) not in win._edge_codec
                                       for d in dsts)):
                            enc, est, fold_mode = win._encode_row(
                                src, x, wire_t, mode)
                        for dst in dsts:
                            wt = float(table[src][dst])
                            k = win.layout.slot_of[dst][src]
                            pc = float(p_own[src] * wt) if use_p else 0.0
                            d_enc, d_est, d_fold = enc, est, fold_mode
                            d_cid = win.codec.cid if enc is not None else 0
                            x_dst = x
                            if win._edge_codec and \
                                    (src, dst) in win._edge_codec:
                                if win._edge_codec[(src, dst)] is None:
                                    # raw override: exact wire; folds any
                                    # pre-switch EF mass (accumulate)
                                    d_enc = d_est = None
                                    d_fold = mode
                                    x_dst = win._edge_raw_base(
                                        (src, dst), x, mode)
                                else:
                                    d_enc, d_est, d_fold, d_wire = \
                                        win._encode_edge(
                                            (src, dst), x, wire_t, mode)
                                    d_cid = d_wire.cid
                            if dst in owned:
                                base_row = x_dst if d_est is None else d_est
                                # unit weights (the optimizer default)
                                # skip a full-row multiply; _fold_record
                                # never mutates its contrib
                                contrib = base_row if wt == 1.0 else \
                                    base_row * np.asarray(wt, acc_t)
                                with fl.span("win.fold", a=contrib.nbytes):
                                    win._fold_record(dst, k, d_fold,
                                                     contrib)
                                if use_p:
                                    if accumulate:
                                        win.host.add_p_mail(dst, k, pc)
                                    else:
                                        win.host.set_p_mail(dst, k, pc)
                                deposited.add((src, dst, k))
                            elif d_enc is not None:
                                # codec deposit: the encoded payload (one
                                # self-describing record) with the edge
                                # weight + byte count in the extension
                                # header; flow events below report the
                                # POST-CODEC bytes, so step attribution
                                # and the plane planner see real wire cost
                                payload = d_enc
                                recs = _pack_deposit(
                                    mode, int(use_p), pc, payload,
                                    codec_id=d_cid, wt=wt,
                                    shard=dep_shard)
                                key = win._dep_key(dst, k)
                            else:
                                # wire payload stays a live numpy buffer:
                                # _pack_deposit slices it zero-copy and the
                                # native scatter-gather write streams it
                                payload = np.ascontiguousarray(
                                    (x_dst * np.asarray(wt, acc_t)).astype(
                                        wire_t, copy=False))
                                recs = _pack_deposit(
                                    mode, int(use_p), pc, payload,
                                    shard=dep_shard)
                                key = win._dep_key(dst, k)
                            if dst not in owned:
                                win._dep_seq += 1
                                dep_names.extend([key] * len(recs))
                                dep_blobs.extend(recs)
                                dep_tags.extend(_deposit_tags(
                                    win._dep_seq, len(recs),
                                    origin=st.process_index))
                                dep_edge_of.extend(
                                    [(src, dst, k)] * len(recs))
                                # flow id == the drain-side tag sequence
                                dep_flows.append((
                                    (src, dst, k),
                                    ((st.process_index & 0x7F) << 32)
                                    | (win._dep_seq & 0xFFFFFFFF),
                                    payload.nbytes))
                        # post-send self scaling (push-sum down-weighting)
                        win._rows[src] = (
                            rows[src].astype(acc_t) * np.asarray(
                                sw_list[src], acc_t)).astype(win.dtype)
                    full: set = set()
                    if dep_names:
                        full = _precheck_mailbox_cap(
                            win, dep_names, dep_blobs, dep_edge_of)
                        if full:
                            keep = [i for i, nm in enumerate(dep_names)
                                    if dep_edge_of[i] not in full]
                            dep_names = [dep_names[i] for i in keep]
                            dep_blobs = [dep_blobs[i] for i in keep]
                            dep_tags = [dep_tags[i] for i in keep]
                            dep_edge_of = [dep_edge_of[i] for i in keep]
                    if dep_names:
                        wire_out = sum(_blen(b) for b in dep_blobs)
                        # per-step win-op wire bytes, counter-delta-
                        # verified by win_microbench's sharded probe (the
                        # shard factor's ≥0.9·S reduction claim)
                        _metrics.counter("win.deposit_bytes").inc(wire_out)
                        _dl = _native.edge_delays()
                        with fl.span("win.wire", a=wire_out):
                            if _dl:
                                replies = _send_deposits_delayed(
                                    dep_names, dep_blobs, dep_tags,
                                    dep_edge_of, _dl)
                            else:
                                replies = \
                                    _cp.client().append_bytes_tagged_many(
                                        dep_names, dep_blobs, dep_tags)
                        # backstop only: the pre-check above keeps the
                        # server cap from ever tearing a multi-record
                        # deposit; a -2 here means the client's
                        # BLUEFOG_CP_MAILBOX_MAX_MB disagrees with the
                        # server's
                        full.update(dep_edge_of[i]
                                    for i, r in enumerate(replies)
                                    if r == -2)
                        deposited.update(
                            e for i, e in enumerate(dep_edge_of)
                            if replies[i] >= 0 and e not in full)
                    if full:
                        _metrics.counter("win.deposits_rejected").inc(
                            len(full))
                        raise RuntimeError(
                            f"window '{win.name}': deposit mailbox full "
                            f"for edges (src, dst, slot) {sorted(full)} "
                            "(server byte cap, BLUEFOG_CP_MAILBOX_MAX_MB) "
                            "— the owning controller has not drained; it "
                            "may be dead (check bf.dead_controllers())")
                    # cross-process trace correlation: one flow arrow per
                    # LANDED remote deposit, id = the tag sequence the
                    # owner's drain recovers from the wire. The flight ring
                    # gets the same pairing (edge.<src>.<dst> flow starts,
                    # drain.<origin> finishes) plus per-edge byte totals —
                    # the input scripts/step_attribution.py aggregates.
                    sent = 0
                    for edge, fid, nbytes in dep_flows:
                        if edge in deposited:
                            timeline_flow_start(_FLOW_DEPOSIT, fid)
                            fl.rec(_flight.FLOW_S,
                                   fl.intern(f"edge.{edge[0]}.{edge[1]}"),
                                   nbytes, fid)
                            sent += 1
                    if sent:
                        _metrics.counter("win.deposits_sent").inc(sent)
                    with fl.span("win.publish"):
                        win._publish_selves(win.owned)
                except Exception:
                    # un-bump the edges whose deposits never landed (e.g. a
                    # full mailbox for a dead owner) so healthy neighbors'
                    # version counters don't advertise writes that will
                    # never arrive; best-effort — a broken wire fails this
                    # too, and then the job is down anyway
                    try:
                        missing = [(d, k) for s, d, k in edges
                                   if (s, d, k) not in deposited]
                        if missing:
                            win.host.bump_versions(
                                [(d, k) for d, k in missing], force=True,
                                delta=-1)
                    except Exception:  # noqa: BLE001
                        pass
                    raise
                if use_p:
                    win.host.write_p_entries({
                        src: p_own[src] * float(sw_list[src])
                        for src in win.owned})
            else:
                # pull each in-edge source's published tensor into MY
                # mailbox; a get may read a REMOTE source's p scalar.
                p_all = win.host.read_p() if use_p else None
                remote_srcs = sorted({
                    src for dst in win.owned for src in range(win.size)
                    if src not in owned and table[src].get(dst) is not None})
                pulled = []

                fl = _flight.recorder()

                def fold_src(src, val):
                    contrib_base = val.astype(acc_t, copy=False)
                    for dst in win.owned:
                        wt = table[src].get(dst)
                        if wt is None:
                            continue
                        k = win.layout.slot_of[dst][src]
                        with fl.span("win.fold", a=contrib_base.nbytes):
                            win._fold_record(
                                dst, k, _DEP_PUT,
                                contrib_base * np.asarray(wt, acc_t))
                        if use_p:
                            win.host.set_p_mail(dst, k,
                                                float(p_all[src] * wt))
                        pulled.append((dst, k))

                for src in sorted(owned):
                    if any(table[src].get(dst) is not None
                           for dst in win.owned):
                        fold_src(src, win._rows[src])
                # Remote rows: ALL sources issue in flight at once (bounded
                # by the pool width for memory), each fetched as striped
                # byte ranges over the connection pool, folding in source
                # order as they land. The r6 1-deep chain overlapped one
                # stream with one fold; with the pool the streams
                # themselves also run concurrently.
                depth = max(2, getattr(_cp.client(), "streams", 1))
                fetches: Dict[int, _Prefetch] = {}

                def launch(j):
                    fetches[j] = _Prefetch(
                        lambda s=remote_srcs[j]:
                        win._read_remote_self_view(s))

                # the pull leg is the get path's wire phase: the fold spans
                # inside carve themselves out of it for attribution
                fl.begin("win.wire")
                try:
                    for j in range(min(depth, len(remote_srcs))):
                        launch(j)
                    for j, src in enumerate(remote_srcs):
                        row, owner = fetches.pop(j).result()
                        if j + depth < len(remote_srcs):
                            launch(j + depth)
                        try:
                            fold_src(src, row)
                        finally:
                            owner.close()
                finally:
                    fl.end("win.wire")
                win.host.bump_versions(pulled)
    finally:
        if require_mutex:
            for r in reversed(touched):
                win.host.mutex_release(r)
    return _handles.allocate(f"{activity.lower()}.{win.name}",
                             np.zeros((), np.float32))


def _do_exchange(win: Window, tensor, table, sw_list, accumulate: bool,
                 require_mutex: bool, activity: str, from_get: bool = False,
                 donate_source: bool = False):
    if win.hosted:
        return _hosted_exchange(win, tensor, table, sw_list, accumulate,
                                require_mutex, activity, from_get)
    st = _global_state()
    w, active = _edge_arrays(win, table)
    if from_get:
        # A get READS the source ranks' window tensors: lock the sources
        # (the reference locks win.mutexes[src] in WinGet).
        touched = [src for src in range(win.size) if table[src]]
    else:
        # A put/accumulate WRITES the destinations' mailboxes: lock the dsts.
        touched = [dst for src in range(win.size) for dst in table[src]]
    # numpy for host-side operands: jit places them on the mesh directly; an
    # eager jnp.asarray would round-trip them through the default device.
    source = None if from_get else tensor  # get reads under lock
    sw_arr = np.asarray(sw_list, np.float32)
    # Compile-time specializations, gated on donate_source so the default
    # path keeps its ONE compiled variant (specializing on runtime weight
    # values would double every test/user compile). A donated source must
    # not be a get's x (win.self_value survives the op); with it, all-ones
    # self weights make the program's self output a pure alias of the
    # donated input — the optimizer-gossip put drops a full window of
    # alloc+copy.
    donate = donate_source and not from_get
    identity_self = donate and bool(np.all(sw_arr == 1.0))
    fn = win._exchange_fn(accumulate, donate, identity_self)
    _acquire(win, touched, require_mutex)
    try:
        with timeline_context(win.name, activity), _op_timer(activity), \
                win.state_mu:
            new_self, new_mail = fn(
                source if not from_get else win.self_value, win.mail,
                np.asarray(w), np.asarray(active), sw_arr)
            if not from_get:
                win.self_value = new_self
            win.mail = new_mail
            _bump_host_state(win, table, accumulate)
            # Barrier between the mailbox p-contributions (which read OTHER
            # ranks' pre-scale p) and the owner rescale of p below: without
            # it a fast controller could rescale before a slow one reads.
            win.host.flush()
            if st.win_ops_with_associated_p and not from_get:
                win.host.write_p(
                    win.host.read_p() * np.asarray(sw_list, np.float64))
                win.host.flush()
    finally:
        _release(win, touched, require_mutex)
    return _handles.allocate(f"{activity.lower()}.{win.name}", win.self_value)


def win_put_nonblocking(
    tensor,
    name: str,
    self_weight: Optional[Weights] = None,
    dst_weights: Optional[Weights] = None,
    require_mutex: bool = False,
    donate_source: bool = False,
) -> int:
    """Write ``tensor[src] * w`` into each destination's mailbox slot for src.

    After the sends, the locally stored window tensor becomes
    ``tensor * self_weight`` (the reference's in-place post-send scaling,
    mpi_ops.py:1036-1073).

    ``donate_source``: the caller relinquishes ``tensor`` (its buffer may
    be reused by the compiled exchange — read it again and jax raises a
    deleted-buffer error). The window optimizers pass this for their
    packed fusion buffers, which are dead after the put.
    """
    win = _get_window(name)
    st = _global_state()
    _check_rank_stacked(tensor, st.size, "win_put")
    table = _edge_weights(dst_weights, win.out_neighbors, 1.0, "dst_weights", st.size)
    sw = _per_rank(1.0 if self_weight is None else self_weight, st.size, "self_weight")
    return _do_exchange(win, tensor, table, sw, accumulate=False,
                        require_mutex=require_mutex, activity="WIN_PUT",
                        donate_source=donate_source)


def win_put(tensor, name: str, self_weight=None, dst_weights=None,
            require_mutex: bool = False, donate_source: bool = False) -> bool:
    handle = win_put_nonblocking(tensor, name, self_weight, dst_weights,
                                 require_mutex, donate_source)
    return win_wait(handle)


def win_accumulate_nonblocking(
    tensor,
    name: str,
    self_weight: Optional[Weights] = None,
    dst_weights: Optional[Weights] = None,
    require_mutex: bool = False,
    donate_source: bool = False,
) -> int:
    """Add ``tensor[src] * w`` into each destination's mailbox slot (SUM only,
    like the reference, mpi_ops.py:1168-1213). ``donate_source`` as in
    :func:`win_put_nonblocking`."""
    win = _get_window(name)
    st = _global_state()
    _check_rank_stacked(tensor, st.size, "win_accumulate")
    table = _edge_weights(dst_weights, win.out_neighbors, 1.0, "dst_weights", st.size)
    sw = _per_rank(1.0 if self_weight is None else self_weight, st.size, "self_weight")
    return _do_exchange(win, tensor, table, sw, accumulate=True,
                        require_mutex=require_mutex, activity="WIN_ACCUMULATE",
                        donate_source=donate_source)


def win_accumulate(tensor, name: str, self_weight=None, dst_weights=None,
                   require_mutex: bool = False,
                   donate_source: bool = False) -> bool:
    handle = win_accumulate_nonblocking(
        tensor, name, self_weight, dst_weights, require_mutex, donate_source
    )
    return win_wait(handle)


def win_get_nonblocking(
    name: str,
    src_weights: Optional[Weights] = None,
    require_mutex: bool = False,
) -> int:
    """Pull each source's current window tensor into the local mailbox.

    Reference: mpi_ops.py:1105-1136 / WinGet pulling from the global window
    (mpi_controller.cc:1123-1184); win_update then surfaces the values.
    """
    win = _get_window(name)
    st = _global_state()
    # src-keyed table: entry (dst pulls from src with weight w) is an edge
    # src -> dst, same wire direction as a put.
    recv_table = _edge_weights(src_weights, win.in_neighbors, 1.0,
                               "src_weights", st.size)
    table: Dict[int, Dict[int, float]] = {r: {} for r in range(st.size)}
    for dst in range(st.size):
        for src, wt in recv_table[dst].items():
            table[src][dst] = wt
    sw = [1.0] * st.size  # get leaves the stored window tensor unchanged
    return _do_exchange(win, None, table, sw, accumulate=False,
                        require_mutex=require_mutex, activity="WIN_GET",
                        from_get=True)


def win_get(name: str, src_weights=None, require_mutex: bool = False) -> bool:
    handle = win_get_nonblocking(name, src_weights, require_mutex)
    return win_wait(handle)


# ---------------------------------------------------------------------------
# update (the local combine; reference "win_sync")
# ---------------------------------------------------------------------------

def win_update(
    name: str,
    self_weight: Optional[Weights] = None,
    neighbor_weights: Optional[Weights] = None,
    reset: bool = False,
    clone: bool = False,
    require_mutex: bool = False,
):
    """Combine the window tensor with its mailbox buffers.

    result[r] = self_weight[r] * self[r] + sum_src w[r][src] * mail[(r, src)]

    Defaults mirror mpi_ops.py:958-1029: topology recv-weights when the
    topology is weighted, else the uniform 1/(indegree+1) average. ``reset``
    zeroes the buffers that were read (after the combine); ``clone`` leaves
    the stored window tensor unchanged. Versions of read buffers reset to 0.
    """
    win = _get_window(name)
    st = _global_state()
    n = st.size

    if (self_weight is None) != (neighbor_weights is None):
        raise ValueError(
            "self_weight and neighbor_weights must be presented together"
        )
    if self_weight is None:
        if st.is_topo_weighted:
            sw_list, nw_table = [], {}
            for r in range(n):
                s, w = topology_util.GetRecvWeights(st.topology, r)
                sw_list.append(s)
                nw_table[r] = w
        else:
            sw_list = []
            nw_table = {}
            for r in range(n):
                u = 1.0 / (len(win.in_neighbors[r]) + 1)
                sw_list.append(u)
                nw_table[r] = {src: u for src in win.in_neighbors[r]}
    else:
        sw_list = _per_rank(self_weight, n, "self_weight")
        nw_table = _edge_weights(
            neighbor_weights, win.in_neighbors, 1.0, "neighbor_weights", n
        )

    lay = win.layout
    nw = np.zeros((n, lay.d_max), np.float32)
    read_mask = np.zeros((n, lay.d_max), np.float32)
    for r, wmap in nw_table.items():
        for src, wt in wmap.items():
            k = lay.slot_of[r][src]
            nw[r, k] = wt
            read_mask[r, k] = 1.0

    if win.hosted:
        return _hosted_update(win, sw_list, nw_table, nw, read_mask,
                              reset, clone, require_mutex)

    with timeline_context(name, "WIN_UPDATE"), _op_timer("WIN_UPDATE"):
        _acquire(win, range(n), require_mutex)
        win.state_mu.acquire()
        try:
            fn = win._update_fn(reset)
            result, new_mail = fn(
                win.self_value, win.mail,
                np.asarray(sw_list, np.float32), np.asarray(nw),
                np.asarray(read_mask if reset else np.zeros_like(read_mask)))
            if st.win_ops_with_associated_p:
                p_mail = win.host.read_p_mail()
                new_p = np.asarray(sw_list, np.float64) * win.host.read_p() + \
                    np.sum(nw.astype(np.float64) * p_mail, axis=1)
            # versions of read buffers reset; optionally clear the buffers
            win.host.reset_versions(
                (r, lay.slot_of[r][src])
                for r, wmap in nw_table.items() for src in wmap)
            win.mail = new_mail
            if reset and st.win_ops_with_associated_p:
                win.host.write_p_mail(
                    p_mail * (1.0 - read_mask.astype(np.float64)))
            if not clone:
                win.self_value = result
                if st.win_ops_with_associated_p:
                    win.host.write_p(new_p)
            win.host.flush()
        finally:
            win.state_mu.release()
            _release(win, range(n), require_mutex)
    return result


def _hosted_update(win: Window, sw_list, nw_table, nw, read_mask,
                   reset: bool, clone: bool, require_mutex: bool,
                   return_rows: bool = False):
    """Owner-local combine for the hosted plane.

    Drains this controller's pending server deposits, folds them, then runs
    the weighted combine for OWNED ranks only — other controllers' ranks are
    their own business (that is what makes a sleeping peer harmless). The
    result is the rank-stacked global array assembled from owned shards.

    ``return_rows`` (the hybrid residual leg): skip the global assembly and
    return ``(rows, p_sums)`` — the per-owned-rank combined numpy rows and,
    when associated-p is on, the per-rank p-mailbox contraction
    ``sum(nw[r] * p_mail[r])`` (None otherwise). Used with ``clone=True``
    so the stored window rows and p scalars stay untouched.
    """
    st = _global_state()
    acc_t = np.dtype(_win_acc_dtype(win.mail_dtype))
    lay = win.layout
    with timeline_context(win.name, "WIN_UPDATE"), _op_timer("WIN_UPDATE"):
        # lock only OWNED ranks (the reference's win_update locks the local
        # window; remote ranks' updates are their owners' job)
        if require_mutex:
            _acquire_all(win, win.owned)
        win.state_mu.acquire()
        try:
            win._drain_deposits(strict=require_mutex)
            use_p = st.win_ops_with_associated_p
            if use_p:
                # batched, owned-only: no n-scaling server traffic
                p_own = win.host.read_p_owned()
                p_mail = win.host.read_p_mail_owned()
            results: Dict[int, np.ndarray] = {}
            for r in win.owned:
                # fewest full-row passes (this loop is ~10 % of a 100 MB
                # win_update): the multiply reads the stored dtype straight
                # into the acc dtype (no same-dtype .astype copy), each
                # edge folds as one multiply + one in-place add, and the
                # final cast is a no-op view when the window dtype IS the
                # acc dtype (f32/f64 windows)
                combined = np.multiply(
                    win._rows[r], np.asarray(sw_list[r], acc_t),
                    dtype=acc_t)
                for src, wt in nw_table.get(r, {}).items():
                    k = lay.slot_of[r][src]
                    np.add(combined,
                           np.multiply(win._mail_rows[r][k],
                                       np.asarray(wt, acc_t), dtype=acc_t),
                           out=combined)
                results[r] = combined.astype(win.dtype, copy=False)
                if reset:
                    keep = (1.0 - read_mask[r]).reshape(
                        (lay.d_max,) + (1,) * len(win.row_shape))
                    win._mail_rows[r] = (
                        win._mail_rows[r].astype(acc_t) * keep.astype(acc_t)
                    ).astype(win.mail_dtype)
            win.host.reset_versions(
                (r, lay.slot_of[r][src])
                for r in win.owned for src in nw_table.get(r, {}))
            if reset and use_p:
                win.host.write_p_mail_rows({
                    r: p_mail[r] * (1.0 - read_mask[r].astype(np.float64))
                    for r in win.owned})
            pub = None
            if not clone:
                for r in win.owned:
                    win._rows[r] = results[r]
                if use_p:
                    win.host.write_p_entries({
                        r: float(sw_list[r]) * p_own[r] + float(
                            np.sum(nw[r].astype(np.float64) * p_mail[r]))
                        for r in win.owned})
                # stream the publish while the result assembles below (a
                # 100 MB publish is most of a win_update's non-drain wall
                # time); joined before the locks release, so mutex-holding
                # readers still see the new value strictly after this
                # update
                pub = _Prefetch(lambda: win._publish_selves(win.owned))
            if return_rows:
                p_sums = None
                if use_p:
                    p_sums = {r: float(np.sum(nw[r].astype(np.float64)
                                              * p_mail[r]))
                              for r in win.owned}
                out = (results, p_sums)
            else:
                out = _assemble_global(win, results)
            if pub is not None:
                pub.result()
        finally:
            win.state_mu.release()
            if require_mutex:
                for r in reversed(win.owned):
                    win.host.mutex_release(r)
    return out


def win_update_then_collect(name: str, require_mutex: bool = True):
    """Sum self + all neighbor buffers, then clear them (mpi_ops.py:940-956)."""
    return win_update(
        name, self_weight=1.0,
        neighbor_weights={
            r: {src: 1.0 for src in _get_window(name).in_neighbors[r]}
            for r in range(_global_state().size)
        },
        reset=True, require_mutex=require_mutex,
    )


def win_fence(name: str) -> bool:
    """Close the window's RMA epoch: collective over all controllers.

    Reference: bf.win_fence (torch/mpi_win_ops.cc:714 DoWinFence ->
    MPI_Win_fence transport, mpi_controller.cc:917-929). On return, every
    ``win_put``/``win_accumulate``/``win_get`` issued by ANY controller
    before its fence is complete at its target — folded into the
    destination's mailbox buffers, ready for the next ``win_update``.

    Collective plane: every op is a collective program all controllers
    dispatched, so the fence reduces to the alignment barrier. Hosted
    plane: barrier (all origins' deposits reached the server) -> each owner
    drains its ranks' server mailboxes -> barrier (all owners folded).
    """
    win = _get_window(name)
    with timeline_context(name, "WIN_FENCE"), _op_timer("WIN_FENCE"):
        win.host.flush()
        if win.hosted:
            with win.state_mu:
                win._drain_deposits()
            win.host.flush()
    return True


# ---------------------------------------------------------------------------
# poll / wait / versions / mutex / associated-p
# ---------------------------------------------------------------------------

def win_poll(handle: int) -> bool:
    return _handles.poll(handle)


def win_wait(handle: int) -> bool:
    _handles.synchronize(handle)
    return True


def get_win_version(name: str, rank: Optional[int] = None) -> Dict[int, int]:
    """Versions of this rank's neighbor buffers: 0 = read since last write.

    Reference: mpi_ops.py:1257-1272. ``rank`` selects whose buffers to
    inspect (every rank is visible to the controller).
    """
    win = _get_window(name)
    r = 0 if rank is None else rank
    return {
        src: win.host.get_version(r, win.layout.slot_of[r][src])
        for src in win.in_neighbors[r]
    }


class win_mutex:
    """Acquire the window mutexes of the given ranks (default: out-neighbors).

    Context manager, matching bf.win_mutex (mpi_ops.py:1304-1336). The
    distributed fetch-and-op spin lock becomes controller-owned locks.
    """

    def __init__(self, name: str, for_self: bool = False,
                 ranks: Optional[Sequence[int]] = None, rank: int = 0) -> None:
        self._win = _get_window(name)
        if ranks is None:
            ranks = [rank] if for_self else self._win.out_neighbors[rank]
        # Explicit user request: take exactly these ranks' locks (even ones
        # another controller owns — this is how an external actor excludes
        # the collective window ops on those ranks).
        self._ranks = sorted(set(ranks))

    def __enter__(self):
        # Exception-safe multi-acquire: a PeerLostError (dead holder) on
        # the k-th rank must not leak the k-1 already-held mutexes — the
        # self-healing retry (optimizers) re-enters this context, and a
        # leaked depth count would pin those locks for the process's life.
        acquired = []
        try:
            for r in self._ranks:
                self._win.host.mutex_acquire(r)
                acquired.append(r)
        except BaseException:
            for r in reversed(acquired):
                try:
                    self._win.host.mutex_release(r)
                except Exception:  # noqa: BLE001 — unwind must not mask
                    pass
            raise
        return self

    def __exit__(self, *exc):
        for r in reversed(self._ranks):
            self._win.host.mutex_release(r)
        return False


class win_lock:
    """RMA access-epoch context manager (no-op beyond validation on TPU).

    The MPI passive epoch (MPI_Win_lock, mpi_controller.cc:1194-1237) has no
    analog: mailbox writes are always well-ordered device ops.
    """

    def __init__(self, name: str) -> None:
        _get_window(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def win_associated_p(name: str, rank: Optional[int] = None) -> float:
    """The push-sum correction scalar p for ``rank`` (init 1.0)."""
    win = _get_window(name)
    return float(win.host.read_p()[0 if rank is None else rank])


def win_associated_p_all(name: str) -> np.ndarray:
    return _get_window(name).host.read_p()


def turn_on_win_ops_with_associated_p() -> None:
    _global_state().win_ops_with_associated_p = True


def turn_off_win_ops_with_associated_p() -> None:
    _global_state().win_ops_with_associated_p = False
