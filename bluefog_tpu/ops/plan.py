"""Combine plans: graph topologies compiled to ppermute / all-gather programs.

This module is where BlueFog's per-edge MPI/NCCL message scheduling
(reference: mpi_controller.cc:369-525, nccl_controller.cc:546-756) is replaced
by a TPU-native design. A weighted digraph over the rank axis is decomposed
into *circulant shifts*: edge set {(i, (i+s) mod n) : i} for each distinct
shift s. One shift is exactly one ``jax.lax.ppermute`` over the mesh — a
single hop on the ICI torus for ring/expo-2 style graphs — and the weighted
combine

    out[j] = W[j, j] * x[j] + sum_s W[(j-s) % n, j] * x[(j-s) % n]

is fused into the same compiled program (the reference does this combine on
the host in the binding layer after communication, torch/mpi_ops.cc:354-430;
here XLA fuses it into the collective schedule).

Two execution strategies, chosen per graph:
  * ``ppermute``: one weighted ppermute per shift. Optimal for sparse graphs
    (expo-2 has ceil(log2 n) shifts; dynamic one-peer has 1).
  * ``gather``: one tiled all-gather + an MXU matvec against the [n, n]
    weight matrix. Better for dense graphs (fully-connected, star) where the
    shift count approaches n.

Weights are *traced* (passed as device arrays), shifts are *static* (part of
the jit cache key). Dynamic topologies (per-step one-peer schedules) therefore
re-jit only per distinct shift set — the Expo-2 schedule has ceil(log2 n)
distinct sets total — and per-step weight changes are free. This resolves the
reference's "dynamic topology" re-negotiation (operations.cc:945-1000) with
zero per-step host work after warmup.
"""

from __future__ import annotations

import functools
from typing import Dict, FrozenSet, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import topology as topology_util


# Accumulate in f32 whenever inputs are lower precision (bf16 params on TPU):
# neighbor averaging is a convex combination and bf16 accumulation loses the
# consensus invariant tests rely on.
def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) and \
        jnp.dtype(dtype).itemsize < 4 else jnp.dtype(dtype)


class CombinePlan:
    """Host-side decomposition of a combine matrix W (edge i->j = W[i,j])."""

    __slots__ = ("n", "shifts", "rows", "W", "use_gather")

    def __init__(self, W: np.ndarray, force_gather: bool | None = None) -> None:
        W = np.asarray(W, dtype=np.float32)
        n = W.shape[0]
        assert W.shape == (n, n), "combine matrix must be square"
        self.n = n
        self.W = W
        self.shifts = tuple(topology_util.shift_support(W))
        # rows[0, j] = self weight of rank j; rows[k+1, j] = weight rank j
        # applies to the value arriving over shift k.
        rows = np.zeros((len(self.shifts) + 1, n), dtype=np.float32)
        rows[0] = np.diag(W)
        for k, s in enumerate(self.shifts):
            rows[k + 1] = [W[(j - s) % n, j] for j in range(n)]
        self.rows = rows
        if force_gather is None:
            # all-gather moves (n-1) blocks; k ppermutes move k blocks.
            self.use_gather = len(self.shifts) >= max(4, n // 2)
        else:
            self.use_gather = force_gather

    def weight_array(self) -> np.ndarray:
        return self.W if self.use_gather else self.rows


def spmd_combine(w, tree, *, axis: str, n: int, shifts: Tuple[int, ...],
                 use_gather: bool = False, stacked: bool = True):
    """Weighted neighbor combine, callable INSIDE shard_map per-rank code.

    ``w`` is the plan's traced weight array (``CombinePlan.weight_array()``):
    ``[k+1, n]`` rows for the ppermute strategy or the full ``[n, n]`` matrix
    for the gather strategy. ``shifts`` must be static. ``stacked=True`` means
    leaves carry the size-1 rank-block dim shard_map produces for
    rank-stacked arrays; ``stacked=False`` operates on bare per-rank values
    (the fused-train-step path in optimizers.py).
    """
    me = lax.axis_index(axis)

    def one(x):
        blk = x if stacked else x[None]
        acc_t = _acc_dtype(blk.dtype)
        if use_gather:
            col = jnp.take(w, me, axis=1)  # my combine column [n]
            xg = lax.all_gather(blk[0], axis, axis=0, tiled=False)  # [n, ...]
            out = jnp.tensordot(col.astype(acc_t), xg.astype(acc_t), axes=(0, 0))
            out = out.astype(x.dtype)[None]
        else:
            wm = jnp.take(w, me, axis=1)  # my weights [k+1]
            acc = wm[0].astype(acc_t) * blk.astype(acc_t)
            for k, s in enumerate(shifts):
                perm = [(i, (i + s) % n) for i in range(n)]
                moved = lax.ppermute(blk, axis, perm)
                acc = acc + wm[k + 1].astype(acc_t) * moved.astype(acc_t)
            out = acc.astype(x.dtype)
        return out if stacked else out[0]

    return jax.tree_util.tree_map(one, tree)


@functools.lru_cache(maxsize=256)
def _combine_fn(mesh: Mesh, axis: str, shifts: Tuple[int, ...], use_gather: bool,
                n_axis: int):
    """Build & cache the jitted rank-stacked combine function for one plan shape."""

    n = n_axis

    def per_rank(w, *leaves):
        return tuple(
            spmd_combine(w, x, axis=axis, n=n, shifts=shifts,
                         use_gather=use_gather)
            for x in leaves
        )

    # shard_map specs must match the number of leaves; rebuild per leaf-count
    # (traced once per shape signature under the jit below).
    def call(w, leaves: Tuple):
        mapped = shard_map(
            per_rank,
            mesh=mesh,
            in_specs=(P(),) + tuple(P(axis) for _ in leaves),
            out_specs=tuple(P(axis) for _ in leaves),
        )
        return mapped(w, *leaves)

    return jax.jit(call)


def apply_plan(plan: CombinePlan, mesh: Mesh, axis: str, tree):
    """Run the combine over a pytree of rank-stacked arrays ([n, ...] each)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    fn = _combine_fn(mesh, axis, plan.shifts, plan.use_gather, plan.n)
    # numpy, not jnp.asarray: jit places host arrays straight onto the mesh;
    # an eager conversion would hop through the default device (possibly a
    # different backend) on every call.
    outs = fn(plan.weight_array(), tuple(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(outs))


# ---------------------------------------------------------------------------
# Per-edge plane planner (hybrid gossip; ISSUE r13)
# ---------------------------------------------------------------------------
#
# The hosted window plane made the plane choice per WINDOW; the planner makes
# it per EDGE. An edge is *compiled-eligible* when it can ride one fused
# shard_map/ppermute program this controller may dispatch unilaterally: both
# endpoints live (no compiled program may name a dead rank), the topology
# static (the window's edge set is frozen at creation), and the edge
# mesh-local — src and dst hosted by the SAME controller process, because a
# cross-controller collective dispatch would need the lockstep the hosted
# plane exists to avoid. Everything else — cross-controller-boundary edges,
# dead/suspect-adjacent edges, sub-floor windows — stays on the hosted
# mailbox residual with its deposit/drain semantics intact.
#
# Planner inputs: the frozen edge set, the rank→controller ownership map,
# the heartbeat dead set, the window's per-edge wire bytes (one full row per
# deposit), and — when ingested — the measured per-edge byte/wire-cost
# attribution that ``scripts/step_attribution.py --json`` emits (r12's
# step-time attribution, now a machine interface with a stable
# ``schema_version``). Partitions are cached keyed on
# (edge set, dead set, membership epoch), so elastic rejoin and self-healing
# re-plan exactly when r9's epoch fences bump and never re-derive per step.

ATTRIBUTION_SCHEMA_VERSION = 1

Edge = Tuple[int, int]


def load_attribution(doc: dict) -> Dict[Edge, dict]:
    """Per-edge cost hints from a ``step_attribution.py --json`` document.

    Returns ``{(src, dst): {"bytes": ..., "wire_sec_est": ...}}`` summed
    over ranks. Raises ValueError on a missing/unknown ``schema_version``
    — the dump is a machine interface now, and silently consuming a future
    incompatible layout would mis-plan every edge.
    """
    ver = doc.get("schema_version")
    if ver != ATTRIBUTION_SCHEMA_VERSION:
        raise ValueError(
            f"step-attribution document has schema_version={ver!r}, "
            f"expected {ATTRIBUTION_SCHEMA_VERSION} — regenerate it with "
            "this tree's scripts/step_attribution.py --json")
    hints: Dict[Edge, dict] = {}
    for rep in doc.get("ranks", {}).values():
        for label, e in rep.get("edges", {}).items():
            try:
                src, dst = (int(x) for x in label.split("->"))
            except ValueError:
                continue
            h = hints.setdefault((src, dst),
                                 {"bytes": 0.0, "wire_sec_est": 0.0})
            h["bytes"] += float(e.get("bytes", 0.0))
            h["wire_sec_est"] += float(e.get("wire_sec_est", 0.0))
    return hints


class PlanePartition(NamedTuple):
    """One planning verdict: every frozen edge lands in exactly one plane."""

    compiled: FrozenSet[Edge]
    hosted: FrozenSet[Edge]
    dead: FrozenSet[int]
    epoch: int

    @property
    def key(self):
        """Stable identity of the compiled sub-topology (jit-cache key for
        the fused program: re-jit happens only when the partition itself
        changes, never on weight changes)."""
        return tuple(sorted(self.compiled))


class PlanePlanner:
    """Per-edge plane decisions for one hosted window.

    ``policy`` mirrors ``BLUEFOG_WIN_PLANE``: only ``"auto"`` ever compiles
    an edge; ``"hosted"`` pins everything to the mailbox plane (the r6/r7
    wire, bit for bit) and ``"compiled"`` never reaches a planner at all
    (the window itself is on the collective plane). ``hosted_override`` is
    the test seam: edges forced onto the residual regardless of score.
    """

    def __init__(self, n: int, edges, owner_of: Dict[int, int],
                 row_bytes: int, min_bytes: int = 0, policy: str = "auto",
                 hosted_override=(), wire_scale: float = 1.0) -> None:
        self.n = n
        self.edges: FrozenSet[Edge] = frozenset(
            (int(s), int(d)) for s, d in edges)
        self.owner_of = dict(owner_of)
        # One full window row per deposit. Under sharded windows
        # (docs/sharded_windows.md) the window's row IS the shard row, so
        # this estimate — and every verdict derived from it — already
        # operates on shard-sized wire cost; measured attribution hints
        # are post-codec AND post-shard for the same reason (flow events
        # record the real payload).
        self.row_bytes = int(row_bytes)
        # Wire codec discount (docs/compression.md): with a codec on the
        # hosted wire, a deposit ships ~codec.nominal_ratio of the row, so
        # the static size estimate must shrink with it or the min-bytes
        # floor would mis-plan every edge. Codecs are per-EDGE since the
        # self-tuning wire (docs/self_tuning.md): ``edge_scale`` carries
        # each overridden edge's own nominal ratio and the scalar stays
        # the fallback for every other edge. Measured attribution hints
        # (ingest_attribution) already carry POST-codec bytes — the
        # edge.<src>.<dst> flow events record the encoded payload size —
        # so they are never rescaled here.
        self.wire_scale = float(wire_scale)
        self.edge_scale: Dict[Edge, float] = {}
        self.min_bytes = int(min_bytes)
        self.policy = policy
        self.hosted_override = frozenset(hosted_override)
        self.hints: Optional[Dict[Edge, dict]] = None
        # Online per-edge measured bytes (the r19 tuner's live feed):
        # highest-precedence cost source, replacing the offline --json
        # attribution dump with the streaming telemetry plane's numbers.
        self.live: Dict[Edge, float] = {}
        self.rebuilds = 0  # cache misses — asserted by the re-plan tests
        self._cache: Dict[Tuple, PlanePartition] = {}

    def ingest_attribution(self, doc: dict) -> int:
        """Feed a real ``step_attribution.py --json`` dump; its measured
        per-edge bytes replace the static row-size estimate in
        :meth:`edge_cost`. Returns the number of edges with hints and
        drops the partition cache (new inputs → new plans)."""
        self.hints = load_attribution(doc)
        self._cache.clear()
        return len(self.hints)

    def _floor_verdicts(self) -> Tuple[bool, ...]:
        """Each edge's size-floor verdict, in sorted edge order — the only
        part of eligibility that cost inputs can move."""
        return tuple(self.edge_cost(e) >= self.min_bytes
                     for e in sorted(self.edges))

    def ingest_live(self, edge_bytes: Dict[Edge, float]) -> bool:
        """Online measured per-edge wire bytes (per gossip step), fed by
        the runtime tuner from the streaming telemetry plane's per-edge
        estimators. Replaces both the static estimate and any offline
        attribution hints for the named edges.

        Re-plans ONLY on decision change: the partition cache is dropped
        when some edge's size-floor verdict actually flips, so a stream
        of measurements that all land on the same side of the floor
        costs a dict update and nothing else. Returns True when the next
        :meth:`partition` call will re-derive."""
        before = self._floor_verdicts()
        for edge, nbytes in edge_bytes.items():
            self.live[(int(edge[0]), int(edge[1]))] = float(nbytes)
        if self._floor_verdicts() == before:
            return False
        self._cache.clear()
        return True

    def set_edge_scale(self, edge: Edge, scale: float) -> bool:
        """Pin one edge's wire-scale (its codec's nominal ratio after a
        per-edge codec switch). Same decision-change gating as
        :meth:`ingest_live`; returns True when the partition will
        re-derive."""
        before = self._floor_verdicts()
        self.edge_scale[(int(edge[0]), int(edge[1]))] = float(scale)
        if self._floor_verdicts() == before:
            return False
        self._cache.clear()
        return True

    def edge_cost(self, edge: Edge) -> float:
        """Wire bytes one gossip step moves over this edge if it stays
        hosted. Precedence: live measured bytes (tuner feed, post-codec)
        > offline attribution hints (post-codec) > the window row size
        scaled by the edge's codec nominal ratio (``edge_scale``, falling
        back to the window-wide scalar)."""
        if edge in self.live:
            return self.live[edge]
        if self.hints is not None and edge in self.hints:
            return float(self.hints[edge]["bytes"])
        return float(self.row_bytes) * self.edge_scale.get(
            edge, self.wire_scale)

    def _eligible(self, edge: Edge, dead: FrozenSet[int]) -> bool:
        src, dst = edge
        if src in dead or dst in dead:
            return False  # dead/suspect-adjacent → hosted residual
        if edge in self.hosted_override:
            return False
        owner_s = self.owner_of.get(src)
        owner_d = self.owner_of.get(dst)
        if owner_s is None or owner_s != owner_d:
            return False  # cross-controller boundary → hosted residual
        if self.edge_cost(edge) < self.min_bytes:
            return False  # below the floor, hosted latency beats a re-jit
        return True

    def partition(self, dead=frozenset(), epoch: int = 0) -> PlanePartition:
        """The cached per-edge plane split for (dead set, membership epoch).

        The epoch rides the key even though the verdict depends only on
        the dead set: an epoch bump (join/leave/re-admission, r9 fences)
        is the externally visible "membership changed" signal, and keying
        on it guarantees a re-plan exactly then — the property the
        epoch-bump invalidation test pins."""
        dead = frozenset(dead)
        key = (dead, int(epoch))
        part = self._cache.get(key)
        if part is not None:
            return part
        self.rebuilds += 1
        if self.policy != "auto":
            compiled: FrozenSet[Edge] = frozenset()
        else:
            compiled = frozenset(
                e for e in self.edges if self._eligible(e, dead))
        part = PlanePartition(compiled, self.edges - compiled, dead,
                              int(epoch))
        if len(self._cache) > 32:  # dead sets churn at most with membership
            self._cache.clear()
        self._cache[key] = part
        return part


def rank_sharding(mesh: Mesh, axis: str = "rank") -> NamedSharding:
    """Sharding that lays a rank-stacked array out one-slice-per-device."""
    return NamedSharding(mesh, P(axis))


def shard_rank_stacked(mesh: Mesh, tree, axis: str = "rank"):
    """Place a rank-stacked pytree so slice r lives on device r."""
    sh = rank_sharding(mesh, axis)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)
