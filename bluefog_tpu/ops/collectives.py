"""Classic collectives over the rank mesh: allreduce / broadcast / allgather /
barrier / pair_gossip.

TPU-native rebuild of the reference's MPI/NCCL collective surface
(reference: torch/mpi_ops.py:60-370 API; mpi_controller.cc:101-293 transport).
All ops take rank-stacked inputs (leading dim = rank axis) and return
rank-stacked outputs, so results compose with the neighbor ops and optimizer
wrappers. Transport is XLA: psum/pmean/all_gather/ppermute over the mesh's
ICI links — there is no vendor routing (BLUEFOG_*_BY_MPI) to configure.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..runtime import handles as _handles
from ..runtime.state import _global_state
from ..runtime.timeline import timeline_context
from .neighbors import _auto_name, _check_rank_stacked


def _jit_smap(mesh, spec, body):
    """jit-wrapped shard_map over a variable-length tuple of leaves.

    The returned callable has stable identity, so jax's jit cache is actually
    hit on repeat calls — building ``jax.jit(shard_map(...))`` inline per op
    call would re-trace and re-lower the program every single time (~0.5 s of
    host overhead per collective on the CPU mesh). Every op below routes
    through an ``lru_cache``d builder keyed by its static parameters.
    """

    def call(leaves):
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=tuple(spec for _ in leaves),
            out_specs=tuple(spec for _ in leaves),
        )
        return mapped(*leaves)

    return jax.jit(call)


def _tree_op(fn, tensor):
    leaves, treedef = jax.tree_util.tree_flatten(tensor)
    outs = fn(tuple(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(outs))


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

def allreduce(
    tensor,
    average: bool = True,
    is_hierarchical_local: bool = False,
    name: Optional[str] = None,
):
    """Sum or average every rank's tensor; each rank gets the result.

    ``is_hierarchical_local`` restricts the reduction to this rank's machine
    group (reference: allreduce on the LOCAL comm, mpi_controller.cc:138-160).
    """
    return _handles.synchronize(
        allreduce_nonblocking(tensor, average, is_hierarchical_local, name)
    )


def allreduce_nonblocking(
    tensor,
    average: bool = True,
    is_hierarchical_local: bool = False,
    name: Optional[str] = None,
) -> int:
    st = _global_state()
    st.check_initialized()
    op_name = _auto_name("allreduce", name)
    if not st.skip_negotiate:
        _check_rank_stacked(tensor, st.size, "allreduce")
    if is_hierarchical_local and st.machine_mesh is None:
        raise RuntimeError("hierarchical-local allreduce needs a homogeneous layout")

    mesh = st.machine_mesh if is_hierarchical_local else st.mesh
    with timeline_context(op_name, "ALLREDUCE"):
        out = _tree_op(
            _allreduce_fn(mesh, average, is_hierarchical_local), tensor)
    return _handles.allocate(op_name, out)


@functools.lru_cache(maxsize=64)
def _allreduce_fn(mesh, average: bool, hierarchical: bool):
    axis = "local" if hierarchical else "rank"
    spec = P(("machine", "local")) if hierarchical else P("rank")

    def body(*xs):
        outs = []
        for x in xs:
            acc_t = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
            red = lax.pmean(x.astype(acc_t), axis) if average else \
                lax.psum(x.astype(acc_t), axis)
            outs.append(red.astype(x.dtype))
        return tuple(outs)

    return _jit_smap(mesh, spec, body)


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    """Every rank receives rank ``root_rank``'s slice (reference: mpi_ops.py:174-236)."""
    return _handles.synchronize(broadcast_nonblocking(tensor, root_rank, name))


def broadcast_nonblocking(tensor, root_rank: int, name: Optional[str] = None) -> int:
    st = _global_state()
    st.check_initialized()
    op_name = _auto_name("broadcast", name)
    _check_rank_stacked(tensor, st.size, "broadcast")
    if not 0 <= root_rank < st.size:
        raise ValueError(f"root_rank {root_rank} out of range [0, {st.size})")

    with timeline_context(op_name, "BROADCAST"):
        out = _tree_op(_broadcast_fn(st.mesh, root_rank), tensor)
    return _handles.allocate(op_name, out)


@functools.lru_cache(maxsize=64)
def _broadcast_fn(mesh, root_rank: int):
    def body(*xs):
        me = lax.axis_index("rank")
        outs = []
        for x in xs:
            masked = jnp.where(me == root_rank, x, jnp.zeros_like(x))
            outs.append(lax.psum(masked, "rank").astype(x.dtype))
        return tuple(outs)

    return _jit_smap(mesh, P("rank"), body)


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------

def allgather(tensor, name: Optional[str] = None):
    """Concatenate all ranks' tensors along dim 0; every rank gets the result.

    Rank-stacked in [n, b, ...] -> rank-stacked out [n, n*b, ...]. Equal
    shapes are required in the SPMD path, matching the NCCL-path restriction
    in the reference (nccl_controller.cc:389-396); use :func:`allgather_v`
    for per-rank varying first dims.
    """
    return _handles.synchronize(allgather_nonblocking(tensor, name))


def allgather_nonblocking(tensor, name: Optional[str] = None) -> int:
    st = _global_state()
    st.check_initialized()
    op_name = _auto_name("allgather", name)
    _check_rank_stacked(tensor, st.size, "allgather")

    with timeline_context(op_name, "ALLGATHER"):
        out = _tree_op(_allgather_fn(st.mesh), tensor)
    return _handles.allocate(op_name, out)


@functools.lru_cache(maxsize=8)
def _allgather_fn(mesh):
    def body(*xs):
        outs = []
        for x in xs:
            g = lax.all_gather(x[0], "rank", axis=0, tiled=False)
            g = g.reshape((1, -1) + x.shape[2:]) if x.ndim > 1 else g.reshape(1, -1)
            outs.append(g)
        return tuple(outs)

    return _jit_smap(mesh, P("rank"), body)


def allgather_v(tensors: Sequence, name: Optional[str] = None):
    """Variable-first-dim allgather: list of per-rank arrays -> concatenation.

    The reference supports ragged gathers on its CPU/MPI path via a
    pre-allgather of first-dim sizes followed by MPI_Allgatherv
    (mpi_context.cc:443-508). The SPMD compiled path cannot trace ragged
    shapes, so the TPU-native transport is the padded analog: every rank's
    slice is zero-padded to the max first dim, the padded block rides ONE
    compiled all_gather over the mesh (real ICI traffic, not a controller
    concat), and the statically-known sizes trim the padding at the edge.
    """
    return _handles.synchronize(allgather_v_nonblocking(tensors, name))


def allgather_v_nonblocking(tensors: Sequence, name: Optional[str] = None) -> int:
    st = _global_state()
    st.check_initialized()
    op_name = _auto_name("allgather_v", name)
    if len(tensors) != st.size:
        raise ValueError(f"expected {st.size} per-rank tensors, got {len(tensors)}")
    tensors = [jnp.asarray(t) for t in tensors]
    trailing = tensors[0].shape[1:]
    dtype = tensors[0].dtype
    for r, t in enumerate(tensors):
        if t.ndim < 1:
            raise ValueError(f"allgather_v: rank {r} slice must have a first dim")
        if t.shape[1:] != trailing or t.dtype != dtype:
            raise ValueError(
                f"allgather_v: rank {r} slice {t.dtype}{t.shape} does not match "
                f"rank 0's trailing shape {dtype}{(-1,) + trailing}"
            )

    sizes = tuple(int(t.shape[0]) for t in tensors)
    with timeline_context(op_name, "ALLGATHER_V"):
        if max(sizes) == 0:
            # match the compiled path's placement: replicated over the mesh,
            # not the default device (which may be a different backend)
            out = jax.device_put(
                jnp.zeros((0,) + trailing, dtype),
                jax.sharding.NamedSharding(st.mesh, P()),
            )
        else:
            out = _allgather_v_fn(st.mesh, sizes)(*tensors)
    return _handles.allocate(op_name, out)


@functools.lru_cache(maxsize=64)
def _allgather_v_fn(mesh, sizes: tuple):
    b_max = max(sizes)
    # static gather indices skipping each rank's padding rows
    idx = np.concatenate(
        [np.arange(r * b_max, r * b_max + s) for r, s in enumerate(sizes)]
    ).astype(np.int32)

    def body(x):
        g = lax.all_gather(x[0], "rank", axis=0, tiled=True)  # [n*b_max, ...]
        # the trim is identical on every rank, but the gather primitive defeats
        # shard_map's static replication inference, so it stays rank-stacked
        return jnp.take(g, idx, axis=0)[None]

    def call(*leaves):
        # pad + stack + row select all under one jit, so a single host
        # dispatch covers the whole op (the _jit_smap rationale applies)
        pad_trailing = [(0, 0)] * (leaves[0].ndim - 1)
        padded = jnp.stack([
            jnp.pad(t, [(0, b_max - t.shape[0])] + pad_trailing) for t in leaves
        ])
        mapped = shard_map(
            body, mesh=mesh, in_specs=P("rank"), out_specs=P("rank"))
        return mapped(padded)[0]

    return jax.jit(call)


# ---------------------------------------------------------------------------
# barrier
# ---------------------------------------------------------------------------

def barrier(name: Optional[str] = None) -> None:
    """Block until all outstanding device work completes.

    The reference implements barrier as a tiny allreduce unless negotiation
    is skipped (mpi_ops.py:872-881); on TPU a psum across the mesh plus a
    host block gives the same guarantee. Multi-controller jobs additionally
    rendezvous all controller processes through the control plane's named
    barrier (runtime/control_plane.py).
    """
    from ..runtime import control_plane as _cp

    st = _global_state()
    st.check_initialized()
    # numpy, not jnp.zeros: an eager jnp constant would materialize on the
    # DEFAULT device (possibly a different backend than the mesh) and force a
    # cross-backend transfer into the compiled program on every call.
    token = np.zeros((st.size, 1), np.float32)
    out = _barrier_fn(st.mesh)((token,))
    jax.block_until_ready(out)
    _cp.barrier(name or "bf.barrier")


@functools.lru_cache(maxsize=8)
def _barrier_fn(mesh):
    def body(x):
        return (lax.psum(x, "rank"),)

    return _jit_smap(mesh, P("rank"), body)


# ---------------------------------------------------------------------------
# pair_gossip
# ---------------------------------------------------------------------------

def pair_gossip(
    tensor,
    target_ranks: Union[Dict[int, int], Sequence[int]],
    self_weight: float = 0.5,
    pair_weight: float = 0.5,
    name: Optional[str] = None,
):
    """Exchange tensors within mutually-paired ranks and combine.

    Reference: MPI_Sendrecv-based PairGossip (mpi_controller.cc:748-774);
    each rank sends to and receives from the same target, so ``target_ranks``
    (rank -> peer) must be a symmetric pairing. Default is the plain average.
    """
    return _handles.synchronize(
        pair_gossip_nonblocking(tensor, target_ranks, self_weight, pair_weight, name)
    )


def pair_gossip_nonblocking(
    tensor,
    target_ranks: Union[Dict[int, int], Sequence[int]],
    self_weight: float = 0.5,
    pair_weight: float = 0.5,
    name: Optional[str] = None,
) -> int:
    st = _global_state()
    st.check_initialized()
    op_name = _auto_name("pair_gossip", name)
    _check_rank_stacked(tensor, st.size, "pair_gossip")

    n = st.size
    if isinstance(target_ranks, dict):
        peers = [target_ranks.get(r, r) for r in range(n)]
    else:
        peers = list(target_ranks)
    if len(peers) != n:
        raise ValueError("target_ranks must give a peer for every rank")
    for r, p in enumerate(peers):
        if not 0 <= p < n:
            raise ValueError(f"peer {p} for rank {r} out of range")
        if peers[p] != r:
            raise ValueError(
                f"pair_gossip needs mutual pairs: rank {r} -> {p} but "
                f"rank {p} -> {peers[p]} (sendrecv semantics)"
            )

    with timeline_context(op_name, "PAIR_GOSSIP"):
        out = _tree_op(
            _pair_gossip_fn(st.mesh, tuple(peers),
                            float(self_weight), float(pair_weight)),
            tensor,
        )
    return _handles.allocate(op_name, out)


@functools.lru_cache(maxsize=128)
def _pair_gossip_fn(mesh, peers: tuple, self_weight: float, pair_weight: float):
    perm = [(p, r) for r, p in enumerate(peers)]  # rank r receives from its peer

    def body(*xs):
        outs = []
        for x in xs:
            recv = lax.ppermute(x, "rank", perm)
            outs.append((self_weight * x + pair_weight * recv).astype(x.dtype))
        return tuple(outs)

    return _jit_smap(mesh, P("rank"), body)


# ---------------------------------------------------------------------------
# in-place name-parity aliases
# ---------------------------------------------------------------------------
# The reference's trailing-underscore variants mutate the input tensor and
# return it (mpi_ops.py:150-201, 265-308). jax.Arrays are immutable: these
# aliases return the reduced value for callers to rebind, and the true
# in-place analog — reusing the input buffer — is XLA donation, which the
# fused optimizer steps already apply (optimizers.py donate_argnums).

def allreduce_(*args, **kwargs):
    """Name-parity alias of :func:`allreduce` (reference in-place variant)."""
    return allreduce(*args, **kwargs)


def allreduce_nonblocking_(*args, **kwargs) -> int:
    """Name-parity alias of :func:`allreduce_nonblocking`."""
    return allreduce_nonblocking(*args, **kwargs)


def broadcast_(*args, **kwargs):
    """Name-parity alias of :func:`broadcast` (reference in-place variant)."""
    return broadcast(*args, **kwargs)


def broadcast_nonblocking_(*args, **kwargs) -> int:
    """Name-parity alias of :func:`broadcast_nonblocking`."""
    return broadcast_nonblocking(*args, **kwargs)
