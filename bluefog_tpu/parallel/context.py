"""Context parallelism: ring attention and Ulysses all-to-all attention.

Ring attention (Liu et al. 2023) maps 1:1 onto the framework's ring-topology
machinery: the mesh's rank axis forms the ring, K/V shards hop one neighbor
per step via ``lax.ppermute`` (a single ICI hop on a TPU torus), and each
chip folds the arriving block into a numerically stable online softmax.
Peak memory per chip is O(S/n) for activations and O(Sq/n * Sk/n) for the
score block, so sequence length scales linearly with the ring size.

Layout contract: ``[batch, seq, heads, head_dim]``, sequence sharded over
the mesh axis. Compute runs in float32 accumulation regardless of input
dtype (bf16 in, f32 softmax statistics — the standard MXU recipe).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Python scalar, not jnp.float32(...): a concrete array here would initialize
# the XLA backend at import time, breaking jax.distributed.initialize() in
# multi-controller jobs (it must run before any backend touch).
_NEG = -1e30

def _pvary(x, axes):
    return lax.pcast(x, axes, to="varying")


def reference_attention(q, k, v, causal: bool = False, window=None):
    """Dense single-device attention; the correctness oracle for the tests.
    Fewer k/v heads than q heads are repeated (query head h reads k/v head
    h // (Hq / Hkv)); ``window`` (with ``causal``) keeps the columns s of
    row t with 0 <= t - s < window."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        behind = jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :]
        mask = behind >= 0 if window is None else (
            (behind >= 0) & (behind < window))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ring_attention_shard(q, k, v, *, axis_name: str, causal: bool = False,
                         use_flash: bool = False, interpret: bool = False):
    """Per-device ring attention body; call INSIDE shard_map.

    ``q/k/v``: this chip's sequence shard [B, S/n, H, D]. K and V make one
    full trip around the ring; each step computes a [Sq/n, Sk/n] score block
    against the currently held K/V block and renormalizes the running
    (max, sum, out) accumulators — flash attention's streaming update with
    the stream order given by ring position.

    ``use_flash=True`` computes each block's partials with the pallas VMEM
    kernel (parallel.flash.flash_block) instead of XLA einsums: scores never
    reach HBM, which is what lets per-chip K/V blocks grow long. ``interpret``
    runs that kernel in interpreter mode (CPU test meshes). Both paths
    differentiate through the same reverse-rotation ring backward schedule
    (``_ring_backward``): one more K/V trip around the ring with gradient
    blocks traveling alongside — residuals and carries are O(S/n) per chip.
    The flash path's per-step block gradients run in the pallas backward
    kernel (``flash.flash_block_bwd``: flash-attention-2's dq, dk and dv
    from one pass), so probability tiles stay in VMEM in the backward too; the
    einsum path materializes one [S/n, S/n] f32 block per step via XLA.
    Reverse-mode only: the custom VJP means ``jax.jvp``/forward-over-reverse
    is unsupported on both ring paths.
    """
    if use_flash:
        return _ring_flash_diff(q, k, v, axis_name, causal, interpret)
    return _ring_einsum_diff(q, k, v, axis_name, causal)


def _ring_einsum_partials(q, k, v, axis_name: str, causal: bool):
    """Einsum ring forward; returns (normalized out, row max m, row sum l),
    m/l in [B, Sq, H] layout — the backward's softmax reconstruction keys."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    q_pos = me * Sq + jnp.arange(Sq)

    # K/V travel "backwards" (rank i -> i+1) so that at step t rank ``me``
    # holds block (me - t) % n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, t):
        o, m, l, kc, vc = carry
        blk = (me - t) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32))
        k_pos = blk * Sk + jnp.arange(Sk)
        if causal:
            allowed = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(allowed[None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            # guard fully-masked rows: never let masked scores contribute
            p = jnp.where(allowed[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32))
        o_new = o * jnp.moveaxis(corr, 1, -1)[..., None] + pv
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o_new, m_new, l_new, kc, vc), None

    # pvary: the accumulators are device-varying from step 0 (shard_map's
    # varying-manual-axes check requires carry types to match body outputs).
    o0 = _pvary(jnp.zeros((B, Sq, H, D), jnp.float32), (axis_name,))
    m0 = _pvary(jnp.full((B, H, Sq), _NEG, jnp.float32), (axis_name,))
    l0 = _pvary(jnp.zeros((B, H, Sq), jnp.float32), (axis_name,))
    (o, m, l, _, _), _ = lax.scan(body, (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.moveaxis(l, 1, -1)[..., None]
    return (out.astype(q.dtype),
            jnp.moveaxis(m, 1, -1), jnp.moveaxis(l, 1, -1))


def _ring_backward(axis_name: str, causal: bool, res, g,
                   use_flash: bool = False, interpret: bool = False):
    """Reverse-rotation ring-attention backward.

    One more K/V trip around the ring: per-block softmax probabilities are
    reconstructed from the saved final (m, l) row statistics, and each K/V
    block's gradient accumulates on a buffer that TRAVELS with the block —
    after n steps every gradient block is back on its home chip. Residuals
    and carries are all O(S/n) per chip; nothing quadratic, nothing
    sequence-global (the standard ring-attention backward schedule).

    ``use_flash=True`` computes each step's (dq, dk, dv) partials with the
    pallas backward kernel (``flash.flash_block_bwd``: flash-attention-2's
    dq, dk and dv from one pass) instead of XLA einsums — probability tiles
    live in VMEM only, restoring the kernel forward's scores-never-reach-HBM
    property for the backward as well.
    """
    q, k, v, out, m, l = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    gf = g.astype(jnp.float32)
    # D_i = sum_d g_i * out_i: the softmax-jacobian projection term
    d_term = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # [B, Sq, H]
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_off = me * Sq

    def block_grads_einsum(kc, vc, blk):
        qf = q.astype(jnp.float32) * scale
        m_b = jnp.moveaxis(m, -1, 1)          # [B, H, Sq]
        inv_l = 1.0 / jnp.moveaxis(l, -1, 1)  # l > 0 for every valid row
        d_b = jnp.moveaxis(d_term, -1, 1)
        q_pos = me * Sq + jnp.arange(Sq)
        kcf = kc.astype(jnp.float32)
        vcf = vc.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kcf)
        k_pos = blk * Sk + jnp.arange(Sk)
        if causal:
            allowed = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(allowed[None, None], s, _NEG)
        p = jnp.exp(s - m_b[..., None]) * inv_l[..., None]
        if causal:
            p = jnp.where(allowed[None, None], p, 0.0)
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vcf)
        ds = p * (dp - d_b[..., None])
        return (jnp.einsum("bhqk,bkhd->bqhd", ds, kcf) * scale,
                jnp.einsum("bhqk,bqhd->bkhd", ds, qf),  # qf carries scale
                jnp.einsum("bhqk,bqhd->bkhd", p, gf))

    def block_grads_flash(kc, vc, blk):
        from .flash import flash_block_bwd
        return flash_block_bwd(q, kc, vc, gf, d_term, m, l,
                               q_off, blk * Sk, causal=causal,
                               interpret=interpret)

    block_grads = block_grads_flash if use_flash else block_grads_einsum

    def body(carry, t):
        dq, kc, vc, dkc, dvc = carry
        blk = (me - t) % n
        dq_blk, dk_blk, dv_blk = block_grads(kc, vc, blk)
        dq = dq + dq_blk
        dkc = dkc + dk_blk
        dvc = dvc + dv_blk
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        dkc = lax.ppermute(dkc, axis_name, perm)
        dvc = lax.ppermute(dvc, axis_name, perm)
        return (dq, kc, vc, dkc, dvc), None

    dq0 = _pvary(jnp.zeros((B, Sq, H, D), jnp.float32), (axis_name,))
    dk0 = _pvary(jnp.zeros((B, Sk, H, D), jnp.float32), (axis_name,))
    dv0 = _pvary(jnp.zeros((B, Sk, H, D), jnp.float32), (axis_name,))
    (dq, _, _, dk, dv), _ = lax.scan(
        body, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_einsum_diff(q, k, v, axis_name, causal):
    out, _, _ = _ring_einsum_partials(q, k, v, axis_name, causal)
    return out


def _ring_einsum_fwd(q, k, v, axis_name, causal):
    out, m, l = _ring_einsum_partials(q, k, v, axis_name, causal)
    return out, (q, k, v, out, m, l)


def _ring_einsum_bwd(axis_name, causal, res, g):
    return _ring_backward(axis_name, causal, res, g)


_ring_einsum_diff.defvjp(_ring_einsum_fwd, _ring_einsum_bwd)


def _ring_attention_flash(q, k, v, *, axis_name: str, causal: bool,
                          interpret: bool):
    """Ring loop whose per-block compute is the pallas flash kernel.

    Returns (normalized out, m, l) — the same partials contract as
    :func:`_ring_einsum_partials`, so both forwards share
    :func:`_ring_backward`.
    """
    from .flash import flash_block

    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    q_off = me * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        o, m, l, kc, vc = carry
        blk = (me - t) % n
        bo, bm, bl = flash_block(q, kc, vc, q_off, blk * Sk,
                                 causal=causal, interpret=interpret)
        m_new = jnp.maximum(m, bm)                      # [B, Sq, H]
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(bm - m_new)
        l_new = l * c_old + bl * c_blk
        o_new = o * c_old[..., None] + bo * c_blk[..., None]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return o_new, m_new, l_new, kc, vc

    o0 = _pvary(jnp.zeros((B, Sq, H, D), jnp.float32), (axis_name,))
    m0 = _pvary(jnp.full((B, Sq, H), _NEG, jnp.float32), (axis_name,))
    l0 = _pvary(jnp.zeros((B, Sq, H), jnp.float32), (axis_name,))
    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    return (o / l[..., None]).astype(q.dtype), m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash_diff(q, k, v, axis_name, causal, interpret):
    out, _, _ = _ring_attention_flash(q, k, v, axis_name=axis_name,
                                      causal=causal, interpret=interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, interpret):
    out, m, l = _ring_attention_flash(q, k, v, axis_name=axis_name,
                                      causal=causal, interpret=interpret)
    return out, (q, k, v, out, m, l)


def _ring_flash_bwd(axis_name, causal, interpret, res, g):
    # same reverse-rotation schedule as the einsum ring (the flash kernel's
    # (m, l) partials are the identical softmax statistics), with the
    # per-block math in the pallas backward kernel
    return _ring_backward(axis_name, causal, res, g,
                          use_flash=True, interpret=interpret)


_ring_flash_diff.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ulysses_attention_shard(q, k, v, *, axis_name: str, causal: bool = False):
    """Per-device Ulysses body; call INSIDE shard_map.

    All-to-all re-shards sequence -> heads, dense attention runs on full
    sequence with H/n local heads, all-to-all re-shards back. One big
    bisection-bandwidth exchange instead of n ring hops — better when heads
    are plentiful and the interconnect is fat; requires H % n == 0.
    """
    n = lax.psum(1, axis_name)
    # [B, S/n, H, D] -> [B, S, H/n, D]
    q, k, v = (
        lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
        for x in (q, k, v)
    )
    out = reference_attention(q, k, v, causal=causal)
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def sequence_sharding(mesh: Mesh, axis: str = "rank") -> NamedSharding:
    """Sharding for [B, S, H, D] arrays, sequence dim over the mesh axis."""
    return NamedSharding(mesh, P(None, axis))


def mesh_1d(n: int, axis: str, devices=None) -> Mesh:
    """A 1-D mesh of ``n`` devices under the given axis name (shared by the
    pipe/expert mesh builders)."""
    import numpy as _np

    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    return Mesh(_np.asarray(devices[:n]), (axis,))


@functools.lru_cache(maxsize=32)
def _cp_fn(mesh: Mesh, axis: str, causal: bool, kind: str,
           use_flash: bool = False, interpret: bool = False):
    if kind == "ring":
        body = functools.partial(ring_attention_shard, axis_name=axis,
                                 causal=causal, use_flash=use_flash,
                                 interpret=interpret)
    else:
        body = functools.partial(ulysses_attention_shard, axis_name=axis,
                                 causal=causal)
    spec = P(None, axis)
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # The pallas HLO *interpreter* (CPU tests) mis-propagates vma through
        # the kernel's mixed varying/uniform operands and aborts; real TPU
        # lowering handles it (flash.py declares vma on out_shape). Disable
        # the check only for interpret mode, per the JAX-suggested
        # workaround.
        check_vma=not (use_flash and interpret),
    )
    return jax.jit(mapped)


def _cp_call(kind: str, q, k, v, mesh: Optional[Mesh], axis: str,
             causal: bool, use_flash: bool = False, interpret: bool = False):
    if mesh is None:
        from ..runtime.state import _global_state
        st = _global_state()
        st.check_initialized()
        mesh = st.mesh
        axis = "rank"
    n = mesh.shape[axis]
    if q.shape[1] % n or k.shape[1] % n:
        raise ValueError(
            f"sequence length must divide the {axis} axis size {n}; got "
            f"q seq {q.shape[1]}, k seq {k.shape[1]}")
    if kind == "ulysses" and q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads % {n} == 0; got {q.shape[2]} heads")
    return _cp_fn(mesh, axis, causal, kind, use_flash, interpret)(q, k, v)


def ring_attention(q, k, v, mesh: Optional[Mesh] = None, axis: str = "rank",
                   causal: bool = False, use_flash: bool = False,
                   interpret: bool = False):
    """Ring attention over global [B, S, H, D] arrays (S sharded on ``axis``).

    Uses the initialized runtime's rank mesh when ``mesh`` is None.
    ``use_flash`` routes each block through the pallas VMEM kernel.
    """
    return _cp_call("ring", q, k, v, mesh, axis, causal, use_flash, interpret)


def ulysses_attention(q, k, v, mesh: Optional[Mesh] = None,
                      axis: str = "rank", causal: bool = False):
    """All-to-all (Ulysses) context-parallel attention over [B, S, H, D]."""
    return _cp_call("ulysses", q, k, v, mesh, axis, causal)
