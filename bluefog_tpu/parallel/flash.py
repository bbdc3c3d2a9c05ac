"""Pallas flash-attention block kernel.

The MXU hot path for attention: one fused kernel computes, per query tile,
the unnormalized attention partials

    o = exp(s - m) @ V,   m = rowmax(s),   l = rowsum(exp(s - m))

against one K/V block held in VMEM — scores never touch HBM, which is the
whole point of flash attention (XLA would materialize the [Sq, Sk] score
tensor for long sequences). Returning (o, m, l) instead of normalized output
makes the kernel the *inner step* of ring attention: the XLA-level ring loop
(context.py) merges the per-block statistics exactly as it does for its
einsum fallback.

Global-position offsets are scalar-prefetch operands so the SAME compiled
kernel serves every ring step (block positions are runtime values, not
trace constants). ``interpret=True`` (the Pallas interpreter, for CPU-mesh
tests) is only ever the caller's explicit choice: nothing here looks at the
backend, so a run on the chip is a run of the compiled kernels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import metrics

_NEG = -1e30

# The two kernels as ``jax.named_scope``s, each around exactly one
# ``pallas_call``: the scope, not a function's name, is what a trace reducer
# finds the kernel by (docs/timeline.md, "Names in a device trace"). The
# backward makes dq as well as dk and dv: "dkv" is the name its readers
# know (benchmark/phases.KERNELS).
SCOPE_FWD = "bf.flash.fwd"
SCOPE_DKV = "bf.flash.dkv"


def _tile(s: int, candidates) -> int:
    """Largest candidate tile evenly dividing s (1 is always a candidate)."""
    return next(t for t in candidates if s % t == 0)


# 512 x 2048 first: the r5 on-chip sweep (S=8192, D=128) had them ~5 % faster
# a step than 256 x 1024, and 1024 x 4096 does not fit VMEM.
_Q_TILES = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
# bound the [TQ, TK] f32 score tile (+ K/V tiles) well inside VMEM: holding
# the whole K/V block per kernel invocation overflows the 16 MB scoped limit
# past S~4k
_K_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _q_tile(sq: int) -> int:
    return _tile(sq, _Q_TILES)


def _k_tile(sk: int) -> int:
    return _tile(sk, _K_TILES)


def _vma(*arrays) -> dict:
    """``vma=`` for a ``pallas_call``'s ``out_shape``. Inside shard_map the
    inputs carry varying-mesh-axes (vma) metadata and pallas_call requires
    out_shape to declare the same — without it the kernel compiles under
    interpret mode but fails to lower on real TPU. Union over the operands:
    any varying operand makes the outputs varying (k/v can be rank-varying
    while q is replicated, e.g. broadcast-query)."""
    vmas = [getattr(jax.typeof(t), "vma", None) for t in arrays]
    if all(m is None for m in vmas):
        return {}
    return {"vma": frozenset().union(*(m for m in vmas if m is not None))}


def _lanes(width: int) -> int:
    """Lanes a width is held in: whole 128-lane tiles."""
    return -(-width // 128) * 128


# What the two buffers of the backward's resident dq block may take of a
# core's VMEM (128 MiB on a v5e; the kernel's stack stands beside them):
# 4 Mi rows x lanes of f32, i.e. 32k tokens of a 128-wide head, 16k at 192.
_DQ_VMEM_BYTES = 32 << 20


def _dq_rows(sq: int, d: int) -> int:
    """Rows of q one backward call holds dq for: all of them where the
    block fits ``_DQ_VMEM_BYTES`` twice over (Pallas double-buffers an
    output), else the most whole q tiles that fit and divide ``sq``."""
    tq = _q_tile(sq)
    nq = sq // tq
    fit = max(_DQ_VMEM_BYTES // (2 * 4 * _lanes(d) * tq), 1)
    return tq * next(n for n in range(min(fit, nq), 0, -1) if nq % n == 0)


def _bwd_vmem(rows: int, d: int, dv: int) -> int:
    """Scoped-VMEM limit of the backward kernel, from the shapes alone. Its
    512 x 2048 tiles were sized (r5 sweep) for two 128-lane operands a side
    inside Mosaic's default 16 MiB; a width is held in whole 128-lane
    tiles, so a 192-wide q.k takes 256 lanes and the stack comes to 16.8
    MiB: it grows with the lanes held instead of the tiles shrinking.
    Beside it stand the two buffers of the f32 dq block, ``rows`` long."""
    stack = (16 << 20) * max(_lanes(d) + _lanes(dv), 256) // 256
    return stack + 2 * rows * _lanes(d) * 4


def _dot_prec(dtype):
    """Kernel matmul precision: DEFAULT for sub-f32 operands (bf16 x bf16
    runs the MXU at 4x its f32 rate and the products are exact for bf16
    operands), HIGHEST for f32 (DEFAULT decomposes f32 dots into bf16
    passes on some backends — measured 0.1-level error — which would break
    the f32 oracle contract interpret-mode tests pin)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


# -- the causal schedule -----------------------------------------------------
# One predicate decides what a grid step fetches (the index maps), whether it
# computes (the kernels' ``pl.when``) and what ``causal_schedule`` counts.
# ``offs`` is (q_off, k_off), the global positions of element 0 of either
# side: the scalar-prefetch ref in a kernel or an index map, a pair of ints
# in ``causal_schedule``, which passes ``xp=np`` and whole grids of indices.
# ``window`` (None: none) is the sliding window's size W: row t sees the
# columns s with 0 <= t - s < W, so the allowed band has a second, trailing
# edge W columns behind the diagonal and everything below takes it.

def _causal_tile(offs, qi, kj, tq, tk, window=None):
    """(live, interior) of the tile pair (qi, kj). Live: some column of the
    K tile is at or before the q tile's last row (and inside the window of
    its first); a dead step computes nothing. Interior: the whole K tile is
    at or before its first row (and inside the window of its last), so the
    mask is a no-op. Live and not interior is a tile on an edge: the
    diagonal, the window's trailing edge, or both."""
    q_lo = offs[0] + qi * tq
    k_lo = offs[1] + kj * tk
    live, interior = k_lo <= q_lo + tq - 1, k_lo + tk - 1 <= q_lo
    if window is not None:
        live = live & (k_lo + tk - 1 > q_lo - window)
        interior = interior & (k_lo > q_lo + tq - 1 - window)
    return live, interior


def _chunk(tq: int, tk: int) -> int:
    """Width of the column chunks a diagonal tile is computed in: ``tq``
    where the K tile is several whole ones, else the tile (one chunk)."""
    return tq if tk > tq and tk % tq == 0 else tk


def _live_chunks(offs, qi, kj, tq, tk, xp=jnp):
    """Leading column chunks of a LIVE K tile that hold a column allowed to
    some row of q tile qi (1 .. tk / chunk). The columns behind them are
    masked for every row: p = 0 there and no row's maximum comes from them,
    so leaving them out changes no number."""
    cw = _chunk(tq, tk)
    q_hi = offs[0] + qi * tq + tq - 1
    return xp.minimum((q_hi - offs[1] - kj * tk) // cw + 1, tk // cw)


def _first_chunk(offs, qi, kj, tq, tk, window, xp=jnp):
    """Column chunks at the head of a LIVE K tile that lie behind the window
    of every row of q tile qi (0 .. tk / chunk - 1): masked for every row,
    as the chunks past ``_live_chunks`` are, and left out like them."""
    cw = _chunk(tq, tk)
    oldest = offs[0] + qi * tq - window + 1     # column the first row sees
    return xp.maximum((oldest - offs[1] - kj * tk) // cw, 0)


def _kv_block(offs, qi, kj, tq, tk, xp=jnp, window=None, nq=None, nk=None):
    """K/V block of a forward grid step (kj innermost, so a row's dead
    steps are its last): block 0 on a dead step. Pallas copies a block only
    when its index differs from the previous step's: block 0 arrives behind
    the last live step's compute, stays through the dead steps and is what
    the next row starts with, so no copy is issued that no step reads.

    Under a window a row's live steps are a run in its middle: a dead step
    before them names the row's first live block, one after them the block
    the next row starts with (the last row stays where it is)."""
    live, _ = _causal_tile(offs, qi, kj, tq, tk, window)
    if window is None:
        return xp.where(live, kj, 0)

    def first(row):  # the K tile that holds the oldest column the row sees
        return xp.clip((offs[0] + row * tq - window + 1 - offs[1]) // tk,
                       0, nk - 1)

    last = xp.clip((offs[0] + qi * tq + tq - 1 - offs[1]) // tk, 0, nk - 1)
    after = xp.where(qi == nq - 1, last, first(qi + 1))
    return xp.where(live, kj, xp.where(kj < first(qi), first(qi), after))


def _q_block(offs, qi, kj, tq, tk, nq, xp=jnp, window=None, nk=None):
    """q-side block (q, g, m, l, d) of a backward grid step (qi innermost, so
    a row's dead steps are its first): the row's first live q tile on a dead
    step, the last q tile where the whole row is dead (what the row before
    ended on). Under a window a K tile's row also ends in dead steps, the
    q tiles that no longer see it: they name what the next row starts with
    (the last row stays on its last live q tile)."""
    live, _ = _causal_tile(offs, qi, kj, tq, tk, window)

    def first(row):
        return xp.minimum(xp.maximum(offs[1] + row * tk - offs[0], 0) // tq,
                          nq - 1)

    if window is None:
        return xp.where(live, qi, first(kj))
    last = xp.clip((offs[1] + kj * tk + tk - 1 + window - 1 - offs[0]) // tq,
                   0, nq - 1)
    after = xp.where(kj == nk - 1, last, first(kj + 1))
    return xp.where(live, qi, xp.where(qi < first(kj), first(kj), after))


def _idle_fetches(block, live) -> int:
    """Copies no step reads: runs of one block index, in grid order, that
    hold no live step."""
    starts = np.flatnonzero(np.r_[True, block[1:] != block[:-1]])
    return int((np.add.reduceat(live.astype(np.int64), starts) == 0).sum())


def causal_schedule(sq: int, sk: int, q_off: int = 0, k_off: int = 0,
                    window=None) -> dict:
    """What the causal kernels do for one (batch, head) of q [sq]
    against a K/V block [sk] at these offsets (under a sliding ``window``,
    if one is given), counted from the helpers the kernels and their index
    maps are made of:

    ``steps`` / ``live`` / ``dead``: grid steps of one kernel and their
    causal classes; ``dead_fetching``: copies issued for dead steps only
    (the larger of the two grid orders: K/V blocks with kj innermost, q-side
    blocks with qi innermost); ``chunks_computed``: [tq, chunk] score chunks
    the live steps work on; ``chunks_needed``: those that hold any allowed
    (row, column) pair, counted over the whole block without the tiles;
    ``chunks_masked``: those of the edge steps that the kernels mask, when
    the offsets reach them as these Python ints (``_edge_chunks`` where the
    edges lie on chunk boundaries, else every chunk an edge step computes)."""
    tq, tk = _q_tile(sq), _k_tile(sk)
    nq, nk, cw = sq // tq, sk // tk, _chunk(tq, tk)
    offs = (q_off, k_off)
    qi, kj = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    live, interior = _causal_tile(offs, qi, kj, tq, tk, window)
    chunks = _live_chunks(offs, qi, kj, tq, tk, xp=np)
    q_lo = q_off + np.arange(nq) * tq
    column = k_off + np.arange(sk // cw) * cw
    needed = column <= q_lo[:, None] + tq - 1
    if window is not None:
        chunks = chunks - _first_chunk(offs, qi, kj, tq, tk, window, xp=np)
        needed &= column + cw - 1 > q_lo[:, None] - window
    computed = np.where(interior, tk // cw, np.where(live, chunks, 0))
    edge = live & ~interior
    if cw < tk and _on_chunks(q_off - k_off, cw, window):
        bodies = _edge_chunks(tk // cw, cw, window)
        past = (q_off + qi * tq - k_off - kj * tk) // cw
        masked = sum(len(bodies[m][2]) for m in past[edge])
    else:
        masked = int(computed[edge].sum())
    kv = _kv_block(offs, qi, kj, tq, tk, np, window, nq, nk)
    qb = _q_block(offs, qi, kj, tq, tk, nq, np, window, nk)
    n_live = int(live.sum())
    return {
        "steps": nq * nk, "live": n_live, "dead": nq * nk - n_live,
        "dead_fetching": max(_idle_fetches(kv.ravel(), live.ravel()),
                             _idle_fetches(qb.T.ravel(), live.T.ravel())),
        "chunks_computed": int(computed.sum()),
        "chunks_needed": int(needed.sum()),
        "chunks_masked": int(masked),
    }


def _kv_head(group: int):
    """The K/V row of q row ``bh`` of the [B * H, S, C] operands: q head h
    reads k/v head h // group, and Hq = group * Hkv, so the row is
    bh // group."""
    return (lambda bh: bh) if group == 1 else (
        lambda bh: jax.lax.div(bh, group))


def _kv_index_map(causal: bool, tq: int, tk: int, group: int = 1,
                  window=None, nq=None, nk=None):
    """Index map of the K and V specs of the forward grid (bh, qi, kj)."""
    head = _kv_head(group)
    if not causal:
        return lambda bh, qi, kj, offs: (head(bh), kj, 0)
    return lambda bh, qi, kj, offs: (
        head(bh), _kv_block(offs, qi, kj, tq, tk, jnp, window, nq, nk), 0)


def _q_index_map(causal: bool, tq: int, tk: int, nq: int, window=None,
                 nk=None):
    """Index map of the q-side specs of the backward grid (bh, kj, qi)."""
    if not causal:
        return lambda bh, kj, qi, offs: (bh, qi, 0)
    return lambda bh, kj, qi, offs: (
        bh, _q_block(offs, qi, kj, tq, tk, nq, jnp, window, nk), 0)


def _on_chunks(gap, cw: int, window) -> bool:
    """Whether a static ``gap`` = q_off - k_off (None: traced offsets) and
    the window put both edges of the band on column-chunk boundaries."""
    return (gap is not None and gap % cw == 0
            and (window is None or window % cw == 0))


def _edge_chunks(nc: int, cw: int, window) -> dict:
    """The edge steps of a K tile of ``nc`` chunks where the edges lie on
    chunk boundaries (``_on_chunks``), by m, the chunks from the tile's
    first column to the q tile's first row: ``{m: (first, n, masks)}``, the
    live chunks [first, n) and the chunks an edge crosses, as (chunk from
    ``first``, diagonal). The diagonal's chunk keeps row >= column, the
    trailing edge's (W = w chunks behind it) row < column; every other
    chunk is allowed whole. A step holds the diagonal for m < nc, the
    trailing edge for w <= m < w + nc, and is interior in between."""
    w = None if window is None else window // cw
    ms = range(nc) if w is None else sorted({*range(nc), *range(w, w + nc)})
    bodies = {}
    for m in ms:
        first = 0 if w is None else max(m - w, 0)
        masks = ((0, False),) if w is not None and m >= w else ()
        if m < nc:
            masks += ((m - first, True),)
        bodies[m] = (first, min(m + 1, nc), masks)
    return bodies


def _when_causal(offs_ref, qi, kj, tq, tk, body, window=None, gap=None):
    """Run ``body(masked, lo, hi)`` for this step's causal class: not at all
    on a dead step, unmasked over the whole K tile in the interior, and on
    an edge masked over the columns [lo, hi) that hold an allowed one: the
    chunks up to the diagonal's, and under a window from its trailing
    edge's on — static bounds a variant, picked by ``pl.when``. Where a
    static ``gap`` puts the edges on chunk boundaries, ``masked`` names the
    chunks an edge crosses (``_edge_chunks``) and no other is masked; at
    traced offsets it is True, every column of [lo, hi)."""
    live, interior = _causal_tile(offs_ref, qi, kj, tq, tk, window)
    pl.when(interior)(lambda: body(False, 0, tk))
    cw = _chunk(tq, tk)
    if cw < tk and _on_chunks(gap, cw, window):
        past = offs_ref[0] + qi * tq - offs_ref[1] - kj * tk
        for m, (a, c, masks) in _edge_chunks(tk // cw, cw, window).items():
            pl.when(past == m * cw)(
                functools.partial(body, masks, a * cw, c * cw))
        return
    edge = live & ~interior
    if cw == tk:
        pl.when(edge)(lambda: body(True, 0, tk))
        return
    n = _live_chunks(offs_ref, qi, kj, tq, tk)
    if window is None:
        for c in range(1, tk // cw + 1):
            pl.when(edge & (n == c))(functools.partial(body, True, 0, c * cw))
        return
    first = _first_chunk(offs_ref, qi, kj, tq, tk, window)
    # one tile crosses both edges only under a window narrower than a K tile
    both = window < tk - 2 * cw - tq + 2
    for a in range(tk // cw):
        for c in range(a + 1, tk // cw + 1):
            if both or a == 0 or c == tk // cw:
                pl.when(edge & (first == a) & (n == c))(
                    functools.partial(body, True, a * cw, c * cw))


def _mask(offs_ref, qi, kj, tq, tk, lo, hi, window, masked):
    """``x, fill -> x`` with ``fill`` at the pairs of an edge tile's columns
    [lo, hi) that are not allowed: every column tested (``masked`` True),
    or the chunks ``masked`` names alone (``_edge_chunks``), each by a
    static [tq, tq] triangle, the other columns passed on as they are. A
    dot, row max or row sum still sees one [tq, hi - lo] array."""
    if masked is True:
        allowed = _allowed(offs_ref, qi, kj, tq, tk, lo, hi, window)
        return lambda x, fill: jnp.where(allowed, x, fill)
    row = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
    keeps = [(c * tq, row >= col if diagonal else row < col)
             for c, diagonal in masked]

    def apply(x, fill):
        parts, at = [], 0
        for start, keep in keeps:
            parts += [x[:, at:start],
                      jnp.where(keep, x[:, start:start + tq], fill)]
            at = start + tq
        parts.append(x[:, at:])
        return jnp.concatenate([part for part in parts if part.shape[1]],
                               axis=1)

    return apply


def _allowed(offs_ref, qi, kj, tq, tk, lo, hi, window):
    """[tq, hi - lo] mask of an edge tile's columns [lo, hi): the column is
    at or before the row and, under a window, less than ``window`` behind."""
    q_pos = offs_ref[0] + qi * tq + jax.lax.broadcasted_iota(
        jnp.int32, (tq, hi - lo), 0)
    k_pos = offs_ref[1] + kj * tk + jax.lax.broadcasted_iota(
        jnp.int32, (tq, hi - lo), 1)
    if lo:
        k_pos = k_pos + lo
    allowed = q_pos >= k_pos
    if window is not None:
        allowed = allowed & (q_pos - k_pos < window)
    return allowed


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            causal: bool, scale: float, window=None, gap=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]

    # the K dimension iterates innermost over the same output block, so the
    # out refs double as the online-softmax running state
    @pl.when(kj == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])
        m_ref[0] = jnp.full_like(m_ref[0], _NEG)
        l_ref[0] = jnp.zeros_like(l_ref[0])

    def body(masked, lo: int, hi: int):
        # Dots keep the inputs' NATIVE dtype (bf16) with f32 accumulation:
        # the MXU runs bf16x bf16 at 4x its f32 rate, and the operands are
        # already bf16 so the products are bit-identical; only the scale
        # (applied post-dot, in f32) and the p cast below round differently
        # — the standard flash-attention-2 precision recipe.
        q = q_ref[0]                                  # [TQ, D] native dtype
        k = k_ref[0, lo:hi, :]                        # [W, D], W <= TK
        v = v_ref[0, lo:hi, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(q_ref.dtype)) * scale
        if masked:
            mask = _mask(offs_ref, qi, kj, tq, tk, lo, hi, window, masked)
            s = mask(s, _NEG)
        m_prev = m_ref[0][:, 0]                       # [TQ]
        l_prev = l_ref[0][:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)               # 0 on the first block
        p = jnp.exp(s - m_new[:, None])
        if masked:
            p = mask(p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        o_ref[0] = alpha[:, None] * o_ref[0] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(q_ref.dtype))
        # m/l carry a size-8 lane dim purely for TPU tiling (sublane x lane
        # constraints); consumers read lane 0.
        m_ref[0] = jnp.broadcast_to(m_new[:, None], (tq, 8))
        l_ref[0] = jnp.broadcast_to(l_new[:, None], (tq, 8))

    if causal:
        # Three tile classes: dead tiles (K entirely in the future, or
        # behind the window) are skipped and fetch nothing (_kv_block);
        # interior tiles (K entirely in the past and inside the window) run
        # unmasked; edge tiles work on their live column chunks only and
        # pay the mask — iotas, compares and selects — on the chunk an edge
        # crosses (on all of them at traced offsets). With TK = 4 TQ the
        # diagonal is no small part: 16 of the 40 live tiles of an
        # 8192-token sequence, every tile at 2048.
        _when_causal(offs_ref, qi, kj, tq, tk, body, window, gap)
    else:
        body(False, 0, tk)


def _check_heads(q, k, v, causal: bool, window) -> int:
    """Query heads a k/v head: q [B, Sq, Hq, D] against k [B, Sk, Hkv, D]
    and v [B, Sk, Hkv, Dv] with Hq a multiple of Hkv."""
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q has {q.shape[2]} heads, k {k.shape[2]} and v {v.shape[2]}: k "
            "and v need the same number, and q a multiple of it")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and a size of at least 1: "
            "row t sees the columns s with 0 <= t - s < window")
    return q.shape[2] // k.shape[2]


def _bhsd(x):  # [B, S, H, C] -> [B*H, S, C]
    B, S, H, C = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, C)


def _entry(name: str):
    """``jax.jit`` of an entry point's body, static ``gap`` and all, under
    the entry point's own name: what jaxprs and traces call it (a trace's
    op names read ``jit(flash_block)/bf.flash.fwd``)."""
    def jit(body):
        body.__name__ = body.__qualname__ = name
        return jax.jit(body, static_argnames=("causal", "window",
                                              "interpret", "gap"))
    return jit


def _gap(q_off, k_off):
    """q_off - k_off where both offsets are Python ints, else None: a
    traced offset (a ring step's) leaves the kernels masking every live
    column of an edge tile."""
    if isinstance(q_off, int) and isinstance(k_off, int):
        return q_off - k_off
    return None


def flash_block(q, k, v, q_off, k_off, *, causal: bool = True, window=None,
                interpret: bool = False):
    """Attention partials of q against one K/V block.

    q: [B, Sq, Hq, D]; k: [B, Sk, Hkv, D]; v: [B, Sk, Hkv, Dv] (Dv may
    differ from D: latent attention has a 192-wide q.k and a 128-wide v; the
    score scale is 1/sqrt(D); Hq is a multiple of Hkv, query head h reading
    k/v head h // (Hq / Hkv) through the block specs: K and V are not
    repeated); q_off/k_off: scalar global positions of element 0 (for
    causal masking across ring steps). ``window`` (with ``causal``): row t
    sees the columns s with 0 <= t - s < window, in global positions.
    Returns (o, m, l): [B, Sq, Hq, Dv] f32 unnormalized output and
    [B, Sq, Hq] f32 row max / row sum. Final output = o / l after merging
    blocks; a row that sees no column of this block has l = 0.

    Offsets given as Python ints let an edge tile mask only the chunk an
    edge crosses (``_edge_chunks``); the numbers are the same either way.
    """
    return _flash_block(q, k, v, q_off, k_off, causal=causal, window=window,
                        interpret=interpret, gap=_gap(q_off, k_off))


@_entry("flash_block")
def _flash_block(q, k, v, q_off, k_off, *, causal, window, interpret, gap):
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    group = _check_heads(q, k, v, causal, window)
    scale = 1.0 / math.sqrt(D)
    tq = _q_tile(Sq)
    tk = _k_tile(Sk)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    grid = (B * H, Sq // tq, Sk // tk)
    kernel = functools.partial(_kernel, causal=causal, scale=scale,
                               window=window, gap=gap)
    kv_map = _kv_index_map(causal, tq, tk, group, window, Sq // tq, Sk // tk)
    kw = _vma(q, k, v)
    out_shape = (
        jax.ShapeDtypeStruct((B * H, Sq, Dv), jnp.float32, **kw),
        jax.ShapeDtypeStruct((B * H, Sq, 8), jnp.float32, **kw),
        jax.ShapeDtypeStruct((B * H, Sq, 8), jnp.float32, **kw),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, D), lambda bh, qi, kj, offs: (bh, qi, 0)),
            pl.BlockSpec((1, tk, D), kv_map),
            pl.BlockSpec((1, tk, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, Dv), lambda bh, qi, kj, offs: (bh, qi, 0)),
            pl.BlockSpec((1, tq, 8), lambda bh, qi, kj, offs: (bh, qi, 0)),
            pl.BlockSpec((1, tq, 8), lambda bh, qi, kj, offs: (bh, qi, 0)),
        ],
    )
    # bh/qi grid dims are independent (parallel); kj is the sequential
    # online-softmax accumulation and must stay "arbitrary"
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    qkv = (_bhsd(q), _bhsd(k), _bhsd(v))
    with jax.named_scope(SCOPE_FWD):
        o, m, l = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, **params,
        )(offs, *qkv)

    def sbhd(x):  # [B*H, Sq, C] -> [B, Sq, H, C]
        return x.reshape((B, H) + x.shape[1:]).transpose(0, 2, 1, 3)

    return sbhd(o), sbhd(m)[..., 0], sbhd(l)[..., 0]


# the compiled programs are the jitted body's: a caller that changes the
# tiles (the tests) drops them through the entry point
flash_block.clear_cache = _flash_block.clear_cache


def _bwd_tiles(offs_ref, qi, kj, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref,
               d_ref, masked, lo: int, hi: int, scale: float,
               window=None):
    """Shared backward-tile recompute -> (q, k, g*inv_l, P_unnorm, dS) over
    the columns [lo, hi) of the K/V tile (all of it but on an edge tile:
    _when_causal).

    The probability tile is rebuilt in VMEM from the saved GLOBAL (m, l)
    row statistics with the same offset-based causal mask as the forward
    kernel; the row normalizer rides the RETURNED g (see the inline note)
    so the [TQ, TK] tile is touched once less, and dS = P * (dP - D) is
    the softmax-jacobian product dk and dq are both made from. q is
    returned UNSCALED — the dk product applies the score scale itself."""
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    # native-dtype (bf16) dot operands, f32 accumulation — see _kernel; the
    # scale moves AFTER the qk dot (q stays unscaled, so the dk product
    # applies it explicitly)
    q = q_ref[0]
    k = k_ref[0, lo:hi, :]
    v = v_ref[0, lo:hi, :]
    g = g_ref[0]
    m = m_ref[0][:, 0]
    inv_l = 1.0 / l_ref[0][:, 0]
    d = d_ref[0][:, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_dot_prec(q_ref.dtype)) * scale
    if masked:
        mask = _mask(offs_ref, qi, kj, tq, tk, lo, hi, window, masked)
        s = mask(s, _NEG)
    # VPU saver: the softmax row normalizer inv_l is folded into the
    # per-ROW quantities instead of the [TQ, TK] tile — p stays
    # UNNORMALIZED (exp(s - m), in [0, 1] since m is the global row max)
    # and the returned g is pre-scaled g * inv_l, so
    #   dP  = g @ V^T           becomes dp' = (g inv_l) @ V^T = dP inv_l
    #   dS  = P (dP - d)        becomes ds  = p_un (dp' - d inv_l) = dS
    #   dV += P^T g             becomes      p_un^T (g inv_l)      = dV
    # — one fewer full-tile elementwise pass per (q, k) tile pair.
    p = jnp.exp(s - m[:, None])
    if masked:
        p = mask(p, 0.0)
    g_scaled = (g.astype(jnp.float32)
                * inv_l[:, None]).astype(g.dtype)   # [TQ, D]: cheap
    dp = jax.lax.dot_general(g_scaled, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=_dot_prec(q_ref.dtype))
    ds = p * (dp - (d * inv_l)[:, None])
    return q, k, g_scaled, p, ds


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref,
                dk_ref, dv_ref, dq_ref, *, causal: bool, scale: float,
                window=None, gap=None):
    """The backward (flash-attention-2): for each K/V tile, iterate query
    tiles innermost and accumulate dv += P^T @ dO and dk += dS^T @ (Q *
    scale) into the K tile's output blocks, and dq += dS @ K * scale into
    rows [qi * tq, +tq) of dq_ref, one (batch, head)'s whole [Sq, D] f32:
    that block's index moves with bh alone, so it stays in VMEM across the
    head's (kj, qi) steps and is written back once. Scores and
    probabilities never reach HBM, and a tile pair's are built once."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    tq, tk = q_ref.shape[1], k_ref.shape[1]

    @pl.when((kj == 0) & (qi == 0))
    def _init_head():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    def body(masked, lo: int, hi: int):
        q, k, g, p, ds = _bwd_tiles(offs_ref, qi, kj, q_ref, k_ref, v_ref,
                                    g_ref, m_ref, l_ref, d_ref, masked, lo,
                                    hi, scale, window)
        prec = _dot_prec(q_ref.dtype)
        # rows of dk/dv outside [lo, hi) belong to columns that are masked
        # for this whole q tile: nothing is added to them
        dv_ref[0, lo:hi, :] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        ds = ds.astype(q.dtype)         # cast once for both products
        # q is unscaled in the shared tile recompute: apply the score scale
        # here (dK = dS^T @ (scale * Q))
        dk_ref[0, lo:hi, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale
        dq_ref[0, pl.ds(pl.multiple_of(qi * tq, tq), tq), :] += (
            jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec) * scale)

    if causal:
        _when_causal(offs_ref, qi, kj, tq, tk, body, window, gap)
    else:
        body(False, 0, tk)


def _lane8(x):  # [B, S, H] -> [B*H, S, 8] (TPU sublane x lane tiling)
    B, S, H = x.shape
    t = x.transpose(0, 2, 1).reshape(B * H, S)
    return jnp.broadcast_to(t[:, :, None], (B * H, S, 8))


def flash_block_bwd(q, k, v, g, d_term, m, l, q_off, k_off, *,
                    causal: bool = True, window=None,
                    interpret: bool = False):
    """Gradients of q's attention against one K/V block (one pallas kernel).

    Inputs: q [B, Sq, Hq, D]; k [B, Sk, Hkv, D]; v [B, Sk, Hkv, Dv];
    g = dOut [B, Sq, Hq, Dv];
    ``d_term = sum(dOut * Out, -1)`` and the saved GLOBAL softmax row stats
    ``m`` (row max) and ``l`` (row sum), all [B, Sq, Hq] f32 — the same
    quantities the XLA ring backward reconstructs per block
    (context._ring_backward). Returns (dq_partial, dk, dv) in f32, shaped
    as q, k and v: the caller sums dq partials over blocks and ships dk/dv
    home with the ring. ``window`` as in :func:`flash_block`.

    With fewer k/v heads than q heads the kernel reads a k/v head through
    its block specs and writes dk/dv a q head (the grid keeps a q head's
    steps together for its resident dq, so a K tile's output block cannot
    be revisited by the next head of its group); the heads of a group are
    summed in XLA, outside the kernel's scope.

    The kernel holds a head's whole dq in VMEM. Where [Sq, D] is past what
    ``_dq_rows`` allows (32k tokens at 128 lanes), q is walked in row
    blocks that fit, each one call of the same kernel at its own ``q_off``,
    and the blocks' dk/dv are summed: no second kernel for long shapes, and
    no score built twice there either. Offsets as in :func:`flash_block`.
    """
    return _flash_block_bwd(q, k, v, g, d_term, m, l, q_off, k_off,
                            causal=causal, window=window, interpret=interpret,
                            gap=_gap(q_off, k_off))


@_entry("flash_block_bwd")
def _flash_block_bwd(q, k, v, g, d_term, m, l, q_off, k_off, *, causal,
                     window, interpret, gap):
    Sq = q.shape[1]
    rows = _dq_rows(Sq, q.shape[-1])

    def block(r):
        qb, gb, db, mb, lb = (x[:, r:r + rows] for x in (q, g, d_term, m, l))
        return _bwd_call(qb, k, v, gb, db, mb, lb, q_off + r, k_off,
                         causal, window, interpret,
                         None if gap is None else gap + r)

    dq, dk, dv = zip(*map(block, range(0, Sq, rows)))
    return jnp.concatenate(dq, axis=1), sum(dk[1:], dk[0]), sum(dv[1:], dv[0])


flash_block_bwd.clear_cache = _flash_block_bwd.clear_cache


def _bwd_call(q, k, v, g, d_term, m, l, q_off, k_off, causal, window,
              interpret, gap):
    """One ``pallas_call`` of the backward kernel: (dq, dk, dv) of q's rows
    against the K/V block, dq resident (``flash_block_bwd``)."""
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    group = _check_heads(q, k, v, causal, window)
    scale = 1.0 / math.sqrt(D)
    tq = _q_tile(Sq)
    tk = _k_tile(Sk)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    kw = _vma(q, k, v, g)
    # dq is summed over kj and dk/dv over qi: only bh is independent
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem(Sq, D, Dv))}
    q_map = _q_index_map(causal, tq, tk, Sq // tq, window, Sk // tk)
    head = _kv_head(group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, Sk // tk, Sq // tq),
        in_specs=[
            pl.BlockSpec((1, tq, D), q_map),
            pl.BlockSpec((1, tk, D), lambda bh, kj, qi, o: (head(bh), kj, 0)),
            pl.BlockSpec((1, tk, Dv), lambda bh, kj, qi, o: (head(bh), kj, 0)),
            pl.BlockSpec((1, tq, Dv), q_map),
            pl.BlockSpec((1, tq, 8), q_map),
            pl.BlockSpec((1, tq, 8), q_map),
            pl.BlockSpec((1, tq, 8), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, tk, D), lambda bh, kj, qi, o: (bh, kj, 0)),
            pl.BlockSpec((1, tk, Dv), lambda bh, kj, qi, o: (bh, kj, 0)),
            pl.BlockSpec((1, Sq, D), lambda bh, kj, qi, o: (bh, 0, 0)),
        ],
    )
    # the operands are made outside the scope: it times the kernel alone
    operands = (offs, _bhsd(q), _bhsd(k), _bhsd(v), _bhsd(g),
                _lane8(m), _lane8(l), _lane8(d_term))
    with jax.named_scope(SCOPE_DKV):
        dk, dv, dq = pl.pallas_call(
            functools.partial(_bwd_kernel, causal=causal, scale=scale,
                              window=window, gap=gap),
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct((B * H, Sk, D), jnp.float32, **kw),
                jax.ShapeDtypeStruct((B * H, Sk, Dv), jnp.float32, **kw),
                jax.ShapeDtypeStruct((B * H, Sq, D), jnp.float32, **kw),
            ),
            interpret=interpret, **params,
        )(*operands)

    def sbhd(x):  # [B*H, S, C] -> [B, S, H, C]
        return x.reshape((B, H) + x.shape[1:]).transpose(0, 2, 1, 3)

    def of_group(x):  # [B, S, Hq, C] -> [B, S, Hkv, C]: a k/v head's q heads
        if group == 1:
            return x
        return x.reshape(x.shape[:2] + (H // group, group, -1)).sum(axis=3)

    return sbhd(dq), of_group(sbhd(dk)), of_group(sbhd(dv))


def _blockwise_attention(q, k, v, causal: bool, tk: int):
    """Pure-XLA blockwise attention: lax.scan over K blocks with online
    softmax, each step under jax.checkpoint. Numerically the same function
    as the pallas kernel, O(S*tk) live memory — kept as the independent
    test oracle for the kernel's values (tests/test_flash.py); the
    production backward is the pallas kernel (flash_block_bwd)."""
    B, S, H, D = q.shape
    Sk = k.shape[1]
    nk = Sk // tk
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale
    # keep K/V in their input dtype; each block upcasts inside the
    # checkpointed step, so only one block's f32 copy is ever live
    kb = k.reshape(B, nk, tk, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, tk, H, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    @jax.checkpoint
    def step(carry, inp):
        o, m, l = carry
        kj, kblk, vblk = inp
        kblk = kblk.astype(jnp.float32)
        vblk = vblk.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kblk,
                       preferred_element_type=jnp.float32)
        if causal:
            k_pos = kj * tk + jnp.arange(tk)
            allowed = (q_pos[None, :, None, None] >= k_pos[None, None, None, :])
            s = jnp.where(allowed, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(allowed, p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        o_new = alpha[..., None] * o + jnp.einsum(
            "bqhk,bkhd->bqhd", p, vblk, preferred_element_type=jnp.float32)
        return (o_new, m_new, l_new), None

    init = (jnp.zeros((B, S, H, v.shape[-1]), jnp.float32),
            jnp.full((B, S, H), _NEG, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    (o, m, l), _ = jax.lax.scan(step, init, (jnp.arange(nk), kb, vb))
    return (o / l[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, interpret, named=False):
    o, m, l = flash_block(q, k, v, 0, 0, causal=causal, window=window,
                          interpret=interpret)
    return (o / l[..., None]).astype(q.dtype)


def _flash_fwd(q, k, v, causal, window, interpret, named):
    o, m, l = flash_block(q, k, v, 0, 0, causal=causal, window=window,
                          interpret=interpret)
    out = (o / l[..., None]).astype(q.dtype)
    return _named(named, (q, k, v, out, m, l))


def _flash_bwd(causal, window, interpret, named, res, g):
    # flash-attention-2 style kernel backward: one kernel makes dq, dk and
    # dv from probability tiles rebuilt in VMEM from the saved (m, l) stats
    # — no autodiff-through-recompute, no [S, S] tensor in either direction
    q, k, v, out, m, l = res
    gf = g.astype(jnp.float32)
    d_term = jnp.sum(gf * out.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_block_bwd(q, k, v, gf, d_term, m, l, 0, 0,
                                 causal=causal, window=window,
                                 interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    interpret: bool = False, name_residuals: bool = False):
    """Single-device flash attention: q [B, S, Hq, D], k [B, S, Hkv, D] and
    v [B, S, Hkv, Dv] give the normalized output [B, S, Hq, Dv] (Dv = D for
    equal-width heads, latent attention has D = 192 and Dv = 128; scale
    1/sqrt(D)). Grouped-query heads: Hq is a multiple of Hkv, query head h
    reads k/v head h // (Hq / Hkv), K and V stay at Hkv heads in HBM.
    ``window=W`` (with ``causal``): row t sees the columns s with
    0 <= t - s < W; grid steps wholly behind it are skipped like the future.

    Differentiable: the forward runs the pallas VMEM kernel, the backward
    one flash-attention-2 kernel (:func:`flash_block_bwd`, each probability
    tile rebuilt once in VMEM from the saved (m, l) stats): no [S, S] scores.
    ``name_residuals`` names all the backward reads of the forward (q, k, v,
    the output, the f32 row max and sum) by ``RESIDUAL_NAMES``, for a caller's
    ``jax.checkpoint`` policy to keep: the forward kernel then runs once."""
    # trace-time gauges (docs/metrics.md): whether the backward of this
    # shape is one call of the kernel, and the schedule the kernels run
    metrics.gauge("flash.bwd_fused").set(
        int(_dq_rows(q.shape[1], q.shape[-1]) == q.shape[1]))
    metrics.gauge("flash.kv_group").set(_check_heads(q, k, v, causal, window))
    metrics.gauge("flash.window").set(window or 0)
    if causal:
        sched = causal_schedule(q.shape[1], k.shape[1], window=window)
        metrics.gauge("flash.dead_steps_fetching").set(sched["dead_fetching"])
        metrics.gauge("flash.chunks_computed").set(sched["chunks_computed"])
        metrics.gauge("flash.chunks_needed").set(sched["chunks_needed"])
        metrics.gauge("flash.chunks_masked").set(sched["chunks_masked"])
    if name_residuals:  # a line of its own: the default call keeps its source position
        return _flash(q, k, v, causal, window, interpret, True)
    return _flash(q, k, v, causal, window, interpret)


# what ``flash_attention(..., name_residuals=True)`` names for a caller's
# ``jax.checkpoint`` policy (``jax.checkpoint_policies.save_only_these_names``):
# every residual of the forward rule, so that the backward reads the very q and
# k whose scores gave m and l, not copies made again (which XLA is free to round
# otherwise). With (out, m, l) alone one full-size run of ``ouro-*`` failed the
# harness's step check, 6.8e-4 of the loss for 5e-4; with all six, three read
# 2e-5 to 9e-5, and the q, k, v projections are not made again (PERF.md, PR 38)
RESIDUAL_NAMES = ("bf.flash.q", "bf.flash.k", "bf.flash.v", "bf.flash.out", "bf.flash.m",
                  "bf.flash.l")


def _named(named: bool, residuals):
    """The forward rule's (output, residuals), each residual under its name of
    ``RESIDUAL_NAMES`` if ``named``, and the output the named one."""
    # imported here: a line added above the kernels would move the source
    # positions that every cell's serialized kernel bodies hold
    from jax.ad_checkpoint import checkpoint_name

    if named:
        residuals = tuple(map(checkpoint_name, residuals, RESIDUAL_NAMES))
    return residuals[3], residuals
