"""Pallas flash-attention kernel tests.

This suite runs the kernels in interpret mode (pallas has no CPU lowering).
The compiled kernels — the forward and the one backward, inside the
optimizer's shard_map where the operands carry vma — are checked against the
reference on the chip by ``chip_smoke.py``'s ``lm_flash`` phase.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.parallel import flash, ring_attention
from bluefog_tpu.parallel.context import reference_attention
from bluefog_tpu.parallel.flash import flash_attention
from bluefog_tpu.runtime import metrics


def _qkv(B=1, S=256, H=2, D=128, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    return (jax.random.normal(k1, (B, S, H, D), dtype),
            jax.random.normal(k2, (B, S, H, D), dtype),
            jax.random.normal(k3, (B, S, H, D), dtype))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense_interpret(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


def test_ring_attention_flash_path_interpret(bf8):
    """The flash kernel inside the sharded ring exchange (8-way CPU mesh)."""
    import bluefog_tpu as bf

    q, k, v = _qkv(S=512)
    mesh = bf.mesh()
    got = ring_attention(q, k, v, mesh=mesh, causal=True, use_flash=True,
                         interpret=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow  # kernel-vs-dense VJP kept in the full suite
def test_flash_gradient_matches_dense():
    """flash_attention differentiates: grads match the dense oracle (the
    backward is the VJP of the checkpointed blockwise twin)."""
    B, S, H, D = 1, 64, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in keys)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def dense_loss(q, k, v):
        out = reference_attention(q, k, v, causal=True)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5,
            err_msg=f"grad mismatch for {name}")


def test_blockwise_twin_matches_kernel_values():
    from bluefog_tpu.parallel.flash import _blockwise_attention

    B, S, H, D = 2, 32, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in keys)
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = _blockwise_attention(q, k, v, causal=True, tk=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_flash_gradients_match_einsum_ring(bf8):
    """The flash ring differentiates: its custom VJP (the einsum-ring twin)
    yields the same gradients as differentiating the einsum ring directly."""
    import bluefog_tpu as bf

    q, k, v = _qkv(S=64, D=8)
    mesh = bf.mesh()

    def loss(use_flash):
        def f(q, k, v):
            out = ring_attention(q, k, v, mesh=mesh, causal=True,
                                 use_flash=use_flash, interpret=use_flash)
            return jnp.sum(out * jnp.sin(out))
        return f

    gf = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=f"ring grad mismatch for {name}")


# -- the causal schedule at tk = 4 tq ---------------------------------------
# The default tiles are 512 x 2048: a K tile is four column chunks of the q
# tile's width. The same geometry at test size: 8 x 32 over 64-row blocks of a
# 128-token sequence, so a block pair has dead steps, interior tiles and
# diagonal tiles of one to four live chunks.

TQ, TK, BLOCK, SEQ = 8, 32, 64, 128
# first row of the q block: K block 0 straddles and K block 64 is wholly in
# the future (0); wholly in the past and on the diagonal (64); between K
# tiles (24); not a multiple of tq (20, 61)
Q_OFFS = [0, 64, 24, 20, 61]


@pytest.fixture
def tiles_8x32(monkeypatch):
    """The tile candidates are read while tracing: drop what was compiled
    under other tiles, before and after."""
    monkeypatch.setattr(flash, "_Q_TILES", (TQ,))
    monkeypatch.setattr(flash, "_K_TILES", (TK,))
    flash.flash_block.clear_cache()
    flash.flash_block_bwd.clear_cache()
    yield
    flash.flash_block.clear_cache()
    flash.flash_block_bwd.clear_cache()


def _seq():
    """q, k, v and the loss's weights: [1, SEQ, 2 heads, 8]."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    return tuple(jax.random.normal(kk, (1, SEQ, 2, 8), jnp.float32)
                 for kk in keys)


def _blocks(q, k, v, w, q_off):
    """Output and gradients of rows [q_off, q_off + BLOCK) of causal
    attention over the whole sequence, from the kernels' block partials the
    way the ring merges them: (out, dq) of those rows, (dk, dv) of all."""
    rows = slice(q_off, q_off + BLOCK)
    qb, wb = q[:, rows], w[:, rows]
    parts = [flash.flash_block(qb, k[:, o:o + BLOCK], v[:, o:o + BLOCK],
                               q_off, o, causal=True, interpret=True)
             for o in range(0, SEQ, BLOCK)]
    m = functools.reduce(jnp.maximum, (p[1] for p in parts))
    l = sum(p[2] * jnp.exp(p[1] - m) for p in parts)
    out = sum(p[0] * jnp.exp(p[1] - m)[..., None] for p in parts) / l[..., None]
    d_term = jnp.sum(wb * out, axis=-1)
    grads = [flash.flash_block_bwd(qb, k[:, o:o + BLOCK], v[:, o:o + BLOCK],
                                   wb, d_term, m, l, q_off, o, causal=True,
                                   interpret=True)
             for o in range(0, SEQ, BLOCK)]
    return (out, sum(g[0] for g in grads),
            jnp.concatenate([g[1] for g in grads], axis=1),
            jnp.concatenate([g[2] for g in grads], axis=1))


@pytest.mark.parametrize("q_off", Q_OFFS)
def test_causal_chunks_match_dense_at_offsets(tiles_8x32, q_off):
    q, k, v, w = _seq()
    rows = slice(q_off, q_off + BLOCK)

    def dense_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True)[:, rows]
                       * w[:, rows])

    want_out = reference_attention(q, k, v, causal=True)[:, rows]
    dq, dk, dv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    got = _blocks(q, k, v, w, q_off)
    for a, b, name in zip(got, (want_out, dq[:, rows], dk, dv),
                          ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=f"{name} at q_off {q_off}")


@pytest.mark.parametrize("q_off", Q_OFFS)
def test_causal_chunks_equal_whole_diagonal_tiles(tiles_8x32, monkeypatch,
                                                  q_off):
    """The columns a diagonal tile leaves out had p = 0: the numbers are
    those of the kernels that work on the whole tile."""
    q, k, v, w = _seq()
    chunked = _blocks(q, k, v, w, q_off)
    monkeypatch.setattr(
        flash, "_live_chunks",
        lambda offs, qi, kj, tq, tk, xp=jnp: jnp.int32(tk // tq))
    flash.flash_block.clear_cache()
    flash.flash_block_bwd.clear_cache()
    whole = _blocks(q, k, v, w, q_off)
    for a, b, name in zip(chunked, whole, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=0, err_msg=name)


def test_flash_attention_gradients_at_4to1_tiles(tiles_8x32):
    """The grid of an 8192-token sequence at the default tiles (16 x 4: 24
    dead steps, 16 diagonal tiles) through the custom VJP."""
    q, k, v, w = _seq()

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    got = jax.grad(loss(functools.partial(
        flash_attention, causal=True, interpret=True)), argnums=(0, 1, 2))(
            q, k, v)
    want = jax.grad(loss(functools.partial(reference_attention, causal=True)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=f"grad mismatch for {name}")
    gauges = metrics.snapshot(include_native=False)["gauges"]
    assert (gauges["flash.dead_steps_fetching"], gauges["flash.chunks_computed"],
            gauges["flash.chunks_needed"]) == (0.0, 136.0, 136.0)
    assert gauges["flash.bwd_fused"] == 1.0


@pytest.mark.parametrize("q_off,k_off", [
    (0, 0), (64, 0), (0, 64), (24, 0), (20, 0), (61, 64), (3, 40)])
def test_dead_steps_keep_their_neighbours_block(q_off, k_off):
    """What the index maps ask for on a dead step is what a live neighbour
    reads: block 0 of K/V in the forward / dq order, the row's first live q
    tile in the dk/dv order (the last q tile under a wholly dead row)."""
    nq, nk = BLOCK // TQ, BLOCK // TK
    offs = (q_off, k_off)
    qi, kj = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    live, _ = flash._causal_tile(offs, qi, kj, TQ, TK)
    kv = flash._kv_block(offs, qi, kj, TQ, TK, xp=np)
    qb = flash._q_block(offs, qi, kj, TQ, TK, nq, xp=np)
    np.testing.assert_array_equal(kv[live], kj[live])
    np.testing.assert_array_equal(qb[live], qi[live])
    assert (kv[~live] == 0).all()
    for j in range(nk):
        first = qi[live[:, j], j].min() if live[:, j].any() else nq - 1
        assert (qb[~live[:, j], j] == first).all(), (j, qb[:, j])
    # so, in either grid order, no run of one block index is all dead steps
    # (but the one copy a pipeline starts with, where nothing is live)
    fetching = max(flash._idle_fetches(kv.ravel(), live.ravel()),
                   flash._idle_fetches(qb.T.ravel(), live.T.ravel()))
    assert fetching == (0 if live.any() else 1)


@pytest.mark.parametrize("env", [{}, {"BLUEFOG_FLASH_TQ": "8",
                                      "BLUEFOG_FLASH_TK": "32"}],
                         ids=["env_unset", "old_tile_knobs_set"])
@pytest.mark.parametrize("s,want", [
    (8192, (64, 40, 24, 0, 136, 136)), (2048, (4, 4, 0, 0, 10, 10))])
def test_causal_schedule_of_the_benchmarks_sequences(monkeypatch, s, want,
                                                     env):
    """The cells' geometry is the module's, whatever the environment holds."""
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert (flash._q_tile(s), flash._k_tile(s)) == (512, 2048)
    got = flash.causal_schedule(s, s, 0, 0)
    assert tuple(got[key] for key in (
        "steps", "live", "dead", "dead_fetching", "chunks_computed",
        "chunks_needed")) == want


@pytest.mark.parametrize("q_off,k_off", [(8192, 0), (1000, 300), (300, 1000)])
def test_causal_schedule_computes_what_is_needed_at_ring_offsets(q_off, k_off):
    got = flash.causal_schedule(8192, 8192, q_off, k_off)
    assert got["chunks_computed"] == got["chunks_needed"] > 0
    assert got["dead_fetching"] == 0


# -- the one backward kernel -------------------------------------------------
# dq is an output of the dk/dv grid, a head's whole [Sq, D] resident across
# its (kj, qi) steps; past ``flash._DQ_VMEM_BYTES`` q is walked in row blocks.

def _wide(d, dv, seq=SEQ):
    """q, k [1, seq, 2, d], v and the loss's weights [1, seq, 2, dv]."""
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    return tuple(jax.random.normal(kk, (1, seq, 2, w), jnp.float32)
                 for kk, w in zip(keys, (d, d, dv, dv)))


def _grads(attn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d,dv", [(8, 8), (192, 128)], ids=["8x8", "192x128"])
def test_one_backward_kernel_matches_grad_of_blockwise(tiles_8x32, d, dv,
                                                       causal):
    """dq, dk and dv of the single backward against autodiff through the
    XLA twin, on the 16 x 4 grid of an 8192-token sequence."""
    q, k, v, w = _wide(d, dv)
    got = _grads(functools.partial(flash_attention, causal=causal,
                                   interpret=True), q, k, v, w)
    want = _grads(functools.partial(flash._blockwise_attention, causal=causal,
                                    tk=TK), q, k, v, w)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   err_msg=name)


def _block_attention(q, k, v, q_off, k_off):
    """Dense causal attention of q's rows (at q_off) against this K/V block
    alone (at k_off) -> (out, m, l, has): rows with no allowed column come
    back 0 with the stats (0, 1) and ``has`` false."""
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / np.sqrt(q.shape[-1])
    allowed = ((q_off + jnp.arange(q.shape[1]))[:, None, None]
               >= (k_off + jnp.arange(k.shape[1]))[None, None, :])
    has = allowed.any(axis=-1)                       # [Sq, 1]
    m = jnp.where(has, jnp.max(jnp.where(allowed, s, -jnp.inf), axis=-1), 0.0)
    p = jnp.where(allowed, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.where(has, jnp.sum(p, axis=-1), 1.0)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v) / l[..., None], m, l, has


@pytest.mark.parametrize("q_off,k_off,dead_rows", [
    (8192, 0, 0), (1000, 300, 0), (300, 1000, 700)])
def test_one_backward_kernel_at_ring_offsets(q_off, k_off, dead_rows):
    """The default tiles (2 x 2 of 512 x 2048) at a ring step's runtime
    offsets: every tile interior; a diagonal K tile and a dead one; a dead q
    tile, and a diagonal one whose first rows see no column (dq rows 0)."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q, w = (jax.random.normal(kk, (1, 1024, 1, 8), jnp.float32)
            for kk in keys[:2])
    k, v = (jax.random.normal(kk, (1, 4096, 1, 8), jnp.float32)
            for kk in keys[2:])
    out, m, l, has = _block_attention(q, k, v, q_off, k_off)
    assert int((~has).sum()) == dead_rows
    want = _grads(lambda q, k, v: _block_attention(q, k, v, q_off, k_off)[0],
                  q, k, v, w)
    got = flash.flash_block_bwd(q, k, v, w, jnp.sum(w * out, axis=-1), m, l,
                                q_off, k_off, causal=True, interpret=True)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)
    assert not np.asarray(got[0])[:, :dead_rows].any()


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d,dv", [(8, 8), (192, 128)], ids=["8x8", "192x128"])
def test_walked_backward_equals_the_resident_one(
        tiles_8x32, monkeypatch, d, dv, causal):
    """Both sides of the shape rule: the same sums, dk/dv of the row blocks
    added in XLA. The gauge says which side was traced."""
    q, k, v, w = _wide(d, dv)
    attn = functools.partial(flash_attention, causal=causal, interpret=True)

    def fused_gauge():
        return metrics.snapshot(include_native=False)["gauges"][
            "flash.bwd_fused"]

    resident = _grads(attn, q, k, v, w)
    assert fused_gauge() == 1.0
    monkeypatch.setattr(flash, "_DQ_VMEM_BYTES", 2 * 4 * TQ * 128 * 4)
    flash.flash_block_bwd.clear_cache()
    # room for four q tiles of a width held in 128 lanes, two at 192
    assert flash._dq_rows(SEQ, d) == 4 * TQ * 128 // flash._lanes(d)
    walked = _grads(attn, q, k, v, w)
    assert fused_gauge() == 0.0
    for a, b, name in zip(walked, resident, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6,
                                   rtol=0, err_msg=name)


MIB = 1 << 20


@pytest.mark.parametrize("sq,d,dv,rows,vmem_mib", [
    (8192, 128, 128, 8192, 24),      # pythia-s8192-*
    (2048, 128, 128, 2048, 18),      # pythia-s2048-1chip
    (8192, 192, 128, 8192, 40),      # joyai-*: 256 lanes of dq, 384 of stack
    (32768, 128, 128, 32768, 48),    # the longest that stays one call
    (65536, 128, 128, 32768, 48),
    (32768, 192, 128, 16384, 56),
    (512 * 96, 128, 128, 512 * 48, 40),   # 64 tiles fit; 48 divide 96
    (24, 8, 8, 24, 16 + 24 * 1024 / MIB),  # one tile of 8: tq = 8, 3 tiles
])
def test_dq_rows_and_vmem_limit_follow_the_shapes(sq, d, dv, rows, vmem_mib):
    assert flash._dq_rows(sq, d) == rows
    assert sq % rows == 0 and rows % flash._q_tile(sq) == 0
    assert flash._bwd_vmem(rows, d, dv) == vmem_mib * MIB


# -- grouped-query heads and the sliding window ------------------------------
# q at Hq heads against k and v at Hkv (query head h reads k/v head h // group
# through the block specs), and a trailing edge W columns behind the diagonal:
# a tile is dead past either edge, and an edge tile works on the chunks from
# the window's first to the diagonal's last.

def _grouped(hq, hkv, seq=SEQ, d=8):
    """q [1, seq, hq, d], k and v [1, seq, hkv, d], the loss's weights as q."""
    keys = jax.random.split(jax.random.PRNGKey(19), 4)
    return tuple(jax.random.normal(kk, (1, seq, h, d), jnp.float32)
                 for kk, h in zip(keys, (hq, hkv, hkv, hq)))


# no window; narrower than a column chunk (8) -- one tile crosses both edges;
# across two K tiles of 32
WINDOWS = [None, 5, 40]


@pytest.mark.parametrize("window", WINDOWS, ids=["none", "w5", "w40"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (7, 1), (28, 4)])
def test_grouped_heads_and_window_match_dense(tiles_8x32, hq, hkv, window):
    """Forward and all three gradients against dense masked attention with
    K and V repeated, on the 16 x 4 grid; dk and dv come back at Hkv heads."""
    q, k, v, w = _grouped(hq, hkv)
    attn = functools.partial(flash_attention, causal=True, window=window,
                             interpret=True)
    dense = functools.partial(reference_attention, causal=True, window=window)
    got = (attn(q, k, v),) + _grads(attn, q, k, v, w)
    want = (dense(q, k, v),) + _grads(dense, q, k, v, w)
    assert [g.shape for g in got] == [q.shape, q.shape, k.shape, v.shape]
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)
    gauges = metrics.snapshot(include_native=False)["gauges"]
    assert gauges["flash.kv_group"] == hq // hkv
    assert gauges["flash.window"] == (window or 0)
    assert gauges["flash.dead_steps_fetching"] == 0.0
    assert gauges["flash.chunks_computed"] == gauges["flash.chunks_needed"]


def _window_blocks(q, k, v, w, q_off, window):
    """``_blocks`` under a window and grouped heads: rows [q_off, +BLOCK) of
    q against the two K/V blocks at their runtime offsets, merged the way the
    ring merges them (a block a row sees nothing of has l = 0)."""
    rows = slice(q_off, q_off + BLOCK)
    qb, wb = q[:, rows], w[:, rows]
    kw = dict(causal=True, window=window, interpret=True)
    parts = [flash.flash_block(qb, k[:, o:o + BLOCK], v[:, o:o + BLOCK],
                               jnp.int32(q_off), jnp.int32(o), **kw)
             for o in range(0, SEQ, BLOCK)]
    m = functools.reduce(jnp.maximum, (p[1] for p in parts))
    l = sum(p[2] * jnp.exp(p[1] - m) for p in parts)
    out = sum(p[0] * jnp.exp(p[1] - m)[..., None] for p in parts) / l[..., None]
    d_term = jnp.sum(wb * out, axis=-1)
    grads = [flash.flash_block_bwd(qb, k[:, o:o + BLOCK], v[:, o:o + BLOCK],
                                   wb, d_term, m, l, jnp.int32(q_off),
                                   jnp.int32(o), **kw)
             for o in range(0, SEQ, BLOCK)]
    return (out, sum(g[0] for g in grads),
            jnp.concatenate([g[1] for g in grads], axis=1),
            jnp.concatenate([g[2] for g in grads], axis=1))


@pytest.mark.parametrize("window", [5, 40])
@pytest.mark.parametrize("q_off", Q_OFFS)
def test_window_and_groups_at_runtime_offsets(tiles_8x32, q_off, window):
    """``flash_block`` keeps working in a ring under a window: the offsets
    are traced values (one compiled kernel serves every ring step), and the
    window is measured in global positions across the two K/V blocks."""
    q, k, v, w = _grouped(4, 2)
    rows = slice(q_off, q_off + BLOCK)
    dense = functools.partial(reference_attention, causal=True, window=window)
    want_out = dense(q, k, v)[:, rows]
    dq, dk, dv = jax.grad(
        lambda q, k, v: jnp.sum(dense(q, k, v)[:, rows] * w[:, rows]),
        argnums=(0, 1, 2))(q, k, v)
    got = _window_blocks(q, k, v, w, q_off, window)
    for a, b, name in zip(got, (want_out, dq[:, rows], dk, dv),
                          ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=f"{name} at q_off {q_off}")


def _chunks_by_rows(sq, sk, q_off, k_off, window, tq, cw):
    """[q tiles, column chunks]: whether any row of the q tile is allowed any
    column of the chunk, marked row by row from the definition."""
    t = q_off + np.arange(sq)
    first = np.maximum(t - window + 1 if window else np.full(sq, k_off), k_off)
    last = np.minimum(t, k_off + sk - 1)
    seen = np.zeros((sq // tq, sk // cw), bool)
    for row in np.flatnonzero(first <= last):
        seen[row // tq, (first[row] - k_off) // cw:(last[row] - k_off) // cw + 1] = True
    return seen


@pytest.mark.parametrize("sq,sk,q_off,k_off,window", [
    (16384, 16384, 0, 0, 4096),      # smallthinker-s16384-*: 252 chunks of 528
    (16384, 16384, 0, 0, None),
    (8192, 8192, 0, 0, 4096), (8192, 8192, 8192, 0, 4096),
    (8192, 8192, 1000, 300, 4096), (8192, 8192, 300, 1000, 700),
    (4096, 8192, 5000, 0, 300), (2048, 2048, 0, 0, 512), (2048, 2048, 0, 0, 1),
])
def test_causal_schedule_under_a_window_computes_what_is_needed(
        sq, sk, q_off, k_off, window):
    """At the default 512 x 2048 tiles: the chunks the live steps work on are
    the chunks that hold an allowed pair, counted by brute force, and no copy
    is issued for dead steps alone -- both edges."""
    got = flash.causal_schedule(sq, sk, q_off, k_off, window)
    needed = int(_chunks_by_rows(sq, sk, q_off, k_off, window, 512, 512).sum())
    assert got["chunks_computed"] == got["chunks_needed"] == needed > 0
    assert got["dead_fetching"] == 0
    assert got["live"] + got["dead"] == got["steps"]
    if (sq, q_off, window) == (16384, 0, 4096):
        assert (got["live"], needed) == (84, 252)   # of 144 live and 528


@pytest.mark.parametrize("window", [1, 5, 8, 9, 31, 32, 40, 64, 127, 128, 500])
@pytest.mark.parametrize("q_off,k_off", [
    (0, 0), (64, 0), (24, 0), (20, 0), (3, 40), (61, 64)])
def test_windowed_dead_steps_keep_a_neighbours_block(q_off, k_off, window):
    """8 x 32 tiles over a 64 x 64 block: a live step names its own block, and
    in either grid order no run of one block index is all dead steps (but
    where a whole block pair is dead, and the one copy a pipeline starts on)."""
    nq, nk = BLOCK // TQ, BLOCK // TK
    offs = (q_off, k_off)
    qi, kj = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    live, interior = flash._causal_tile(offs, qi, kj, TQ, TK, window)
    seen = _chunks_by_rows(BLOCK, BLOCK, q_off, k_off, window, TQ, TK)
    np.testing.assert_array_equal(live, seen)
    assert not (interior & ~live).any()
    kv = flash._kv_block(offs, qi, kj, TQ, TK, np, window, nq, nk)
    qb = flash._q_block(offs, qi, kj, TQ, TK, nq, np, window, nk)
    np.testing.assert_array_equal(kv[live], kj[live])
    np.testing.assert_array_equal(qb[live], qi[live])
    fetching = max(flash._idle_fetches(kv.ravel(), live.ravel()),
                   flash._idle_fetches(qb.T.ravel(), live.T.ravel()))
    # rows that are wholly dead in the middle of the grid (a window narrower
    # than the gap between the blocks) have no neighbour to borrow from
    whole_rows_dead = (~live.any(axis=1)).sum() + (~live.any(axis=0)).sum()
    assert fetching <= (1 if not live.any() else whole_rows_dead)
    if live.all(axis=None) or (live.any(axis=1).all() and live.any(axis=0).all()):
        assert fetching == 0


def test_heads_and_window_are_checked():
    q, k, v, _ = _grouped(4, 3, seq=16)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v, interpret=True)
    q, k, v, _ = _grouped(4, 2, seq=16)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, k, v, causal=False, window=4, interpret=True)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, window=0, interpret=True)


# sha256 of the printed jaxpr of value_and_grad of ``flash_attention(q, k, v,
# causal=True)`` -- both pallas_calls with their kernel bodies, grids and
# index maps -- at the head layouts of the cells that had the kernels before
# grouped heads and the window came (PR 32), taken from the parent commit's
# ``flash.py`` under jax 0.9.0. A ``window`` of None and as many k/v heads as
# query heads must trace to the program they traced to then: the step
# programs of the accepted cells do not move.
_PINNED_JAX = "0.9.0"
_PARENT_JAXPRS = {
    "pythia-s8192": ((1, 8192, 16, 128), 128,
                     "81cd84cd6d968d87dfa726076ad081c4b0846873ee7a07ad2162ec4699aa3452"),
    "pythia-s2048x4": ((4, 2048, 16, 128), 128,
                       "c88260c691d57893ff051bdc9f88008b055f0881e646250563e97e3b5c6af6f2"),
    "joyai-s8192": ((1, 8192, 32, 192), 128,
                    "ceb9478d73301d3d373f0b081ed9fe1d81221462120c01ae4d14855c3021edbf"),
}


@pytest.mark.parametrize("cell", sorted(_PARENT_JAXPRS))
def test_plain_causal_heads_trace_to_the_parents_program(cell):
    import hashlib

    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"the hashes are of jaxprs printed by jax {_PINNED_JAX}")
    shape, dv, want = _PARENT_JAXPRS[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (dv,), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, q, v))
    assert hashlib.sha256(text.encode()).hexdigest() == want
