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
            gauges["flash.chunks_needed"], gauges["flash.chunks_masked"]) == (
                0.0, 136.0, 136.0, 16.0)
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
    (8192, (64, 40, 24, 0, 136, 136, 16)), (2048, (4, 4, 0, 0, 10, 10, 4))])
def test_causal_schedule_of_the_benchmarks_sequences(monkeypatch, s, want,
                                                     env):
    """The cells' geometry is the module's, whatever the environment holds."""
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert (flash._q_tile(s), flash._k_tile(s)) == (512, 2048)
    got = flash.causal_schedule(s, s, 0, 0)
    assert tuple(got[key] for key in (
        "steps", "live", "dead", "dead_fetching", "chunks_computed",
        "chunks_needed", "chunks_masked")) == want


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


def _chunks_cut(sq, sk, q_off, k_off, window, tq, cw):
    """[q tiles, column chunks]: whether the chunk holds an allowed pair of
    the q tile and a pair that is not -- the chunks a mask has work in."""
    t = q_off + np.arange(sq)
    first = np.maximum(t - window + 1 if window else np.full(sq, k_off), k_off)
    last = np.minimum(t, k_off + sk - 1)
    lo = k_off + np.arange(sk // cw) * cw
    whole = ((first.reshape(-1, tq).max(axis=1)[:, None] <= lo)
             & (last.reshape(-1, tq).min(axis=1)[:, None] >= lo + cw - 1))
    return _chunks_by_rows(sq, sk, q_off, k_off, window, tq, cw) & ~whole


@pytest.mark.parametrize("sq,sk,q_off,k_off,window", [
    (16384, 16384, 0, 0, 4096),      # smallthinker-s16384-*: 252 chunks of 528
    (16384, 16384, 0, 0, None),
    (8192, 8192, 0, 0, 2048),        # trinity-mini-s8192-*: 70 chunks of 136
    (8192, 8192, 0, 0, 4096), (8192, 8192, 8192, 0, 4096),
    (8192, 8192, 1000, 300, 4096), (8192, 8192, 300, 1000, 700),
    (4096, 8192, 5000, 0, 300), (2048, 2048, 0, 0, 512), (2048, 2048, 0, 0, 1),
])
def test_causal_schedule_under_a_window_computes_what_is_needed(
        sq, sk, q_off, k_off, window):
    """At the default 512 x 2048 tiles: the chunks the live steps work on are
    the chunks that hold an allowed pair, counted by brute force, and no copy
    is made for dead steps alone -- both edges. Where the offsets and the
    window lie on chunk boundaries the kernels mask just the chunks that
    hold a pair that is not allowed; elsewhere every chunk of an edge step."""
    got = flash.causal_schedule(sq, sk, q_off, k_off, window)
    needed = int(_chunks_by_rows(sq, sk, q_off, k_off, window, 512, 512).sum())
    cut = int(_chunks_cut(sq, sk, q_off, k_off, window, 512, 512).sum())
    assert got["chunks_computed"] == got["chunks_needed"] == needed > 0
    assert got["dead_fetching"] == 0
    assert got["live"] + got["dead"] == got["steps"]
    if (q_off - k_off) % 512 == 0 and (window or 0) % 512 == 0:
        assert got["chunks_masked"] == cut
    else:
        assert needed >= got["chunks_masked"] >= cut
    if (sq, q_off, window) == (16384, 0, 4096):
        # of 144 live, 528 and 140 masked in every chunk of an edge step
        assert (got["live"], needed, got["chunks_masked"]) == (84, 252, 56)
    if (sq, q_off, window) == (8192, 0, 2048):
        assert (needed, got["chunks_masked"]) == (70, 28)   # of 70


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
# grouped heads and the window came, under jax 0.9.0. Taken again
# when an edge step came to mask only the chunk the diagonal crosses: the
# kernel bodies changed by design, and the bit-for-bit checks above
# (``test_chunk_masks_equal_the_whole_tiles_bit_for_bit``) are what show that
# they compute the numbers of the bodies before. A ``window`` of None and as
# many k/v heads as query heads must trace to this program: the step programs
# of the accepted cells move only with the kernels.
_PINNED_JAX = "0.9.0"
_PARENT_JAXPRS = {
    "pythia-s8192": ((1, 8192, 16, 128), 128,
                     "db09cd84baa974a61c1cfbb34c1e7f65417c1a7851b21152c9734022bca55ba1"),
    "pythia-s2048x4": ((4, 2048, 16, 128), 128,
                       "f95d5d4850bf7e8e72490444572a4094a9f2dc324c0a80dd9c1991cd7fe36903"),
    "joyai-s8192": ((1, 8192, 32, 192), 128,
                    "6e034a6a6c8cc25ecb90326f7b669955ed370ff77709d1ea300fdabf1af88ec2"),
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


# The ring's calls of the two kernels (offsets traced, a ring step's) at two
# cells' layouts: sha256 of the kernels' printed jaxprs and grid mappings, taken
# from the parent commit's ``flash.py`` (before an edge step masked by chunk)
# under jax 0.9.0. At traced offsets an edge step masks every live column: the
# ring runs the kernels it ran.
_RING_KERNELS = {
    "grouped-window": ((1, 8192, 32, 128), 4, 2048,
                       "c44ef2df82611e6ba2e83983d2f69c520f79f394bef60971611542febd283be3"),
    "causal": ((1, 8192, 16, 128), 16, None,
               "bb962cf2c6c0f09c06d0f0948cb96ba310e8a9dc78016193987ccc1fa8980660"),
}


def _kernels(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, nested jaxprs included, in
    order: each one's kernel jaxpr and grid mapping, printed."""
    from jax import core as jax_core

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.params["jaxpr"]) + str(eqn.params["grid_mapping"]))
        for sub in jax_core.jaxprs_in_params(eqn.params):
            found += _kernels(sub)
    return found


def _block_jaxprs(shape, hkv, window, offs):
    """Jaxprs of ``flash_block`` and ``flash_block_bwd`` at bf16 q ``shape``
    and k, v at ``hkv`` heads of 128: the offsets traced (``offs`` None) or
    ``offs`` as Python ints."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(shape[:2] + (hkv, 128), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    g = jax.ShapeDtypeStruct(shape, jnp.float32)
    kw = dict(causal=True, window=window)
    if offs is None:
        fwd = jax.make_jaxpr(lambda q, k, v, a, b: flash.flash_block(
            q, k, v, a, b, **kw))(q, kv, kv, 0, 0)
        bwd = jax.make_jaxpr(lambda *x: flash.flash_block_bwd(*x, **kw))(
            q, kv, kv, g, stat, stat, stat, 0, 0)
    else:
        fwd = jax.make_jaxpr(lambda q, k, v: flash.flash_block(
            q, k, v, *offs, **kw))(q, kv, kv)
        bwd = jax.make_jaxpr(lambda *x: flash.flash_block_bwd(*x, *offs, **kw))(
            q, kv, kv, g, stat, stat, stat)
    return fwd.jaxpr, bwd.jaxpr


@pytest.mark.parametrize("layout", sorted(_RING_KERNELS))
def test_the_ring_traces_to_the_parents_kernels(layout):
    import hashlib

    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"the hashes are of jaxprs printed by jax {_PINNED_JAX}")
    shape, hkv, window, want = _RING_KERNELS[layout]
    text = "".join(sum(map(_kernels, _block_jaxprs(shape, hkv, window, None)), []))
    assert hashlib.sha256(text.encode()).hexdigest() == want


@pytest.mark.parametrize("s,hq,hkv,window,bodies", [
    (16384, 28, 4, 4096, (10, 11)),   # smallthinker-s16384-*: a window layer
    (16384, 28, 4, None, (6, 7)),     # its global layer
    (8192, 32, 4, 2048, (10, 11)),    # trinity-mini-s8192-*: a window layer
    (8192, 16, 16, None, (6, 7)),     # pythia-s8192-*, trinity-mini's global layer
])
def test_one_more_body_a_kernel_under_a_window(s, hq, hkv, window, bodies):
    """``pl.when`` bodies of the forward and the backward kernel at the
    cells' layouts, offsets 0 and 0: the zeroing of the running state (one in
    the forward, two in the backward), the interior and one an edge step's
    m (``_edge_chunks``): as many as the variants of [lo, hi) with no window,
    one more with one -- the (0, all chunks) variant, diagonal or trailing
    edge. At traced offsets the parent's count, one fewer under a window."""
    got = tuple(str(_kernels(j)[0]).count("cond[")
                for j in _block_jaxprs((1, s, hq, 128), hkv, window, (0, 0)))
    assert got == bodies
    ring = tuple(str(_kernels(j)[0]).count("cond[")
                 for j in _block_jaxprs((1, s, hq, 128), hkv, window, None))
    assert ring == tuple(n - (window is not None) for n in bodies)


# -- an edge step masks the chunk an edge crosses ------------------------------
# Where the offsets reach the kernels as Python ints that put both edges on
# chunk boundaries (flash_attention's 0 and 0), an edge step masks just the
# chunk the diagonal crosses and the one the window's trailing edge crosses
# (``flash._edge_chunks``), the other columns of [lo, hi) as they are; at
# traced offsets (a ring step's) it masks every live column, as before. The two
# compute the same numbers bit for bit. XLA:CPU is kept from rewriting them: its
# algebraic simplifier turns a row sum of concatenated chunks into a sum of
# per-chunk sums (a Mosaic body is not rewritten so), and at 8-column chunks its
# exp rounds otherwise in a fusion of pieces than of the tile, so the check is
# compiled without that pass and at 128 x 512 tiles: four lane-wide chunks a K
# tile, the default 512 x 2048's geometry.

EDGE_TQ, EDGE_TK = 128, 512


@pytest.fixture
def tiles_128x512(monkeypatch):
    monkeypatch.setattr(flash, "_Q_TILES", (EDGE_TQ,))
    monkeypatch.setattr(flash, "_K_TILES", (EDGE_TK,))
    flash.flash_block.clear_cache()
    flash.flash_block_bwd.clear_cache()
    yield
    flash.flash_block.clear_cache()
    flash.flash_block_bwd.clear_cache()


def _op_for_op(f, *args):
    """``f(*args)`` compiled without XLA's algebraic simplifier."""
    return jax.jit(f).lower(*args).compile(
        {"xla_disable_hlo_passes": "algsimp"})(*args)


def _static_and_traced(q, k, v, w, q_off, k_off, window):
    """(o, m, l, dq, dk, dv) of ``flash_block`` and ``flash_block_bwd`` with
    the offsets as Python ints and as traced values; both backwards from the
    first forward's statistics."""
    kw = dict(causal=True, window=window, interpret=True)
    fwd = [_op_for_op(lambda q, k, v: flash.flash_block(q, k, v, q_off, k_off, **kw),
                      q, k, v),
           _op_for_op(lambda q, k, v, a, b: flash.flash_block(q, k, v, a, b, **kw),
                      q, k, v, q_off, k_off)]
    o, m, l = fwd[0]
    stats = (w, jnp.sum(w * o / l[..., None], axis=-1), m, l)
    bwd = [_op_for_op(lambda *x: flash.flash_block_bwd(*x, q_off, k_off, **kw),
                      q, k, v, *stats),
           _op_for_op(lambda *x: flash.flash_block_bwd(*x, **kw),
                      q, k, v, *stats, q_off, k_off)]
    return [tuple(f) + tuple(b) for f, b in zip(fwd, bwd)]


@pytest.mark.parametrize("window,hq,hkv", [
    (None, 2, 2), (EDGE_TQ, 4, 2), (2 * EDGE_TQ, 4, 2), (4 * EDGE_TQ, 4, 2)],
    ids=["causal", "window-1-chunk", "window-2-chunks", "window-4-chunks"])
def test_chunk_masks_equal_the_whole_tiles_bit_for_bit(tiles_128x512, window,
                                                       hq, hkv):
    """A 1024-token block at offsets 0 and 0 (8 x 2 steps): grouped heads,
    and windows from one chunk (a step holds both edges) to four (the
    (0, all chunks) variant is the diagonal's in one q tile, the trailing
    edge's in another)."""
    q, k, v, w = _grouped(hq, hkv, seq=1024)
    static, traced = _static_and_traced(q, k, v, w, 0, 0, window)
    for a, b, name in zip(static, traced, ("o", "m", "l", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    masked = flash.causal_schedule(1024, 1024, window=window)["chunks_masked"]
    assert masked == _chunks_cut(1024, 1024, 0, 0, window, EDGE_TQ, EDGE_TQ).sum()


@pytest.mark.parametrize("q_off,window,masks", [
    (3 * EDGE_TQ, None, ((3, True),)), (EDGE_TK, EDGE_TK, ((0, False),))],
    ids=["zero-all-diagonal", "zero-all-trailing"])
def test_the_zero_all_variant_masks_the_edge_it_holds(tiles_128x512, q_off,
                                                      window, masks):
    """One q tile against one K tile, the q tile's first row 3 chunks past
    the K tile's first column (it holds the diagonal in its last chunk) or 4
    under a 4-chunk window (the trailing edge in its first): both are the
    step of all four chunks from the first, told apart by m at run time."""
    m = q_off // EDGE_TQ
    assert flash._edge_chunks(4, EDGE_TQ, window)[m] == (0, 4, masks)
    q, k, v, w = _grouped(2, 1, seq=EDGE_TK)
    q, w = q[:, :EDGE_TQ], w[:, :EDGE_TQ]
    static, traced = _static_and_traced(q, k, v, w, q_off, 0, window)
    for a, b, name in zip(static, traced, ("o", "m", "l", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert float(jnp.min(static[2])) > 0    # every row sees a column


@pytest.mark.parametrize("window", [None, 2048, 4096])
def test_edge_chunks_at_the_default_tiles(window):
    """The edge steps of a 2048-column K tile of four 512-wide chunks: the
    diagonal's chunk is the m-th, the trailing edge's is m - w's; both kinds
    give the (0, all chunks) variant under the cells' windows (w = 4, 8)."""
    bodies = flash._edge_chunks(4, 512, window)
    w = window and window // 512
    want = {m: (0, m + 1, ((m, True),)) for m in range(4)}
    if w:
        want.update({m: (m - w, 4, ((0, False),)) for m in range(w, w + 4)})
    assert bodies == want
    zero_all = [b[2] for b in bodies.values() if b[:2] == (0, 4)]
    assert zero_all == ([((3, True),), ((0, False),)] if w else [((3, True),)])


def _named(jaxpr):
    """``(name, shape)`` of every ``checkpoint_name`` equation of a jaxpr,
    nested jaxprs included, in order."""
    from jax import core as jax_core

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.append((eqn.params["name"], eqn.invars[0].aval.shape))
        for sub in jax_core.jaxprs_in_params(eqn.params):
            found += _named(sub)
    return found


@pytest.mark.parametrize("named", [True, False], ids=["named", "default"])
def test_the_forward_rule_names_its_residuals_only_when_asked(named):
    """``name_residuals=True``: the gradient's jaxpr holds the six names of
    ``RESIDUAL_NAMES``, on q, k and v (k and v at their own heads), the output
    ``[B, S, Hq, Dv]`` and the two row statistics ``[B, S, Hq]``; off (the
    default), none -- the program the pinned hashes above hold."""
    q, k, v, _ = _grouped(4, 2, seq=16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               name_residuals=named).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    shapes = [q.shape, k.shape, v.shape, q.shape[:3] + v.shape[3:], q.shape[:3], q.shape[:3]]
    want = list(zip(flash.RESIDUAL_NAMES, shapes))
    assert _named(jaxpr.jaxpr) == (want if named else [])
