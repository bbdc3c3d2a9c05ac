"""Pallas flash-attention kernel tests.

This suite runs the kernels in interpret mode (pallas has no CPU lowering).
The compiled kernels — forward, dq and dk/dv, inside the optimizer's
shard_map where the operands carry vma — are checked against the reference
on the chip by ``chip_smoke.py``'s ``lm_flash`` phase.
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
