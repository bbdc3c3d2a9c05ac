"""Pallas flash-attention kernel tests.

This suite runs the kernels in interpret mode (pallas has no CPU lowering).
The compiled kernels — forward, dq and dk/dv, inside the optimizer's
shard_map where the operands carry vma — are checked against the reference
on the chip by ``chip_smoke.py``'s ``lm_flash`` phase.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.parallel import ring_attention
from bluefog_tpu.parallel.context import reference_attention
from bluefog_tpu.parallel.flash import flash_attention


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
