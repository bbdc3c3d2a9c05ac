"""Expert parallelism: SPMD Switch routing vs the dense oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu import parallel as bfp
from bluefog_tpu.parallel import expert as ep

from conftest import cpu_devices

E = 8


def make_moe(batch=8, seq=4, d=16, d_ff=32, seed=0):
    model = ep.SwitchFFN(num_experts=E, d_ff=d_ff)
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, seq, d))
    params = model.init(jax.random.PRNGKey(1), x)["params"]
    return model, params, x


def test_ep_matches_dense_oracle():
    model, params, x = make_moe()
    oracle = model.apply({"params": params}, x)
    mesh = ep.ep_mesh(E, cpu_devices(8))
    # capacity_factor=E guarantees no token drops -> exact equality
    out, aux = ep.ep_apply(params, x, mesh, capacity_factor=E)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=1e-4)
    assert aux.shape == (E,)
    assert np.isfinite(np.asarray(aux)).all()


def test_ep_capacity_drops_overflow_tokens():
    model, params, x = make_moe()
    # zero gate: uniform probs, argmax -> expert 0 for every token
    params = dict(params, gate=jnp.zeros_like(params["gate"]))
    mesh = ep.ep_mesh(E, cpu_devices(8))
    out, aux = ep.ep_apply(params, x, mesh, capacity_factor=1.0)
    # per device: T=4 local tokens all routed to expert 0, capacity
    # ceil(1.0 * 4 / 8) = 1 -> exactly 1 token per device survives
    flat = np.asarray(out).reshape(-1, out.shape[-1])
    nonzero_rows = (np.abs(flat) > 0).any(axis=1).sum()
    assert nonzero_rows == E  # one surviving token per device
    # uniform-to-one-expert routing: switch aux loss = E * 1 * (1/E) = 1
    np.testing.assert_allclose(np.asarray(aux), 1.0, atol=1e-5)


def test_ep_survivors_match_oracle_scaling():
    model, params, x = make_moe()
    params = dict(params, gate=jnp.zeros_like(params["gate"]))
    mesh = ep.ep_mesh(E, cpu_devices(8))
    # big capacity: every token survives even though all hit expert 0
    out, _ = ep.ep_apply(params, x, mesh, capacity_factor=float(E * E))
    oracle = model.apply({"params": dict(params)}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=1e-4)


def test_ep_validations():
    model, params, x = make_moe()
    mesh = ep.ep_mesh(E, cpu_devices(8))
    with pytest.raises(ValueError, match="experts"):
        ep.ep_apply({**params, "up": params["up"][:4]}, x, mesh)
    with pytest.raises(ValueError, match="divide"):
        ep.ep_apply(params, x[:6], mesh)
    with pytest.raises(ValueError, match="devices"):
        ep.ep_mesh(16, cpu_devices(8))


@pytest.mark.slow  # 40 jitted shard_map training steps, minutes on CPU mesh
def test_ep_training_converges():
    """Gradients flow through the sparse dispatch: a Switch classifier
    trained expert-parallel converges (short version of examples/moe.py)."""
    import optax

    mesh = ep.ep_mesh(E, cpu_devices(8))
    d, classes = 8, 8
    key = jax.random.PRNGKey(0)
    centers = jax.random.normal(key, (classes, d)) * 3.0
    x = (centers[:, None, :]
         + 0.3 * jax.random.normal(jax.random.PRNGKey(1), (classes, 16, d)))
    y = jnp.broadcast_to(jnp.arange(classes)[:, None], (classes, 16))
    moe = ep.SwitchFFN(num_experts=E, d_ff=32)
    params = {
        "moe": moe.init(jax.random.PRNGKey(2), x)["params"],
        "head": 0.1 * jax.random.normal(jax.random.PRNGKey(3), (d, classes)),
    }

    def loss_fn(p, batch):
        bx, by = batch
        h, aux = ep.ep_apply(p["moe"], bx, mesh, capacity_factor=4.0)
        logits = (bx + h) @ p["head"]
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()
        return ce + 0.01 * aux.mean()

    opt = optax.adam(3e-2)
    state = opt.init(params)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for step in range(40):
        loss, grads = grad_fn(params, (x, y))
        if step == 0:
            # the stated claim, pinned directly: gradients reach every MoE
            # param THROUGH the sparse dispatch (a dead ep_apply would leave
            # the residual head to learn alone and still drop the loss)
            for name in ("gate", "up", "down"):
                g = np.asarray(grads["moe"][name])
                assert np.abs(g).max() > 0, f"no gradient reached moe/{name}"
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses[::10]


def test_moe_lm_ep_apply_matches_dense_oracle():
    """The expert-parallel MoE TransformerLM (shard_map over the expert
    axis, all_to_all dispatch inside every MoE block) computes exactly the
    dense oracle's forward when capacity guarantees no token drops."""
    import dataclasses

    from bluefog_tpu.models import MoETransformerLM

    E = 8
    mesh = bfp.ep_mesh(E, cpu_devices(E))
    model = MoETransformerLM(
        vocab_size=64, num_experts=E, num_layers=2, num_heads=2,
        d_model=32, d_ff=64, moe_every=2, expert_axis="expert",
        capacity_factor=float(E))  # no drops -> exact parity
    toks = jax.random.randint(jax.random.PRNGKey(3), (E, 12), 0, 64)
    params = bfp.ep_lm_init(model, jax.random.PRNGKey(0), toks)
    dense = dataclasses.replace(model, expert_axis=None)
    want = dense.apply({"params": params}, toks)
    got, aux = bfp.ep_lm_apply(model, params, toks, mesh)
    # atol: the shard_map all_to_all path and the dense oracle reassociate
    # the same sums differently, and backend-dependent codegen shifts the
    # rounding further — observed up to 3.4e-5 on unit-scale logits.
    # Parity here means "same math", not "same rounding": 1e-4 on O(1)
    # logits is far below any routing or combine error.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    assert np.isfinite(float(aux)) and float(aux) > 0.0


def test_moe_lm_ep_training_converges():
    """jax.grad through the shard_mapped MoE loss: expert-sharded up/down
    grads + replicated dense grads drive a real training loop downhill."""
    import optax

    from bluefog_tpu.models import MoETransformerLM

    E = 4
    mesh = bfp.ep_mesh(E, cpu_devices(4))
    model = MoETransformerLM(
        vocab_size=32, num_experts=E, num_layers=2, num_heads=2,
        d_model=32, d_ff=64, moe_every=2, expert_axis="expert",
        capacity_factor=float(E))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 32, (4, 16)))
    batch = (toks, jnp.roll(toks, -1, axis=1))
    params = bfp.ep_lm_init(model, jax.random.PRNGKey(0), toks)
    loss_fn = bfp.ep_lm_loss_fn(model, mesh)
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < 0.6 * losses[0], losses[::10]
    # the expert grads really were per-expert: up/down shards differ
    up = np.asarray(
        params["block_1"]["moe"]["up"])
    assert up.shape[0] == E
    assert not np.allclose(up[0], up[1])
