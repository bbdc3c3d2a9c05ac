"""Expert-parallel Mixture-of-Experts training.

Trains a Switch-FFN classifier expert-parallel: one expert per device on an
("expert",) mesh, tokens dispatched with all_to_all, gradients flowing
through the sparse dispatch (bluefog_tpu.parallel.ep_apply is fully
differentiable — the routing one-hots are piecewise-constant, the gate
learns through the top-1 probability scaling, standard Switch semantics).

No reference analog (the reference is data-parallel only); this is the
expert-parallelism end-to-end demo, same spirit as examples/long_context_lm.py
for sequence parallelism.

Run (CPU-simulated 8-device mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/moe.py
"""

from __future__ import annotations

import argparse
import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu import parallel as bfp


def make_data(key, n_clusters=8, per=64, d=16):
    """Clustered inputs: an ideal router sends each cluster to one expert."""
    centers = jax.random.normal(key, (n_clusters, d)) * 3.0
    xs, ys = [], []
    for c in range(n_clusters):
        k = jax.random.fold_in(key, c + 1)
        xs.append(centers[c] + jax.random.normal(k, (per, d)) * 0.3)
        ys.append(jnp.full((per,), c, jnp.int32))
    return jnp.concatenate(xs), jnp.concatenate(ys)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--aux-weight", type=float, default=0.01)
    args = p.parse_args()

    E, d, d_ff, classes, per = args.experts, 16, 64, 8, 64
    devices = jax.devices()
    tokens = classes * per
    if tokens % E or len(devices) < E:
        usable = [e for e in (2, 4, 8, 16, 32)
                  if tokens % e == 0 and e <= len(devices)]
        hint = f"try --experts {usable}" if usable else (
            "run under JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 for a "
            "simulated 8-device mesh")
        raise SystemExit(
            f"--experts {E} needs to divide the {tokens}-token dataset and "
            f"fit the {len(devices)} available devices ({hint})")
    mesh = bfp.ep_mesh(E, devices)
    print(f"experts: {E} on {mesh.devices.flat[0].platform}")

    key = jax.random.PRNGKey(0)
    x, y = make_data(key, n_clusters=classes, per=per, d=d)
    # [B, S, d] layout with B divisible by the expert axis
    x = x.reshape(E, -1, d)
    y = y.reshape(E, -1)

    moe = bfp.SwitchFFN(num_experts=E, d_ff=d_ff)
    params = {
        "moe": moe.init(jax.random.PRNGKey(1), x)["params"],
        "head": jax.random.normal(jax.random.PRNGKey(2), (d, classes)) * 0.1,
    }

    def loss_fn(params, batch):
        bx, by = batch
        h, aux = bfp.ep_apply(params["moe"], bx, mesh, capacity_factor=4.0)
        logits = (bx + h) @ params["head"]  # residual MoE + linear head
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean()
        return ce + args.aux_weight * aux.mean()

    opt = optax.adam(3e-2)
    opt_state = opt.init(params)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    losses = []
    for step in range(args.steps):
        loss, grads = grad_fn(params, (x, y))
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
        if step % 10 == 0:
            print(f"step {step:3d}  loss {losses[-1]:.4f}")

    print(f"final loss: {losses[-1]:.4f} (from {losses[0]:.4f})")
    assert losses[-1] < 0.5 * losses[0], "MoE training failed to converge"
    print("MOE OK")

    # ---- part 2: the full MoE transformer LM (models.MoETransformerLM) --
    # Switch-FFN blocks INSIDE the LM, expert-sharded up/down weights,
    # next-token loss differentiated straight through the shard_map.
    from bluefog_tpu.models import MoETransformerLM

    lm = MoETransformerLM(
        vocab_size=64, num_experts=E, num_layers=2, num_heads=2,
        d_model=32, d_ff=d_ff, expert_axis="expert")
    rng = jax.random.PRNGKey(7)
    toks = jax.random.randint(rng, (E, 16), 0, 64)
    batch = (toks, jnp.roll(toks, -1, axis=1))
    lm_params = bfp.ep_lm_init(lm, jax.random.PRNGKey(8), toks)
    lm_loss = bfp.ep_lm_loss_fn(lm, mesh, aux_weight=args.aux_weight)
    lm_opt = optax.adam(3e-3)
    lm_state = lm_opt.init(lm_params)
    lm_grad = jax.jit(jax.value_and_grad(lm_loss))
    lm_losses = []
    for step in range(args.steps):
        loss, grads = lm_grad(lm_params, batch)
        updates, lm_state = lm_opt.update(grads, lm_state, lm_params)
        lm_params = optax.apply_updates(lm_params, updates)
        lm_losses.append(float(loss))
        if step % 20 == 0:
            print(f"lm step {step:3d}  loss {lm_losses[-1]:.4f}")
    print(f"lm final loss: {lm_losses[-1]:.4f} (from {lm_losses[0]:.4f})")
    assert lm_losses[-1] < 0.7 * lm_losses[0], "MoE LM failed to converge"
    print("MOE_LM OK")


if __name__ == "__main__":
    main()
