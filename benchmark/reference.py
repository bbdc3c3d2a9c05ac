"""The plain references and the comparisons that decide ``correct``.

Two things are checked, both outside the timed window and after the device's
peak memory was read:

* ``compare_steps``: the first steps through ``bf.Distributed*Optimizer.step``
  against plain ``jax.jit`` + optax steps on the same seeded parameters and
  batches -- one such step per device, and then the parameters mixed leaf by
  leaf with the numpy weight matrix the schedule built from the graph's edges
  (no ``CombinePlan``, no ``ppermute``). Both sides run the same model code in
  the same precision, so they agree far more closely than two precisions do.
* ``compare_forward``: the program's forward pass (its kernels, its compute
  dtype) against the family's plain float32 forward, on logits.
"""

from __future__ import annotations

from typing import List

import numpy as np

import jax
import jax.numpy as jnp
import optax

# Per-rank losses of the checked steps, relative. Both sides compute the same
# loss from the same numbers in the same precision; what differs is how XLA
# fuses two programs, and by the third step the parameters they start from (see
# PRINT_TOL). On the chip the two agree to 1e-5, at worst 6e-5 (the bf16 LM,
# step 3). A combine that was skipped at step k moves step k+1's loss (a rank
# then keeps all of its own update): by 2e-2 at full size on four chips, 3e-3
# at toy size; mixing with another peer than the schedule's by 1e-3 at toy size.
LOSS_RTOL = 5e-4

# sqrt(sum over leaves (P_sys - P_ref)^2) / sqrt(sum over leaves ||x_ref - x_0||^2),
# P a leaf's sum against ``_phase``: how far the parameters after the checked
# steps are from the reference's, in units of how far the reference moved. With
# SGD the two programs agree to 1e-3. With Adam they cannot agree closely: its
# update is lr * m / sqrt(v), so an element whose gradient is lost in bf16
# rounding noise gets a full-size step of random sign in each program; on the
# chip that is 0.01-0.04 over the whole LM and 0.08 in its worst leaf. Mixing
# that was skipped gives 1.0 (full size, four chips), another peer 0.4 (toy).
PRINT_TOL = 0.15
# A float32 sum of 1e8 terms differs by ~1e-8 of ||x|| between two reduction
# orders; leaves that do not move (a batch-norm scale whose gradient is under
# one ulp) would otherwise be compared against zero.
PRINT_FLOOR = 1e-6

# Share of all parameter elements that a bfloat16 holds exactly. Parameters
# updated in float32 have almost none (a random float32 is bf16-exact once in
# 65536; a few scales still sit at 1.0); parameters computed or stored in
# bfloat16 have all of them.
BF16_EXACT_SHARE = 0.5

# max |logits_sys - logits_ref| / max |logits_ref| of the forward check: bf16
# compute (8 bits of mantissa through some tens of matmuls) against float32 at
# the highest precision gave 0.005 (ResNet-50) and 0.009 (LM, 6 layers) on the
# chip. fp8 compute, a wrong mask or a wrong rotation give errors of order 1.
FORWARD_TOL = {"bfloat16": 4e-2, "float32": 1e-4}


def _phase(shape):
    """A fixed pattern in [-1, 1] over a leaf. A sum against it sees what a plain
    sum or a sum of squares cannot: sign flips, values that changed places."""
    angle = jnp.zeros(shape, jnp.float32)
    for axis in range(len(shape)):
        iota = jax.lax.broadcasted_iota(jnp.float32, shape, axis)
        angle = angle + (0.7548776662 * (axis + 1)) * iota
    return jnp.cos(angle)


def fingerprint(x):
    """[sum against ``_phase``, number of elements a bfloat16 holds exactly] of a leaf."""
    x = x.astype(jnp.float32)
    # on the bits: XLA:TPU may drop a float32 -> bfloat16 -> float32 round trip
    # as excess precision, and every element then compares equal to itself
    exact = (jax.lax.bitcast_convert_type(x, jnp.uint32) & 0xFFFF) == 0
    return jnp.stack([jnp.sum(x * _phase(x.shape)), jnp.sum(exact.astype(jnp.float32))])


@jax.jit
def fingerprint_stacked(tree):
    """``fingerprint`` of every rank's slice of a rank-stacked tree: [n, 2] a leaf."""
    return jax.tree_util.tree_map(jax.vmap(fingerprint), tree)


@jax.jit
def _fingerprint_and_norms(params, initial):
    """[projection, ||x - x0||, ||x||] a leaf, on the reference's side."""
    return jax.tree_util.tree_map(
        lambda x, x0: jnp.stack([fingerprint(x)[0], jnp.sqrt(jnp.sum((x - x0) ** 2)),
                                 jnp.sqrt(jnp.sum(x * x))]),
        params, initial)


def _plain_step(loss_fn, with_model_state: bool, tx):
    """value_and_grad + optax, nothing else."""

    def step(params, opt_state, model_state, batch):
        if with_model_state:
            (loss, (model_state, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, model_state, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, model_state, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def rank_slice(tree, rank: int):
    """Rank ``rank``'s slice of a rank-stacked tree, on the device that holds it."""
    def one(x):
        for shard in x.addressable_shards:
            if (shard.index[0].start or 0) == rank:
                return shard.data[0]
        raise ValueError(f"no shard of rank {rank}")
    return jax.tree_util.tree_map(one, tree)


def run_steps(loss_fn, with_model_state: bool, tx, init, batches,
              weights: List[np.ndarray], devices) -> dict:
    """``len(batches)`` plain steps on each device from the parameters ``init()``
    makes, each followed by the mix ``new[r] = sum_s W[s, r] * x[s]``. Returns
    the per-rank losses [steps, n], per rank a tree of
    [projection, ||x - x0||, ||x||] a leaf, and the elements of one rank."""
    n = len(devices)
    step = _plain_step(loss_fn, with_model_state, tx)
    params, model_state = init()
    x = [jax.device_put(params, d) for d in devices]
    state = [jax.device_put(model_state, d) for d in devices]
    del params, model_state  # rank 0's may be these very buffers, and are donated
    # keep_unused: an optimizer's init reads shapes only, and jit would drop the
    # argument with its device and build every rank's state on the first chip
    opt_state = [jax.jit(tx.init, keep_unused=True)(p) for p in x]
    losses = []
    for batch, W in zip(batches, weights):
        row = []
        for r in range(n):
            x[r], opt_state[r], state[r], loss = step(
                x[r], opt_state[r], state[r], rank_slice(batch, r))
            row.append(loss)
        losses.append([float(loss) for loss in row])
        x = [_mix(x, W[:, r], devices[r]) for r in range(n)]
    del opt_state, state
    initial = init()[0]  # made again: a copy kept through the steps would not fit
    return {
        "losses": np.array(losses),
        "prints": [jax.device_get(_fingerprint_and_norms(
            x[r], jax.device_put(initial, devices[r]))) for r in range(n)],
        "elements": sum(leaf.size for leaf in jax.tree_util.tree_leaves(initial)),
    }


@jax.jit
def _weighted_sum(weights, trees):
    return jax.tree_util.tree_map(
        lambda *leaves: sum(w * leaf for w, leaf in zip(weights, leaves)), *trees)


def _mix(x: list, column: np.ndarray, device):
    """sum_s column[s] * x[s], leaf by leaf, on ``device``."""
    sources = [s for s, w in enumerate(column) if w != 0.0]
    return _weighted_sum([np.float32(column[s]) for s in sources],
                         [jax.device_put(x[s], device) for s in sources])


def compare_steps(sys_losses: np.ndarray, sys_prints, ref: dict, param_dtype: str) -> dict:
    """The optimizer's checked steps against ``run_steps``'s."""
    loss_err = float(np.max(np.abs(sys_losses - ref["losses"]) / np.abs(ref["losses"])))
    sys_leaves = jax.tree_util.tree_leaves(sys_prints)
    print_err = 0.0
    for r, ref_tree in enumerate(ref["prints"]):
        off = moved = 0.0
        for got, want in zip(sys_leaves, jax.tree_util.tree_leaves(ref_tree)):
            off += float(got[r][0] - want[0]) ** 2
            moved += float(want[1]) ** 2 + (PRINT_FLOOR * float(want[2])) ** 2
        print_err = max(print_err, float(np.sqrt(off / moved)))
    out = {"loss_rel_err": loss_err, "print_err": print_err}
    ok = np.isfinite(loss_err) and loss_err <= LOSS_RTOL and print_err <= PRINT_TOL
    if param_dtype == "float32":
        exact = sum(float(np.sum(leaf[:, 1])) for leaf in sys_leaves)
        out["bf16_exact_share"] = exact / ref["elements"] / len(ref["prints"])
        ok = ok and out["bf16_exact_share"] <= BF16_EXACT_SHARE
    return {"ok": bool(ok), **out}


def compare_forward(family, cfg: dict, params, model_state, inputs) -> dict:
    """The program's forward against the family's plain float32 forward."""
    got = jax.jit(lambda p, s, x: family.system_logits(cfg, p, s, x))(
        params, model_state, inputs)
    want = jax.jit(lambda p, s, x: family.plain_logits(cfg, p, s, x))(
        params, model_state, inputs)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    tol = FORWARD_TOL[cfg["compute_dtype"]]
    return {"ok": bool(np.isfinite(err) and got.shape == want.shape and err <= tol),
            "logits_rel_err": err, "tol": tol}
