"""chip_smoke.py — the quickest proof that bluefog_tpu still starts on the chip.

``python chip_smoke.py`` (no arguments, one process, every chip of the host)
drives the gossip trainer once through the entry points a user calls —
``bf.init()``, the model classes, ``bf.Distributed*Optimizer.init/.step``,
``bf.neighbor_allreduce`` — at the full width of the two models the repo
benchmarks, with random weights from a seed:

* ``resnet50``: the step of the benchmark's ``resnet50-b128-1chip`` cell
  (ResNet-50, bf16, 224x224, 128 images per chip, SGD-momentum, neighbor
  averaging), and the check that rank ``r``'s slice of every state leaf lives
  on device ``r``.
* ``gossip`` (more than one chip): ``bf.neighbor_allreduce`` against the
  topology's weight matrix, one ResNet-50 step per shift set of the dynamic
  one-peer Expo-2 schedule and where the compiler put each program's permutes
  (``scaling.permute_start_slack``), the hierarchical optimizer on the host's
  machine mesh, and two ``DistributedWinPutOptimizer`` steps on a small MLP.
* ``lm_flash``: the two compiled flash-attention kernels against the dense
  f32-softmax reference at 2048 tokens (one K tile) and at 8192 (dead steps,
  interior tiles and diagonal tiles of every chunk count), and at 28 query
  heads over 4 k/v heads under a 1024-token window at 4096 tokens (dead steps
  past either edge, tiles on the window's trailing edge), then the 4-layer
  d_model-2048 LM at 8192 tokens per chip through
  ``bf.DistributedNeighborAllreduceOptimizer.step``.
* ``mla_moe``: the kernels again at latent attention's widths (q.k 192, v
  128); the three compiled kernels of ``grouped_matmul`` (the product and both
  gradients) against XLA matmuls on ragged loads with an empty expert, and the
  row movers on both sides of them (``rows_in``, ``rows_out``, forward and
  gradients) against the plain gather and scatter-add on the same loads; then
  ``bf.models.ConfigLM`` at JoyAI-LLM-Flash's widths (one dense and one expert
  layer and the MTP module, experts [0, 8) of 256 held, top-8) at 8192 tokens
  per chip through the same optimizer with the routing biases as its model
  state: the expert layers' counters of the last step are printed
  (``tiles_in_use`` among them), and an overflowed row raises.
* ``looped``: ``ConfigLM`` as a looped model at Ouro's head layout (16 heads
  of 128, two layers run four times, sandwich norms, the exit gate) at 1,024
  tokens with every layer application recomputed -- ``jax.checkpoint`` around
  the flash kernels' ``custom_vjp`` -- its loss and gradients against the same
  model without recomputation, and the Mosaic calls each program holds (as
  many: the recomputed one keeps the forward kernel's named residuals).

It refuses to start unless every rank is a TPU device, and a failing phase
raises (nothing is caught). A run that passed ends with two JSON lines on
stdout: the report (versions, peak HBM, and per phase the compile seconds,
steady seconds per step, first and last loss), then as the last line exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it. It makes no performance claim: the seconds it
prints are observations with a device stamp. Compiled programs go to the
persistent cache ``bf.init()`` configures (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), so a second run in the same checkout reports far
smaller compile times.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.realpath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

import bluefog_tpu as bf  # noqa: E402
from bluefog_tpu.models import (ConfigLM, LMConfig, MLP, ResNet50,  # noqa: E402
                                TransformerLM, looped_exit_loss, next_token_loss)
from bluefog_tpu.optimizers import PERMUTES_IN_FLIGHT_MAX  # noqa: E402
from bluefog_tpu.parallel import expert  # noqa: E402
from bluefog_tpu.parallel.context import reference_attention  # noqa: E402
from bluefog_tpu.parallel.flash import flash_attention  # noqa: E402
from bluefog_tpu.runtime import native  # noqa: E402
from bluefog_tpu.runtime.config import compile_cache_dir  # noqa: E402
from bluefog_tpu.scaling import permute_start_slack  # noqa: E402

if os.path.dirname(os.path.dirname(os.path.realpath(bf.__file__))) != HERE:
    raise SystemExit(
        f"chip_smoke: bluefog_tpu was imported from {bf.__file__}, not from "
        f"the checkout beside this script ({HERE})")

RESNET_BATCH, IMAGE, RESNET_STEPS = 128, 224, 5
LM = dict(vocab_size=32768, num_layers=4, num_heads=16, d_model=2048,
          d_ff=8192)
LM_SEQ, LM_STEPS = 8192, 4
# B, S, H, D of the kernel-vs-reference legs: one K tile, every step on the
# diagonal; then the 16 x 4 grid of a benchmark sequence, one head of it (24
# dead steps, 24 interior tiles, 16 diagonal tiles of one to four live chunks)
KERNEL_SHAPES = {"s2048": (1, 2048, 16, 128), "s8192": (1, 8192, 1, 128)}
# SmallThinker's heads under a window a quarter of the sequence, as its 4096
# is of 16384 (the dense reference holds [28, S, S] scores, so S is 4096): 28
# query heads read 4 k/v heads, row t sees the 1024 columns up to t; a K tile
# is dead behind the window as it is in the future
GROUPED_WINDOW = dict(bsh=(1, 4096, 28), d_v=128, d_qk=128, kv_heads=4, window=1024)
# JoyAI-LLM-Flash's config.json, two layers and an eighth of the vocabulary
MLA_MOE = LMConfig(
    vocab_size=16160, hidden_size=2048, num_hidden_layers=2, num_attention_heads=32,
    intermediate_size=7168, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=3.2e7, first_k_dense_replace=1,
    n_routed_experts=256, num_experts_per_tok=8, moe_intermediate_size=768,
    routed_scaling_factor=2.5, experts_held=(0, 8), bias_update_speed=0.001,
    num_nextn_predict_layers=1)
# Ouro's block, two layers run four times, a sixth of the vocabulary
LOOPED = LMConfig(
    vocab_size=8192, hidden_size=2048, num_hidden_layers=2, num_attention_heads=16,
    intermediate_size=5632, attention="grouped", num_key_value_heads=16, head_dim=128,
    rope_theta=1e6, rope_interleave=False, total_ut_steps=4, sandwich_norms=True,
    exit_gate=True)
LOOPED_SEQ = 1024
# rows of each held expert in the grouped products' check: ragged, one empty,
# one of a single row, one of exactly a tile
GROUPED_LOADS = (700, 0, 130, 1, 300, 128, 5, 900)
GROUPED_SLOTS = sum(GROUPED_LOADS) + 1000    # and 1,000 slots held elsewhere
KERNEL_TOL = 3e-2
FLASH = partial(flash_attention, causal=True)


def _timed_steps(opt, state, batch, steps):
    """One step that compiles, then ``steps`` more closed by one
    ``block_until_ready``. Returns (state, compile s, steady s/step, the
    per-step [n] losses as numpy)."""
    t0 = time.perf_counter()
    state, m = opt.step(state, batch)
    jax.block_until_ready(m["loss"])
    compile_s = time.perf_counter() - t0
    losses = [m["loss"]]
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = opt.step(state, batch)
        losses.append(m["loss"])
    jax.block_until_ready(m["loss"])
    steady = (time.perf_counter() - t0) / steps
    losses = np.stack([np.asarray(l, np.float32) for l in losses])
    if not np.isfinite(losses).all():
        raise RuntimeError(f"non-finite loss: {losses.tolist()}")
    return state, compile_s, steady, losses


def _report(compile_s, steady, losses):
    return {"compile_s": round(compile_s, 2),
            "steady_s_per_step": round(steady, 4),
            "first_loss": round(float(losses[0].mean()), 4),
            "last_loss": round(float(losses[-1].mean()), 4)}


def _check_layout(tree, what):
    """Every leaf: one addressable shard per device, of leading size 1, and
    row r on the device of rank r."""
    devices = list(bf.mesh().devices.flat)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = what + jax.tree_util.keystr(path)
        shards = leaf.addressable_shards
        if len(shards) != len(devices) or leaf.shape[0] != len(devices):
            raise RuntimeError(
                f"{name}: {len(shards)} shard(s) of shape {leaf.shape} over "
                f"{len(devices)} device(s)")
        for sh in shards:
            r = sh.index[0].start or 0
            if sh.data.shape[0] != 1 or sh.device != devices[r]:
                raise RuntimeError(
                    f"{name}: row {r} has shard shape {sh.data.shape} on "
                    f"{sh.device}, expected leading 1 on {devices[r]}")


def _rank_batch(make):
    """Build a rank-stacked batch with each rank's slice made on its chip."""
    return jax.jit(make, out_shardings=bf.rank_sharding(bf.mesh()))(
        jax.random.PRNGKey(1))


def phase_resnet50():
    n = bf.size()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((RESNET_BATCH, IMAGE, IMAGE, 3), jnp.float32),
        train=True))(jax.random.PRNGKey(0))
    params = variables["params"]

    def loss_fn(p, ms, batch):
        images, labels = batch
        logits, updates = model.apply(
            {"params": p, "batch_stats": ms}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, (updates["batch_stats"], {})

    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1, momentum=0.9), loss_fn, with_model_state=True)
    state = opt.init(params, model_state=variables["batch_stats"])
    batch = _rank_batch(lambda k: (
        jax.random.normal(k, (n, RESNET_BATCH, IMAGE, IMAGE, 3), jnp.float32),
        jnp.zeros((n, RESNET_BATCH), jnp.int32)))

    state, compile_s, steady, losses = _timed_steps(
        opt, state, batch, RESNET_STEPS)
    moved = np.asarray(jax.jit(lambda new, old: sum(
        jnp.sum(jnp.abs(a - b[None]), axis=tuple(range(1, a.ndim)))
        for a, b in zip(jax.tree_util.tree_leaves(new),
                        jax.tree_util.tree_leaves(old))))(state.params, params))
    if not (moved > 0).all():
        raise RuntimeError(f"parameters did not move on every rank: {moved}")
    for what in ("params", "opt_state", "model_state"):
        _check_layout(getattr(state, what), what)
    out = _report(compile_s, steady, losses)
    out["layout"] = "row r of every state leaf on device r"
    return out, (opt, state, batch)


def phase_gossip(opt, state, batch):
    """Runs on the ResNet-50 optimizer and state of the previous phase."""
    n = bf.size()
    # 1. neighbor_allreduce of rank-distinct rows against the weight matrix:
    # M[r, s] is the weight rank r gives to what it receives from s
    M = np.zeros((n, n))
    for r in range(n):
        srcs = bf.in_neighbor_ranks(r)
        M[r, [r] + srcs] = 1.0 / (len(srcs) + 1)
    x = ((np.arange(n)[:, None] + 1) / 8 + np.arange(8)[None] / 64).astype(
        np.float32)
    got = np.asarray(bf.neighbor_allreduce(
        bf.shard_rank_stacked(bf.mesh(), x)))
    np.testing.assert_allclose(got, M @ x, rtol=1e-6, atol=1e-6)

    # 2. the paper's configuration: one ResNet-50 step per shift set of the
    # dynamic one-peer Expo-2 schedule (ceil(log2 n) compiled programs)
    rounds = max(1, math.ceil(math.log2(n)))
    gens = [bf.topology_util.GetDynamicSendRecvRanks(bf.load_topology(), r)
            for r in range(n)]
    t0 = time.perf_counter()
    seen = set()
    for _ in range(rounds):
        sends = {r: next(g)[0] for r, g in enumerate(gens)}
        recv = {r: [s for s, dsts in sends.items() if r in dsts]
                for r in range(n)}
        seen.add(tuple((d - s) % n for s, dsts in sorted(sends.items())
                       for d in dsts))
        opt.send_neighbors = sends
        opt.self_weight = {r: 1.0 / (len(recv[r]) + 1) for r in range(n)}
        opt.neighbor_weights = {
            r: {s: 1.0 / (len(recv[r]) + 1) for s in recv[r]}
            for r in range(n)}
        state, m = opt.step(state, batch)
        if not np.isfinite(np.asarray(m["loss"])).all():
            raise RuntimeError(f"non-finite dynamic-step loss: {m['loss']}")
    if len(seen) != rounds:
        raise RuntimeError(f"expected {rounds} distinct shift sets: {seen}")
    dynamic_s = time.perf_counter() - t0
    _check_layout(state.params, "params")
    # where the compiler put those programs' permutes: all that the step's
    # compile option allows may be in flight at once, and the starts sit
    # beside the updates that feed them (docs/timeline.md)
    schedules = {}
    for program in bf.step_programs()[-rounds:]:
        found = permute_start_slack(program.hlo_text())
        if found["max_in_flight"] < min(found["starts"], PERMUTES_IN_FLIGHT_MAX):
            raise RuntimeError(
                f"{program!r}: {found['starts']} permutes, but at most "
                f"{found['max_in_flight']} in flight")
        schedules[str(program.key[1])] = {
            "permutes": found["starts"],
            "max_in_flight": found["max_in_flight"],
            "median_start_slack": int(np.median(found["slack"]))}

    # 3. small MLP: the hierarchical optimizer on this host's machine mesh,
    # and the window plane's mailbox programs
    mlp = MLP(features=(32, 8))
    xb = jax.random.normal(jax.random.PRNGKey(2), (n, 4, 16))
    yb = jnp.zeros((n, 4), jnp.int32)
    mlp_params = mlp.init(jax.random.PRNGKey(3), xb[0])["params"]

    def mlp_loss(p, b):
        return optax.softmax_cross_entropy_with_integer_labels(
            mlp.apply({"params": p}, b[0]), b[1]).mean()

    hopt = bf.DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.1), mlp_loss)
    _, hm = hopt.step(hopt.init(mlp_params), (xb, yb))
    wopt = bf.DistributedWinPutOptimizer(optax.sgd(0.05), mlp_loss)
    wstate = wopt.init(mlp_params)
    for _ in range(2):  # step 1 fills the mailboxes, step 2 mixes them
        wstate, wm = wopt.step(wstate, (xb, yb))
    wopt.free()
    for what, m in (("hierarchical", hm), ("win_put", wm)):
        if not np.isfinite(np.asarray(m["loss"])).all():
            raise RuntimeError(f"non-finite {what} loss: {m['loss']}")
    return {
        "neighbor_allreduce": "equals the weight matrix product to 1e-6",
        "dynamic_one_peer_programs": rounds,
        "dynamic_s_total": round(dynamic_s, 2),
        "dynamic_one_peer_schedules": schedules,
        "machine_mesh": list(bf.machine_mesh().devices.shape),
        "hierarchical_loss": round(float(np.mean(np.asarray(hm["loss"]))), 4),
        "win_put_loss": round(float(np.mean(np.asarray(wm["loss"]))), 4),
    }


def _mosaic_calls(fn, *args):
    """Mosaic kernels in the program jit lowers for ``fn`` (an interpreted
    kernel lowers to none)."""
    return fn.lower(*args).as_text().count("tpu_custom_call")


def _check_flash_kernels(d_v=None, d_qk=None):
    """Compiled forward and backward kernels against the dense reference, at
    each of ``KERNEL_SHAPES`` (its width unless the caller gives two)."""
    return {name: _check_flash_kernels_at(shape[:3], d_v or shape[3],
                                          d_qk or shape[3])
            for name, shape in KERNEL_SHAPES.items()}


def _check_flash_kernels_at(bsh, d_v, d_qk, kv_heads=None, window=None):
    """``kv_heads`` (fewer than the query heads of ``bsh``) and ``window``
    are handed to the kernels and to the dense reference alike."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    kv = bsh[:2] + (kv_heads or bsh[2],)
    q, k, v, w = (jax.random.normal(kk, shape + (d,), jnp.bfloat16)
                  for kk, shape, d in zip(keys, (bsh, kv, kv, bsh),
                                          (d_qk, d_qk, d_v, d_v)))
    flash = partial(FLASH, window=window)

    def weighted(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    grad = jax.jit(jax.grad(weighted(flash), argnums=(0, 1, 2)))
    calls = _mosaic_calls(grad, q, k, v)
    if calls != 2:
        raise RuntimeError(
            f"expected 2 Mosaic kernels under the flash gradient (the forward "
            f"and the one backward), found {calls}")
    ref = partial(reference_attention, causal=True, window=window)
    got = (jax.jit(flash)(q, k, v),) + grad(q, k, v)
    want = (jax.jit(ref)(q, k, v),) + jax.jit(
        jax.grad(weighted(ref), argnums=(0, 1, 2)))(q, k, v)
    err = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=KERNEL_TOL, rtol=KERNEL_TOL,
                                   err_msg=f"flash {name} vs reference")
        err[name] = round(float(np.max(np.abs(a - b))), 4)
    return err


def phase_lm_flash():
    n = bf.size()
    kernel_err = _check_flash_kernels()
    kernel_err["s4096_28over4_w1024"] = _check_flash_kernels_at(**GROUPED_WINDOW)
    model = TransformerLM(dtype=jnp.bfloat16, attn_fn=FLASH, **LM)
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, LM_SEQ), jnp.int32))["params"])(jax.random.PRNGKey(0))

    def loss_fn(p, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, tokens), targets).mean()

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.adam(1e-3), loss_fn)
    state = opt.init(params)
    del params  # the rank-stacked copy is the only one the step needs

    def make(k):
        tokens = jax.random.randint(k, (n, 1, LM_SEQ), 0, LM["vocab_size"])
        return tokens, jnp.roll(tokens, -1, axis=2)

    state, compile_s, steady, losses = _timed_steps(
        opt, state, _rank_batch(make), LM_STEPS)
    if not (losses[-1] < losses[0]).all():
        raise RuntimeError(
            f"LM loss did not fall on every rank: {losses.tolist()}")
    _check_layout(state.params, "params")
    out = _report(compile_s, steady, losses)
    out["kernel_max_abs_err"] = kernel_err
    return out


def _grouped_dispatch(key):
    """``GROUPED_LOADS`` slots on the held experts and 1,000 held elsewhere, one
    a token, shuffled: ``(slot, valid, tile_expert, tiles_used, token_rows)`` of
    a buffer with tiles to spare."""
    held = len(GROUPED_LOADS)
    ids = np.concatenate([np.full(count, e) for e, count in enumerate(GROUPED_LOADS)]
                         + [np.full(GROUPED_SLOTS - sum(GROUPED_LOADS), held + 3)])
    ids = jax.random.permutation(key, jnp.asarray(ids, jnp.int32))[:, None]
    slot, valid, tile_expert, used, counters, token_rows = expert.dispatch_held(
        ids, (0, held), 4096)
    tiles = sum(max(-(-count // expert.ROW_TILE), 1) for count in GROUPED_LOADS)
    if (int(counters["rows_routed"]) != sum(GROUPED_LOADS) or int(counters["rows_overflowed"])
            or int(counters["tiles_in_use"]) != tiles):
        raise RuntimeError(f"dispatch_held miscounted {GROUPED_LOADS}: {counters}")
    return slot, valid, tile_expert, used, token_rows


def _check_row_movers():
    """The compiled row movers and their gradients at the expert layer's width
    against the plain expressions they replace -- a gather under the rows'
    mask, a weighted scatter-add, autodiff's transposes -- on the same ragged
    loads: the loops stop after 3 chunks of 8 tiles of the buffer's 5."""
    d = MLA_MOE.hidden_size
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    token, valid, _, used, token_rows = _grouped_dispatch(keys[0])  # one slot a token
    x = jax.random.normal(keys[1], (GROUPED_SLOTS, d), jnp.bfloat16)
    # stands for the experts' result: anything on padding rows, zero past the tiles in use
    extra = jnp.where(jnp.arange(token.shape[0])[:, None] < used[0] * expert.ROW_TILE,
                      jax.random.normal(keys[2], (token.shape[0], d), jnp.bfloat16), 0)
    weight = jax.random.uniform(keys[3], (GROUPED_SLOTS,), jnp.float32, 0.5, 1.5)
    cot = jax.random.normal(keys[4], (GROUPED_SLOTS, d), jnp.float32)

    def moved(x, extra, weight):
        y = expert.rows_in(x, token, valid, used, token_rows) + extra
        return expert.rows_out(y, jnp.where(valid, weight[token], 0.0), token, used,
                               token_rows).astype(jnp.float32)

    def plain(x, extra, weight):
        y = jnp.where(valid[:, None], x[token], 0) + extra
        return jnp.zeros((GROUPED_SLOTS, d), jnp.float32).at[token].add(
            y.astype(jnp.float32) * jnp.where(valid, weight[token], 0.0)[:, None]
        ).astype(jnp.bfloat16).astype(jnp.float32)

    def with_gradients(fn):
        grad = jax.grad(lambda *args: jnp.sum(fn(*args) * cot), argnums=(0, 1, 2))
        return (jax.jit(fn)(x, extra, weight),) + jax.jit(grad)(x, extra, weight)

    err = {}
    for name, a, b in zip(("out", "d_x", "d_y", "d_weight"),
                          with_gradients(moved), with_gradients(plain)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # one row a token, so no sum's order differs: the two differ where
        # XLA rounds y to bfloat16 on its way into the product
        np.testing.assert_allclose(a, b, atol=1e-2 * np.max(np.abs(b)), rtol=1e-2,
                                   err_msg=f"row movers {name} vs XLA")
        err[name] = round(float(np.max(np.abs(a - b)) / np.max(np.abs(b))), 5)
    return err


def _check_grouped_matmul():
    """The compiled grouped product, its rows' gradient and its weights'
    gradient at the expert layer's widths against one XLA matmul per expert
    under its rows' mask, on ``GROUPED_LOADS`` in a buffer with tiles to spare."""
    d, f, held = MLA_MOE.hidden_size, MLA_MOE.moe_intermediate_size, len(GROUPED_LOADS)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    slot, valid, tile_expert, used, _ = _grouped_dispatch(keys[0])
    x = jax.random.normal(keys[1], (GROUPED_SLOTS, d), jnp.bfloat16)
    rows = jnp.where(valid[:, None], x[slot], 0)
    weights = (jax.random.normal(keys[2], (held, d, f), jnp.float32) / np.sqrt(d)).astype(
        jnp.bfloat16)
    # the cotangent, exact in bfloat16 so that both sides are handed the same
    w = jax.random.normal(keys[3], (rows.shape[0], f), jnp.bfloat16).astype(jnp.float32)
    row_expert = tile_expert[jnp.arange(rows.shape[0]) // expert.ROW_TILE]
    in_use = jnp.arange(rows.shape[0]) < used[0] * expert.ROW_TILE

    def grouped(rows, weights):
        return expert.grouped_matmul(rows, weights, tile_expert, used).astype(jnp.float32)

    def masked(rows, weights):
        out = jnp.zeros((rows.shape[0], f), jnp.float32)
        for e in range(held):
            y = jnp.dot(rows.astype(jnp.float32), weights[e].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
            out = jnp.where(((row_expert == e) & in_use)[:, None], y, out)
        return out

    weighted = lambda fn: lambda rows, weights: jnp.sum(fn(rows, weights) * w)  # noqa: E731
    # the product is linear in both, so its gradient alone holds two kernels
    grad = jax.jit(jax.grad(weighted(grouped), argnums=(0, 1)))
    calls = _mosaic_calls(grad, rows, weights) + _mosaic_calls(jax.jit(grouped), rows, weights)
    if calls != 3:
        raise RuntimeError(f"expected 3 Mosaic kernels in the grouped product and "
                           f"its gradient, found {calls}")
    got = (jax.jit(grouped)(rows, weights),) + grad(rows, weights)
    want = (jax.jit(masked)(rows, weights),) + jax.jit(
        jax.grad(weighted(masked), argnums=(0, 1)))(rows, weights)
    err = {}
    for name, a, b in zip(("out", "d_rows", "d_weights"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # sums of hundreds of terms that cancel: an element's error goes with
        # the leaf's size, not its own (a misplaced tile is wrong by the size)
        np.testing.assert_allclose(a, b, atol=KERNEL_TOL * np.max(np.abs(b)), rtol=KERNEL_TOL,
                                   err_msg=f"grouped_matmul {name} vs XLA")
        err[name] = round(float(np.max(np.abs(a - b)) / np.max(np.abs(b))), 5)
    empty = GROUPED_LOADS.index(0)
    if np.any(np.asarray(got[2][empty], np.float32)):
        raise RuntimeError("the empty expert's weight gradient is not zero")
    return err


def phase_mla_moe():
    n = bf.size()
    kernel_err = _check_flash_kernels(
        d_v=MLA_MOE.v_head_dim,
        d_qk=MLA_MOE.qk_nope_head_dim + MLA_MOE.qk_rope_head_dim)
    grouped_err = _check_grouped_matmul()
    movers_err = _check_row_movers()
    model = ConfigLM(MLA_MOE, dtype=jnp.bfloat16, attn_fn=FLASH)
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, LM_SEQ), jnp.int32)))(jax.random.PRNGKey(0))
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.adam(1e-3), next_token_loss(model), with_model_state=True)
    state = opt.init(variables["params"], model_state=variables["routing"])
    del variables

    def make(k):
        tokens = jax.random.randint(k, (n, 1, LM_SEQ), 0, MLA_MOE.vocab_size)
        return tokens, jnp.roll(tokens, -1, axis=2), jnp.roll(tokens, -2, axis=2)

    batch = _rank_batch(make)
    state, compile_s, steady, losses = _timed_steps(opt, state, batch, LM_STEPS)
    if not (losses[-1] < losses[0]).all():
        raise RuntimeError(
            f"MLA/MoE LM loss did not fall on every rank: {losses.tolist()}")
    _check_layout(state.params, "params")
    _, metrics = opt.step(state, batch)
    counters = {name: np.asarray(value).tolist()
                for name, value in metrics["aux"].items()}
    if any(counters["rows_overflowed"]):
        raise RuntimeError(f"rows overflowed the held experts' buffer: {counters}")
    out = _report(compile_s, steady, losses)
    out["kernel_max_abs_err"] = kernel_err
    out["grouped_matmul_max_rel_err"] = grouped_err
    out["row_movers_max_rel_err"] = movers_err
    out["moe_counters_per_rank"] = counters
    return out


def phase_looped():
    """The first ``jax.checkpoint`` around the kernels' ``custom_vjp`` on a
    chip: value and gradients of the expected-exit loss with every layer
    application recomputed against the same program without; the policy
    keeps the forward kernel's residuals, so both programs run it once an
    application."""
    applications = LOOPED.total_ut_steps * LOOPED.num_hidden_layers
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, LOOPED_SEQ), 0, LOOPED.vocab_size)
    batch = (tokens, jnp.roll(tokens, -1, axis=1))
    out = {}
    kernels = 2 * applications      # the forward and the backward kernel, once each
    for name, remat in (("recomputed", True), ("kept", False)):
        model = ConfigLM(dataclasses.replace(LOOPED, remat_layers=remat), dtype=jnp.bfloat16,
                         attn_fn=FLASH)
        if not out:
            params = jax.jit(lambda k: model.init(k, tokens)["params"])(jax.random.PRNGKey(0))
        grad = jax.jit(jax.value_and_grad(looped_exit_loss(model, 0.1), has_aux=True)).lower(
            params, {}, batch).compile()
        # the compiled program's: the lowered text holds a kernel once, its callers many times
        calls = grad.as_text().count('custom_call_target="tpu_custom_call"')
        if calls != kernels:
            raise RuntimeError(f"expected {kernels} Mosaic kernels in the {name} looped "
                               f"gradient ({applications} applications), found {calls}")
        (loss, (_, aux)), grads = grad(params, {}, batch)
        out[name] = (loss, grads)
        mass = float(aux["exit_mass_by_pass"].sum())
        if abs(mass - 1.0) > 1e-5:
            raise RuntimeError(f"the exit masses of the {name} program sum to {mass}")
    (loss, grads), (want_loss, want_grads) = out["recomputed"], out["kept"]
    err, leaf = max(
        (float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))), jax.tree_util.keystr(path))
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                jax.tree_util.tree_leaves(want_grads)))
    # the two programs round alike but fuse apart: bf16 noise, not a fault
    if not (abs(float(loss) - float(want_loss)) <= 1e-3 * abs(float(want_loss))
            and err <= KERNEL_TOL):
        raise RuntimeError(f"recomputed looped gradients differ from the kept ones: loss "
                           f"{float(loss)} for {float(want_loss)}, worst leaf {leaf} {err}")
    return {"loss_recomputed": round(float(loss), 5), "loss_kept": round(float(want_loss), 5),
            "grad_max_rel_err": round(err, 5), "worst_leaf": leaf,
            "mosaic_calls": kernels}


def _device_stamp():
    """The device as JAX reports it; exits unless every rank is a TPU chip and
    every chip is a rank."""
    devices = list(bf.mesh().devices.flat)
    found = sorted({d.platform for d in devices})
    if found != ["tpu"] or len(devices) != len(jax.devices()):
        raise SystemExit(
            f"chip_smoke: needs every rank on a TPU chip, but bf.init() "
            f"ranked over {len(devices)} of {len(jax.devices())} device(s), "
            f"platform {found} ({devices[0].device_kind}); nothing was run")
    return {"platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}


def result_line(stamp):
    """The last line of stdout: these keys and no others."""
    return json.dumps({"ok": True, "device": stamp})


def main():
    import jaxlib
    from importlib.metadata import version

    bf.init()
    stamp = _device_stamp()
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": version("libtpu")}
    native_loaded = native.load() is not None
    print(f"device: {stamp}\nversions: {versions}\n"
          f"native runtime loaded: {native_loaded}\n"
          f"compile cache: {compile_cache_dir()}", flush=True)

    phases = {}
    phases["resnet50"], resnet = phase_resnet50()
    print("resnet50:", phases["resnet50"], flush=True)
    if stamp["count"] > 1:
        phases["gossip"] = phase_gossip(*resnet)
    else:
        phases["gossip"] = "not applicable: one chip has no peer to gossip with"
    print("gossip:", phases["gossip"], flush=True)
    del resnet  # ResNet-50 and the LM do not fit beside each other
    phases["lm_flash"] = phase_lm_flash()
    print("lm_flash:", phases["lm_flash"], flush=True)
    phases["mla_moe"] = phase_mla_moe()
    print("mla_moe:", phases["mla_moe"], flush=True)
    phases["looped"] = phase_looped()
    print("looped:", phases["looped"], flush=True)
    peak_gib = [round(d.memory_stats()["peak_bytes_in_use"] / 2**30, 2)
                for d in bf.mesh().devices.flat]
    bf.shutdown()
    print(json.dumps({"device": stamp, "versions": versions,
                      "native_runtime": native_loaded,
                      "peak_hbm_gib_per_chip": peak_gib, "phases": phases}))
    print(result_line(stamp), flush=True)


if __name__ == "__main__":
    main()
