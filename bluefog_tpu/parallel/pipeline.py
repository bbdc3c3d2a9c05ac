"""Pipeline parallelism — GPipe microbatching over a "pipe" mesh axis.

Net-new vs the reference (data-parallel only, SURVEY §2.6). The TPU-native
shape of pipeline parallelism: transformer blocks are *stage-stacked* (the
same rank-stacked idiom the collectives use — leaf ``x[s]`` is stage s's
layer chunk, sharded one stage per device), activations hand off between
stages with one ``lax.ppermute`` per tick, and the whole GPipe schedule
(fill, steady state, drain — M + S - 1 ticks for M microbatches over S
stages) is a single ``lax.scan`` inside one compiled program. Every stage
runs the same SPMD code; "stage 0 ingests" / "last stage records" are
``lax.select`` on ``axis_index``, not control flow.

Embedding, final norm, and the LM head are replicated and run outside the
pipelined block stack (they are a few percent of the FLOPs; the block stack
is the memory that forces pipelining).

Memory model, stated honestly: the plain forward (:func:`_pp_fwd`,
``pp_apply``) shards *parameters* (one stage chunk per device) but
replicates the microbatch activation buffer and recorded outputs across
stages — fine for exactness demos. The TRAINING path offers the real GPipe
memory discipline via ``pp_train_step_fn(..., fused_loss=True)``
(:func:`_pp_fused_loss`): stage 0 embeds its next microbatch inside each
tick (only tiny int32 tokens are replicated), the last stage folds each
drained microbatch straight into the cross-entropy scalar, and the scan
carry is one [mb, seq, d] activation per stage — with per-layer
``jax.checkpoint`` remat in both paths.

Exact by construction: the pipeline computes the same composition of blocks
as the dense model, so tests assert equality with the single-device oracle.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import optax

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .context import _pvary, reference_attention


def pp_mesh(n_stages: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D ``("pipe",)`` mesh over ``n_stages`` devices."""
    from .context import mesh_1d
    return mesh_1d(n_stages, "pipe", devices)


def pp_stack_params(params, n_stages: int):
    """Split TransformerLM params into (stage-stacked blocks, shared rest).

    ``params["block_i"]`` subtrees are stacked along a new leading stage
    axis as ``[n_stages, layers_per_stage, ...]`` leaves; everything else
    (embed, final_norm, lm_head) is returned as-is for the replicated
    prologue/epilogue.
    """
    blocks = sorted(
        (k for k in params if k.startswith("block_")),
        key=lambda k: int(k.split("_")[1]))
    n_layers = len(blocks)
    if n_layers == 0 or n_layers % n_stages:
        raise ValueError(
            f"num_layers {n_layers} must be a positive multiple of "
            f"n_stages {n_stages}")
    per = n_layers // n_stages
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (n_stages, per) + leaves[0].shape),
        *[params[k] for k in blocks])
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    return stacked, rest


def _mirror_modules(model):
    """(block, embed, final_norm, lm_head) mirroring TransformerLM's
    submodules — the ONE place the prologue/epilogue coupling lives (the
    pp-vs-oracle exactness tests pin it against TransformerLM.apply)."""
    # deferred: models.transformer imports parallel.context at package
    # import time, so a top-level import here would be circular
    from ..models.transformer import Block
    import flax.linen as nn

    block = Block(
        model.num_heads, model.d_ff, model.dtype,
        model.attn_fn or functools.partial(reference_attention, causal=True))
    emb = nn.Embed(model.vocab_size, model.d_model, dtype=model.dtype,
                   param_dtype=jnp.float32)
    norm = nn.RMSNorm(dtype=model.dtype, param_dtype=jnp.float32)
    head = nn.Dense(model.vocab_size, dtype=model.dtype,
                    param_dtype=jnp.float32, use_bias=False)
    return block, emb, norm, head


def _chunk_applier(block, stage_params):
    """Per-layer-rematted scan over this stage's layer chunk: the backward
    recomputes each block instead of storing its internals for every tick
    of the schedule — the activation-memory discipline GPipe needs."""
    sp = jax.tree_util.tree_map(lambda x: x[0], stage_params)

    def apply_chunk(x, positions):
        @jax.checkpoint
        def body(h, p):
            return block.apply({"params": p}, h, positions), None
        out, _ = lax.scan(body, x, sp)
        return out

    return apply_chunk


@functools.lru_cache(maxsize=16)
def _pp_fwd(model, mesh: Mesh, n_stages: int, n_micro: int):
    """Unjitted pipelined forward (the differentiable building block)."""
    block, emb_mod, norm_mod, head_mod = _mirror_modules(model)

    def per_stage(stage_params, mb_acts, positions):
        # stage_params: [1, per, ...] this stage's layer chunk
        # mb_acts:      [n_micro, mb, seq, d_model] (replicated)
        me = lax.axis_index("pipe")
        apply_chunk = _chunk_applier(block, stage_params)

        zero = jnp.zeros_like(mb_acts[0])
        outputs = jnp.zeros_like(mb_acts)

        def tick(carry, t):
            x_cur, outputs = carry
            y = apply_chunk(x_cur, positions)
            # last stage records microbatch t-(S-1) when it has drained
            idx = t - (n_stages - 1)
            rec = lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(idx, 0, n_micro - 1), axis=0)
            outputs = jnp.where(
                jnp.logical_and(me == n_stages - 1, idx >= 0), rec, outputs)
            # hand y to the next stage; stage 0's incoming slot is fed the
            # next microbatch instead (the wrap-around edge carries garbage)
            nxt = lax.ppermute(
                y, "pipe", [(s, (s + 1) % n_stages) for s in range(n_stages)])
            ingest = lax.dynamic_index_in_dim(
                mb_acts, jnp.clip(t + 1, 0, n_micro - 1), axis=0,
                keepdims=False)
            x_next = jnp.where(me == 0,
                               jnp.where(t + 1 < n_micro, ingest, zero), nxt)
            return (x_next, outputs), None

        x0 = jnp.where(me == 0, mb_acts[0], zero)  # varying via me
        # the replicated zero-init output buffer becomes stage-varying
        # inside the loop; declare it up front so the scan carry types match
        outputs = _pvary(outputs, ("pipe",))
        (_, outputs), _ = lax.scan(
            tick, (x0, outputs), jnp.arange(n_micro + n_stages - 1))
        # replicate the recorded outputs off the last stage
        return lax.psum(
            jnp.where(me == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            "pipe")

    spec_stage = P("pipe")
    mapped = shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_stage, P(), P()),
        out_specs=P(),
    )

    def fwd(stacked_blocks, rest, tokens):
        b, seq = tokens.shape
        if b % n_micro:
            raise ValueError(
                f"batch {b} must divide into {n_micro} microbatches")
        positions = jnp.arange(seq)
        x = emb_mod.apply({"params": rest["embed"]}, tokens)
        mb = x.reshape((n_micro, b // n_micro) + x.shape[1:])
        out = mapped(stacked_blocks, mb, positions)
        x = out.reshape((b, seq, out.shape[-1]))
        x = norm_mod.apply({"params": rest["final_norm"]}, x)
        logits = head_mod.apply({"params": rest["lm_head"]}, x)
        return logits.astype(jnp.float32)

    return fwd


@functools.lru_cache(maxsize=16)
def _pp_fn(model, mesh: Mesh, n_stages: int, n_micro: int):
    return jax.jit(_pp_fwd(model, mesh, n_stages, n_micro))


def pp_forward_fn(model, mesh: Mesh, n_micro: int = 2):
    """Compiled pipelined forward: ``fwd(stacked_blocks, rest, tokens)``.

    The step-over-step training path: stage-stack and place the params ONCE
    (:func:`pp_stack_params` + :func:`pp_place_params`), then call the
    returned function every step without restacking.
    """
    return _pp_fn(model, mesh, mesh.shape["pipe"], n_micro)


def pp_place_params(stacked, mesh: Mesh):
    """Put a stage-stacked block tree on the mesh, one stage per device."""
    return jax.device_put(stacked, NamedSharding(mesh, P("pipe")))


@functools.lru_cache(maxsize=16)
def _pp_fused_loss(model, mesh: Mesh, n_stages: int, n_micro: int):
    """Loss-fused, activation-light pipelined training loss.

    The plain forward (:func:`_pp_fwd`) replicates the microbatch
    activation buffer and the recorded outputs across stages — fine for
    exactness demos, wrong memory model for training. This builder keeps
    only O(mb · seq · d) live per stage:

      * stage 0 EMBEDS its next microbatch inside each tick (tokens are
        replicated int32 — a few KB — instead of a replicated activation
        buffer; other stages compute the same cheap gather and discard it,
        the standard SPMD select idiom);
      * the LAST stage consumes each drained microbatch immediately —
        final norm + lm_head + cross-entropy inside the tick — and
        accumulates a scalar loss instead of recording logits;
      * the scan carry is one [mb, seq, d] activation per stage, the true
        GPipe boundary-activation footprint, with per-layer remat inside
        the block chunk.

    Gradients of the replicated prologue/epilogue params are psum'd by
    shard_map's transpose automatically. Returns
    ``loss(stacked_blocks, rest, (tokens, targets)) -> scalar``.
    """
    block, emb_mod, norm_mod, head_mod = _mirror_modules(model)

    def per_stage(stage_params, rest, tokens_mb, targets_mb):
        # stage_params [1, per, ...]; rest replicated; tokens/targets
        # [n_micro, mb, seq] replicated int32 (tiny)
        me = lax.axis_index("pipe")
        apply_chunk = _chunk_applier(block, stage_params)

        seq = tokens_mb.shape[2]
        positions = jnp.arange(seq)

        def embed(i):
            toks = lax.dynamic_index_in_dim(tokens_mb, i, axis=0,
                                            keepdims=False)
            return emb_mod.apply({"params": rest["embed"]}, toks)

        # rematted: without the checkpoint the scan backward would stash a
        # per-tick fp32 [mb, seq, vocab] logits residual on every stage —
        # larger than the buffers this schedule exists to avoid
        @jax.checkpoint
        def microbatch_loss(y, idx):
            h = norm_mod.apply({"params": rest["final_norm"]}, y)
            logits = head_mod.apply({"params": rest["lm_head"]},
                                    h).astype(jnp.float32)
            tgts = lax.dynamic_index_in_dim(targets_mb, idx, axis=0,
                                            keepdims=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgts).mean()

        def tick(carry, t):
            x_cur, loss_acc = carry
            y = apply_chunk(x_cur, positions)
            idx = t - (n_stages - 1)
            # every stage computes the epilogue (RMSNorm + d x vocab head
            # matmul + CE) and non-last stages discard it via the mask —
            # the SPMD select idiom. Stated cost: the epilogue is paid
            # S x (M+S-1)/M times vs once in the plain path; a per-device
            # lax.cond would skip it but aborts XLA at runtime (collective
            # -free branches notwithstanding), so uniformity wins here.
            contrib = microbatch_loss(y, jnp.clip(idx, 0, n_micro - 1))
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(me == n_stages - 1, idx >= 0), contrib, 0.0)
            nxt = lax.ppermute(
                y, "pipe", [(s, (s + 1) % n_stages) for s in range(n_stages)])
            ingest = embed(jnp.clip(t + 1, 0, n_micro - 1))
            x_next = jnp.where(
                me == 0,
                jnp.where(t + 1 < n_micro, ingest, jnp.zeros_like(ingest)),
                nxt)
            return (x_next, loss_acc), None

        x0 = jnp.where(me == 0, embed(0), jnp.zeros_like(embed(0)))
        loss0 = _pvary(jnp.zeros((), jnp.float32), ("pipe",))
        (_, loss_acc), _ = lax.scan(
            tick, (x0, loss0), jnp.arange(n_micro + n_stages - 1))
        # only the last stage accumulated; psum replicates the total
        return lax.psum(loss_acc, "pipe")

    mapped = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P("pipe"), P(), P(), P()),
        out_specs=P(),
    )

    def loss(stacked_blocks, rest, batch):
        tokens, targets = batch
        b, seq = tokens.shape
        if b % n_micro:
            raise ValueError(
                f"batch {b} must divide into {n_micro} microbatches")
        mb = b // n_micro
        return mapped(stacked_blocks, rest,
                      tokens.reshape(n_micro, mb, seq),
                      targets.reshape(n_micro, mb, seq)) / n_micro

    return loss


def pp_loss_fn(model, mesh: Mesh, n_micro: int = 2):
    """Next-token cross-entropy through the pipelined forward.

    ``loss(stacked_blocks, rest, (tokens, targets)) -> scalar``, fully
    differentiable: autodiff through the GPipe scan runs the backward
    pipeline in reverse tick order (gradient handoffs are the transposed
    ppermutes), with per-layer rematerialization (``jax.checkpoint``) so
    activation memory stays per-tick, not per-schedule.
    """
    fwd = _pp_fwd(model, mesh, mesh.shape["pipe"], n_micro)

    def loss(stacked_blocks, rest, batch):
        tokens, targets = batch
        logits = fwd(stacked_blocks, rest, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    return loss


def pp_train_step_fn(model, mesh: Mesh, optimizer, n_micro: int = 2,
                     fused_loss: bool = False):
    """Compiled pipelined TRAINING step (net-new; SURVEY §2.6 PP row).

    Build ONCE and reuse across the training loop (like ``jax.jit``): each
    call constructs a fresh jitted step, so calling this inside the loop
    recompiles the whole GPipe schedule every iteration.

    ``fused_loss=True`` uses the activation-light schedule
    (:func:`_pp_fused_loss`): stage 0 embeds its next microbatch inside
    each tick and the last stage folds each drained microbatch straight
    into the cross-entropy — per-stage live memory is O(mb·seq·d) instead
    of the replicated full-batch activation buffers of the plain forward.
    Same numerics (loss curves match to fp tolerance).

    ``step(stacked_blocks, rest, opt_state, batch) -> (stacked, rest,
    opt_state, loss)`` where ``batch = (tokens, targets)``; gradients flow
    through the whole GPipe schedule (microbatch accumulation is implicit:
    the loss averages over every microbatch, so its gradient IS the
    accumulated per-microbatch gradient), the optax update runs on both the
    stage-sharded block stack and the replicated prologue/epilogue params,
    and state is donated. Init with :func:`pp_stack_params` +
    :func:`pp_place_params`; numerics match the single-device step exactly
    (tests/test_pipeline_parallel.py pins the loss curve).
    """
    if fused_loss:
        loss = _pp_fused_loss(model, mesh, mesh.shape["pipe"], n_micro)
    else:
        loss = pp_loss_fn(model, mesh, n_micro)

    def step(stacked_blocks, rest, opt_state, batch):
        l, grads = jax.value_and_grad(
            lambda s, r: loss(s, r, batch), argnums=(0, 1))(
                stacked_blocks, rest)
        updates, opt_state = optimizer.update(
            grads, opt_state, (stacked_blocks, rest))
        stacked_blocks, rest = optax.apply_updates(
            (stacked_blocks, rest), updates)
        return stacked_blocks, rest, opt_state, l

    return jax.jit(step, donate_argnums=(0, 1, 2))


def pp_train_init(model, mesh: Mesh, params, optimizer):
    """(stacked_blocks placed on the pipe mesh, rest, opt_state) for
    :func:`pp_train_step_fn` from a plain TransformerLM param dict.

    ``rest`` and ``opt_state`` are explicitly placed mesh-replicated: the
    train step's outputs come back with mesh shardings, so placing the
    inputs the same way avoids a full second compile on step 2 — and since
    the step donates its state, placement also COPIES ``rest`` so donation
    can never invalidate the caller's original param arrays."""
    stacked, rest = pp_stack_params(params, mesh.shape["pipe"])
    stacked = pp_place_params(stacked, mesh)
    rep = NamedSharding(mesh, P())
    # jitted copy-with-placement: device_put may alias an already-placed
    # input even with may_alias=False, and the donating train step must
    # never be able to invalidate the caller's original param arrays — an
    # XLA copy guarantees fresh buffers with the steady-state sharding
    rest = jax.jit(
        lambda t: jax.tree_util.tree_map(jnp.copy, t),
        out_shardings=rep)(rest)
    # Optimizer state must enter the step with the SAME shardings the step
    # outputs (stage-sharded moments for stacked params, replicated for the
    # rest) or call 2 pays a full recompile. optax's init builds moments as
    # shape-only constants, so sharding does not propagate from the params —
    # place param-shaped state leaves like their params explicitly, and
    # sweep the param-independent leaves (e.g. adam's count) to
    # mesh-replicated (plain init would drop them on the default device,
    # which may not even belong to the mesh).
    opt_state = optimizer.init((stacked, rest))
    opt_state = optax.tree_utils.tree_map_params(
        optimizer, lambda s, p: jax.device_put(s, p.sharding), opt_state,
        (stacked, rest))
    opt_state = jax.tree_util.tree_map(
        lambda x: x if isinstance(getattr(x, "sharding", None), NamedSharding)
        else jax.device_put(x, rep), opt_state)
    return stacked, rest, opt_state


def pp_apply(model, params, tokens, mesh: Mesh, n_micro: int = 2):
    """One-shot pipelined forward: GPipe schedule over the "pipe" axis.

    ``params`` is the plain TransformerLM param dict; it is stage-stacked
    and placed on every call — convenient for evaluation. For training
    loops use :func:`pp_forward_fn` with pre-placed params.
    """
    n_stages = mesh.shape["pipe"]
    stacked, rest = pp_stack_params(params, n_stages)
    return pp_forward_fn(model, mesh, n_micro)(
        pp_place_params(stacked, mesh), rest, tokens)
