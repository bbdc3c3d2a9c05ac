"""Context-parallel execution of the transformer LM.

``cp_apply`` runs a :class:`~bluefog_tpu.models.transformer.TransformerLM`
with the sequence dimension sharded across the mesh: each device holds S/n
tokens, attention is ring attention over the ppermute ring (or Ulysses), and
every other layer (embed, RMSNorm, MLP, head) is purely token-local so it
needs no communication at all. ``cp_loss_fn`` wraps it into the
``loss_fn(params, batch)`` contract of the distributed optimizers, with the
cross-entropy mean taken over the full sequence via ``psum``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .context import ring_attention_shard, ulysses_attention_shard


def _cp_model(model, kind: str, axis: str):
    body = {"ring": ring_attention_shard,
            "ulysses": ulysses_attention_shard}[kind]
    return model.clone(attn_fn=functools.partial(
        body, axis_name=axis, causal=True))


def cp_apply(model, variables, tokens, mesh: Optional[Mesh] = None,
             axis: str = "rank", kind: str = "ring"):
    """Sequence-parallel forward: tokens [B, S] -> logits [B, S, V].

    Equivalent (to numerics) to ``model.apply`` on one device; the sequence
    is sharded over ``axis`` and attention runs as a ring/Ulysses program.
    """
    if mesh is None:
        from ..runtime.state import _global_state
        st = _global_state()
        st.check_initialized()
        mesh = st.mesh
    n = mesh.shape[axis]
    if tokens.shape[1] % n:
        raise ValueError(
            f"sequence length {tokens.shape[1]} must divide mesh axis {n}")
    if kind == "ulysses" and model.num_heads % n:
        raise ValueError(
            f"ulysses needs num_heads % {n} == 0; got {model.num_heads}")
    return _cp_apply_fn(model, mesh, axis, kind)(variables, tokens)


@functools.lru_cache(maxsize=32)
def _cp_apply_fn(model, mesh: Mesh, axis: str, kind: str):
    """Cached jitted CP forward — stable identity so repeat calls hit the
    jit cache instead of re-tracing (flax Modules hash by value)."""
    cp = _cp_model(model, kind, axis)

    def body(variables, toks):
        me = lax.axis_index(axis)
        sq = toks.shape[1]
        positions = me * sq + jnp.arange(sq)
        return cp.apply(variables, toks, positions=positions)

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis),
    )
    return jax.jit(mapped)


def chunked_ce_loss(model, params, tokens, targets, chunk: int = 1024,
                    remat_backbone: bool = False):
    """Next-token cross-entropy WITHOUT materializing the [S, V] logits.

    At long S the logits tensor dominates HBM traffic: S=8192 x V=32768
    f32 is 1 GB written by the forward, read by the softmax, and touched
    twice more in the backward. This computes the backbone hidden states
    once, then projects to the vocabulary one sequence chunk at a time
    under ``jax.checkpoint`` inside a sequential ``lax.map`` — the
    backward recomputes each chunk's [chunk, V] logits instead of reading
    stored ones, so peak logits memory falls from [S, V] to [chunk, V].
    Numerics are exact (a mean over disjoint chunk sums; matmul dtype is
    the model's, softmax in f32 — identical to the full-logits path).
    """
    def backbone(p, toks):
        return model.apply({"params": p}, toks, method="hidden")

    if remat_backbone:
        backbone = jax.checkpoint(backbone)
    h = backbone(params, tokens)
    # hoist the [d, V] kernel cast out of the chunk loop: inside the map
    # body it would re-materialize per iteration (and per checkpointed
    # backward recompute) — wasted HBM traffic on exactly the
    # long-context path this function exists for
    W = params["lm_head"]["kernel"].astype(h.dtype)
    b, s, d = h.shape
    t = b * s
    if t % chunk:
        raise ValueError(
            f"CE chunk {chunk} must divide the token count {t}")
    hc = h.reshape(t // chunk, chunk, d)
    tc = targets.reshape(t // chunk, chunk)

    def chunk_nll(args):
        h_c, t_c = args
        logits = (h_c @ W).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t_c[..., None], axis=-1))

    totals = lax.map(jax.checkpoint(chunk_nll), (hc, tc))
    return totals.sum() / t


def cp_loss_fn(model, mesh: Optional[Mesh] = None, axis: str = "rank",
               kind: str = "ring"):
    """``loss_fn(params, (tokens, targets)) -> loss`` with CP attention.

    For sequence-parallel training of ONE long-sequence model replica:
    differentiate it directly (``jax.value_and_grad``) under jit. It builds
    its own shard_map over ``axis``, so do not nest it inside the
    data-parallel distributed optimizers — context parallelism and
    decentralized DP consume different mesh axes by design.
    """
    if mesh is None:
        from ..runtime.state import _global_state
        st = _global_state()
        st.check_initialized()
        mesh = st.mesh
    cpm = _cp_model(model, kind, axis)

    def body(params, toks, tgts):
        me = lax.axis_index(axis)
        sq = toks.shape[1]
        positions = me * sq + jnp.arange(sq)
        logits = cpm.apply({"params": params}, toks, positions=positions)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgts[..., None], axis=-1)
        # mean over the FULL sequence: psum local sums over the axis
        total = lax.psum(jnp.sum(nll), axis)
        count = lax.psum(jnp.asarray(nll.size, jnp.float32), axis)
        return total / count

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis)),
        out_specs=P(),
    )

    def loss(params, batch):
        tokens, targets = batch
        return mapped(params, tokens, targets)

    return loss
