"""Expert parallelism — Switch-style MoE with all_to_all dispatch.

Net-new vs the reference (data-parallel only, SURVEY §2.6). The GShard/
Switch recipe in its TPU-native form: one expert FFN per device along an
"expert" mesh axis, top-1 gating, capacity-bounded dispatch expressed as
static-shape einsums, and exactly two ``lax.all_to_all`` hops per layer
(tokens to their expert, results back). Everything is static shapes — the
capacity bound C is what makes data-dependent routing compile.

Semantics (standard Switch): each token goes to its top-scoring expert,
scaled by the gate probability; tokens beyond an expert's capacity are
dropped (output zero) — choose ``capacity_factor >= num_experts`` to make
dropping impossible, which is how the exactness tests pin the SPMD path to
the dense oracle (``SwitchFFN``'s plain ``__call__``).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ep_mesh(n_experts: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D ``("expert",)`` mesh over ``n_experts`` devices."""
    from .context import mesh_1d
    return mesh_1d(n_experts, "expert", devices)


class SwitchFFN(nn.Module):
    """Mixture-of-experts FFN, top-1 (Switch) routing.

    Two execution modes sharing one gating function:

    * ``expert_axis=None`` (default): the dense single-device oracle — it
      evaluates every expert on every token and selects with a one-hot.
      O(E) FLOPs; used for init, small models, and as the correctness
      reference for the sparse path.
    * ``expert_axis="expert"``: the module is being applied INSIDE a
      ``shard_map`` over that mesh axis (one expert per device, ``up`` /
      ``down`` arriving as this device's local ``[1, ...]`` shard via a
      ``P(axis)`` in_spec). Tokens route to their expert and back with
      two ``lax.all_to_all`` hops — the GShard/Switch dispatch, usable as
      a drop-in FFN inside a larger sharded model (``MoETransformerLM``).

    In the sparse mode the Switch load-balance aux loss is sowed under
    ``intermediates/moe_aux`` (per-device scalar).
    """

    num_experts: int
    d_ff: int
    dtype: Any = jnp.float32
    expert_axis: Optional[str] = None
    capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        e_local = 1 if self.expert_axis else self.num_experts
        gate = self.param("gate", nn.initializers.lecun_normal(),
                          (d, self.num_experts), jnp.float32)
        up = self.param("up", nn.initializers.lecun_normal(),
                        (e_local, d, self.d_ff), jnp.float32)
        down = self.param("down", nn.initializers.lecun_normal(),
                          (e_local, self.d_ff, d), jnp.float32)
        if self.expert_axis:
            leading = x.shape[:-1]
            t = int(np.prod(leading))
            capacity = int(np.ceil(
                self.capacity_factor * t / self.num_experts))
            out, aux = switch_dispatch(
                gate, up, down, x.reshape(t, d), self.expert_axis,
                self.num_experts, capacity, self.dtype)
            self.sow("intermediates", "moe_aux", aux)
            return out.reshape(leading + (d,))
        in_dtype = x.dtype
        x = x.astype(self.dtype)
        probs = jax.nn.softmax(
            (x @ gate.astype(self.dtype)).astype(jnp.float32), axis=-1)
        best = jnp.argmax(probs, axis=-1)                       # [..,]
        sel = jax.nn.one_hot(best, self.num_experts, dtype=self.dtype)
        h = jnp.einsum("...d,edf->...ef", x, up.astype(self.dtype))
        h = nn.gelu(h)
        y = jnp.einsum("...ef,efd->...ed", h, down.astype(self.dtype))
        p_best = jnp.max(probs, axis=-1).astype(self.dtype)
        out = jnp.einsum("...ed,...e->...d", y, sel) * p_best[..., None]
        return out.astype(in_dtype)


def switch_dispatch(gate, up_local, down_local, xt, axis: str,
                    num_experts: int, capacity: int, dtype):
    """The sparse Switch body for ONE device inside a shard_map over
    ``axis``: top-1 gate, capacity-bounded dispatch, all_to_all to the
    owning expert, FFN, all_to_all back. ``xt`` is this device's tokens
    ``[t, d]``; ``up_local``/``down_local`` are its expert's weights
    ``[1, d, d_ff]`` / ``[1, d_ff, d]``. Returns ``([t, d], aux_scalar)``.
    Shared by :func:`ep_apply` and the ``expert_axis`` mode of
    :class:`SwitchFFN`."""
    in_dtype = xt.dtype
    xt = xt.astype(dtype)
    probs = jax.nn.softmax(
        (xt @ gate.astype(dtype)).astype(jnp.float32), axis=-1)
    best = jnp.argmax(probs, axis=-1)                        # [t]
    p_best = jnp.max(probs, axis=-1).astype(dtype)
    sel = jax.nn.one_hot(best, num_experts, dtype=jnp.int32)  # [t, E]
    # position of each token within its expert's send buffer
    pos = jnp.cumsum(sel, axis=0) * sel - 1                   # [t, E]
    keep = (pos < capacity) & (sel > 0)
    # dispatch[t, e, c]: token t occupies slot c of the buffer to e
    disp = keep[..., None] & (
        jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                       dtype=jnp.int32) > 0)
    disp = disp.astype(dtype)                                 # [t, E, C]
    send = jnp.einsum("tec,td->ecd", disp, xt)                # [E, C, d]
    # tokens to their expert: device e receives one [C, d] block per peer
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=True)                         # [E, C, d]
    h = nn.gelu(jnp.einsum("ncd,df->ncf", recv, up_local[0].astype(dtype)))
    y = jnp.einsum("ncf,fd->ncd", h, down_local[0].astype(dtype))
    # results back to the token-owning devices
    back = lax.all_to_all(y, axis, split_axis=0, concat_axis=0,
                          tiled=True)                         # [E, C, d]
    out = jnp.einsum("tec,ecd->td", disp, back) * p_best[:, None]
    aux = load_balance_loss(probs, best, num_experts)
    return out.astype(in_dtype), aux


def load_balance_loss(probs, best, num_experts: int):
    """Switch aux loss: ``E * sum_e f_e * P_e`` (Fedus et al. 2021, eq. 4)."""
    f = jnp.mean(jax.nn.one_hot(best, num_experts, dtype=jnp.float32),
                 axis=tuple(range(best.ndim)))
    pbar = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return num_experts * jnp.sum(f * pbar)


@functools.lru_cache(maxsize=16)
def _ep_fn(mesh: Mesh, num_experts: int, capacity: int, dtype):
    def per_device(gate, up, down, x):
        # gate [d, E] replicated; up [1, d, d_ff] / down [1, d_ff, d] = this
        # device's expert; x [b_local, s, d] = this device's tokens.
        b, s, d = x.shape
        out, aux = switch_dispatch(gate, up, down, x.reshape(b * s, d),
                                   "expert", num_experts, capacity, dtype)
        return out.reshape(b, s, d), aux[None]

    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P("expert"), P("expert"), P("expert")),
        out_specs=(P("expert"), P("expert")),
    )
    return jax.jit(lambda g, u, dn, x: mapped(g, u, dn, x))


def moe_param_specs(params, axis: str = "expert"):
    """PartitionSpec tree for a model containing :class:`SwitchFFN`
    submodules: expert weights (``up``/``down`` leaves of a SwitchFFN,
    named ``moe`` inside :class:`models.transformer.MoEBlock`) shard on
    the expert axis; the gate and every dense/attention/embedding param
    stay replicated. (A dense FFN's ``up``/``down`` *modules* hold a
    ``kernel`` leaf, so their paths end in ``kernel`` and fall through to
    replicated.)"""
    def spec(path, leaf):  # noqa: ARG001
        keys = [str(getattr(k, "key", k)) for k in path]
        if keys and keys[-1] in ("up", "down") and (
                "moe" in keys or any(k.startswith("SwitchFFN")
                                     for k in keys)):
            return P(axis)
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def _sum_intermediates(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + jnp.sum(jnp.asarray(leaf, jnp.float32))
    return total


def ep_lm_init(model, rng, tokens):
    """Init params for an ``expert_axis`` MoE model via its dense twin.

    The sparse variant declares per-device ``[1, ...]`` expert shards, so
    it cannot init outside the mesh; the dense twin (same config,
    ``expert_axis=None``) declares the full ``[E, ...]`` weights with the
    SAME tree structure and rng stream. Shard the result with
    :func:`moe_param_specs` (P(axis) splits the leading expert dim back
    into the per-device views the sparse apply expects)."""
    import dataclasses
    twin = dataclasses.replace(model, expert_axis=None)
    return twin.init(rng, tokens)["params"]


def ep_lm_apply(model, params, tokens, mesh: Mesh, axis: str = "expert"):
    """Expert-parallel forward of a ``expert_axis=axis`` MoE LM.

    One ``shard_map`` over the whole model: the batch and every MoE
    layer's experts ride the same 1-D mesh axis (DP+EP co-location, the
    GShard deployment); attention and dense blocks compute data-parallel
    on the local batch, each MoE layer does its two all_to_all hops.
    Returns ``(logits [B, S, V], aux)`` with ``aux`` the summed Switch
    load-balance loss averaged over devices.
    """
    _check_moe_model(model, mesh, axis)
    n = mesh.shape[axis]
    if tokens.shape[0] % n:
        raise ValueError(f"batch {tokens.shape[0]} must divide the "
                         f"{axis} axis size {n}")
    logits, aux = _ep_lm_fn(model, mesh, axis)(params, tokens)
    return logits, aux[0]


def _check_moe_model(model, mesh: Mesh, axis: str) -> None:
    if model.expert_axis != axis:
        raise ValueError(f"model.expert_axis={model.expert_axis!r}; "
                         f"construct the model with expert_axis={axis!r}")
    n = mesh.shape[axis]
    ne = getattr(model, "num_experts", None)
    if ne is not None and ne != n:
        raise ValueError(
            f"model has {ne} experts but the {axis!r} mesh axis is {n} — "
            "one expert per device is the supported layout")


@functools.lru_cache(maxsize=16)
def _ep_lm_fn(model, mesh: Mesh, axis: str):
    """Cached jitted forward (keyed on the model config and mesh) — a
    fresh shard_map+jit per call would retrace and recompile the whole
    model every invocation. The param specs are path-derived inside the
    traced call, so one cache entry serves any param tree structure (jit
    itself retraces on structure changes)."""

    def body(p, toks):
        logits, inter = model.apply({"params": p}, toks,
                                    mutable=["intermediates"])
        aux = lax.pmean(_sum_intermediates(inter), axis)
        return logits, aux[None]

    def call(p, toks):
        mapped = shard_map(
            body, mesh=mesh, in_specs=(moe_param_specs(p, axis), P(axis)),
            out_specs=(P(axis), P(axis)))
        return mapped(p, toks)

    return jax.jit(call)


def ep_lm_loss_fn(model, mesh: Mesh, axis: str = "expert",
                  aux_weight: float = 0.01):
    """``loss_fn(params, (tokens, targets)) -> scalar`` for the
    expert-parallel MoE LM: next-token cross-entropy + the Switch
    load-balance aux term. Differentiable straight through the
    ``shard_map`` (``jax.grad(loss_fn)`` gives correct expert-sharded
    grads for up/down and batch-averaged grads for everything else), so
    it plugs into the same optimizer wrappers as ``cp_loss_fn``."""
    _check_moe_model(model, mesh, axis)

    def loss_fn(params, batch):
        tokens, targets = batch
        specs = moe_param_specs(params, axis)

        def body(p, toks, tgts):
            logits, inter = model.apply({"params": p}, toks,
                                        mutable=["intermediates"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ce = -jnp.mean(jnp.take_along_axis(
                logp, tgts[..., None], axis=-1))
            aux = _sum_intermediates(inter)
            return (ce + aux_weight * aux)[None]

        mapped = shard_map(
            body, mesh=mesh, in_specs=(specs, P(axis), P(axis)),
            out_specs=P(axis))
        # per-device local losses; equal local batches -> mean is global
        return mapped(params, tokens, targets).mean()

    return loss_fn


def ep_place_params(params, mesh: Mesh):
    """Place a SwitchFFN param dict on the expert mesh ONCE (gate
    replicated, up/down one expert per device); re-placing already-placed
    arrays is a no-op, so training loops can pass the result to
    :func:`ep_apply` every step without transfers."""
    return {
        "gate": jax.device_put(params["gate"], NamedSharding(mesh, P())),
        "up": jax.device_put(params["up"], NamedSharding(mesh, P("expert"))),
        "down": jax.device_put(params["down"],
                               NamedSharding(mesh, P("expert"))),
    }


def ep_apply(params, x, mesh: Mesh, capacity_factor: float = 2.0,
             dtype=None) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel SwitchFFN forward.

    ``params`` is a :class:`SwitchFFN` param dict (``gate``/``up``/``down``)
    with ``num_experts == mesh.shape["expert"]``; ``x`` is ``[B, S, d]``
    with B divisible by the expert-axis size (tokens ride the same devices
    as experts, the standard DP+EP co-location). Returns ``(y, aux)`` where
    ``aux`` is the per-device Switch load-balance loss ``[n]``.

    ``dtype`` is the compute dtype and must match the ``SwitchFFN.dtype``
    used as the oracle (default: ``x.dtype``, which equals the module
    default of float32 for float32 inputs).

    Capacity per expert and source device is
    ``ceil(capacity_factor * local_tokens / num_experts)``; overflowed
    tokens get zero output (Switch semantics). ``capacity_factor >=
    num_experts`` guarantees no drops.
    """
    n = mesh.shape["expert"]
    if params["up"].shape[0] != n:
        raise ValueError(
            f"params have {params['up'].shape[0]} experts but the mesh "
            f"axis is {n}")
    b, s, d = x.shape
    if b % n:
        raise ValueError(f"batch {b} must divide the expert axis size {n}")
    local_tokens = (b // n) * s
    capacity = int(np.ceil(capacity_factor * local_tokens / n))
    placed = ep_place_params(params, mesh)
    x = jax.device_put(x, NamedSharding(mesh, P("expert")))
    return _ep_fn(mesh, n, capacity, jnp.dtype(dtype or x.dtype).name)(
        placed["gate"], placed["up"], placed["down"], x)
