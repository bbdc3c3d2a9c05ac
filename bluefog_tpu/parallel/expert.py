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

:class:`RoutedExperts` is the layer of a fine-grained mixture as it is
deployed (top-k of hundreds of experts, sigmoid scores with a choice-only
bias, a shared expert, no token dropped): it is told which experts it holds,
scores all of them, and computes its own experts' part of the result with
grouped matrix products over ragged per-expert row counts. Rows enter the
held experts' buffer and leave it through a pair of movers (:func:`rows_in`,
:func:`rows_out`): what goes into the buffer walks it in chunks of whole tiles
and stops with the last tile in use, as the grouped products do, so the
buffer's size costs its zero fills, not passes over it; what comes back into
the tokens is gathered, a pass for each of a chunk of tokens' rows, over a
map of each token's rows (:class:`TokenRows`), and never scattered. On one
chip it runs without an exchange; the all-to-all that would bring other
chips' tokens is not here.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime import metrics
from .flash import _dot_prec, _tile, _vma


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


# ---------------------------------------------------------------------------
# Top-k routing over many experts, the chip's share of them held here
# ---------------------------------------------------------------------------

# ``jax.named_scope``s a trace reducer finds the layer's parts by
SCOPE_ROUTE = "bf.moe.route"      # scores, top-k, sort, the row movers in and out
SCOPE_EXPERTS = "bf.moe.experts"  # the grouped products
SCOPE_SHARED = "bf.moe.shared"    # the shared expert, computed on every chip in full
# the flax collection of what routing keeps beside the parameters: the bias
ROUTING = "routing"


# Rows of one tile of the held experts' buffer. Every expert's rows start on a
# tile, so a tile multiplies one expert's weights; an expert's last tile is
# part padding. 128 is the v5e matrix unit's own height: at one chip's own
# tokens a held expert has a few hundred rows a layer, and a taller tile
# would be mostly padding.
ROW_TILE = 128


def _last(i, used):
    """The tile the blocks of grid step ``i`` are: ``i``, or the last one in use."""
    return jnp.minimum(i, used[0] - 1)


def _interpreter_cannot(*arrays) -> bool:
    """Inside ``shard_map`` the Pallas interpreter cannot run these kernels:
    it evaluates an index map's read of a prefetched scalar with a varying
    operand and an unvarying index, which the vma check refuses ("please open
    an issue"). The compiled kernels are not concerned. There -- a CPU mesh
    stepping a toy model through ``opt.step`` -- the same products are written
    with XLA ops below; outside ``shard_map`` the interpreter runs the kernels."""
    return bool(_vma(*arrays))


def _tiles_in_use(tile_expert, tiles_used):
    return jnp.arange(tile_expert.shape[0]) < tiles_used[0]


def _rows_kernel(used_ref, expert_ref, x_ref, w_ref, o_ref, *, transpose: bool):
    """One row tile times its expert's matrix (``w`` or its transpose)."""
    del expert_ref  # the index maps read it

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        contract = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], contract, preferred_element_type=jnp.float32,
            precision=_dot_prec(x_ref.dtype)).astype(o_ref.dtype)


def _rows_matmul(rows, weights, tile_expert, tiles_used, transpose: bool, interpret: bool):
    """``rows[tile i] @ weights[tile_expert[i]]`` (or ``@ weights[...].T``) for
    the first ``tiles_used`` tiles; the tiles after them are skipped -- no
    product, and no block moves, because their index maps stay on the last
    tile in use -- and their rows of the result are never written."""
    m, k = rows.shape
    n = weights.shape[1] if transpose else weights.shape[2]
    if interpret and _interpreter_cannot(rows, weights, tile_expert):
        out = jnp.einsum("tik,tnk->tin" if transpose else "tik,tkn->tin",
                         rows.reshape(-1, ROW_TILE, k), weights[tile_expert],
                         precision=_dot_prec(rows.dtype))
        return jnp.where(_tiles_in_use(tile_expert, tiles_used)[:, None, None], out,
                         jnp.nan).reshape(m, n).astype(rows.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, k), lambda i, used, expert: (_last(i, used), 0)),
            pl.BlockSpec((1,) + weights.shape[1:],
                         lambda i, used, expert: (expert[_last(i, used)], 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, n), lambda i, used, expert: (_last(i, used), 0)))
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=32 << 20)}
    return pl.pallas_call(
        functools.partial(_rows_kernel, transpose=transpose), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype, **_vma(rows, weights)),
        interpret=interpret, **params)(tiles_used, tile_expert, rows, weights)


def _weights_kernel(used_ref, expert_ref, x_ref, g_ref, o_ref, acc_ref):
    """``x[tile]^T @ g[tile]`` summed over an expert's tiles, which are
    consecutive: the accumulator starts at an expert's first tile and is
    written out at its last."""
    i = pl.program_id(1)
    used = used_ref[0]
    here = expert_ref[jnp.minimum(i, used - 1)]
    first = (i == 0) | (expert_ref[jnp.maximum(i - 1, 0)] != here)
    final = (i == used - 1) | (expert_ref[jnp.minimum(i + 1, used - 1)] != here)

    @pl.when(i < used)
    def _():
        @pl.when(first)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_prec(x_ref.dtype))

        @pl.when(final)
        def _store():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _weights_grad(rows, g, tile_expert, tiles_used, num_groups: int, interpret: bool):
    """``[groups, k, n]``: each expert's ``rows^T @ g`` over its own tiles.
    Every expert has a tile (``dispatch_held`` gives an empty one a tile of
    padding), so every block of the result is written."""
    m, k = rows.shape
    n = g.shape[1]
    if interpret and _interpreter_cannot(rows, g, tile_expert):
        by_tile = jnp.einsum("tik,tin->tkn", rows.reshape(-1, ROW_TILE, k),
                             g.reshape(-1, ROW_TILE, n), precision=_dot_prec(rows.dtype))
        by_tile = jnp.where(_tiles_in_use(tile_expert, tiles_used)[:, None, None], by_tile, 0)
        return jnp.zeros((num_groups, k, n), rows.dtype).at[tile_expert].add(by_tile)
    # an f32 accumulator [k, tn] of at most 4 MiB beside the double-buffered blocks
    tn = _tile(n, [c for c in (1024, 768, 512, 384, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                   if k * c * 4 <= 4 << 20])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn, m // ROW_TILE),
        in_specs=[
            pl.BlockSpec((ROW_TILE, k), lambda j, i, used, expert: (_last(i, used), 0)),
            pl.BlockSpec((ROW_TILE, tn), lambda j, i, used, expert: (_last(i, used), j)),
        ],
        out_specs=pl.BlockSpec((1, k, tn),
                               lambda j, i, used, expert: (expert[_last(i, used)], 0, j)),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)])
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=32 << 20)}
    return pl.pallas_call(
        _weights_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), rows.dtype, **_vma(rows, g)),
        interpret=interpret, **params)(tiles_used, tile_expert, rows, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(rows, weights, tile_expert, tiles_used, interpret: bool = False):
    """``rows[i] @ weights[e(i)]`` for rows laid out as :func:`dispatch_held`
    lays them: ``rows`` ``[m, k]`` in tiles of ``ROW_TILE`` rows, tile ``t``
    all of expert ``tile_expert[t]`` (``[m / ROW_TILE]`` int32, experts in
    ascending order, every expert at least once), of which the first
    ``tiles_used`` (``[1]`` int32) are in use; ``weights`` ``[g, k, n]``.
    Three Pallas kernels (this product, its transpose for the rows' gradient,
    and the per-expert ``rows^T @ g`` for the weights') whose grids skip the
    tiles not in use: those cost no product and no traffic. The result's rows
    past the tiles in use are zero, here and in the gradient."""
    out = _rows_matmul(rows, weights, tile_expert, tiles_used, False, interpret)
    return _zero_past(out, tiles_used)


def _zero_past(out, tiles_used):
    """The kernels never write the rows of the tiles not in use: zero them."""
    in_use = jnp.arange(out.shape[0]) < tiles_used[0] * ROW_TILE
    return jnp.where(in_use[:, None], out, jnp.zeros((), out.dtype))


def _grouped_matmul_fwd(rows, weights, tile_expert, tiles_used, interpret):
    return (grouped_matmul(rows, weights, tile_expert, tiles_used, interpret),
            (rows, weights, tile_expert, tiles_used))


def _grouped_matmul_bwd(interpret, res, g):
    rows, weights, tile_expert, tiles_used = res
    d_rows = _zero_past(
        _rows_matmul(g, weights, tile_expert, tiles_used, True, interpret), tiles_used)
    d_weights = _weights_grad(rows, g, tile_expert, tiles_used, weights.shape[0], interpret)
    return d_rows, d_weights, None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# Tiles a trip of the loops that walk the buffer takes: 1,024 rows. Small
# against the rows a layer routes here (the rounding to whole chunks stays a
# few per cent of them), large enough that a trip's gather moves megabytes.
CHUNK_TILES = 8
# Tokens a trip of the loop back into the tokens takes: a chunk's rows, likewise.
TOKEN_CHUNK = 1024


def chunk_tiles(rows: int) -> int:
    """Tiles of a chunk of a buffer of ``rows`` rows: ``CHUNK_TILES``, or the
    whole of a smaller buffer."""
    return min(CHUNK_TILES, rows // ROW_TILE)


def token_chunk(tokens: int) -> int:
    """Tokens a trip of the loop that adds rows back into tokens takes:
    ``TOKEN_CHUNK``, or all of fewer tokens."""
    return min(TOKEN_CHUNK, tokens)


def _fill(shape, dtype, *like):
    """Zeros for a mover's loop to carry: varying over the mesh axes the
    operands ``like`` vary over (inside ``shard_map``), as the body's result
    will be, or the carry's type would not match its body's."""
    zeros = jnp.zeros(shape, dtype)
    vma = _vma(*like).get("vma")
    return lax.pcast(zeros, tuple(vma), to="varying") if vma else zeros


def _walk(tiles_used, rows: int, body, init):
    """``body(start, n, fresh, carry) -> carry`` for each chunk (rows ``[start,
    start + n)``) of a ``rows``-row buffer, up to the chunk that holds tile
    ``tiles_used - 1``: a loop with a run-time trip count, so the chunks past
    the tiles in use cost nothing and ``carry`` keeps there what ``init`` held.
    The last chunk of a buffer that is no whole number of chunks starts early
    and overlaps the one before it; ``fresh`` ``[n]`` marks its rows no earlier
    chunk has walked. (A chunk that divides the buffer would need neither, but
    a buffer has ``bound / ROW_TILE + held`` tiles, which can be prime.)"""
    n = chunk_tiles(rows) * ROW_TILE
    trips = lax.div(tiles_used[0] * ROW_TILE + (n - 1), n)

    def trip(i, carry):
        start = jnp.minimum(i * n, rows - n)
        return body(start, n, start + jnp.arange(n) >= i * n, carry)

    return lax.fori_loop(0, trips, trip, init)


@jax.custom_vjp
def rows_in(xt, token, valid, tiles_used, token_rows):
    """The gather into the held experts' buffer: row ``i`` of the result
    ``[rows, d]`` is ``xt[token[i]]`` where ``valid[i]`` and zero elsewhere,
    for the rows of the first ``tiles_used`` tiles (``token``, ``valid``,
    ``tiles_used``, ``token_rows`` as :func:`dispatch_held` gives them); the
    rows past them are zero. Walked in chunks of :func:`chunk_tiles` tiles
    that stop with the last tile in use. The gradient adds the rows'
    cotangent back into ``[T, d]`` in float32 by gathers over ``token_rows``
    (:func:`_to_tokens`): a gather a row routed here and one of every token,
    where a scatter-add would move the rows one at a time."""
    def chunk(start, n, fresh, out):
        del fresh  # a row walked twice is written the same twice
        rows = jnp.where(lax.dynamic_slice_in_dim(valid, start, n)[:, None],
                         xt[lax.dynamic_slice_in_dim(token, start, n)], 0)
        return lax.dynamic_update_slice_in_dim(out, rows, start, axis=0)

    rows = token.shape[0]
    return _walk(tiles_used, rows, chunk, _fill((rows, xt.shape[1]), xt.dtype, xt, token))


def _rows_in_fwd(xt, token, valid, tiles_used, token_rows):
    return rows_in(xt, token, valid, tiles_used, token_rows), token_rows


def _rows_in_bwd(token_rows, g):
    return _to_tokens(g, token_rows), None, None, None, None


rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def rows_out(y, row_weight, token, tiles_used, token_rows):
    """The weighted sum back: ``[T, d]`` in ``y``'s type, row ``t`` the float32
    sum of ``row_weight[i] * y[i]`` over the buffer's rows ``i`` with
    ``token[i] == t`` (``row_weight`` is zero on padding), added in ascending
    ``i`` and rounded once, by gathers over ``token_rows`` (:func:`_to_tokens`,
    the weights gathered beside the rows): a gather a row routed here and one
    of every token, where a scatter-add would move the rows one at a time.

    **``y`` has to be zero past the tiles in use**, as :func:`grouped_matmul`
    leaves its result: the value does not read those rows, but the gradient
    walks the buffer in chunks of :func:`chunk_tiles` tiles up to the last
    tile in use and writes ``d_y`` over ``y``, which it needs no longer (no
    second buffer, no fill) -- ``d_y = g[token] * row_weight`` up to the last
    chunk in use and ``y``'s own rows past it, which are the zeros ``d_y``
    holds there only if ``y`` did. ``d_row_weight = sum(g[token] * y, -1)``."""
    return _to_tokens(y, token_rows, row_weight)


def _rows_out_fwd(y, row_weight, token, tiles_used, token_rows):
    return rows_out(y, row_weight, token, tiles_used, token_rows), (y, row_weight, token, tiles_used)


def _rows_out_bwd(res, g):
    y, row_weight, token, tiles_used = res

    def chunk(start, n, fresh, carry):
        d_y, d_weight = carry
        rows = g[lax.dynamic_slice_in_dim(token, start, n)].astype(jnp.float32)
        # d_y starts as y: a chunk's rows of y are read here, then written over;
        # a row walked twice holds d_y by then, and keeps its first sum
        d_row = jnp.sum(rows * lax.dynamic_slice_in_dim(d_y, start, n).astype(jnp.float32),
                        axis=-1)
        d_weight = lax.dynamic_update_slice_in_dim(
            d_weight, jnp.where(fresh, d_row, lax.dynamic_slice_in_dim(d_weight, start, n)),
            start, axis=0)
        weight = lax.dynamic_slice_in_dim(row_weight, start, n)
        d_y = lax.dynamic_update_slice_in_dim(
            d_y, (rows * weight[:, None]).astype(y.dtype), start, axis=0)
        return d_y, d_weight

    d_y, d_weight = _walk(tiles_used, y.shape[0], chunk,
                          (y, _fill(row_weight.shape, jnp.float32, y, row_weight, token, g)))
    return d_y, d_weight.astype(row_weight.dtype), None, None, None


rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


def routed_rows_bound(tokens: int, experts_per_token: int, held: int,
                      num_experts: int) -> int:
    """How many of a step's (token, chosen expert) slots the held experts'
    buffer takes: four times what uniform routing sends here
    (``tokens * k * held / E``), never more than every slot of every token.
    How they divide between the held experts is free; only their total is
    bounded, and what exceeds it is counted as overflow. Four, not two: with
    seeded initial weights the tokens' hidden states share a component, the
    router's choice is skewed by it, and the fullest of 32 shares of 8 experts
    was measured at up to 2.84 times the uniform share (PERF.md section 6, PR 27).
    The bound sizes the buffer, and the buffer's size costs memory and zero
    fills only: the grouped products and the two walks over the buffer (the
    gather into it, :func:`rows_out`'s gradient) stop with the tiles in use
    (:func:`grouped_matmul`, :func:`rows_in`, :func:`rows_out`), and the way
    back into the tokens costs what is routed, not the buffer: a gather of a
    row for each (chunk of tokens, pass) trip, ``gather_trips`` of them, about
    rows routed / :func:`token_chunk` plus a chunk count, and one gather of
    every token back into its own order. No row is scattered: XLA:TPU adds a
    scatter's rows one at a time, 0.29--0.36 us a 10 KB row, where a gather
    moves one in 33--60 ns (PERF.md section 6, PR 33)."""
    slots = tokens * experts_per_token
    return min(slots, -(-4 * slots * held // num_experts))


def buffer_rows(bound: int, held: int) -> int:
    """Rows of the buffer: the bound, a tile's padding for every held expert,
    rounded up to whole tiles."""
    rows = bound + held * ROW_TILE
    return -(-rows // ROW_TILE) * ROW_TILE


def route_top_k(scores, bias, experts_per_token: int, scaling: float,
                choice=None):
    """``(ids [T, k] int32, weights [T, k] f32)``: the ``k`` experts with the
    largest ``scores + bias`` of each token (``bias`` None: the largest
    scores), and ``scaling * s / (sum of the chosen s + 1e-20)`` from the
    scores themselves. The bias enters the choice only, and the ids carry no
    gradient. A given ``choice`` of ids replaces the top-k and leaves the
    weights to the scores."""
    if choice is None:
        _, choice = lax.top_k(scores if bias is None else scores + bias,
                              experts_per_token)
    chosen = jnp.take_along_axis(scores, choice, axis=-1)
    weights = scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), weights


def balance_bias(bias, ids, speed: float):
    """The auxiliary-loss-free balancing step (DeepSeek-V3's ``noaux_tc``): the
    bias of every expert that ``ids`` ``[T, k]`` chose more often than the mean
    goes down by ``speed``, of every one chosen less often up by it. The load
    is counted over the tokens at hand -- one chip's; a data-parallel group
    would sum the counts first."""
    load = jnp.sum(ids.reshape(-1, 1) == jnp.arange(bias.shape[0]), axis=0,
                   dtype=jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(load) - load)


def dispatch_held(ids, held: Tuple[int, int], bound: int):
    """Where each row of the held experts' buffer comes from. ``ids`` ``[T, k]``
    are every token's chosen experts, ``held = (lo, hi)`` the ids computed
    here, ``bound`` the slots taken at most (:func:`routed_rows_bound`).

    The buffer has :func:`buffer_rows` rows in tiles of ``ROW_TILE``. The
    slots that chose a held expert are laid out in expert order, every
    expert's rows starting on a tile and an expert without rows still given
    one tile, so that a tile is all one expert's. Returns ``(slot [rows],
    valid [rows], tile_expert [tiles], tiles_used [1], counters,
    token_rows)``: row ``i`` is slot ``slot[i]`` of the flattened ``[T * k]``
    choices (token ``slot // k``) where ``valid``, and padding elsewhere;
    ``counters`` are scalars -- ``rows_routed`` (slots that chose a held
    expert), ``load_max_over_mean`` (the fullest held expert's rows over the
    mean), ``rows_overflowed`` (slots past ``bound``, cut from the end of the
    expert order: their contribution is lost, and this is where it shows),
    ``tiles_in_use`` (``tiles_used``: where the grouped products and the
    loops that walk the buffer stop), ``gather_trips`` (the trips of the loop
    that adds rows back into tokens, the sum of ``token_rows.passes``);
    ``token_rows`` (:class:`TokenRows`) is the way back, from a sort of each
    token's ``k`` rows and one sort of the tokens by their count of rows."""
    lo, hi = held
    n_held = hi - lo
    rows = buffer_rows(bound, n_held)
    local = ids.reshape(-1) - lo
    mine = (local >= 0) & (local < n_held)
    key = jnp.where(mine, local, n_held)            # the others sort last
    order = jnp.argsort(key, stable=True)
    of_expert = key[:, None] == jnp.arange(n_held)  # [T * k, held]
    sizes = jnp.sum(of_expert, axis=0, dtype=jnp.int32)
    routed = jnp.sum(sizes)
    starts = jnp.cumsum(sizes) - sizes              # of each expert in ``order``
    kept = jnp.minimum(jnp.cumsum(sizes), bound) - jnp.minimum(starts, bound)
    tiles = jnp.maximum(-(-kept // ROW_TILE), 1)
    tile_ends = jnp.cumsum(tiles)
    # the expert whose tiles tile t is among: how many experts end at or before
    # it (the tiles past the last one in use stay with the last expert)
    tile_expert = jnp.minimum(
        jnp.sum(tile_ends[None, :] <= jnp.arange(rows // ROW_TILE)[:, None], axis=1),
        n_held - 1).astype(jnp.int32)
    row = jnp.arange(rows)
    expert = tile_expert[row // ROW_TILE]
    within = row - (tile_ends - tiles)[expert] * ROW_TILE
    valid = (within < kept[expert]) & (row < tile_ends[-1] * ROW_TILE)
    slot = jnp.where(valid, order[jnp.minimum(starts[expert] + within, order.shape[0] - 1)], 0)
    token_rows = _token_rows(key.reshape(ids.shape), of_expert, kept,
                             (tile_ends - tiles) * ROW_TILE, rows)
    counters = {
        "rows_routed": routed,
        "load_max_over_mean": jnp.max(sizes) * n_held / jnp.maximum(routed, 1).astype(jnp.float32),
        "rows_overflowed": routed - jnp.sum(kept),
        "tiles_in_use": tile_ends[-1],
        "gather_trips": jnp.sum(token_rows.passes),
    }
    return slot, valid, tile_expert, tile_ends[-1:].astype(jnp.int32), counters, token_rows


class RoutedExperts(nn.Module):
    """The chip's share of a top-k expert layer, with or without a shared expert.

    ``num_experts`` experts are scored (``scoring``: ``"sigmoid"`` or
    ``"softmax"``, in float32) and ``experts_per_token`` chosen by score plus
    the routing bias; ``held = (lo, hi)`` are the ids whose weights live
    here. The result is ``shared(x) + sum over the chosen experts held here
    of w_e * E_e(x)`` with ``w_e`` normalised over all the chosen ones and
    multiplied by ``scaling``: what the experts held elsewhere would add is
    left out (with ``held = (0, num_experts)`` nothing is). Every expert is a
    gated unit of width ``d_ff``, ``down(act(gate(x)) * up(x))`` with
    ``activation`` ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU); ``n_shared = 0``
    builds no shared expert. Rows routed here are gathered in expert order
    into a buffer of a static number of rows (:func:`routed_rows_bound`,
    :func:`buffer_rows`); per-expert counts are ragged inside it, so imbalance
    between experts costs nothing, and all work on the buffer is done for the
    tiles in use only: the grouped products skip the others
    (:func:`grouped_matmul`), and the gather into the buffer and the
    gradient of the experts' result (:func:`rows_in`, :func:`rows_out`) walk
    it in chunks of :func:`chunk_tiles` tiles and stop with the last tile in
    use. Past it the buffer holds its zero fill. The weighted sum back into the
    tokens and the gradient of the tokens' rows gather each token's rows over
    :class:`TokenRows`, which :func:`dispatch_held` makes once a layer. The
    gauges ``moe.buffer_tiles`` and ``moe.chunk_tiles`` are set while tracing.

    The bias is no parameter: it gets no gradient and lives in the
    ``"routing"`` collection (``model_state`` of the ``bf`` optimizers, as
    batch-norm statistics do), seeded small and not zero. Where that
    collection is mutable, a forward pass ends with the auxiliary-loss-free
    balancing update (``noaux_tc``): ``bias += bias_update_speed *
    sign(mean load - load)`` from the tokens' choices of this pass over all
    ``num_experts`` (:func:`balance_bias`); the pass itself chose with the
    bias as it came in. ``routing_bias=False`` is a router without one: no
    such variable is made, the top-k is of the scores, nothing balances.

    ``router_logits`` (``[..., num_experts]`` float32) are the router's
    outputs where the caller computes them -- a block whose router reads the
    attention's input scores before attention and hands them over; the layer
    then has no ``router`` parameter of its own. ``choice`` (``[..., k]``
    ids) forces the chosen experts, for a comparison with a reference that
    must not depend on which side of a near tie the last score fell. The
    counters of :func:`dispatch_held` are sowed under
    ``intermediates/moe_counters``, the chosen ids under
    ``intermediates/moe_choice``.
    """

    num_experts: int
    experts_per_token: int
    d_ff: int
    held: Tuple[int, int]
    n_shared: int = 1
    scoring: str = "sigmoid"
    scaling: float = 1.0
    bias_update_speed: float = 0.0
    dtype: Any = jnp.float32
    interpret: bool = False
    activation: str = "silu"
    routing_bias: bool = True

    @nn.compact
    def __call__(self, x, choice=None, router_logits=None):
        d = x.shape[-1]
        leading = x.shape[:-1]
        t = int(np.prod(leading))
        n_held = self.held[1] - self.held[0]
        k = self.experts_per_token
        act = {"silu": nn.silu, "relu": nn.relu}[self.activation]
        init = nn.initializers.lecun_normal()
        if router_logits is None:
            router = self.param("router", init, (d, self.num_experts), jnp.float32)
        # small and seeded, not zero: a forgotten bias must change the choice
        bias = self.variable(
            ROUTING, "bias", lambda: nn.initializers.normal(0.02)(
                self.make_rng("params"), (self.num_experts,), jnp.float32)
        ) if self.routing_bias else None
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        gate = self.param("gate", expert_init, (n_held, d, self.d_ff), jnp.float32)
        up = self.param("up", expert_init, (n_held, d, self.d_ff), jnp.float32)
        down = self.param("down", expert_init, (n_held, self.d_ff, d), jnp.float32)

        xt = x.reshape(t, d).astype(self.dtype)
        with jax.named_scope(SCOPE_ROUTE):
            if router_logits is None:
                logits = jnp.dot(xt.astype(jnp.float32), router,
                                 precision=lax.Precision.HIGHEST)
            else:
                logits = router_logits.reshape(t, self.num_experts)
            scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))
            ids, weights = route_top_k(
                scores, bias.value if self.routing_bias else None, k, self.scaling,
                None if choice is None else choice.reshape(t, k))
            if (self.routing_bias and self.is_mutable_collection(ROUTING)
                    and not self.is_initializing()):
                bias.value = balance_bias(bias.value, ids, self.bias_update_speed)
            bound = routed_rows_bound(t, k, n_held, self.num_experts)
            slot, valid, tile_expert, tiles_used, counters, token_rows = dispatch_held(
                ids, self.held, bound)
            metrics.gauge("moe.buffer_tiles").set(slot.shape[0] // ROW_TILE)
            metrics.gauge("moe.chunk_tiles").set(chunk_tiles(slot.shape[0]))
            token = lax.div(slot, k)                                # of each row
            gathered = rows_in(xt, token, valid, tiles_used, token_rows)  # [rows, d]
            row_weight = jnp.where(valid, weights.reshape(-1)[slot], 0.0)
        with jax.named_scope(SCOPE_EXPERTS):
            mm = lambda rows, w: grouped_matmul(  # noqa: E731
                rows, w.astype(self.dtype), tile_expert, tiles_used, self.interpret)
            y = mm(act(mm(gathered, gate)) * mm(gathered, up), down)  # [rows, d]
        with jax.named_scope(SCOPE_ROUTE):
            routed = rows_out(y, row_weight, token, tiles_used, token_rows)  # [t, d]
        if self.n_shared:
            with jax.named_scope(SCOPE_SHARED):
                shared = SwiGLU(self.n_shared * self.d_ff, self.dtype, name="shared")(xt)
        self.sow("intermediates", "moe_counters", counters)
        self.sow("intermediates", "moe_choice", ids.reshape(leading + (k,)))
        return (routed + shared if self.n_shared else routed).reshape(leading + (d,)).astype(x.dtype)


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases: the gated FFN of the dense
    layers and of the shared expert."""

    d_ff: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, dtype=self.dtype, param_dtype=jnp.float32,
                                  use_bias=False)
        h = nn.silu(dense(self.d_ff, name="gate")(x)) * dense(self.d_ff, name="up")(x)
        return dense(x.shape[-1], name="down")(h)


# ---------------------------------------------------------------------------
# The way back into the tokens: gathers over each token's rows, no scatter.
# Below the layers, which keep their lines: a compiled program holds its
# ops' source lines, and the dense cells' programs hold SwiGLU's.
# ---------------------------------------------------------------------------

class TokenRows(NamedTuple):
    """Each token's rows in the held experts' buffer, as :func:`dispatch_held`
    lays it out: the map by which :func:`rows_out`'s value and
    :func:`rows_in`'s gradient add rows back into tokens with gathers. The
    tokens are taken in an order of their own, those with the most rows here
    first, so that a chunk of them needs as many passes as its first token
    has rows."""

    row: jax.Array      # [k, T] int32: row j of the order's token p (ascending in j), -1 past its rows
    inverse: jax.Array  # [T] int32: where token t stands in that order
    passes: jax.Array   # [chunks of token_chunk(T)] int32: the rows of a chunk's first token


def _to_tokens(src, token_rows, weight=None):
    """``[T, d]`` in ``src``'s type: row ``t`` the float32 sum of ``src[i]``
    (times ``weight[i]``) over token ``t``'s rows ``i`` of the buffer, added in
    ascending ``i`` from zero and rounded once -- what a scatter-add of the
    buffer's rows in ascending order computes, bit for bit, without a scatter.

    One loop over (chunk of the ordered tokens, pass ``j``), ``passes[c]``
    trips for chunk ``c``: a trip gathers row ``j`` of each of the chunk's
    tokens (a token with fewer rows adds zero) into a float32 carry, which the
    chunk's first pass starts from zero, and writes the carry out rounded;
    one gather by ``inverse`` then puts the tokens back in their own order.
    A chunk whose tokens have no row here takes no trip."""
    row, inverse, passes = token_rows
    t = inverse.shape[0]
    n = token_chunk(t)
    ends = jnp.cumsum(passes)

    def trip(i, carry):
        acc, out = carry
        c = jnp.sum(ends <= i)
        j = i - ends[c] + passes[c]
        start = jnp.minimum(c * n, t - n)   # the last chunk may overlap the one before
        here = lax.dynamic_slice(row, (j, start), (1, n))[0]
        term = src[jnp.maximum(here, 0)].astype(jnp.float32)
        if weight is not None:
            term = term * weight[jnp.maximum(here, 0)][:, None]
        # times 1 or 0 last: a multiply-add the compiler may fuse then still adds
        # the rounded product, as the scatter does
        acc = jnp.where(j == 0, 0.0, acc) + term * (here >= 0)[:, None].astype(jnp.float32)
        return acc, lax.dynamic_update_slice_in_dim(out, acc.astype(out.dtype), start, axis=0)

    d = src.shape[1]
    init = (_fill((n, d), jnp.float32, src, row), _fill((t, d), src.dtype, src, row))
    return lax.fori_loop(0, ends[-1], trip, init)[1][inverse]


def _token_rows(key, of_expert, kept, first_row, rows: int) -> TokenRows:
    """The way back from :func:`dispatch_held`'s buffer (``rows`` rows, held
    expert ``e``'s from ``first_row[e]``, its first ``kept[e]`` slots taken)
    to the tokens. ``key`` ``[T, k]`` is each slot's held expert (``held``
    where it chose none), ``of_expert`` ``[T * k, held]`` the same one-hot. A
    slot's row is its expert's first row plus its place among the expert's
    slots, which the stable sort that laid out the buffer kept in slot order.
    Each token's rows are sorted along ``k`` (absent slots last), then the
    tokens by their count of rows, most first (a tie keeps the tokens'
    order), in one variadic sort that carries the rows; the inverse is
    counted, not sorted."""
    t, k = key.shape
    held = of_expert.shape[1]
    place = _rank(of_expert)
    e = jnp.minimum(key.reshape(-1), held - 1)
    live = (key.reshape(-1) < held) & (place < kept[e])
    by_token = lax.sort(jnp.where(live, first_row[e] + place, rows).reshape(t, k), dimension=1)
    count = jnp.sum(by_token < rows, axis=1, dtype=jnp.int32)
    negated, *columns = lax.sort((-count, *by_token.T), num_keys=1, is_stable=True)
    row = jnp.stack(columns)
    of_count = count[:, None] == jnp.arange(k + 1)  # [T, k + 1]
    more = jnp.cumsum(jnp.sum(of_count, axis=0)[::-1])[::-1] - jnp.sum(of_count, axis=0)
    n = token_chunk(t)
    return TokenRows(row=jnp.where(row < rows, row, -1).astype(jnp.int32),
                     inverse=(more[count] + _rank(of_count)).astype(jnp.int32),
                     passes=-negated[jnp.minimum(jnp.arange(-(-t // n)) * n, t - n)])


def _rank(members):
    """For each row of the one-hot ``members`` ``[n, groups]``, how many rows
    before it are of its group (0 for a row of none)."""
    return jnp.sum((jnp.cumsum(members, axis=0, dtype=jnp.int32) - 1) * members, axis=1)
