"""Compile-time scaling evidence: AOT-lowered train steps on abstract meshes.

The reference's headline claim is >95 % scaling efficiency for ResNet-50 at
128 GPUs with dynamic Expo-2 neighbor averaging (reference:
docs/performance.rst:44-48) — a claim whose mechanism is *communication
volume*: one parameter-size transmit to one peer per step instead of a ring
allreduce. On a single-chip dev box that mechanism can still be proven at
compile time: XLA's lowered program is the ground truth for what goes on the
wire, and ``jax.sharding.AbstractMesh`` lets us lower the EXACT production
step programs (``optimizers.build_fused_step`` / ``build_sharded_step``) for
8/16/64/128-device meshes without owning a single extra chip, targeting the
real TPU lowering path.

What this module asserts (and ``tests/test_scaling.py`` locks):

  * static Expo-2 neighbor step: exactly ``ceil(log2 n)`` collective-permutes
    per parameter leaf, ZERO all-reduces — the decentralized path never
    degenerates to a global reduction at any mesh size;
  * dynamic one-peer step: exactly ONE collective-permute, zero all-reduces —
    the O(1)-per-step communication the >95 %@128 claim rests on;
  * hierarchical step: all-reduce over the intra-machine ("local") axis only,
    permutes over the machine axis only;
  * ZeRO-1 step: one reduce-scatter + one all-gather, no full all-reduce;
  * per-chip wire bytes per step, tabulated against ring allreduce
    (``SCALING.md``, regenerated by ``python -m bluefog_tpu.scaling``).

Counts are taken on the StableHLO emitted for platform "tpu" — the same
module XLA compiles for a pod — so the numbers cannot drift from the
production step code without the test failing.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from . import topology as topology_util
from .ops.plan import CombinePlan
from .optimizers import build_fused_step, build_sharded_step

_COLLECTIVES = (
    "collective_permute", "all_reduce", "all_gather", "reduce_scatter",
    "all_to_all",
)


# ---------------------------------------------------------------------------
# Plans (mirrors of the optimizer _plan() defaults, minus global state)
# ---------------------------------------------------------------------------

def uniform_weight_matrix(topo) -> np.ndarray:
    """Uniform 1/(indegree+1) combine matrix — the optimizer default when no
    user weights are given (see ``ops.neighbors._static_weight_matrix``)."""
    n = topo.number_of_nodes()
    W = np.zeros((n, n))
    for r in range(n):
        nbrs = topology_util.in_neighbor_ranks(topo, r)
        u = 1.0 / (len(nbrs) + 1)
        W[r, r] = u
        for src in nbrs:
            W[src, r] = u
    return W


def static_expo2_plan(n: int) -> CombinePlan:
    return CombinePlan(uniform_weight_matrix(
        topology_util.ExponentialTwoGraph(n)))


def dynamic_onepeer_plan(n: int, step: int = 0) -> CombinePlan:
    """The one-peer Expo-2 schedule's step-``step`` combine matrix: every
    rank sends to exactly one peer (reference topology_util.py:315-357)."""
    topo = topology_util.ExponentialTwoGraph(n)
    send = {}
    for r in range(n):
        it = topology_util.GetDynamicSendRecvRanks(topo, r)
        for _ in range(step + 1):
            send_ranks, _ = next(it)
        send[r] = send_ranks
    return CombinePlan(topology_util.dynamic_weight_matrix(n, send))


# ---------------------------------------------------------------------------
# AOT lowering
# ---------------------------------------------------------------------------

def _struct(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def lower_train_step(kind: str, n: int, *, local_size: int = 4,
                     d: int = 64, batch: int = 8, n_leaves: int = 1):
    """Lower one production train step for an abstract ``n``-device mesh.

    ``kind``: allreduce | gradient_allreduce | neighbor_static_expo2 |
    neighbor_dynamic_onepeer | hierarchical | zero1. Returns the lowered
    StableHLO text. ``n_leaves`` controls how many parameter matrices the
    model has (collective counts scale with leaves at the StableHLO level;
    XLA's collective combiner merges same-shift permutes downstream).
    """
    hier = kind == "hierarchical"
    if hier:
        if n % local_size:
            raise ValueError("local_size must divide n for hierarchical")
        m = n // local_size
        mesh = AbstractMesh((m, local_size), ("machine", "local"))
    else:
        mesh = AbstractMesh((n,), ("rank",))

    def loss(p, ms, b):
        x, y = b
        h = x
        for i in range(n_leaves):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2), (ms, {})

    opt = optax.sgd(0.1)
    params1 = {f"w{i}": jnp.zeros((d, d)) for i in range(n_leaves)}

    plan: Optional[CombinePlan] = None
    if kind == "neighbor_static_expo2":
        plan, kind = static_expo2_plan(n), "neighbor_allreduce"
    elif kind == "neighbor_dynamic_onepeer":
        plan, kind = dynamic_onepeer_plan(n), "neighbor_allreduce"
    elif hier:
        plan = static_expo2_plan(m) if m > 1 else CombinePlan(np.ones((1, 1)))

    if kind == "zero1":
        fn = build_sharded_step(mesh, loss, opt)
        opt_state1 = jax.eval_shape(
            opt.init, jnp.zeros((-(-(d * d * n_leaves) // n),)))
    else:
        fn = build_fused_step(mesh, kind, loss, opt, plan)
        opt_state1 = jax.eval_shape(opt.init, params1)

    rep = NamedSharding(mesh, P())
    stacked = NamedSharding(mesh, P(mesh.axis_names))

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: _struct((n,) + s.shape, stacked, s.dtype), tree)

    w = plan.weight_array() if plan is not None else np.zeros((1, 1), np.float32)
    args = (
        _struct(w.shape, rep),
        stack(params1),
        stack(opt_state1),
        None,
        stack((jnp.zeros((batch, d)), jnp.zeros((batch, d)))),
    )
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def lower_cp_forward(n: int, *, seq: int = 1024, heads: int = 8,
                     d_head: int = 64, batch: int = 1,
                     causal: bool = True) -> str:
    """Lower ring attention over an abstract ``n``-chip sequence mesh.

    The long-context axis of the scaling story: the ring forward is a
    ``lax.scan`` whose body holds exactly TWO collective-permutes (the K
    and V block hops), each carrying one [B, S/n, H, D] block — so
    per-chip bytes per ring step shrink linearly with the mesh while the
    schedule stays one-neighbor-hop ICI traffic. Asserted on the HLO by
    tests/test_scaling.py.
    """
    from .parallel.context import ring_attention_shard

    mesh = AbstractMesh((n,), ("rank",))
    body = functools.partial(ring_attention_shard, axis_name="rank",
                             causal=causal)
    spec = P(None, "rank")
    mapped = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    arg = _struct((batch, seq, heads, d_head), NamedSharding(mesh, spec),
                  jnp.bfloat16)
    return mapped.trace(arg, arg, arg).lower(
        lowering_platforms=("tpu",)).as_text()


def lower_moe_lm_grad(n: int, *, n_layers: int = 2, moe_every: int = 2,
                      seq: int = 256, d_model: int = 16, d_ff: int = 32,
                      vocab: int = 32) -> str:
    """Lower the expert-parallel MoE-LM loss GRADIENT for an abstract
    ``n``-expert mesh.

    The expert-parallelism axis of the scaling story: each MoE layer is
    exactly two ``all_to_all`` hops in the forward (tokens to experts,
    results back) and two more in the backward (their transposes) — a
    count INDEPENDENT of the mesh size, with per-chip all_to_all payload
    ~2 x local-token bytes (the capacity bound shrinks as 1/n while the
    expert fan-out grows as n — exactly until capacity floors at one
    token per expert-peer pair, i.e. while n <= capacity_factor * seq;
    ``seq`` here keeps that true through n=128). Asserted on the lowered
    HLO by tests/test_scaling.py.
    """
    from .models import MoETransformerLM
    from .parallel.expert import ep_lm_init, ep_lm_loss_fn, moe_param_specs

    mesh = AbstractMesh((n,), ("expert",))
    model = MoETransformerLM(
        vocab_size=vocab, num_experts=n, num_layers=n_layers,
        num_heads=2, d_model=d_model, d_ff=d_ff, moe_every=moe_every,
        expert_axis="expert")
    toks1 = jnp.zeros((1, seq), jnp.int32)
    # the production init path, shape-only (no FLOPs at 128-expert sizes)
    shapes = jax.eval_shape(
        lambda r: ep_lm_init(model, r, toks1), jax.random.PRNGKey(0))
    specs = moe_param_specs(shapes, "expert")
    params = jax.tree_util.tree_map(
        lambda s, sp: _struct(s.shape, NamedSharding(mesh, sp), s.dtype),
        shapes, specs)
    sharded = NamedSharding(mesh, P("expert"))
    batch = (_struct((n, seq), sharded, jnp.int32),
             _struct((n, seq), sharded, jnp.int32))
    fn = jax.jit(jax.grad(ep_lm_loss_fn(model, mesh)))
    return fn.trace(params, batch).lower(
        lowering_platforms=("tpu",)).as_text()


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each StableHLO collective op in a lowered module."""
    return {
        name: len(re.findall(rf"stablehlo\.{name}\b", hlo_text))
        for name in _COLLECTIVES
    }


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8E4M3FN": 1, "f8E5M2": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
}


def _tensor_bytes(ty: str) -> int:
    """bytes of one 'AxBx...xdtype' tensor-type body (scalar = bare dtype)."""
    parts = ty.strip().split("x")
    dtype = parts[-1]
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown element type in tensor<{ty}>")
    n = 1
    for d in parts[:-1]:
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-chip bytes ENTERING each collective op class, measured from the
    lowered module's operand types — the program's own statement of what
    each chip hands to the interconnect per step (attrs like
    source_target_pairs carry their own tensor types, so parsing anchors on
    the op's trailing function-type signature ``: (operands) -> results``).
    """
    sig_re = re.compile(r":\s*\(((?:tensor<[^>]+>(?:,\s*)?)+)\)\s*->")
    any_collective = re.compile(
        "|".join(rf"stablehlo\.{n}\b" for n in _COLLECTIVES))
    out = {name: 0 for name in _COLLECTIVES}
    for name in _COLLECTIVES:
        for m in re.finditer(rf"stablehlo\.{name}\b", hlo_text):
            # The op's trailing function type ``: (operands) -> results`` is
            # the first such pattern after the op name: attribute types
            # (``dense<...> : tensor<...>``) have no parenthesized operand
            # list, and reduction-region bodies (all_reduce/reduce_scatter
            # span multiple lines) only contain unparenthesized ``: tensor``
            # annotations. Bound the search at the NEXT collective op
            # (collectives never nest inside each other's regions) so a
            # future printer change fails LOUD instead of silently summing
            # some downstream op's operands.
            nxt = any_collective.search(hlo_text, m.end())
            end = nxt.start() if nxt else len(hlo_text)
            sig = sig_re.search(hlo_text, m.start(), end)
            if sig is None:
                raise ValueError(
                    f"could not parse operand types of {name} at offset "
                    f"{m.start()}")
            for ty in re.findall(r"tensor<([^>]+)>", sig.group(1)):
                out[name] += _tensor_bytes(ty)
    return out


def count_step_collectives(kind: str, n: int, **kw) -> Dict[str, int]:
    return collective_counts(lower_train_step(kind, n, **kw))


_HLO_ENTRY = re.compile(r"^ENTRY\b.*$", re.M)
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(?:\([^=]*?\)|\S+)\s+([a-z][\w\-]*)\((.*)$")
_HLO_FIRST_OPERAND = re.compile(r"\s*(?:[^%(),]*\s)?%?([\w.\-]+)")
# ops that hand a buffer on without computing it: a permute's operand is
# followed through them to the instruction that wrote the value
_HLO_PASS_THROUGH = ("get-tuple-element", "bitcast", "copy", "copy-start",
                     "copy-done")


def permute_start_slack(hlo_text: str) -> Dict[str, object]:
    """Where the scheduler put each ``collective-permute-start`` of a compiled
    (scheduled) HLO module's entry computation.

    ``slack``: for every start, in schedule order, the instructions between
    the one that produced its operand (followed back through
    ``get-tuple-element`` / ``bitcast`` / copies) and the start itself -- 1 is
    "right behind its producer", hundreds mean the start was held back.
    ``max_in_flight``: the most permutes started and not yet done at any point
    of the schedule. A step whose permutes may start where their leaf's update
    ends has ``max_in_flight == starts`` and small slacks; one that is held to
    a cap of k in flight has ``max_in_flight == k`` and its starts sunk behind
    the backward (docs/timeline.md, "Reading a step's schedule")."""
    position: Dict[str, int] = {}
    opcode: Dict[str, str] = {}
    operand: Dict[str, str] = {}
    slack, in_flight, most = [], 0, 0
    entry = _HLO_ENTRY.search(hlo_text)
    body = hlo_text[entry.end():] if entry else ""
    for line in body.splitlines():
        if line.startswith("}"):
            break
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            continue
        name, op, rest = m.groups()
        first = _HLO_FIRST_OPERAND.match(rest)
        position[name], opcode[name] = len(position), op
        operand[name] = first.group(1) if first else ""
        if op == "collective-permute-start":
            src = operand[name]
            while opcode.get(src) in _HLO_PASS_THROUGH:
                src = operand[src]
            slack.append(position[name] - position.get(src, 0))
            in_flight += 1
            most = max(most, in_flight)
        elif op == "collective-permute-done":
            in_flight -= 1
    return {"starts": len(slack), "max_in_flight": most, "slack": slack}


# ---------------------------------------------------------------------------
# Wire-bytes model
# ---------------------------------------------------------------------------

def wire_bytes_per_chip(kind: str, n: int, param_bytes: float,
                        local_size: int = 4) -> Tuple[float, int]:
    """(bytes sent per chip per step, serial collective rounds).

    P = param_bytes. Ring allreduce sends 2P(n-1)/n in 2(n-1) rounds;
    a ppermute sends P in one round; ZeRO-1 is reduce_scatter + all_gather =
    allreduce volume split into 2(n-1) one-shard rounds. Hierarchical's
    figure is the combined intra-machine (2P(l-1)/l over fast ICI) plus
    machine-axis (ceil(log2 m) * P, the only DCN-facing part) traffic.
    """
    P_ = param_bytes
    if kind in ("allreduce", "gradient_allreduce", "zero1"):
        return 2 * P_ * (n - 1) / n, 2 * (n - 1)
    if kind == "neighbor_static_expo2":
        k = max(1, math.ceil(math.log2(n)))
        return k * P_, k
    if kind == "neighbor_dynamic_onepeer":
        return P_, 1
    if kind == "hierarchical":
        m = n // local_size
        k = max(0, math.ceil(math.log2(m))) if m > 1 else 0
        local = 2 * P_ * (local_size - 1) / local_size
        return local + k * P_, 2 * (local_size - 1) + k
    raise ValueError(kind)


KINDS = (
    "allreduce", "gradient_allreduce", "neighbor_static_expo2",
    "neighbor_dynamic_onepeer", "hierarchical", "zero1",
)

# ResNet-50's parameter footprint, the reference benchmark model
RESNET50_BYTES = 25_557_032 * 4


def evidence(ns=(8, 16, 64, 128), local_size: int = 4):
    """Counts + HLO-measured bytes + analytic byte model per strategy/size."""
    rows = []
    for n in ns:
        for kind in KINDS:
            txt = lower_train_step(kind, n, local_size=local_size)
            counts = collective_counts(txt)
            hlo_bytes = collective_bytes(txt)
            bytes_, rounds = wire_bytes_per_chip(
                kind, n, RESNET50_BYTES, local_size)
            rows.append({
                "kind": kind, "n": n, "counts": counts,
                "hlo_kb_in": sum(hlo_bytes.values()) / 1e3,
                "mb_per_chip_step": bytes_ / 1e6, "rounds": rounds,
            })
    return rows


def write_scaling_md(path: str = "SCALING.md", ns=(8, 16, 64, 128)) -> None:
    rows = evidence(ns)
    ar = {r["n"]: r["mb_per_chip_step"] for r in rows
          if r["kind"] == "allreduce"}
    lines = [
        "# Compile-time scaling evidence",
        "",
        "Generated by `python -m bluefog_tpu.scaling`; locked by"
        " `tests/test_scaling.py`.",
        "",
        "The production train-step programs (`optimizers.build_fused_step` /"
        " `build_sharded_step`) AOT-lowered for TPU over abstract meshes of"
        " 8-128 devices. Collective-op counts come from the lowered StableHLO"
        " — the program XLA compiles for a pod — and the byte model follows"
        " from them (one ppermute = one parameter-size transmit per chip;"
        " ring allreduce = 2P(n-1)/n). Parameter volume: ResNet-50"
        f" ({RESNET50_BYTES / 1e6:.1f} MB f32), the reference benchmark"
        " model.",
        "",
        "| strategy | n | permute | all_reduce | all_gather | reduce_scatter"
        " | HLO KB in | MB/chip/step | serial rounds | vs allreduce |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        c = r["counts"]
        rel = r["mb_per_chip_step"] / ar[r["n"]]
        lines.append(
            f"| {r['kind']} | {r['n']} | {c['collective_permute']} |"
            f" {c['all_reduce']} | {c['all_gather']} | {c['reduce_scatter']} |"
            f" {r['hlo_kb_in']:.1f} | {r['mb_per_chip_step']:.1f} |"
            f" {r['rounds']} | {rel:.2f}x |")
    lines += [
        "",
        "Reading the table:",
        "",
        "- **HLO KB in** is measured FROM the lowered program itself: the"
        " summed operand bytes each chip hands to its collective ops per"
        " step (the table's toy model is a single 64x64 f32 leaf = 16.4 KB;"
        " the MB column scales that shape-independent structure to"
        " ResNet-50's 102 MB). Dynamic one-peer hands exactly one parameter"
        " copy to one permute; allreduce/ZeRO hand one copy to their"
        " reduction ops; static Expo-2 hands log2(n) copies.",
        "- **Dynamic one-peer Expo-2** (the reference's flagship schedule and"
        " this framework's default in `examples/benchmark.py`) moves ONE"
        " parameter copy per chip per step in ONE serial round at every mesh"
        " size — half the bytes of ring allreduce and a round count that does"
        " not grow with n. This O(1) per-step cost is the mechanism behind"
        " the reference's >95 % scaling efficiency at 128 GPUs"
        " (reference docs/performance.rst:44-48); the lowered HLO shows this"
        " framework's step has exactly that communication structure.",
        "- **Static Expo-2** pays ceil(log2 n) permutes — still round-count"
        " O(log n) vs allreduce's O(n), with zero all-reduce ops in the"
        " lowered program at any size.",
        "- **Hierarchical** keeps its all-reduce strictly on the"
        " intra-machine axis (fast ICI) and crosses machines only via"
        " machine-axis permutes — the DCN-facing traffic is the permute"
        " column alone.",
        "- **ZeRO-1** matches allreduce bytes (reduce_scatter + all_gather)"
        " while holding 1/n optimizer state per chip.",
        "",
        "Counts are per parameter leaf (the table uses a single-leaf model;"
        " XLA's collective-combiner pass merges same-shift permutes across"
        " leaves downstream, and `tests/test_scaling.py` asserts per-leaf"
        " linearity at the StableHLO level).",
        "",
        "**Long-context axis** (`lower_cp_forward`): the ring-attention"
        " forward's scan body lowers to exactly TWO collective-permutes (the"
        " K and V block hops — one-neighbor ICI traffic), zero all-reduces,"
        " at every mesh size; each hop carries one [B, S/n, H, D] block, so"
        " per-chip bytes per ring step shrink linearly with the mesh"
        " (asserted on the HLO for n = 8-128).",
        "",
        "**Expert-parallel axis** (`lower_moe_lm_grad`): the MoE-LM"
        " gradient lowers to exactly 2 all_to_all per Switch layer forward"
        " + 2 in the backward (their transposes), zero collective-permutes"
        " — a count independent of mesh size, and the per-chip all_to_all"
        " payload stays ~2x the local token bytes (capacity shrinks as 1/n"
        " while expert fan-out grows as n — the GShard property, holding"
        " until capacity floors at one token, i.e. while n <="
        " capacity_factor x local tokens; asserted on the HLO for"
        " n = 8-128).",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    write_scaling_md()
    print("wrote SCALING.md")
