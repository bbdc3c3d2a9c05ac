"""The names a device trace is read by: the ``bf.grad`` / ``bf.update`` /
``bf.combine`` scopes of the fused step and the three ``bf.flash.*`` kernel
scopes in the compiled programs, the programs themselves reachable through
``bf.step_programs()``, and the STEP / PLAN / BUILD host spans.

Everything here runs on the 4-device CPU mesh; the texts are the CPU
backend's, which keeps ``op_name`` metadata as the TPU's does.
"""

import gc
import json
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

import bluefog_tpu as bf
from bluefog_tpu import optimizers
from bluefog_tpu.parallel import flash
from bluefog_tpu.runtime.state import _global_state
from bluefog_tpu.runtime.timeline import Timeline

from conftest import cpu_devices

N = 4
PHASES = (optimizers.SCOPE_GRAD, optimizers.SCOPE_UPDATE, optimizers.SCOPE_COMBINE)


@pytest.fixture()
def bf4():
    bf.init(devices=cpu_devices(N), local_size=2)
    yield bf
    bf.shutdown()


def quad_loss(p, b):
    return 0.5 * jnp.sum((p["w"] @ p["v"] - b) ** 2)


def params():
    return {"w": jnp.ones((4, 4), jnp.float32), "v": jnp.ones((4,), jnp.float32)}


def one_step(opt, steps=1):
    state = opt.init(params())
    batch = jnp.ones((N, 4), jnp.float32)
    for _ in range(steps):
        state, _ = opt.step(state, batch)
    return state


def scopes_of(text):
    """The phases that some instruction's ``op_name`` of a compiled text lies under."""
    paths = re.findall(r'op_name="([^"]*)"', text)
    return {phase for phase in PHASES
            if any(re.search(r"(?<![\w.])" + re.escape(phase) + r"(?![\w.])", p) for p in paths)}


# which phases hold ops, by the optimizer and whether its first step communicates
KINDS = {
    "neighbor_allreduce": (bf.DistributedNeighborAllreduceOptimizer, {}, set(PHASES)),
    "allreduce": (bf.DistributedAllreduceOptimizer, {}, set(PHASES)),
    "hierarchical": (bf.DistributedHierarchicalNeighborAllreduceOptimizer, {}, set(PHASES)),
    "sharded_allreduce": (bf.DistributedShardedAllreduceOptimizer, {}, set(PHASES)),
    # the gradient's pmean belongs to bf.grad, and nothing mixes parameters
    "gradient_allreduce": (bf.DistributedGradientAllreduceOptimizer, {}, set(PHASES[:2])),
    # a local step of local SGD: kind "none"
    "none": (bf.DistributedNeighborAllreduceOptimizer,
             {"num_steps_per_communication": 2}, set(PHASES[:2])),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_compiled_step_has_ops_under_its_phases(bf4, kind):
    cls, kwargs, expected = KINDS[kind]
    before = bf.step_programs()
    opt = cls(optax.adam(1e-2), quad_loss, name=f"opt.{kind}", **kwargs)
    one_step(opt)
    (program,) = [p for p in bf.step_programs() if p not in before]
    assert program.name == f"opt.{kind}"
    assert program.key == next(iter(opt._step_cache))
    assert program.key[0] is (kind != "none")
    text = program.hlo_text()
    assert "ENTRY" in text and scopes_of(text) == expected


def test_a_program_is_registered_once_and_its_shapes_taken_once(bf4, monkeypatch):
    taken = []
    shape_of = optimizers._shape_of
    monkeypatch.setattr(optimizers, "_shape_of", lambda x: taken.append(1) or shape_of(x))
    # the registry is bounded and other files' tests fill it: count the new ones
    before = bf.step_programs()
    new = lambda: [p for p in bf.step_programs() if p not in before]  # noqa: E731
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.adam(1e-2), quad_loss)
    state = one_step(opt, steps=3)
    assert len(new()) == 1
    # params w, v; adam's count, mu, nu; the batch; no model state: once each
    leaves = len(jax.tree_util.tree_leaves((state.params, state.opt_state))) + 1
    assert len(taken) == leaves
    # a second plan (another edge set) is a second program; a step that hits
    # the cache walks nothing
    opt.send_neighbors = {r: [(r + 1) % N] for r in range(N)}
    opt.self_weight, opt.neighbor_weights = 0.5, {r: {(r - 1) % N: 0.5} for r in range(N)}
    state, _ = opt.step(state, jnp.ones((N, 4), jnp.float32))
    state, _ = opt.step(state, jnp.ones((N, 4), jnp.float32))
    assert len(new()) == 2 and len(taken) == 2 * leaves
    assert bf.step_programs()[-1].key != bf.step_programs()[-2].key


def test_registry_is_bounded_and_holds_no_device_array(bf4):
    opt = bf.DistributedAllreduceOptimizer(optax.sgd(0.1), quad_loss)
    state = opt.init(params())
    args = (np.zeros((1, 1), np.float32), state.params, state.opt_state,
            state.model_state, jnp.ones((N, 4), jnp.float32))
    sharding = state.params["w"].sharding
    for i in range(20):
        # a build runs the program once, which donates the state: hand it on
        out = opt._compile((True, "plan", i), None, True, args)
        args = (args[0],) + tuple(out[:3]) + (args[4],)
    programs = bf.step_programs()
    assert len(programs) == 16
    assert [p.key[-1] for p in programs] == list(range(4, 20))  # oldest first, oldest dropped
    for program in programs:
        leaves = jax.tree_util.tree_leaves(program._avals)
        assert leaves and not any(isinstance(x, jax.Array) for x in leaves)
        assert program._avals[1]["w"].sharding == sharding


def test_hlo_text_outlives_the_optimizer_and_traces_nothing_again(bf4):
    traced = []

    def loss(p, b):
        traced.append(1)
        return quad_loss(p, b)

    opt = bf.DistributedNeighborAllreduceOptimizer(optax.adam(1e-2), loss)
    state = one_step(opt, steps=2)
    assert len(traced) == 1
    program = bf.step_programs()[-1]
    del opt, state
    gc.collect()
    first, second = program.hlo_text(), program.hlo_text()
    assert len(traced) == 1 and first == second
    assert scopes_of(first) == set(PHASES)


def test_a_host_batch_lowers_as_it_ran(bf4):
    # a numpy batch has no sharding to copy: the program places it, as in step()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1), quad_loss)
    state = opt.init(params())
    opt.step(state, np.ones((N, 4), np.float32))
    assert scopes_of(bf.step_programs()[-1].hlo_text()) == set(PHASES)


def test_window_optimizers_local_step_is_registered(bf4):
    before = bf.step_programs()
    opt = bf.DistributedWinPutOptimizer(optax.sgd(0.1), quad_loss, name="opt.winput")
    try:
        one_step(opt)
    finally:
        opt.free()
    (program,) = [p for p in bf.step_programs() if p not in before]
    assert (program.name, program.key) == ("opt.winput", (False, "none"))
    assert scopes_of(program.hlo_text()) == set(PHASES[:2])


def _eqns(jaxpr, above=""):
    """(primitive name, name stack) of every equation of a jaxpr, nested ones included
    (but a kernel's body)."""
    for eqn in jaxpr.eqns:
        stack = above + "/" + str(eqn.source_info.name_stack)
        yield eqn.primitive.name, stack
        if eqn.primitive.name != "pallas_call":     # a kernel's body is the kernel's
            for sub in jax_core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, stack)


def _pallas_calls(jaxpr):
    """The name stack of every ``pallas_call`` of a jaxpr; under a flash scope
    nothing else may stand (the scope is what times the kernel)."""
    eqns = list(_eqns(jaxpr))
    strays = [(name, stack) for name, stack in eqns if name != "pallas_call"
              and {flash.SCOPE_FWD, flash.SCOPE_DKV} & set(stack.split("/"))]
    assert not strays, strays
    return [stack for name, stack in eqns if name == "pallas_call"]


def _flash_grad_calls(seq, heads=2, kv_heads=2, window=None):
    q = jnp.ones((1, seq, heads, 8), jnp.float32)
    kv = jnp.ones((1, seq, kv_heads, 8), jnp.float32)

    def loss(q, k, v):
        with jax.named_scope(optimizers.SCOPE_GRAD):
            return jnp.sum(flash.flash_attention(q, k, v, causal=True, window=window,
                                                 interpret=True) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    return list(_pallas_calls(jaxpr.jaxpr)), lowered.as_text(debug_info=True)


@pytest.fixture
def dq_budget_of_one_q_tile(monkeypatch):
    """The backward's shape rule holds dq for one 512-row q tile a call (of a
    width held in 128 lanes); what was traced under another budget is dropped."""
    monkeypatch.setattr(flash, "_DQ_VMEM_BYTES", 2 * 512 * 128 * 4)
    flash.flash_block_bwd.clear_cache()
    yield
    flash.flash_block_bwd.clear_cache()


@pytest.mark.parametrize("scope", [flash.SCOPE_FWD, flash.SCOPE_DKV])
def test_each_flash_kernel_is_one_pallas_call_under_its_scope(scope):
    calls, text = _flash_grad_calls(16)
    assert len(calls) == 2
    (stack,) = [s for s in calls if scope in s.split("/")]
    # the scope is around the kernel alone: the call is its direct child
    assert stack.rstrip("/").endswith(scope)
    assert scope in text


def test_the_group_sum_of_dk_and_dv_is_outside_the_backwards_scope():
    """Grouped-query heads under a window: still two calls, each the direct
    child of its scope, and the sum of a group's per-q-head dk/dv (a
    ``reduce_sum`` in XLA) stands under neither scope -- ``_pallas_calls``
    refuses anything but the kernel there -- so the scope times the kernel
    alone and the sum is counted with the model's other ops."""
    calls, text = _flash_grad_calls(16, heads=4, kv_heads=2, window=4)
    assert len(calls) == 2
    for scope in (flash.SCOPE_FWD, flash.SCOPE_DKV):
        (stack,) = [s for s in calls if scope in s.split("/")]
        assert stack.rstrip("/").endswith(scope)
    jaxpr = jax.make_jaxpr(lambda *a: flash.flash_block_bwd(
        *a, 0, 0, causal=True, window=4, interpret=True))(
            jnp.ones((1, 16, 4, 8)), jnp.ones((1, 16, 2, 8)), jnp.ones((1, 16, 2, 8)),
            jnp.ones((1, 16, 4, 8)), *(jnp.ones((1, 16, 4)),) * 3)
    sums = [stack for name, stack in _eqns(jaxpr.jaxpr) if name == "reduce_sum"]
    assert len(sums) == 2 and not any(flash.SCOPE_DKV in s.split("/") for s in sums)
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [
        (1, 16, 4, 8), (1, 16, 2, 8), (1, 16, 2, 8)]


def test_a_walked_flash_backward_is_one_call_a_row_block(dq_budget_of_one_q_tile):
    """Past the dq budget the same kernel runs once a row block of q, every call
    the direct child of the backward's scope: 1024 rows at 512 a call are two."""
    calls, _ = _flash_grad_calls(1024)
    assert len(calls) == 3
    assert sum(flash.SCOPE_FWD in s.split("/") for s in calls) == 1
    walked = [s for s in calls if flash.SCOPE_DKV in s.split("/")]
    assert len(walked) == 2
    assert all(s.rstrip("/").endswith(flash.SCOPE_DKV) for s in walked)


def test_step_span_holds_plan_and_build(bf4, tmp_path):
    st = _global_state()
    st.timeline = Timeline(str(tmp_path / "tl_"), use_native=False)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1), quad_loss, name="opt.spans")
    try:
        one_step(opt, steps=3)
    finally:
        path = st.timeline.path
        bf.stop_timeline()
    with open(path) as f:
        events = [e for e in json.load(f) if e.get("cat") == "opt.spans"]
    names = [e["name"] for e in events if e["ph"] == "B"]
    assert names.count("STEP") == 3 and names.count("PLAN") == 3 and names.count("BUILD") == 1
    # every PLAN and BUILD opens and closes inside a STEP: depth 2 on the lane
    # (opt.init's INIT stands before the first STEP, outside it)
    depth, inner = 0, []
    for e in events:
        if e["ph"] == "B":
            depth += 1
            inner.append((e["name"], depth))
        elif e["ph"] == "E":
            depth -= 1
    assert depth == 0
    assert all(d == (1 if name in ("STEP", "INIT") else 2) for name, d in inner)
    assert [name for name, _ in inner][:4] == ["INIT", "STEP", "PLAN", "BUILD"]
