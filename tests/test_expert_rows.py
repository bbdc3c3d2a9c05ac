"""The expert layer's row movers (``parallel.expert.rows_in`` / ``rows_out``)
against the four plain expressions they replace -- a gather under a mask, the
rows' weights, the weighted product and one scatter-add, with autodiff's
transposes -- and the shape of the program they make: every pass over the
held experts' buffer sits in a loop that stops with the tiles in use.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.parallel import expert

# 1,152 tokens take 4 of 32 experts, [8, 12) held: 2,304 slots at the 4x bound,
# a buffer of 18 + 4 = 22 tiles -- two chunks of 8 and an overlapping last one
T, K, D, EXPERTS, HELD = 1152, 4, 16, 32, (8, 12)
BOUND = expert.routed_rows_bound(T, K, HELD[1] - HELD[0], EXPERTS)
ROWS = expert.buffer_rows(BOUND, HELD[1] - HELD[0])
CHUNK = expert.chunk_tiles(ROWS) * expert.ROW_TILE
ELSEWHERE = 20


def _ids(loads):
    """``[T, K]`` choices with ``loads[e]`` slots on held expert ``HELD[0] + e``,
    spread over the tokens (a token takes an expert once), the rest elsewhere."""
    ids = np.full((T, K), ELSEWHERE, np.int32)
    for e, load in enumerate(loads):
        assert load <= T
        ids[(np.arange(load) * 7 + 3 * e) % T, e] = HELD[0] + e
    return jnp.asarray(ids)


FILLS = {
    "no row routed": (0, 0, 0, 0),
    "one row": (0, 1, 0, 0),
    "exactly a chunk": (512, 256, 128, 100),
    "a chunk plus one row": (512, 256, 129, 100),
    "one expert takes most rows and another is empty": (1100, 0, 30, 7),
    "the buffer full to the bound": (640, 640, 512, 512),
    "more rows than the bound": (1000, 800, 700, 500),
}


def _plain(xt, extra, weights, cot, ids):
    slot, valid, _, _, _ = expert.dispatch_held(ids, HELD, BOUND)
    token = jax.lax.div(slot, K)
    gathered = jnp.where(valid[:, None], xt[token], 0)
    row_weight = jnp.where(valid, weights.reshape(-1)[slot], 0.0)
    y = jnp.tanh(gathered) + extra
    routed = jnp.zeros((T, D), jnp.float32).at[token].add(
        y.astype(jnp.float32) * row_weight[:, None])
    return jnp.sum(routed * cot), (gathered, routed)


def _moved(xt, extra, weights, cot, ids):
    slot, valid, _, used, _ = expert.dispatch_held(ids, HELD, BOUND)
    token = jax.lax.div(slot, K)
    gathered = expert.rows_in(xt, token, valid, used)
    row_weight = jnp.where(valid, weights.reshape(-1)[slot], 0.0)
    y = jnp.tanh(gathered) + extra
    routed = expert.rows_out(y, row_weight, token, used, T)
    return jnp.sum(routed * cot), (gathered, routed)


@pytest.mark.parametrize("fill", list(FILLS))
def test_the_movers_match_the_plain_expressions(fill):
    """Values, and gradients with respect to the tokens' rows, the experts'
    result and the routing weights. In float32 the two differ in the order of
    a token's up-to-k terms only."""
    ids = _ids(FILLS[fill])
    _, _, _, used, counters = expert.dispatch_held(ids, HELD, BOUND)
    routed = sum(FILLS[fill])
    assert int(counters["rows_routed"]) == routed
    assert int(counters["rows_overflowed"]) == max(routed - BOUND, 0)
    assert int(counters["tiles_in_use"]) == int(used[0])
    tiles = {"no row routed": 4, "one row": 4, "exactly a chunk": CHUNK // expert.ROW_TILE,
             "a chunk plus one row": CHUNK // expert.ROW_TILE + 1,
             "the buffer full to the bound": ROWS // expert.ROW_TILE - 4}
    if fill in tiles:
        assert int(used[0]) == tiles[fill]
    keys = jax.random.split(jax.random.PRNGKey(len(fill)), 4)
    xt = jax.random.normal(keys[0], (T, D), jnp.float32)
    # the experts' result: anything on padding rows, zero past the tiles in use
    extra = jnp.where(jnp.arange(ROWS)[:, None] < used[0] * expert.ROW_TILE,
                      jax.random.normal(keys[1], (ROWS, D), jnp.float32), 0)
    weights = jax.random.uniform(keys[2], (T, K), jnp.float32)
    cot = jax.random.normal(keys[3], (T, D), jnp.float32)
    grad = lambda fn: jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))  # noqa: E731
    (got, got_parts), got_grads = grad(_moved)(xt, extra, weights, cot, ids)
    (want, want_parts), want_grads = grad(_plain)(xt, extra, weights, cot, ids)
    np.testing.assert_array_equal(got_parts[0], want_parts[0])       # the same rows, the same zeros
    np.testing.assert_allclose(got_parts[1], want_parts[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip(("d_xt", "d_y", "d_weights"), got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    past = int(used[0]) * expert.ROW_TILE
    assert not np.any(np.asarray(got_parts[0][past:])) and not np.any(np.asarray(got_grads[1][past:]))


def _route_eqns(jaxpr, routed=False, in_loop=False):
    """``(equation, in_loop)`` for every equation under ``bf.moe.route`` of a
    jaxpr and of the jaxprs it calls (a called jaxpr's name stack starts anew:
    the caller's scope holds for it), with whether it lies in a ``while`` body
    or a ``cond`` branch."""
    for eqn in jaxpr.eqns:
        here = routed or expert.SCOPE_ROUTE in str(eqn.source_info.name_stack)
        if here:
            yield eqn, in_loop
        inner = in_loop or eqn.primitive.name in ("while", "cond")
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _route_eqns(sub, here, inner)


def test_every_pass_over_the_buffer_sits_in_a_loop_that_stops_with_the_tiles_in_use():
    """In ``value_and_grad`` of a toy layer, the equations under ``bf.moe.route``
    that touch a ``[rows, ...]`` matrix outside a loop body are fills and the
    four loops themselves; gathers, scatters, selects, products and sums of
    that size are in the bodies, which move a chunk. What stays ``[rows]``-sized
    outside are the index vectors of ``dispatch_held`` and the rows' weights."""
    layer = expert.RoutedExperts(num_experts=EXPERTS, experts_per_token=K, d_ff=8, held=HELD,
                                 n_shared=0, interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, D), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)

    def loss(params, x):
        return jnp.sum(layer.apply({**variables, "params": params}, x) ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(variables["params"], x)
    # [rows, 1] are a gather's index vectors
    matrix = lambda v: getattr(v.aval, "shape", ())[:1] == (ROWS,) and v.aval.size > ROWS  # noqa: E731
    outside, loops, chunked = [], 0, set()
    for eqn, in_loop in _route_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if in_loop:
            shapes = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                      if hasattr(v.aval, "shape")]
            if name in ("gather", "scatter-add") and (CHUNK, D) in shapes:
                chunked.add(name)
            continue
        loops += name == "while"
        if any(matrix(v) for v in list(eqn.invars) + list(eqn.outvars)):
            outside.append(name)
    assert loops == 4                                   # two movers, forward and gradient
    assert chunked == {"gather", "scatter-add"}
    allowed = {"broadcast_in_dim", "while", "pjit", "jit", "custom_vjp_call", "custom_jvp_call"}
    assert set(outside) <= allowed, sorted(set(outside) - allowed)
