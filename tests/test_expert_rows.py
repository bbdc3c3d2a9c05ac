"""The expert layer's row movers (``parallel.expert.rows_in`` / ``rows_out``)
against the four plain expressions they replace -- a gather under a mask, the
rows' weights, the weighted product and one scatter-add, with autodiff's
transposes -- and the shape of the program they make: every pass over the
held experts' buffer sits in a loop that stops with the tiles in use, and
nothing under ``bf.moe.route`` scatters rows into ``[T, d]``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.parallel import expert

# 1,152 tokens take 4 of 32 experts, [8, 12) held: 2,304 slots at the 4x bound,
# a buffer of 18 + 4 = 22 tiles -- two chunks of 8 and an overlapping last one;
# the tokens are two chunks of 1,024, the last overlapping too
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
    # 97 tokens with none of their slots held, 330 with one, 65 with all four
    "tokens with no, one and all k slots held": (560, 560, 560, 560),
    "every token holds one slot": (T, 0, 0, 0),
}


def _experts(gathered, extra):
    """Stands for the experts' result, in the rows' type: a kernel's output is
    written out in it, so a barrier keeps XLA from carrying it in float32 into
    what follows (excess precision) on one side and not the other."""
    return jax.lax.optimization_barrier(jnp.tanh(gathered) + extra)


def _plain(xt, extra, weights, cot, ids):
    slot, valid, _, _, _, _ = expert.dispatch_held(ids, HELD, BOUND)
    token = jax.lax.div(slot, K)
    gathered = jnp.where(valid[:, None], xt[token], 0)
    row_weight = jnp.where(valid, weights.reshape(-1)[slot], 0.0)
    y = _experts(gathered, extra)
    routed = jnp.zeros((T, D), jnp.float32).at[token].add(
        y.astype(jnp.float32) * row_weight[:, None]).astype(y.dtype)
    return jnp.sum(routed.astype(jnp.float32) * cot), (gathered, routed)


def _moved(xt, extra, weights, cot, ids):
    slot, valid, _, used, _, token_rows = expert.dispatch_held(ids, HELD, BOUND)
    token = jax.lax.div(slot, K)
    gathered = expert.rows_in(xt, token, valid, used, token_rows)
    row_weight = jnp.where(valid, weights.reshape(-1)[slot], 0.0)
    y = _experts(gathered, extra)
    routed = expert.rows_out(y, row_weight, token, used, token_rows)
    return jnp.sum(routed.astype(jnp.float32) * cot), (gathered, routed)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fill", list(FILLS))
def test_the_movers_match_the_plain_expressions(fill, dtype):
    """Values, and gradients with respect to the tokens' rows, the experts'
    result and the routing weights. The routed value and the tokens' rows'
    gradient equal the plain scatter-add bit for bit, in float32 and in
    bfloat16 rows: the movers add a token's terms in the scatter's order
    (ascending buffer row, from zero) and round once."""
    dtype = jnp.dtype(dtype)
    ids = _ids(FILLS[fill])
    slot, valid, _, used, counters, token_rows = expert.dispatch_held(ids, HELD, BOUND)
    routed = sum(FILLS[fill])
    assert int(counters["rows_routed"]) == routed
    assert int(counters["rows_overflowed"]) == max(routed - BOUND, 0)
    assert int(counters["tiles_in_use"]) == int(used[0])
    tiles = {"no row routed": 4, "one row": 4, "exactly a chunk": CHUNK // expert.ROW_TILE,
             "a chunk plus one row": CHUNK // expert.ROW_TILE + 1,
             "the buffer full to the bound": ROWS // expert.ROW_TILE - 4}
    if fill in tiles:
        assert int(used[0]) == tiles[fill]
    keys = jax.random.split(jax.random.PRNGKey(len(fill)), 5)
    xt = jax.random.normal(keys[0], (T, D), jnp.float32).astype(dtype)
    # the experts' result: anything on padding rows, zero past the tiles in use
    extra = jnp.where(jnp.arange(ROWS)[:, None] < used[0] * expert.ROW_TILE,
                      jax.random.normal(keys[1], (ROWS, D), jnp.float32), 0).astype(dtype)
    weights = jax.random.uniform(keys[2], (T, K), jnp.float32)
    cot = jax.random.normal(keys[3], (T, D), jnp.float32)
    grad = lambda fn: jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))  # noqa: E731
    (got, got_parts), got_grads = grad(_moved)(xt, extra, weights, cot, ids)
    (want, want_parts), want_grads = grad(_plain)(xt, extra, weights, cot, ids)
    np.testing.assert_array_equal(got_parts[0], want_parts[0])       # the same rows, the same zeros
    np.testing.assert_array_equal(got_parts[1], want_parts[1])       # the same sums, to the bit
    np.testing.assert_array_equal(got, want)
    # computed in float32 on both sides, whatever the rows' type
    for name, a, b in zip(("d_y", "d_weights"), got_grads[1:], want_grads[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    past = int(used[0]) * expert.ROW_TILE
    assert not np.any(np.asarray(got_parts[0][past:])) and not np.any(np.asarray(got_grads[1][past:]))
    # d_xt: the rows' cotangent scatter-added into [T, D] in float32 and rounded once
    token = jax.lax.div(slot, K)
    g = jax.random.normal(keys[4], (ROWS, D), jnp.float32).astype(dtype)
    _, back = jax.vjp(lambda x: expert.rows_in(x, token, valid, used, token_rows), xt)
    want_dx = jnp.zeros((T, D), jnp.float32).at[token].add(
        jnp.where(valid[:, None], g, 0).astype(jnp.float32)).astype(dtype)
    np.testing.assert_array_equal(jax.jit(back)(g)[0], want_dx)
    if dtype == jnp.float32:   # autodiff's transpose of the plain gather is that scatter
        np.testing.assert_array_equal(got_grads[0], want_grads[0])


@pytest.mark.parametrize("fill", list(FILLS))
def test_gather_trips_count_the_passes_of_the_ordered_chunks(fill):
    """``gather_trips`` against numpy: the tokens ordered by their rows in the
    buffer, most first, cut into chunks of ``token_chunk(T)`` (the last one
    overlapping), each taking as many passes as its first token has rows."""
    slot, valid, _, _, counters, token_rows = expert.dispatch_held(_ids(FILLS[fill]), HELD, BOUND)
    token = np.asarray(slot)[np.asarray(valid)] // K
    count = np.bincount(token, minlength=T)
    ordered = np.sort(count)[::-1]
    n = expert.token_chunk(T)
    starts = [min(c * n, T - n) for c in range(-(-T // n))]
    assert len(starts) == 2 and starts[-1] == T - n          # an overlapping last chunk
    assert int(counters["gather_trips"]) == int(ordered[starts].sum())
    np.testing.assert_array_equal(token_rows.passes, ordered[starts])
    # the map: each token's rows ascending, then -1; the order and its inverse
    rows = np.asarray(token_rows.row)[:, np.asarray(token_rows.inverse)].T     # [T, K], by token
    for t in range(T):
        mine = np.flatnonzero(np.asarray(valid) & (np.asarray(slot) // K == t))
        np.testing.assert_array_equal(rows[t], np.concatenate([mine, np.full(K - len(mine), -1)]))
    assert sorted(np.asarray(token_rows.inverse).tolist()) == list(range(T))


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
    four loops themselves; gathers, selects, products and sums of that size
    are in the bodies, which move a chunk of rows or of tokens. What stays
    ``[rows]``-sized outside are the index vectors of ``dispatch_held`` and the
    rows' weights. No scatter of ``[*, D]`` rows is left anywhere under the
    scope, and the one gather of ``[T, D]`` outside a loop is each direction's
    return of the tokens to their own order."""
    layer = expert.RoutedExperts(num_experts=EXPERTS, experts_per_token=K, d_ff=8, held=HELD,
                                 n_shared=0, interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, D), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)

    def loss(params, x):
        return jnp.sum(layer.apply({**variables, "params": params}, x) ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(variables["params"], x)
    # [rows, 1] are a gather's index vectors
    matrix = lambda v: getattr(v.aval, "shape", ())[:1] == (ROWS,) and v.aval.size > ROWS  # noqa: E731
    rows_of_d = lambda v: len(getattr(v.aval, "shape", ())) == 2 and v.aval.shape[1] == D  # noqa: E731
    outside, loops, gathered, unpermuted, scattered = [], 0, set(), 0, []
    for eqn, in_loop in _route_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        operands = list(eqn.invars) + list(eqn.outvars)
        if name.startswith("scatter") and any(rows_of_d(v) for v in operands):
            scattered.append(name)
        if in_loop:
            if name == "gather" and rows_of_d(eqn.outvars[0]):
                gathered.add(eqn.outvars[0].aval.shape)
            continue
        loops += name == "while"
        unpermuted += name == "gather" and rows_of_d(eqn.outvars[0])
        if name == "gather" and rows_of_d(eqn.outvars[0]):
            assert eqn.outvars[0].aval.shape == (T, D)
        if any(matrix(v) for v in operands):
            outside.append(name)
    assert loops == 4                           # two movers, forward and gradient
    assert not scattered, scattered
    assert gathered == {(CHUNK, D), (expert.token_chunk(T), D)}
    assert unpermuted == 2                      # rows_out's value, rows_in's gradient
    allowed = {"broadcast_in_dim", "while", "pjit", "jit", "custom_vjp_call", "custom_jvp_call"}
    assert set(outside) <= allowed, sorted(set(outside) - allowed)
