"""``bf.models.ConfigLM`` -- latent attention through the flash kernels, the
chip's share of a top-k expert layer, the MTP module -- against the plain
float32 reference the benchmark keeps (``benchmark/families/mla_moe_lm.py``,
which shares no code with ``bluefog_tpu``), at toy widths on the CPU with the
Pallas kernels interpreted.
"""

import dataclasses
import importlib.util
import json
import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu.models import (ConfigLM, LMConfig, config_lm, label_cross_entropy, moe_choices,
                                moe_counters, next_token_loss)
from bluefog_tpu.runtime import metrics
from bluefog_tpu.parallel import expert
from bluefog_tpu.parallel.context import reference_attention
from bluefog_tpu.parallel.flash import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family():
    path = os.path.join(ROOT, "benchmark", "families", "mla_moe_lm.py")
    spec = importlib.util.spec_from_file_location("mla_moe_lm_family", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


FAMILY = _family()

# the published block at toy widths: q.k 24 = 16 + 8 and v 16 (192 = 128 + 64
# and 128), one dense and two expert layers and the MTP module, experts
# [8, 16) of 32 held (share 1 of 4), top-4
TOY = {
    "hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 32000000, "rope_interleave": True, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "scoring_func": "sigmoid", "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
    "vocab_size": 64, "mtp_loss_weight": 0.3, "compute_dtype": "float32",
    "interpret_kernels": True,
    "published": {"n_routed_experts": 32}, "deployment": {"share": 1},
}
BATCH = {"sequences": 2, "seq_len": 64}


@pytest.fixture(scope="module")
def toy():
    """(cfg, params, routing biases, batch of one rank) from fixed seeds."""
    params, routing = FAMILY.init(TOY, BATCH, jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(
        lambda x: x[0], FAMILY.make_batch(TOY, BATCH, jax.random.PRNGKey(1), 1))
    return TOY, params, routing, batch


def _rel(got, want):
    """max |got - want| over max |want|, over a whole tree."""
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    return max(float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-30))
               for g, w in zip(got, want))


def _system_loss(cfg, params, routing, batch):
    return FAMILY.loss(cfg)[0](params, routing, batch)[0]


def _system(cfg, params, routing, batch):
    return jax.value_and_grad(partial(_system_loss, cfg))(params, routing, batch)


def _plain(cfg, params, routing, batch):
    return jax.value_and_grad(partial(FAMILY.plain_loss, cfg))(params, routing, batch)


# Both sides are float32 at the highest matmul precision and differ in the
# order of their sums only (online softmax in tiles, rows gathered by expert):
# the loss agrees to 1e-6 and every gradient leaf to 1e-4 of its largest
# element. Each fault below moves the loss by ten tolerances or more (the
# least, bfloat16 parameters, by 3.6e-5 of it).
LOSS_RTOL, GRAD_RTOL = 2e-6, 2e-4


def test_loss_and_gradients_match_the_plain_reference(toy):
    cfg, params, routing, batch = toy
    loss, grads = _system(cfg, params, routing, batch)
    want_loss, want_grads = _plain(cfg, params, routing, batch)
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        assert not name.endswith("['bias']")   # the routing bias is no parameter
        assert _rel(got, want) <= GRAD_RTOL, (name, _rel(got, want))
    # the shared embedding and head get a contribution from the MTP module
    only_main = jax.grad(partial(_system_loss, {**cfg, "mtp_loss_weight": 0.0}))(
        params, routing, batch)
    for leaf in ("embed", "lm_head"):
        assert _rel(grads[leaf], only_main[leaf]) > 1e-2


def _bf16_parameters(cfg, params, routing):
    return cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params), routing


def _no_bias(cfg, params, routing):
    return cfg, params, jax.tree_util.tree_map(jnp.zeros_like, routing)


def _rope_by_halves(cfg, params, routing):
    return {**cfg, "rope_interleave": False}, params, routing


def _dropped_tokens(cfg, params, routing):
    """A buffer that takes 32 of the ~128 slots routed here: the rest is dropped."""
    return {**cfg, "_bound": 32}, params, routing


@pytest.mark.parametrize("fault", [_bf16_parameters, _no_bias, _rope_by_halves,
                                   _dropped_tokens])
def test_the_comparison_is_tight_enough_to_see(fault, toy, monkeypatch):
    """What the tolerances must catch: the system computes with the fault, the
    reference without."""
    cfg, params, routing, batch = toy
    want = float(FAMILY.plain_loss(cfg, params, routing, batch))
    faulty = fault(cfg, params, routing)
    if "_bound" in faulty[0]:
        monkeypatch.setattr(expert, "routed_rows_bound", lambda *a: faulty[0]["_bound"])
    got = float(_system_loss(*faulty, batch))
    assert abs(got - want) > 10 * LOSS_RTOL * want, (got, want)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_flash_with_a_wider_qk_than_v_matches_dense_attention(what, causal):
    """d_qk 24 and d_v 16: the toy of 192 and 128. The scale is 1/sqrt(d_qk)."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k = (jax.random.normal(key, (2, 64, 2, 24), jnp.float32) for key in keys[:2])
    v = jax.random.normal(keys[2], (2, 64, 2, 16), jnp.float32)
    flash = partial(flash_attention, causal=causal, interpret=True)
    dense = partial(reference_attention, causal=causal)
    if what == "forward":
        got, want = flash(q, k, v), dense(q, k, v)
        assert got.shape == want.shape == (2, 64, 2, 16)
    else:
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * jnp.cos(f(*a))), argnums=(0, 1, 2))(
            q, k, v) for f in (flash, dense))
        assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert _rel(got, want) <= 2e-5


def _layer(held, experts=32, top=4, d=32, d_ff=16, **kw):
    return expert.RoutedExperts(num_experts=experts, experts_per_token=top, d_ff=d_ff,
                                held=held, scaling=2.5, interpret=True, **kw)


def _layer_variables(key, experts=32, d=32):
    """Parameters and routing bias of the uncut layer (all experts held)."""
    return _layer((0, experts)).init(key, jnp.zeros((1, 8, d)))


def _share_of(variables, lo, hi):
    params = variables["params"]
    return {**variables, "params": {
        **params, **{name: params[name][lo:hi] for name in ("gate", "up", "down")}}}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all 16 shares, with the
    shared expert counted once, are the uncut reference's layer output."""
    variables = _layer_variables(jax.random.PRNGKey(2))
    params = variables["params"]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 32), jnp.float32)
    uncut_cfg = {**TOY, "n_routed_experts": 32, "deployment": {"share": 0}}
    want, _ = FAMILY._expert_layer(uncut_cfg, params, variables["routing"]["bias"], h[0])
    shared = expert.SwiGLU(16).apply({"params": params["shared"]}, h)
    total = shared
    for r in range(16):
        out = _layer((2 * r, 2 * r + 2)).apply(_share_of(variables, 2 * r, 2 * r + 2), h)
        total = total + (out - shared)
    assert _rel(total[0], want) <= 1e-5
    # and one share alone is not it
    assert _rel(out[0], want) > 1e-2


def test_the_bias_enters_the_choice_and_not_the_weights():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(4), (16, 32)))
    none = jnp.zeros((32,))
    ids, weights = expert.route_top_k(scores, none, 4, 2.5)
    lifted = none.at[5].set(10.0)
    ids_b, weights_b = expert.route_top_k(scores, lifted, 4, 2.5)
    assert np.all(np.any(np.asarray(ids_b) == 5, axis=1))       # every token now takes 5
    assert not np.all(np.any(np.asarray(ids) == 5, axis=1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(ids_b), axis=1)
    np.testing.assert_allclose(weights_b, 2.5 * chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-6)                        # from s, not from s + b
    np.testing.assert_allclose(np.asarray(weights_b).sum(1), 2.5, rtol=1e-6)
    _, forced = expert.route_top_k(scores, lifted, 4, 2.5, choice=ids)
    np.testing.assert_array_equal(forced, weights)               # a given choice ignores it


def _masked_layer(params, h, ids, weights, lo, hi):
    """The routed part, each held expert on every token under its mask."""
    out = jnp.zeros_like(h)
    for e in range(lo, hi):
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = (jax.nn.silu(h @ params["gate"][e - lo]) * (h @ params["up"][e - lo])) \
            @ params["down"][e - lo]
        out = out + mask[:, None] * y
    return out


@pytest.mark.parametrize("load", ["one expert takes most rows", "more rows than the buffer"])
def test_ragged_loads_drop_nothing_and_an_overflow_is_counted(load):
    """128 tokens, top-4 of 32: with experts [8, 16) held the buffer takes
    every slot (512); with [8, 12) held, four times the uniform share, 256."""
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 32), jnp.float32)
    if load == "one expert takes most rows":
        # every token takes expert 9, every fourth also 12; the rest elsewhere
        lo, hi, bound = 8, 16, 512
        choice = jnp.tile(jnp.array([9, 0, 1, 2]), (128, 1)).at[::4, 1].set(12)
        routed = 128 + 32
    else:
        lo, hi, bound = 8, 12, 256
        choice = jnp.tile(jnp.array([8, 9, 10, 11]), (128, 1))   # 512 slots, all held
        routed = 512
    assert expert.routed_rows_bound(128, 4, hi - lo, 32) == bound
    variables = _share_of(_layer_variables(jax.random.PRNGKey(5)), lo, hi)
    params = variables["params"]
    out, state = _layer((lo, hi)).apply(variables, h, choice[None], mutable=["intermediates"])
    counters = state["intermediates"]["moe_counters"][0]
    assert int(counters["rows_routed"]) == routed
    assert int(counters["rows_overflowed"]) == max(routed - bound, 0)
    scores = jax.nn.sigmoid(h[0] @ params["router"])
    _, weights = expert.route_top_k(scores, variables["routing"]["bias"], 4, 2.5, choice=choice)
    if routed <= bound:
        assert float(counters["load_max_over_mean"]) == pytest.approx(128 * 8 / 160)
    else:
        # expert order: 8 and 9 fit whole, 10 and 11 are cut; nothing else is touched
        weights = jnp.where(choice < 10, weights, 0.0)
        assert np.all(np.isfinite(np.asarray(out)))
    want = _masked_layer(params, h[0], choice, weights, lo, hi) \
        + expert.SwiGLU(16).apply({"params": params["shared"]}, h[0])
    assert _rel(out[0], want) <= 1e-5


def test_grouped_matmul_gradients_with_an_empty_expert():
    """Rows of experts 0 and 2 of three; expert 1 has none and a zero gradient."""
    ids = jnp.array([[0], [2], [2], [0], [2]])
    slot, valid, tile_expert, used, _, _ = expert.dispatch_held(ids, (0, 3), bound=5)
    assert tile_expert.tolist()[:3] == [0, 1, 2] and int(used[0]) == 3
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (3, 16, 8), jnp.float32)
    rows = jnp.where(valid[:, None], x[slot], 0)

    def grouped(rows, w):
        return jnp.sum(jnp.sin(expert.grouped_matmul(rows, w, tile_expert, used, True)))

    def dense(rows, w):
        every = jnp.einsum("rk,ekn->ern", rows, w)
        mine = every[tile_expert[jnp.arange(rows.shape[0]) // expert.ROW_TILE],
                     jnp.arange(rows.shape[0])]
        in_use = jnp.arange(rows.shape[0]) < used[0] * expert.ROW_TILE  # padding rows too
        return jnp.sum(jnp.sin(jnp.where(in_use[:, None], mine, 0.0)))

    got, want = (jax.grad(f, argnums=(0, 1))(rows, w) for f in (grouped, dense))
    assert _rel(got, want) <= 1e-5
    assert not np.any(np.asarray(got[1][1]))


def test_free_running_choices_agree_with_the_reference_and_forcing_changes_nothing(toy):
    """In float32 both sides pick the same experts, so forcing the reference's
    choice on the system leaves its logits as they were; the check on the chip
    (bfloat16 against float32) forces it, and prints this share."""
    cfg, params, routing, batch = toy
    tokens = batch[0][:1]
    net = FAMILY.model(cfg)
    variables = {"params": params, "routing": routing}
    (logits, mtp), state = net.apply(variables, tokens, mutable=["intermediates"])
    want, choices = FAMILY.plain_forward(cfg, params, routing, tokens, positions=64)
    ours = moe_choices(state["intermediates"])
    assert len(ours) == len(choices) == 3
    for a, b in zip(ours, choices):
        assert float(jnp.mean((a[..., :, None] == b[..., None, :]).any(-1))) >= 0.99
    forced, forced_mtp = net.apply(variables, tokens, choices=choices)
    assert _rel(jnp.stack([forced[0], forced_mtp[0][0]]), want) <= 1e-5
    assert _rel(forced, logits) <= 1e-5
    assert int(moe_counters(state["intermediates"])["rows_overflowed"]) == 0


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_a_forward_pass_ends_with_the_balancing_step_where_routing_is_mutable(scoring):
    """``bias += speed * sign(mean load - load)`` over all 32 experts from this
    pass's choices, which were made with the bias as it came in; without
    ``mutable`` the pass leaves no new bias."""
    layer = _layer((8, 16), scoring=scoring, bias_update_speed=0.25)
    variables = _share_of(_layer_variables(jax.random.PRNGKey(14)), 8, 16)
    h = jax.random.normal(jax.random.PRNGKey(15), (1, 48, 32), jnp.float32)
    out, state = layer.apply(variables, h, mutable=["intermediates", "routing"])
    before = np.asarray(variables["routing"]["bias"])
    score = jax.nn.sigmoid if scoring == "sigmoid" else partial(jax.nn.softmax, axis=-1)
    want_ids, _ = expert.route_top_k(score(h[0] @ variables["params"]["router"]), before, 4, 2.5)
    ids = np.asarray(state["intermediates"]["moe_choice"][0])[0]
    np.testing.assert_array_equal(np.sort(ids, axis=1), np.sort(np.asarray(want_ids), axis=1))
    load = np.bincount(ids.reshape(-1), minlength=32)
    assert load.sum() == 48 * 4 and load.max() > 6 > load.min()      # mean 6: both signs
    np.testing.assert_allclose(state["routing"]["bias"],
                               before + 0.25 * np.sign(6.0 - load), rtol=0, atol=1e-7)
    frozen, state = layer.apply(variables, h, mutable=["intermediates"])
    assert "routing" not in state
    np.testing.assert_array_equal(frozen, out)


@pytest.mark.parametrize("speed", [0.0, 0.001])
def test_the_bias_moves_by_the_balancing_rule_alone_through_opt_step(speed, bf8):
    """Three steps of ``DistributedNeighborAllreduceOptimizer(adam)``: the
    routing bias is model state, so Adam never sees it; every step moves each
    of its elements by ``speed`` up or down (not at all at speed 0), every
    parameter moves, and ``metrics["aux"]`` carries the expert layers' counters."""
    cfg = dataclasses.replace(FAMILY.lm_config(TOY), num_hidden_layers=2,
                              bias_update_speed=speed)
    net = ConfigLM(cfg, interpret=True)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (8, 1, 32), 0, cfg.vocab_size)
    batch = (tokens, jnp.roll(tokens, -1, axis=2), jnp.roll(tokens, -2, axis=2))
    variables = net.init(jax.random.PRNGKey(11), tokens[0])
    params, routing = variables["params"], variables["routing"]
    assert not any("bias" in jax.tree_util.keystr(path)
                   for path, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.adam(1e-2), next_token_loss(net), with_model_state=True)
    state = opt.init(params, model_state=routing)
    for _ in range(3):
        state, metrics = opt.step(state, batch)
    after = bf.optimizers.unreplicate(jax.device_get(state.params))
    for (path, leaf), before in zip(jax.tree_util.tree_flatten_with_path(after)[0],
                                    jax.tree_util.tree_leaves(params)):
        assert np.any(np.asarray(leaf) != np.asarray(before)), jax.tree_util.keystr(path)
    biases = jax.tree_util.tree_leaves(jax.device_get(state.model_state))
    assert len(biases) == 2                       # one expert layer and the MTP block
    for leaf, before in zip(biases, jax.tree_util.tree_leaves(routing)):
        assert leaf.shape == (8, 32) and np.any(np.asarray(before))
        moved = (leaf - np.asarray(before)[None]) / (speed or 1.0)
        if speed:
            # 32 tokens x 4 of 32: the mean load is 4, so a step can be 0 too
            np.testing.assert_allclose(moved, np.round(moved), atol=2e-3)
            assert np.abs(moved).max() <= 3 + 2e-3 and np.any(np.abs(moved) > 0.5)
            assert np.any(moved[0] != moved[1])   # every rank by its own tokens
        else:
            assert not np.any(moved)
    aux = jax.device_get(metrics["aux"])
    assert aux["rows_routed"].shape == (8,) and np.all(aux["rows_routed"] > 0)
    assert np.all(aux["rows_overflowed"] == 0) and np.all(aux["load_max_over_mean"] >= 1.0)
    assert np.all(np.isfinite(np.asarray(metrics["loss"])))


def test_a_dense_equal_width_configuration_runs_without_experts_or_mtp():
    """The other choices of the block: equal-width heads, every layer dense."""
    cfg = LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                   intermediate_size=48, attention="equal", rope_interleave=False)
    net = ConfigLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 16), 0, 64)
    variables = net.init(jax.random.PRNGKey(13), tokens)
    params = variables["params"]
    assert set(variables) == {"params"} and set(params["layer_0"]["attn"]) == {"qkv", "o"}
    logits, state = net.apply({"params": params}, tokens, mutable=["intermediates"])
    assert logits.shape == (2, 16, 64) and moe_counters(state.get("intermediates", {})) == {}
    loss, (routing, counters) = next_token_loss(net)(
        params, {}, (tokens, jnp.roll(tokens, -1, axis=1)))
    assert np.isfinite(float(loss)) and routing == {} and counters == {}


# -- the grouped-query / window / ReGLU block (SmallThinker) ------------------
# ``benchmark/families/gqa_window_moe_lm.py`` keeps the plain float32 reference
# of this block, as ``mla_moe_lm.py`` keeps the latent one's; neither shares
# code with ``bluefog_tpu``.

def _gqa_family():
    path = os.path.join(ROOT, "benchmark", "families", "gqa_window_moe_lm.py")
    spec = importlib.util.spec_from_file_location("gqa_window_moe_lm_family", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


GQA = _gqa_family()

# the published block at toy widths: two periods of the layout (NoPE global,
# then three rope layers under a 16-token window), 4 query heads over 2 k/v
# heads of 8 (28 over 4 of 128 -- the only ratio the toy changes), experts
# [8, 16) of 32 held (share 1 of 4), top-4, ReGLU, no shared expert, no bias
with open(os.path.join(ROOT, "benchmark", "tests", "toy", "toy-smallthinker.json")) as _f:
    GQA_TOY = json.load(_f)
GQA_BATCH = {"sequences": 2, "seq_len": 64}


@pytest.fixture(scope="module")
def gqa_toy():
    """(cfg, params, batch of one rank) from fixed seeds."""
    params, state = GQA.init(GQA_TOY, GQA_BATCH, jax.random.PRNGKey(0))
    assert state == {}
    batch = jax.tree_util.tree_map(
        lambda x: x[0], GQA.make_batch(GQA_TOY, GQA_BATCH, jax.random.PRNGKey(1), 1))
    return GQA_TOY, params, batch


def _gqa_system_loss(cfg, params, batch):
    return GQA.loss(cfg)[0](params, {}, batch)[0]


def test_the_grouped_block_has_its_own_parameters_and_no_routing_state(gqa_toy):
    cfg, params, _ = gqa_toy
    variables = GQA.model(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert set(variables) == {"params"}                 # routing_bias=False: no collection
    layer = params["layer_0"]
    assert set(layer) == {"attn_norm", "router", "attn", "ffn_norm", "ffn"}
    assert set(layer["attn"]) == {"q", "k", "v", "o"}
    assert set(layer["ffn"]) == {"gate", "up", "down"}  # no router here, no shared expert
    assert layer["router"].shape == (32, 32) and layer["ffn"]["gate"].shape == (8, 32, 16)
    assert layer["attn"]["q"]["kernel"].shape == (32, 32)
    assert layer["attn"]["k"]["kernel"].shape == layer["attn"]["v"]["kernel"].shape == (32, 16)
    lm = GQA.lm_config(cfg)
    assert [lm.window_of(i) for i in range(8)] == [None, 16, 16, 16] * 2
    assert [lm.rotary_in(i) for i in range(8)] == [False, True, True, True] * 2


# Both sides are float32 at the highest matmul precision and differ in the
# order of their sums only (online softmax in tiles, rows gathered by expert,
# eight layers deep): logits to 2e-5 of the largest, the loss to 2e-6, every
# gradient leaf to 2e-4 of its largest element -- the limits of the latent
# block's test above. Each fault below moves the loss by ten tolerances or
# more (the least, bfloat16 parameters, by ~4e-5 of it).

def test_grouped_logits_loss_and_gradients_match_the_plain_reference(gqa_toy):
    cfg, params, batch = gqa_toy
    tokens = batch[0][:1]
    logits = GQA.model(cfg).apply({"params": params}, tokens)
    want, _ = GQA.plain_forward(cfg, params, tokens, positions=64)
    assert _rel(logits, want) <= 2e-5
    loss, grads = jax.value_and_grad(partial(_gqa_system_loss, cfg))(params, batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: GQA.plain_loss(cfg, p, {}, batch))(params)
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert _rel(got, want) <= GRAD_RTOL, (jax.tree_util.keystr(path), _rel(got, want))


def test_the_toy_smallthinker_through_opt_step_matches_the_plain_reference(gqa_toy, bf8):
    """``DistributedNeighborAllreduceOptimizer(sgd(1)).step`` on eight ranks
    that hold the same parameters and take the same batch: the mix of equal
    parameters is those parameters, so ``before - after`` is the gradient the
    step computed -- against ``jax.grad`` of the plain float32 loss. The limit
    is GRAD_RTOL plus the float32 rounding of ``p - g`` and of the mix
    (an ulp of a parameter of 0.5 is 6e-8; the largest gradients are 1e-2)."""
    cfg, params, batch = gqa_toy
    loss_fn, form = GQA.loss(cfg)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(1.0), loss_fn, **form)
    state = opt.init(params, model_state={})
    stacked = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (8,) + x.shape), batch)
    state, metrics = opt.step(state, stacked)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: GQA.plain_loss(cfg, p, {}, batch))(params)
    np.testing.assert_allclose(np.asarray(metrics["loss"]), float(want_loss),
                               rtol=LOSS_RTOL)
    after = jax.device_get(state.params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, before), new, want in zip(flat, jax.tree_util.tree_leaves(after),
                                         jax.tree_util.tree_leaves(want_grads)):
        for rank in (0, 7):
            got = np.asarray(before) - new[rank]
            assert _rel(got, want) <= GRAD_RTOL + 1e-4, (jax.tree_util.keystr(path), rank)
    aux = jax.device_get(metrics["aux"])
    assert np.all(aux["rows_overflowed"] == 0) and np.all(aux["rows_routed"] > 0)
    # where the row movers and the grouped products stopped, and the trips of the
    # loops that add rows back into tokens, summed over the layers
    _, state = GQA.model(cfg).apply({"params": params}, batch[0], mutable=["intermediates"])
    k, held = cfg["moe_num_active_primary_experts"], cfg["moe_num_primary_experts"]
    bound = expert.routed_rows_bound(batch[0].size, k, held,
                                     cfg["published"]["moe_num_primary_experts"])
    layers = [expert.dispatch_held(ids.reshape(-1, k), GQA.held_range(cfg), bound)
              for ids in moe_choices(state["intermediates"])]
    used = [layer[3] for layer in layers]
    assert len(used) == 8 and np.all(aux["tiles_in_use"] == int(sum(used)[0]))
    assert np.all(aux["gather_trips"] == sum(int(layer[4]["gather_trips"]) for layer in layers))
    assert 8 * held <= int(sum(used)[0]) <= 8 * expert.buffer_rows(bound, held) // expert.ROW_TILE


def _gqa_bf16_parameters(cfg, params):
    return cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)


def _gqa_window_dropped(cfg, params):
    return {**cfg, "sliding_window_layout": [0] * 12}, params


def _gqa_rope_everywhere(cfg, params):
    return {**cfg, "rope_layout": [1] * 12}, params


def _gqa_silu(cfg, params):
    return {**cfg, "expert_act": "silu"}, params


def _gqa_router_reads_the_ffn_input(cfg, params):
    """The layer's router weight applied to ``norm_ffn(x)`` (see the monkeypatch)."""
    return cfg, params


def _gqa_kv_head_by_remainder(cfg, params):
    """Query head h reads k/v head h % Hkv (see the monkeypatch)."""
    return cfg, params


@pytest.mark.parametrize("fault", [
    _gqa_bf16_parameters, _gqa_window_dropped, _gqa_rope_everywhere, _gqa_silu,
    _gqa_router_reads_the_ffn_input, _gqa_kv_head_by_remainder])
def test_the_grouped_comparison_is_tight_enough_to_see(fault, gqa_toy, monkeypatch):
    """What the tolerances must catch: the system computes with the fault, the
    reference without. The four wrong-model controls run on the chip too
    (PERF.md section 6, PR 32)."""
    from bluefog_tpu.models import config_lm
    from bluefog_tpu.parallel import flash

    cfg, params, batch = gqa_toy
    want = float(GQA.plain_loss(cfg, params, {}, batch))
    if fault is _gqa_router_reads_the_ffn_input:
        routed = config_lm.RoutedExperts.__call__

        def misrouted(self, x, choice=None, router_logits=None):
            router = self.parent.variables["params"]["router"]
            return routed(self, x, choice, x.astype(jnp.float32) @ router)
        monkeypatch.setattr(config_lm.RoutedExperts, "__call__", misrouted)
    if fault is _gqa_kv_head_by_remainder:
        monkeypatch.setattr(flash, "_kv_head", lambda group: (
            lambda bh: jax.lax.rem(bh, 4 // group)))       # bh = b * 4 + h, two k/v heads
        flash.flash_block.clear_cache()
    try:
        got = float(_gqa_system_loss(*fault(cfg, params), batch))
    finally:
        flash.flash_block.clear_cache()
    assert abs(got - want) > 10 * LOSS_RTOL * want, (got, want)


def test_the_eight_shares_of_a_reglu_layer_add_up_to_the_uncut_layer():
    """The guide's share test at SmallThinker's shape: the outputs of the
    eight shares of 8 of a 64-expert ReGLU layer, the router's logits handed
    in from outside, add up to the uncut plain layer -- there is no shared
    expert to count once."""
    d, f, experts, top = 32, 16, 64, 6
    layer = lambda held: expert.RoutedExperts(  # noqa: E731
        num_experts=experts, experts_per_token=top, d_ff=f, held=held, n_shared=0,
        scoring="softmax", activation="relu", routing_bias=False, interpret=True)
    u = jax.random.normal(jax.random.PRNGKey(21), (1, 48, d), jnp.float32)
    logits = jax.random.normal(jax.random.PRNGKey(22), (1, 48, experts), jnp.float32)
    variables = layer((0, experts)).init(jax.random.PRNGKey(23), u, None, logits)
    assert set(variables) == {"params"}
    assert set(variables["params"]) == {"gate", "up", "down"}   # no router, shared or bias
    cfg = {"moe_num_primary_experts": experts, "deployment": {"share": 0},
           "moe_num_active_primary_experts": top, "expert_act": "relu"}
    want, _ = GQA._expert_layer(cfg, variables["params"], logits[0], u[0])
    total = 0.0
    for r in range(8):
        out = layer((8 * r, 8 * r + 8)).apply(_share_of(variables, 8 * r, 8 * r + 8),
                                              u, None, logits)
        total = total + out
    assert _rel(total[0], want) <= 1e-5
    assert _rel(out[0], want) > 1e-2                # one share alone is not it
    # the same layer with its own router parameter scores the same way
    own = layer((0, experts)).init(jax.random.PRNGKey(23), u)
    assert set(own["params"]) == {"router", "gate", "up", "down"}


@pytest.mark.parametrize("n_shared,routing_bias", [(0, False), (0, True), (1, False), (1, True)])
def test_no_shared_expert_and_no_bias_create_no_variables(n_shared, routing_bias):
    layer = expert.RoutedExperts(num_experts=32, experts_per_token=4, d_ff=16, held=(8, 16),
                                 n_shared=n_shared, routing_bias=routing_bias, interpret=True)
    h = jax.random.normal(jax.random.PRNGKey(24), (1, 16, 32), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(25), h)
    assert ("shared" in variables["params"]) == bool(n_shared)
    assert ("routing" in variables) == routing_bias
    out, state = layer.apply(variables, h, mutable=["intermediates", "routing"])
    assert ("routing" in state) == routing_bias and np.all(np.isfinite(np.asarray(out)))
    if not routing_bias:
        # the top-k is of the scores themselves
        scores = jax.nn.sigmoid(h[0] @ variables["params"]["router"])
        want, _ = expert.route_top_k(scores, None, 4, 1.0)
        ids = np.asarray(state["intermediates"]["moe_choice"][0])[0]
        np.testing.assert_array_equal(np.sort(ids, axis=1), np.sort(np.asarray(want), axis=1))


def _flash_operands(jaxpr):
    """Shapes of the operands of every ``pallas_call`` of a jaxpr, nested ones included."""
    from jax._src import core as jax_core

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([tuple(v.aval.shape) for v in eqn.invars])
        else:
            for sub in jax_core.jaxprs_in_params(eqn.params):
                found += _flash_operands(sub)
    return found


def test_k_and_v_reach_the_kernels_at_their_own_head_count(gqa_toy):
    """The step's jaxpr: no operand of a flash ``pallas_call`` is a k or v at
    the query heads' count. q, o and dO are [Hq, S, D]; k and v stay [Hkv, S, D]
    forward and backward; only the per-q-head dk/dv the backward returns for
    XLA's group sum are Hq high, and they are outputs."""
    cfg, params, batch = gqa_toy
    cfg = {**cfg, "num_hidden_layers": 4}
    params = {k: v for k, v in params.items() if k not in {f"layer_{i}" for i in range(4, 8)}}
    jaxpr = jax.make_jaxpr(jax.grad(partial(_gqa_system_loss, cfg)))(params, batch)
    calls = [shapes for shapes in _flash_operands(jaxpr.jaxpr) if shapes[0] == (2,)]
    assert len(calls) == 8                            # forward and backward, four layers
    hq, hkv, s, d = 2 * 4, 2 * 2, 64, 8               # two sequences a batch
    for shapes in calls:
        q, k, v = shapes[1:4]
        assert q == (hq, s, d) and k == v == (hkv, s, d), shapes
        # the rest are q-side: dO and the row statistics
        assert all(shape[0] == hq for shape in shapes[4:]), shapes


@pytest.mark.parametrize("keys", [
    {"total_ut_steps": 2, "n_routed_experts": 8}, {"exit_gate": True, "n_routed_experts": 8},
    {"total_ut_steps": 2, "num_nextn_predict_layers": 1},
    {"exit_gate": True, "num_nextn_predict_layers": 1}], ids=lambda keys: "+".join(keys))
def test_a_loop_over_expert_layers_or_mtp_modules_is_refused(keys):
    """The loop and the exit gate are the dense block's: the counters and the
    balancing step are a layer's, not an application's, and an MTP module
    reads the un-normed trunk. Said at construction."""
    with pytest.raises(ValueError, match="looped stack"):
        LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=48, **keys)


def test_sandwich_norms_with_expert_layers_build_and_run():
    """Once refused with the loop, now the expert branch's own: ``ffn_out_norm`` on
    the routed and shared sum before its residual add, as on a dense SwiGLU."""
    cfg = LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                   intermediate_size=48, attention="equal", first_k_dense_replace=1,
                   n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
                   sandwich_norms=True)
    net = ConfigLM(cfg, interpret=True)
    tokens = jax.random.randint(jax.random.PRNGKey(30), (2, 16), 0, 64)
    variables = net.init(jax.random.PRNGKey(31), tokens)
    for layer in ("layer_0", "layer_1"):                # dense, then the expert layer
        assert {"attn_out_norm", "ffn_out_norm"} <= set(variables["params"][layer])
    loss, (routing, counters) = next_token_loss(net)(
        variables["params"], variables["routing"], (tokens, jnp.roll(tokens, -1, axis=1)))
    assert np.isfinite(float(loss)) and int(counters["rows_overflowed"]) == 0
    assert int(counters["rows_routed"]) == 2 * 16 * 2     # every slot: all 8 experts held


def test_the_new_keys_are_off_by_default_and_each_adds_only_its_own_parameters():
    dense = LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                     intermediate_size=48, attention="equal")
    assert (dense.total_ut_steps, dense.sandwich_norms, dense.exit_gate, dense.remat_layers) == (
        1, False, False, False)
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = lambda cfg: ConfigLM(cfg).init(jax.random.PRNGKey(0), tokens)["params"]  # noqa: E731
    base = tree(dense)
    assert set(base) == {"embed", "layer_0", "layer_1", "final_norm", "lm_head"}
    assert set(base["layer_0"]) == {"attn_norm", "attn", "ffn_norm", "ffn"}
    shape = jax.tree_util.tree_structure
    same = lambda a, b: shape(a) == shape(b)  # noqa: E731
    assert same(tree(dataclasses.replace(dense, total_ut_steps=3)), base)
    assert same(tree(dataclasses.replace(dense, remat_layers=True)), base)
    gated = tree(dataclasses.replace(dense, exit_gate=True))
    assert set(gated) - set(base) == {"exit_gate"} and same(gated["layer_0"], base["layer_0"])
    normed = tree(dataclasses.replace(dense, sandwich_norms=True))
    assert set(normed) == set(base)
    assert set(normed["layer_0"]) - set(base["layer_0"]) == {"attn_out_norm", "ffn_out_norm"}
    # one pass of a plain model, asked for all passes: its normed state, no gate
    model = ConfigLM(dense)
    states, gates = model.apply({"params": base}, tokens, all_passes=True)
    assert states.shape == (1, 1, 16, 32) and gates is None
    logits = model.apply({"params": base}, states[0], method=ConfigLM.head)
    assert _rel(logits, model.apply({"params": base}, tokens)) <= 1e-6


# sha256 of the printed jaxpr of value_and_grad of the two accepted ConfigLM
# cells' losses at their toy sizes, under jax 0.9.0 and this suite's matmul
# precision: an ``LMConfig`` without the loop, the sandwich norms, the gate and
# the recomputation must trace to the program it traced to before they came
# (no new parameter, no new equation, no checkpoint), with the expert layer's
# way back to the tokens as it now is (gathers over ``TokenRows``, the counter
# ``gather_trips``). That program took its cross-entropy from optax, so it is
# put back here: ``label_cross_entropy`` is then all that differs.
_PARENT_LOSS_JAXPRS = {
    "joyai": "20932f38a16077fcac45033b0dbd633dba5240be17aaba3bc69c95c40c0e28ae",
    "smallthinker": "d8a70cfa9d3790fe1b10980e1c1a7daf18c059ab5fa71aef8361ae59cd976182",
}


@pytest.mark.parametrize("cell", sorted(_PARENT_LOSS_JAXPRS))
def test_a_config_at_its_defaults_traces_to_the_parents_program(cell, toy, gqa_toy, monkeypatch):
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes are of jaxprs printed by jax 0.9.0")
    monkeypatch.setattr(config_lm, "label_cross_entropy",
                        optax.softmax_cross_entropy_with_integer_labels)
    if cell == "joyai":
        (cfg, params, routing, batch), family = toy, FAMILY
    else:
        (cfg, params, batch), routing, family = gqa_toy, {}, GQA
    lm = family.lm_config(cfg)
    assert (lm.total_ut_steps, lm.sandwich_norms, lm.exit_gate, lm.remat_layers) == (
        1, False, False, False)
    text = str(jax.make_jaxpr(jax.value_and_grad(family.loss(cfg)[0], has_aux=True))(
        params, routing, batch))
    assert "remat" not in text and "checkpoint" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LOSS_JAXPRS[cell]


def _optax_ce(logits, labels):
    """The parent's cross-entropy as ``ConfigLM``'s head fed it: float32."""
    return optax.softmax_cross_entropy_with_integer_labels(logits.astype(jnp.float32), labels)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["2x8x64", "vocabulary 200", "labels at 0 and V-1", "checkpoint"])
def test_label_cross_entropy_is_optaxs_in_value_and_gradient(case, dtype):
    """``label_cross_entropy`` against the gather form it replaces: values to
    1e-6 and the gradient with respect to the logits -- float32 logits to
    1e-6; bfloat16 logits to the one rounding of the float32 gradient that the
    transpose of the head's ``.astype(float32)`` made at the parent, a
    bfloat16 ulp -- under weights that differ by token, as the expected-exit
    objective's do."""
    shape, vocab = ((3, 5), 200) if case == "vocabulary 200" else ((2, 8), 64)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    logits = (4.0 * jax.random.normal(keys[0], shape + (vocab,))).astype(dtype)
    labels = jax.random.randint(keys[1], shape, 0, vocab)
    if case == "labels at 0 and V-1":
        labels = labels.at[0, 0].set(0).at[1, 3].set(vocab - 1).at[1, 7].set(0)
    weights = jax.random.uniform(keys[2], shape)
    wrap = jax.checkpoint if case == "checkpoint" else (lambda f: f)
    value = lambda ce: lambda z: jnp.sum(weights * wrap(ce)(z, labels))  # noqa: E731

    got, want = label_cross_entropy(logits, labels), _optax_ce(logits, labels)
    assert got.dtype == jnp.float32 and got.shape == shape
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    d_got = jax.grad(value(label_cross_entropy))(logits)
    d_want = jax.grad(value(_optax_ce))(logits)
    assert d_got.dtype == dtype and d_got.shape == logits.shape
    np.testing.assert_allclose(d_got.astype(jnp.float32), d_want.astype(jnp.float32),
                               rtol=2 ** -7 if dtype == jnp.bfloat16 else 1e-6, atol=1e-7)
    # the label's column alone is negative
    at_label = jnp.take_along_axis(d_got.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    assert (at_label < 0).all() and ((d_got < 0).sum(axis=-1) == 1).all()


def tokens_by_vocab_movers(fn, *args, tokens: int, vocab: int):
    """The equations of ``fn``'s jaxpr, nested ones included, that gather from,
    scatter into or update a slice of a ``[..., vocab]`` array of ``tokens *
    vocab`` elements -- the logits or their gradient -- as (primitive, shapes)."""
    from jax._src import core as jax_core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            shapes = [tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)]
            if ("gather" in name or "scatter" in name or name == "dynamic_update_slice") and any(
                    shape[-1:] == (vocab,) and int(np.prod(shape)) == tokens * vocab
                    for shape in shapes):
                yield name, shapes
            for sub in jax_core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_no_logit_is_gathered_or_scattered_between_a_head_and_its_gradients(toy, monkeypatch):
    """``value_and_grad`` of the MTP toy's loss: no gather, scatter or
    ``dynamic_update_slice`` touches a ``[tokens, vocab]`` array, both heads
    are counted; with optax's cross-entropy put back (the parent) the same
    walk finds a gather and a scatter-add a head."""
    cfg, params, routing, batch = toy
    size = {"tokens": batch[0].size, "vocab": cfg["vocab_size"]}
    step = lambda: jax.value_and_grad(FAMILY.loss(cfg)[0], has_aux=True)  # noqa: E731
    metrics.gauge("loss.compare_heads").set(0)
    assert tokens_by_vocab_movers(step(), params, routing, batch, **size) == []
    assert metrics.gauge("loss.compare_heads").value == 2
    monkeypatch.setattr(config_lm, "label_cross_entropy",
                        optax.softmax_cross_entropy_with_integer_labels)
    found = [name for name, _ in tokens_by_vocab_movers(step(), params, routing, batch, **size)]
    assert sorted(found) == ["gather", "gather", "scatter-add", "scatter-add"], found


# -- the gated grouped-query block with sandwich-normed sigmoid experts (Trinity) ----------
# ``benchmark/families/gated_gqa_moe_lm.py`` keeps the plain float32 reference of this block;
# it shares no code with ``bluefog_tpu``.

def _trinity_family():
    path = os.path.join(ROOT, "benchmark", "families", "gated_gqa_moe_lm.py")
    spec = importlib.util.spec_from_file_location("gated_gqa_moe_lm_family", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TRINITY = _trinity_family()

# the published block at toy widths: 8 query heads over 2 k/v heads of 8 (32 over 4 of 128),
# a 16-token window under 64 tokens, one dense layer and four expert layers of the published
# layer_types (S, S, S, F, S), experts [2, 4) of 16 held (share 1 of 8), top-4, a shared expert
with open(os.path.join(ROOT, "benchmark", "tests", "toy", "toy-trinity.json")) as _f:
    TRINITY_TOY = json.load(_f)
TRINITY_BATCH = {"sequences": 2, "seq_len": 64}


@pytest.fixture(scope="module")
def trinity_toy():
    """(cfg, params, routing biases, batch of one rank) from fixed seeds."""
    params, routing = TRINITY.init(TRINITY_TOY, TRINITY_BATCH, jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(
        lambda x: x[0], TRINITY.make_batch(TRINITY_TOY, TRINITY_BATCH, jax.random.PRNGKey(1), 1))
    return TRINITY_TOY, params, routing, batch


def _trinity_system_loss(cfg, params, routing, batch, lm=None):
    """The program's loss, of ``lm`` (an ``LMConfig``) where given, else of the family's."""
    if lm is None:
        return TRINITY.loss(cfg)[0](params, routing, batch)[0]
    net = ConfigLM(lm, interpret=True, attn_fn=partial(flash_attention, causal=True,
                                                      interpret=True))
    return next_token_loss(net)(params, routing, batch)[0]


def _trinity_plain(cfg, params, routing, batch):
    return jax.value_and_grad(partial(TRINITY.plain_loss, cfg))(params, routing, batch)


def test_the_gated_block_has_its_own_parameters_and_gauges(trinity_toy):
    cfg, params, routing, _ = trinity_toy
    assert set(params["layer_0"]) == {"attn_norm", "attn", "attn_out_norm", "ffn_norm", "ffn",
                                      "ffn_out_norm"}
    assert set(params["layer_1"]) == set(params["layer_0"])     # sandwich norms, experts too
    attn = params["layer_1"]["attn"]
    assert set(attn) == {"q", "k", "v", "q_norm", "k_norm", "gate", "o"}
    assert attn["gate"]["kernel"].shape == attn["q"]["kernel"].shape == (32, 64)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (8,)
    assert set(params["layer_1"]["ffn"]) == {"router", "gate", "up", "down", "shared"}
    assert params["layer_1"]["ffn"]["router"].shape == (32, 16)
    assert set(routing) == {f"layer_{i}" for i in range(1, 5)}    # the dense layer has none
    lm = TRINITY.lm_config(cfg)
    assert [lm.window_of(i) for i in range(5)] == [16, 16, 16, None, 16]
    assert [lm.rotary_in(i) for i in range(5)] == [True, True, True, False, True]
    assert lm.embedding_scale == pytest.approx(32 ** 0.5)
    metrics.gauge("attn.gated_layers").set(0)
    metrics.gauge("attn.qk_normed_layers").set(0)
    jax.eval_shape(TRINITY.model(cfg).apply, {"params": params, "routing": routing},
                   jnp.zeros((1, 64), jnp.int32))
    assert metrics.gauge("attn.gated_layers").value == 5
    assert metrics.gauge("attn.qk_normed_layers").value == 5


def test_gated_logits_loss_and_gradients_match_the_plain_reference(trinity_toy):
    """Float32 on both sides at the highest matmul precision, differing in the order of
    their sums only: the limits of the blocks above."""
    cfg, params, routing, batch = trinity_toy
    tokens = batch[0][:1]
    logits = TRINITY.model(cfg).apply({"params": params, "routing": routing}, tokens)
    want, _ = TRINITY.plain_forward(cfg, params, routing, tokens, positions=64)
    assert _rel(logits, want) <= 2e-5
    loss, grads = jax.value_and_grad(partial(_trinity_system_loss, cfg))(params, routing, batch)
    want_loss, want_grads = _trinity_plain(cfg, params, routing, batch)
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert _rel(got, want) <= GRAD_RTOL, (jax.tree_util.keystr(path), _rel(got, want))
    # the gate, the q/k norms and both output norms of every layer get a gradient
    for i in range(5):
        layer = grads[f"layer_{i}"]
        for leaf in (layer["attn"]["gate"]["kernel"], layer["attn"]["q_norm"]["scale"],
                     layer["attn"]["k_norm"]["scale"], layer["ffn_out_norm"]["scale"]):
            assert float(jnp.max(jnp.abs(leaf))) > 0


def test_the_toy_trinity_through_opt_step_matches_the_plain_reference(trinity_toy, bf8):
    """``DistributedNeighborAllreduceOptimizer(sgd(1)).step`` on eight ranks that hold the same
    parameters and take the same batch: ``before - after`` is the gradient the step computed,
    against ``jax.grad`` of the plain float32 loss (the limit of the grouped block's test);
    the routing biases come back moved by the balancing rule alone."""
    cfg, params, routing, batch = trinity_toy
    loss_fn, form = TRINITY.loss(cfg)
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(1.0), loss_fn, **form)
    state = opt.init(params, model_state=routing)
    stacked = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (8,) + x.shape), batch)
    state, step_metrics = opt.step(state, stacked)
    want_loss, want_grads = _trinity_plain(cfg, params, routing, batch)
    np.testing.assert_allclose(np.asarray(step_metrics["loss"]), float(want_loss),
                               rtol=LOSS_RTOL)
    after = jax.device_get(state.params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, before), new, want in zip(flat, jax.tree_util.tree_leaves(after),
                                         jax.tree_util.tree_leaves(want_grads)):
        for rank in (0, 7):
            got = np.asarray(before) - new[rank]
            assert _rel(got, want) <= GRAD_RTOL + 1e-4, (jax.tree_util.keystr(path), rank)
    for leaf, before in zip(jax.tree_util.tree_leaves(jax.device_get(state.model_state)),
                            jax.tree_util.tree_leaves(routing)):
        moved = (leaf - np.asarray(before)[None]) / cfg["load_balance_coeff"]
        np.testing.assert_allclose(moved, np.round(moved), atol=2e-3)
        assert np.abs(moved).max() <= 1 + 2e-3 and np.any(moved != 0)
    aux = jax.device_get(step_metrics["aux"])
    assert np.all(aux["rows_overflowed"] == 0) and np.all(aux["rows_routed"] > 0)


def test_the_eight_shares_of_a_sigmoid_layer_with_a_shared_expert_add_up_to_the_uncut_layer():
    """The guide's share test at Trinity's shape: the routed parts of the eight shares of 2 of
    a 16-expert layer (sigmoid scores, a choice-only bias, weights normalised and scaled by
    2.826), with the shared expert -- which every chip computes alike -- counted once, are the
    uncut plain layer's output."""
    d, f, experts, top = 32, 16, 16, 4
    layer = lambda held: expert.RoutedExperts(  # noqa: E731
        num_experts=experts, experts_per_token=top, d_ff=f, held=held, n_shared=1,
        scoring="sigmoid", scaling=2.826, interpret=True)
    u = jax.random.normal(jax.random.PRNGKey(32), (1, 48, d), jnp.float32)
    variables = layer((0, experts)).init(jax.random.PRNGKey(33), u)
    params, bias = variables["params"], variables["routing"]["bias"]
    uncut = {**TRINITY_TOY, "num_experts": experts, "published": {"num_experts": experts},
             "deployment": {"share": 0}, "num_experts_per_tok": top}
    want, _ = TRINITY._expert_layer(uncut, params, bias, u[0])
    shared = expert.SwiGLU(f).apply({"params": params["shared"]}, u)
    total = shared
    for r in range(8):
        out = layer((2 * r, 2 * r + 2)).apply(_share_of(variables, 2 * r, 2 * r + 2), u)
        total = total + (out - shared)
    assert _rel(total[0], want) <= 1e-5
    assert _rel(out[0], want) > 1e-2                      # one share alone is not it
    assert _rel((total + shared)[0], want) > 1e-2         # nor the shared expert counted twice


def _new_key_trees(key):
    base = LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=48, attention="grouped", num_key_value_heads=2, head_dim=8)
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = lambda cfg: ConfigLM(cfg).init(jax.random.PRNGKey(0), tokens)["params"]  # noqa: E731
    changed = {"qk_norm": True, "attn_output_gate": True, "embedding_scale": 4.0}[key]
    return base, tree(base), tree(dataclasses.replace(base, **{key: changed}))


@pytest.mark.parametrize("key", ["qk_norm", "attn_output_gate", "embedding_scale"])
def test_each_new_key_is_off_by_default_and_adds_only_its_own_parameters(key):
    base, before, after = _new_key_trees(key)
    assert (base.qk_norm, base.attn_output_gate, base.embedding_scale) == (False, False, 1.0)
    added = {"qk_norm": {"q_norm", "k_norm"}, "attn_output_gate": {"gate"},
             "embedding_scale": set()}[key]
    for layer in ("layer_0", "layer_1"):
        assert set(after[layer]["attn"]) - set(before[layer]["attn"]) == added
        assert set(before[layer]["attn"]) <= set(after[layer]["attn"])
        assert set(after[layer]) == set(before[layer])
    assert set(after) == set(before)
    if key == "attn_output_gate":
        assert after["layer_0"]["attn"]["gate"]["kernel"].shape == (32, 32)   # d -> Hq * D
    if key == "embedding_scale":
        assert jax.tree_util.tree_structure(after) == jax.tree_util.tree_structure(before)
        tokens = jnp.arange(16)[None]
        cfg = dataclasses.replace(base, embedding_scale=4.0)
        got = ConfigLM(cfg).apply({"params": before}, tokens, method=lambda m, t: m.embed(t))
        want = before["embed"]["embedding"][tokens] * 4.0
        assert _rel(got, want) <= 1e-6


@pytest.mark.parametrize("attention", ["latent", "equal"])
def test_a_qk_norm_outside_grouped_attention_is_refused(attention):
    with pytest.raises(ValueError, match="qk_norm"):
        LMConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=48, attention=attention, qk_norm=True)


def _skip_modules(*names, experts_only=False):
    """A flax interceptor under which the modules of ``names`` return their input."""
    from flax import linen as nn

    def rule(next_fun, args, kwargs, context):
        module = context.module
        if module.name in names and (not experts_only or getattr(module.parent, "experts", False)):
            return args[0]
        return next_fun(*args, **kwargs)

    return nn.intercept_methods(rule)


def _gate_in_bfloat16(a, h, dense):
    with jax.named_scope(config_lm.SCOPE_ATTN_GATE):
        g = dense(a.shape[-1], name="gate")(h).astype(jnp.bfloat16)
        return (a.astype(jnp.bfloat16) * jax.nn.sigmoid(g)).astype(a.dtype)


@pytest.mark.parametrize("fault", [
    "gate dropped", "q/k norm dropped", "expert output norm dropped", "no sqrt(d) scale",
    "rotary on the full layers", "gate in bfloat16"])
def test_the_gated_comparison_is_tight_enough_to_see(fault, trinity_toy, monkeypatch):
    """The wrong-model controls: the system computes with the fault, the reference without,
    and the loss moves by more than ten tolerances."""
    cfg, params, routing, batch = trinity_toy
    want = float(TRINITY.plain_loss(cfg, params, routing, batch))
    lm, context = None, None
    if fault == "gate dropped":
        monkeypatch.setattr(config_lm, "_output_gate", lambda a, h, dense: a)
    elif fault == "gate in bfloat16":
        monkeypatch.setattr(config_lm, "_output_gate", _gate_in_bfloat16)
    elif fault == "q/k norm dropped":
        context = _skip_modules("q_norm", "k_norm")
    elif fault == "expert output norm dropped":
        context = _skip_modules("ffn_out_norm", experts_only=True)
    elif fault == "no sqrt(d) scale":
        lm = dataclasses.replace(TRINITY.lm_config(cfg), embedding_scale=1.0)
    else:
        lm = dataclasses.replace(TRINITY.lm_config(cfg), rope_layout=(1,) * 5)
    if context is None:
        got = float(_trinity_system_loss(cfg, params, routing, batch, lm))
    else:
        with context:
            got = float(_trinity_system_loss(cfg, params, routing, batch, lm))
    assert abs(got - want) > 10 * LOSS_RTOL * want, (got, want)
