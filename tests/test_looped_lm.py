"""``bf.models.ConfigLM`` as a looped model (Ouro) -- one stack of layers run
four times on the same weights, sandwich norms, the normed state fed on, the
exit gate, the expected-exit objective, every layer application recomputed --
against the plain float32 reference the benchmark keeps
(``benchmark/families/looped_lm.py``, which shares no code with
``bluefog_tpu``), at toy widths on the CPU with the Pallas kernels interpreted.
"""

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
from bluefog_tpu.models import ConfigLM

from test_config_lm import GRAD_RTOL, LOSS_RTOL, ROOT, _rel, tokens_by_vocab_movers

def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


LOOPED = _by_path("looped_lm_family", "benchmark", "families", "looped_lm.py")
CONTROLS = _by_path("looped_controls", "benchmark", "tests", "looped_controls.py")

# the published block at toy widths: hidden 64, 4 heads of 16 (16 of 128), a
# SwiGLU of 176, 128 tokens, two layers run four times, every application
# recomputed, beta 0.1, AdamW
with open(os.path.join(ROOT, "benchmark", "tests", "toy", "toy-ouro.json")) as _f:
    LOOP_TOY = json.load(_f)
LOOP_BATCH = {"sequences": 2, "seq_len": 32}


@pytest.fixture(scope="module")
def loop_toy():
    """(cfg, params, batch of one rank) from fixed seeds."""
    params, state = LOOPED.init(LOOP_TOY, LOOP_BATCH, jax.random.PRNGKey(0))
    assert state == {}
    batch = jax.tree_util.tree_map(
        lambda x: x[0], LOOPED.make_batch(LOOP_TOY, LOOP_BATCH, jax.random.PRNGKey(1), 1))
    return LOOP_TOY, params, batch


def _loop_loss(cfg, params, batch):
    return LOOPED.loss(cfg)[0](params, {}, batch)[0]


def test_the_loop_has_one_stack_of_parameters_one_norm_one_head_and_one_gate(loop_toy):
    cfg, params, _ = loop_toy
    assert set(params) == {"embed", "layer_0", "layer_1", "final_norm", "lm_head", "exit_gate"}
    assert set(params["layer_0"]) == {"attn_norm", "attn", "attn_out_norm", "ffn_norm", "ffn",
                                      "ffn_out_norm"}
    assert set(params["layer_0"]["attn"]) == {"q", "k", "v", "o"}
    assert params["exit_gate"]["kernel"].shape == (64, 1)
    assert params["exit_gate"]["bias"].shape == (1,)
    assert params["lm_head"]["kernel"].shape == (64, 128) and "bias" not in params["lm_head"]
    # recomputation is the trainer's choice: it names no parameter
    plain = LOOPED.init({**cfg, "recompute_layers": False}, LOOP_BATCH, jax.random.PRNGKey(0))[0]
    assert jax.tree_util.tree_structure(plain) == jax.tree_util.tree_structure(params)
    assert _rel(plain, params) == 0.0
    # the issue's count at the published widths
    real = {**cfg, "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 16,
            "num_key_value_heads": 16, "intermediate_size": 5632, "vocab_size": 49152,
            "num_hidden_layers": 8}
    assert LOOPED.matmul_params(real) == 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2048 * 49153


def test_looped_logits_gates_loss_and_gradients_match_the_plain_reference(loop_toy):
    """All four passes' logits and gate logits, the objective, its statistics
    and every gradient leaf, recomputed, against the plain float32 loop: the
    limits of the latent block's test above (sums in another order, 8 layer
    applications deep)."""
    cfg, params, batch = loop_toy
    tokens = batch[0][:1]
    net = LOOPED.model(cfg)
    states, gates = net.apply({"params": params}, tokens, all_passes=True)
    assert states.shape == (4, 1, 32, 64) and gates.shape == (4, 1, 32)
    logits = net.apply({"params": params}, states[:, 0], method=ConfigLM.head)
    want_logits, want_gates = LOOPED.plain_forward(cfg, params, tokens, positions=32)
    assert _rel(logits.reshape(1, 128, 128), want_logits) <= 2e-5
    assert _rel(gates[:, 0], want_gates) <= 2e-5
    # what a caller of the other models gets: the last pass's logits
    assert _rel(net.apply({"params": params}, tokens), logits[-1:]) == 0.0
    (loss, (state, aux)), grads = jax.value_and_grad(LOOPED.loss(cfg)[0], has_aux=True)(
        params, {}, batch)
    (want_loss, want_aux), want_grads = jax.value_and_grad(
        lambda p: LOOPED.plain_loss_and_aux(cfg, p, batch), has_aux=True)(params)
    assert state == {}
    assert abs(float(loss) - float(want_loss)) <= LOSS_RTOL * float(want_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert _rel(got, want) <= GRAD_RTOL, (jax.tree_util.keystr(path), _rel(got, want))
    assert set(aux) == {"loss_by_pass", "exit_mass_by_pass", "exit_entropy", "expected_exit_pass"}
    for name in aux:
        assert _rel(aux[name], want_aux[name]) <= 2e-6, name
    assert aux["loss_by_pass"].shape == aux["exit_mass_by_pass"].shape == (4,)
    assert abs(float(aux["exit_mass_by_pass"].sum()) - 1.0) <= 1e-6
    assert 1.0 <= float(aux["expected_exit_pass"]) <= 4.0


def test_the_exit_distribution_sums_to_one_and_the_last_gate_plays_no_part():
    from bluefog_tpu.models import exit_distribution

    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 7))
    p, log_p = exit_distribution(gates)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    assert _rel(exit_distribution(gates.at[3].set(0.0))[0], p) == 0.0
    assert jnp.isfinite(exit_distribution(jnp.full((4, 1), 80.0))[1]).all()   # saturated gates
    assert exit_distribution(gates[:1])[0].tolist() == jnp.ones((1, 5, 7)).tolist()  # one pass


def _checkpoints(fn, *args):
    return sum(eqn.primitive.name in ("checkpoint", "remat2", "remat")
               for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns)


def test_recomputation_changes_the_program_and_not_the_numbers(loop_toy):
    """Every layer application under ``jax.checkpoint``, or none: the same loss
    and gradients to 1e-6; the first program holds a checkpoint equation an
    application, the second none in the model (the loss keeps its four: a
    pass's head and cross-entropy are recomputed either way)."""
    cfg, params, batch = loop_toy
    plain = {**cfg, "recompute_layers": False}
    loss, grads = jax.value_and_grad(partial(_loop_loss, cfg))(params, batch)
    same, same_grads = jax.value_and_grad(partial(_loop_loss, plain))(params, batch)
    assert abs(float(loss) - float(same)) <= 1e-6 * float(loss)
    assert _rel(grads, same_grads) <= 1e-6

    def states(c):
        return lambda p: LOOPED.model(c).apply({"params": p}, batch[0], all_passes=True)[0].sum()
    assert _checkpoints(jax.grad(states(cfg)), params) == 8          # 4 passes x 2 layers
    assert _checkpoints(jax.grad(states(plain)), params) == 0
    assert _checkpoints(jax.grad(partial(_loop_loss, cfg)), params, batch) == 12
    assert _checkpoints(jax.grad(partial(_loop_loss, plain)), params, batch) == 4


def test_no_pass_gathers_or_scatters_its_logits(loop_toy, monkeypatch):
    """``value_and_grad`` of the looped toy's loss, the recomputed passes
    included: no gather, scatter or ``dynamic_update_slice`` touches a
    ``[tokens, vocab]`` array, and the four passes' cross-entropies are
    counted; with optax's put back (the parent) each pass gathers forward and
    scatter-adds backward."""
    from bluefog_tpu.models import config_lm
    from bluefog_tpu.runtime import metrics

    cfg, params, batch = loop_toy
    size = {"tokens": batch[0].size, "vocab": cfg["vocab_size"]}
    step = lambda: jax.value_and_grad(LOOPED.loss(cfg)[0], has_aux=True)  # noqa: E731
    metrics.gauge("loss.compare_heads").set(0)
    assert tokens_by_vocab_movers(step(), params, {}, batch, **size) == []
    assert metrics.gauge("loss.compare_heads").value == cfg["total_ut_steps"] == 4
    monkeypatch.setattr(config_lm, "label_cross_entropy",
                        optax.softmax_cross_entropy_with_integer_labels)
    found = [name for name, _ in tokens_by_vocab_movers(step(), params, {}, batch, **size)]
    assert sorted(found) == ["gather"] * 4 + ["scatter-add"] * 4, found


def test_a_looped_matrix_takes_the_sum_of_its_four_uses_gradients(loop_toy):
    """An untied copy -- the plain reference's layers with a set of parameters a
    pass -- gives a gradient per use; the looped model's gradient of a layer
    is their sum, and no single use's."""
    cfg, params, batch = loop_toy
    head, gate = params["lm_head"]["kernel"], params["exit_gate"]

    def untied_loss(copies):
        def one(tokens, targets):
            with jax.default_matmul_precision("highest"):
                x, states = params["embed"]["embedding"][tokens], []
                for copy in copies:
                    for i in range(cfg["num_hidden_layers"]):
                        x = LOOPED._layer(cfg, copy[f"layer_{i}"], x)
                    x = LOOPED._rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
                    states.append(x)
                states = jnp.stack(states)
                gates = (states @ gate["kernel"])[..., 0] + gate["bias"][0]
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    states @ head, jnp.broadcast_to(targets, gates.shape))
            p, log_p = LOOPED._exit_distribution(gates)
            return jnp.mean(jnp.sum(p * (ce + cfg["exit_beta"] * log_p), axis=0))
        return jnp.mean(jnp.stack([one(*sequence) for sequence in zip(*batch)]))

    copies = [{name: params[name] for name in ("layer_0", "layer_1")}] * 4
    loss, by_use = jax.value_and_grad(untied_loss)(copies)
    got, grads = jax.value_and_grad(partial(_loop_loss, cfg))(params, batch)
    assert abs(float(got) - float(loss)) <= LOSS_RTOL * float(loss)
    for name in ("layer_0", "layer_1"):
        summed = jax.tree_util.tree_map(lambda *g: sum(g), *[use[name] for use in by_use])
        assert _rel(grads[name], summed) <= GRAD_RTOL
        for use in by_use:
            assert _rel(grads[name]["ffn"]["down"], use[name]["ffn"]["down"]) > 0.1


REFERENCE = _by_path("benchmark_reference", "benchmark", "reference.py")
STATIC = _by_path("benchmark_schedule_static", "benchmark", "schedules", "static.py")


def test_the_toy_ouro_through_opt_step_matches_the_plain_steps(bf8):
    """Three ``DistributedNeighborAllreduceOptimizer(adamw).step``s on eight
    ranks with a batch each, against the benchmark's plain steps (``jax.jit``
    + optax a rank, then the mix by the graph's weight matrix) as the harness
    compares them; the loop's statistics in ``metrics["aux"]`` and its gauges."""
    from bluefog_tpu.runtime import metrics as bf_metrics

    cfg = LOOP_TOY
    tx = optax.adamw(**cfg["optimizer"]["args"])
    loss_fn, form = LOOPED.loss(cfg)
    opt = bf.DistributedNeighborAllreduceOptimizer(tx, loss_fn, **form)
    init = lambda: LOOPED.init(cfg, LOOP_BATCH, jax.random.PRNGKey(0))  # noqa: E731
    state = opt.init(init()[0], model_state={})
    weights = [STATIC.Schedule(bf, opt).before_step()] * 3
    sharding = bf.rank_sharding(bf.mesh())
    batches = [jax.device_put(LOOPED.make_batch(cfg, LOOP_BATCH, key, 8), sharding)
               for key in jax.random.split(jax.random.PRNGKey(5), 3)]
    losses = []
    for batch in batches:
        state, metrics = opt.step(state, batch)
        losses.append(np.asarray(metrics["loss"], np.float64))
    prints = jax.device_get(REFERENCE.fingerprint_stacked(state.params))
    plain = REFERENCE.run_steps(loss_fn, True, tx, init, batches, weights,
                                list(bf.mesh().devices.ravel()))
    verdict = REFERENCE.compare_steps(np.stack(losses), prints, plain, "float32")
    assert verdict["ok"] and verdict["loss_rel_err"] < 1e-6 and verdict["print_err"] < 1e-3, verdict
    aux = jax.device_get(metrics["aux"])
    assert aux["loss_by_pass"].shape == aux["exit_mass_by_pass"].shape == (8, 4)
    np.testing.assert_allclose(aux["exit_mass_by_pass"].sum(axis=1), 1.0, atol=1e-5)
    assert aux["exit_entropy"].shape == aux["expected_exit_pass"].shape == (8,)
    assert np.all(aux["exit_entropy"] > 0)
    assert np.all((1 <= aux["expected_exit_pass"]) & (aux["expected_exit_pass"] <= 4))
    gauges = bf_metrics.snapshot(include_native=False)["gauges"]
    assert (gauges["loop.passes"], gauges["loop.layer_applications"],
            gauges["loop.recomputed"]) == (4, 8, 1)


@pytest.mark.parametrize("control", CONTROLS.CONTROLS, ids=lambda c: c.__name__)
def test_the_looped_comparison_is_tight_enough_to_see(control, loop_toy):
    """What the tolerances must catch: the system computes under the control
    (a pass left out, the state fed on un-normed, the output norms dropped,
    float8 matrices, stay and leave swapped in the exit distribution), the
    reference does not. All five run on the chip too (PERF.md section 6,
    PR 34)."""
    cfg, params, batch = loop_toy
    want = float(LOOPED.plain_loss(cfg, params, {}, batch))
    with control(cfg, params) as (faulty_cfg, faulty_params):
        got = float(_loop_loss(faulty_cfg, faulty_params, batch))
    assert abs(got - want) > 10 * LOSS_RTOL * want, (got, want)
    assert abs(float(_loop_loss(cfg, params, batch)) - want) <= LOSS_RTOL * want   # and it ends
