"""Family ``looped_lm``: ``bf.models.ConfigLM`` at the sizes of an Ouro style
``config.json`` (arXiv:2510.25741, "Scaling Latent Reasoning via Looped
Language Models") -- one stack of ``num_hidden_layers`` layers run
``total_ut_steps`` times on the same weights, every layer with four norms (a
norm on what attention and the SwiGLU return as well as on what they read),
as many k/v heads as query heads, rotary by halves, one final norm whose
output the next pass reads and the one untied head reads, and one exit gate
``Linear(hidden -> 1)`` with a bias that gives every pass's state a logit.
Trained on the expected-exit objective: the mean over tokens of
``sum_t p_t CE_t - beta H(p)``, p the distribution over the pass a token
leaves after (``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last pass
taking what is left).

The configuration's file gives ``exit_beta`` and ``recompute_layers`` (the
trainer's choice: every layer application under ``jax.checkpoint``) beside
the published keys.

``plain_passes`` / ``plain_forward`` / ``plain_loss`` are the same again in
plain float32 ``jax.numpy``, written from the equations (PERF.md section 4)
and sharing no code with ``bluefog_tpu``: no recomputation, no kernels,
attention a block of queries at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

THROUGHPUT_METRIC = "tokens_per_s_per_chip"
# logits of this many last positions of one sequence, of every pass, are
# compared with the plain forward: they see the whole context
CHECK_POSITIONS = 256


def lm_config(cfg: dict):
    from bluefog_tpu.models import LMConfig

    return LMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"], intermediate_size=cfg["intermediate_size"],
        attention="grouped", num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], rope_interleave=False, rms_norm_eps=cfg["rms_norm_eps"],
        total_ut_steps=cfg["total_ut_steps"], sandwich_norms=True, exit_gate=True,
        remat_layers=cfg["recompute_layers"])


def model(cfg: dict):
    import bluefog_tpu as bf
    from bluefog_tpu.parallel.flash import flash_attention

    interpret = cfg.get("interpret_kernels", False)  # the CPU tests' toy cell
    return bf.models.ConfigLM(
        lm_config(cfg), dtype=jnp.dtype(cfg["compute_dtype"]), interpret=interpret,
        attn_fn=partial(flash_attention, causal=True, interpret=interpret))


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this. The model's
    own initialisers, nothing scaled; there is no model state."""
    tokens = jnp.zeros((1, batch["seq_len"]), jnp.int32)
    return model(cfg).init(key, tokens)["params"], {}


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form): the
    expected-exit objective; the passes' losses and the exit statistics ride in
    ``metrics["aux"]``."""
    from bluefog_tpu.models import looped_exit_loss

    return looped_exit_loss(model(cfg), cfg["exit_beta"]), {"with_model_state": True}


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: tokens uniform over the whole vocabulary,
    targets one position on (the last wraps)."""
    tokens = jax.random.randint(
        key, (n, batch["sequences"], batch["seq_len"]), 0, cfg["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=2)


def units_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def applications(cfg: dict) -> int:
    """Layer applications of one forward pass: passes x layers."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def attention_pairs(cfg: dict, batch: dict) -> int:
    """Live (row, column) pairs of one step, all applications and sequences
    (a head's): S (S + 1) / 2 an application."""
    s = batch["seq_len"]
    return batch["sequences"] * applications(cfg) * (s * (s + 1) // 2)


def attention_flops(cfg: dict, batch: dict) -> float:
    """QK^T and PV forward, dV, dP, dQ, dK backward over the live pairs: six
    products of 2 D FLOPs a pair and head. Neither the scores the backward
    builds again nor the forward a recomputed application runs again count."""
    return 12.0 * cfg["num_attention_heads"] * cfg["head_dim"] * attention_pairs(cfg, batch)


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply a token in one pass: q, k, v, o and the
    SwiGLU's three of each layer, the head and the gate (the embedding is a
    gather)."""
    d, width = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * d * heads * width + 2 * d * kv_heads * width + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"] + d


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip, forward and backward: every pass
    runs the stack and the head, so 6 x parameters x tokens x passes, plus
    attention. Recomputation is not counted."""
    return (6.0 * matmul_params(cfg) * units_per_step(batch) * cfg["total_ut_steps"]
            + attention_flops(cfg, batch))


def check_inputs(batch_of_rank):
    """What both forwards below are given: the first sequence of a batch."""
    return batch_of_rank[0][:1]


# Three limits this family brings, each between what the honest program read on
# the chip over its seeds and what float8 (e4m3) matrices read, the nearest
# precision below the configuration's bfloat16 (PERF.md section 6, PR 34: the
# readings). ``GATE_TOL``: the gate's logits of the compared positions, max
# |sys - ref| / max |ref| over the four passes -- a linear read-out of the same
# normed state as the logits, but of 1,024 numbers whose largest is ~3 where the
# logits' is the largest of 50 million, so its relative error reads above
# ``logits_rel_err``. ``EXIT_TOL``: the exit distribution the program makes of
# its own gate logits, max |p_sys - p_ref| over passes and positions -- p is a
# product of sigmoids, whose slope is at most 1/4, so an honest gate error of e
# moves it by under e; stay and leave swapped move it by the distribution's own
# size. ``LOSS_TOL``: the program's loss of the check sequence against
# ``plain_loss`` of it, relative -- both are means over 4,096 tokens of numbers
# near ln V, so rounding averages out and only a wrong objective shows.
GATE_TOL = 1e-1
EXIT_TOL = 4e-2
LOSS_TOL = 1e-3


def system_logits(cfg: dict, params, model_state, tokens):
    """The program's own forward (flash kernels, compute dtype, recomputation
    as configured): the logits of the last positions of every pass, side by
    side as ``[1, R x CHECK_POSITIONS, V]``, so that a pass left out, a norm not
    fed on or an error that grows with the passes shows. Three more things are
    held to the plain reference, printed, and make the logits returned
    non-finite where they miss, so that the check fails: the gate's logits of
    those positions (``GATE_TOL``), the exit distribution the program's own
    ``exit_distribution`` makes of them (``EXIT_TOL``), and the program's
    training loss of the check sequence against ``plain_loss`` of it
    (``LOSS_TOL``) -- the checked steps take the trainer's own loss on both
    sides, so only this sees a wrong objective."""
    from bluefog_tpu.models import ConfigLM, config_lm

    net = model(cfg)
    last = lambda x: x[:, 0, -CHECK_POSITIONS:]                       # [R, positions, ...]
    states, gates = net.apply({"params": params}, tokens, all_passes=True)
    logits = net.apply({"params": params}, last(states), method=ConfigLM.head)
    want_states, want_gates = plain_passes(cfg, params, tokens[0])
    shown = want_gates[:, -CHECK_POSITIONS:]
    gate_err = jnp.max(jnp.abs(last(gates) - shown)) / jnp.max(jnp.abs(shown))
    exit_err = jnp.max(jnp.abs(
        config_lm.exit_distribution(last(gates))[0] - _exit_distribution(shown)[0]))
    targets = jnp.roll(tokens, -1, axis=1)
    got_loss = loss(cfg)[0](params, model_state, (tokens, targets))[0]
    want_loss = _objective(cfg, params, want_states, want_gates, targets[0])[0]
    loss_err = jnp.abs(got_loss - want_loss) / jnp.abs(want_loss)
    jax.debug.print(
        "looped check: gate_rel_err {} (limit {}), exit_abs_err {} (limit {}), loss {} against "
        "the plain {}: loss_rel_err {} (limit {})", gate_err, GATE_TOL, exit_err, EXIT_TOL,
        got_loss, want_loss, loss_err, LOSS_TOL)
    held = (gate_err <= GATE_TOL) & (exit_err <= EXIT_TOL) & (loss_err <= LOSS_TOL)
    return jnp.where(held, logits.reshape(1, -1, logits.shape[-1]), jnp.nan)


def plain_logits(cfg: dict, params, model_state, tokens):
    del model_state
    return plain_forward(cfg, params, tokens)[0]


# --- the plain reference: float32 jax.numpy, nothing of bluefog_tpu ---------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_halves(x, theta):
    """Rotation of the pairs (i, i + D/2) of x [S, H, D] by position x theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    low, high = x[..., :half], x[..., half:]
    return jnp.concatenate([low * cos - high * sin, low * sin + high * cos], axis=-1)


def _causal_attention(q, k, v, block=512):
    """softmax(q k^T / sqrt(d)) v over the columns s <= t of row t, a block of
    queries at a time so that [H, block, S] scores are all that is held.
    q, k, v [S, H, d]."""
    s, heads, d = q.shape
    block = min(block, s)
    columns = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        allowed = (start + jnp.arange(block))[:, None] >= columns[None, :]
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(one, jnp.arange(0, s, block)).reshape(s, heads, d)


def _layer(cfg, p, x):
    """x + n2(Attn(n1(x))), then x + n4(SwiGLU(n3(x)))."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s, heads, width = x.shape[0], cfg["num_attention_heads"], cfg["head_dim"]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)
    q, k, v = ((h @ p["attn"][name]["kernel"]).reshape(s, heads, width) for name in "qkv")
    a = _causal_attention(_rope_halves(q, theta), _rope_halves(k, theta), v)
    a = a.reshape(s, -1) @ p["attn"]["o"]["kernel"]
    x = x + _rms_norm(a, p["attn_out_norm"]["scale"], eps)
    u = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    ffn = p["ffn"]
    m = (jax.nn.silu(u @ ffn["gate"]["kernel"]) * (u @ ffn["up"]["kernel"])) @ ffn["down"]["kernel"]
    return x + _rms_norm(m, p["ffn_out_norm"]["scale"], eps)


def plain_passes(cfg: dict, params, tokens):
    """(the normed states [R, S, d], the gate's logits [R, S]) of one sequence
    ``tokens [S]``, in float32 at the highest matmul precision: x^0 = E[tokens];
    pass t runs the layers in order on x^(t-1), the same parameters every
    pass; h^t = n_f of what comes out, x^t = h^t; the gate's logit is
    h^t w_g + b_g."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the plain reference of this family has as many k/v heads as q heads")
    states, gates = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]                      # [S, d]
        for _ in range(cfg["total_ut_steps"]):
            for i in range(cfg["num_hidden_layers"]):
                x = _layer(cfg, params[f"layer_{i}"], x)
            x = _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
            states.append(x)
            gates.append((x @ params["exit_gate"]["kernel"])[:, 0] + params["exit_gate"]["bias"][0])
    return jnp.stack(states), jnp.stack(gates)


def plain_forward(cfg: dict, params, tokens, positions: int = CHECK_POSITIONS):
    """(logits [1, R x positions, V] of the last ``positions`` positions of the
    first sequence, pass after pass; the gate's logits [R, positions] of them)."""
    states, gates = plain_passes(cfg, params, tokens[0])
    with jax.default_matmul_precision("highest"):
        logits = states[:, -positions:] @ params["lm_head"]["kernel"]
    return logits.reshape(1, -1, logits.shape[-1]), gates[:, -positions:]


def _exit_distribution(gates):
    """(p, log p) [R, S] from the gate's logits [R, S], pass by pass: what is
    left after the passes before, times lambda; the last pass takes what is
    left."""
    log_left = jnp.zeros_like(gates[0])
    log_p = []
    for g in gates[:-1]:
        log_p.append(log_left + jax.nn.log_sigmoid(g))
        log_left = log_left + jax.nn.log_sigmoid(-g)
    log_p = jnp.stack(log_p + [log_left])
    return jnp.exp(log_p), log_p


def _objective(cfg: dict, params, states, gates, targets):
    """(the expected-exit objective, its statistics under the names
    ``opt.step``'s ``metrics["aux"]`` gives them) of one sequence, from its
    passes' normed states [R, S, d], gate logits [R, S] and targets [S]."""
    with jax.default_matmul_precision("highest"):
        ce = []
        for state in states:
            logp = jax.nn.log_softmax(state @ params["lm_head"]["kernel"], axis=-1)
            ce.append(-jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])
    ce = jnp.stack(ce)                                                # [R, S]
    p, log_p = _exit_distribution(gates)
    entropy = -jnp.sum(p * log_p, axis=0)
    number = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None]
    return (jnp.mean(jnp.sum(p * ce, axis=0) - cfg["exit_beta"] * entropy),
            {"loss_by_pass": ce.mean(axis=1), "exit_mass_by_pass": p.mean(axis=1),
             "exit_entropy": entropy.mean(),
             "expected_exit_pass": jnp.sum(number * p, axis=0).mean()})


def plain_loss_and_aux(cfg: dict, params, batch):
    """The objective and its statistics of one rank's ``(tokens, targets)``
    from the plain passes, a sequence at a time."""
    def one(sequence):
        return _objective(cfg, params, *plain_passes(cfg, params, sequence[0]), sequence[1])

    return jax.tree_util.tree_map(lambda x: x.mean(axis=0), jax.lax.map(one, batch))


def plain_loss(cfg: dict, params, model_state, batch):
    del model_state
    return plain_loss_and_aux(cfg, params, batch)[0]
