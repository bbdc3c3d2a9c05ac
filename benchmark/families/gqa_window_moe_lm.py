"""Family ``gqa_window_moe_lm``: ``bf.models.ConfigLM`` at the sizes of a
SmallThinker style ``config.json`` -- grouped-query attention (separate q, k
and v projections, fewer k/v heads than q heads), layers that alternate by a
published layout between global attention without rotary (NoPE) and a sliding
causal window with rotary by halves, and in every layer a top-k expert layer
whose router reads the attention block's normed input, scores by softmax over
all experts, renormalises over the chosen ones, has no bias and no shared
expert, and whose experts are ReGLU units.

The configuration's file gives the chip's share of a stated deployment:
``moe_num_primary_experts`` is how many experts are held here (ids
``[share * held, (share + 1) * held)`` of the ``published`` count, which the
router keeps), ``vocab_size`` the slice of the vocabulary, and the layouts are
the published lists, of which the first ``num_hidden_layers`` entries are
used. What the absent experts would add is left out, in the program and in
the reference alike.

``plain_forward`` is the forward pass again in plain float32 ``jax.numpy``,
written from the equations (PERF.md section 4) and sharing no code with
``bluefog_tpu``: K and V repeated for their group's query heads, the mask made
from positions, attention a block of queries at a time, each held expert
evaluated densely on every token under its mask. ``plain_loss`` is the
training loss from it, for the CPU tests' ``jax.grad``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

THROUGHPUT_METRIC = "tokens_per_s_per_chip"
# logits of this many last positions of one sequence are compared with the
# plain forward: they see the whole context, and every one of them has rows
# both inside and behind a 4,096-token window
CHECK_POSITIONS = 256


def held_range(cfg: dict):
    held = cfg["moe_num_primary_experts"]
    share = cfg["deployment"]["share"]
    return share * held, (share + 1) * held


def layouts(cfg: dict):
    """(sliding_window_layout, rope_layout) of the layers that are run."""
    layers = cfg["num_hidden_layers"]
    return (tuple(cfg["sliding_window_layout"][:layers]), tuple(cfg["rope_layout"][:layers]))


def lm_config(cfg: dict):
    from bluefog_tpu.models import LMConfig

    window_layout, rope_layout = layouts(cfg)
    return LMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"], intermediate_size=0,
        attention="grouped", num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window_size"], sliding_window_layout=window_layout,
        rope_layout=rope_layout, rope_theta=cfg["rope_theta"], rope_interleave=False,
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["published"]["moe_num_primary_experts"],
        num_experts_per_tok=cfg["moe_num_active_primary_experts"],
        moe_intermediate_size=cfg["moe_ffn_hidden_size"], n_shared_experts=0,
        scoring_func="softmax", routed_scaling_factor=1.0, experts_held=held_range(cfg),
        expert_act=cfg["expert_act"], routing_bias=False, router_input="block")


def model(cfg: dict):
    import bluefog_tpu as bf
    from bluefog_tpu.parallel.flash import flash_attention

    interpret = cfg.get("interpret_kernels", False)  # the CPU tests' toy cell
    return bf.models.ConfigLM(
        lm_config(cfg), dtype=jnp.dtype(cfg["compute_dtype"]), interpret=interpret,
        attn_fn=partial(flash_attention, causal=True, interpret=interpret))


# The token embedding's initial scale, in units of the model's own (std
# 1 / sqrt(hidden), the 0.02 of a published initializer_range at this width).
# This benchmark's number, with no public source (the configuration's file
# lists it under ``departures``, beside what a citable alternative measured).
EMBEDDING_SCALE = 4.0


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this. There is no
    routing bias, so the model state is empty.

    The model's own initialisers, and then (a) the attention's output
    projection scaled by 1 / sqrt(2 x layers run), GPT-2's and Megatron's
    scaling of what writes into the residual stream, and (b) the embedding
    scaled by ``EMBEDDING_SCALE``. On uniform random tokens attention at
    seeded weights is flat, so what it adds to every token is nearly the same
    vector (the mean of v over thousands of tokens); unscaled, that vector is
    as long as a token's own embedding, every router after layer 0 sees it,
    one held expert takes 4-6 times the mean load and the rows routed here
    swing from 0.8 to 1.55 times the uniform share with the seed, which the
    step's time follows: three seeds' steps differ by 0.9-1.2 %, where the
    driver admits a cell under 0.5 % (PERF.md section 6, PR 32). Scaled, a
    token's own direction leads and the load starts at 0.92-1.05 times the
    deployment's share. The experts' down projections keep the model's
    initialiser: what they add is a token's own, and scaling them too leaves
    the shared vector more room (measured: the rows double in a run)."""
    tokens = jnp.zeros((1, batch["seq_len"]), jnp.int32)
    params = model(cfg).init(key, tokens)["params"]
    residual = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])

    def scaled(path, x):
        names = tuple(k.key for k in path)
        if names[-2:] == ("o", "kernel"):
            return x * residual
        return x * EMBEDDING_SCALE if names[-1] == "embedding" else x

    return jax.tree_util.tree_map_with_path(scaled, params), {}


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form): the
    mean cross-entropy of the next token and nothing else; the expert layers'
    counters ride in ``metrics["aux"]``."""
    from bluefog_tpu.models import next_token_loss

    return next_token_loss(model(cfg)), {"with_model_state": True}


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: tokens uniform over the held slice of the
    vocabulary, targets one position on (the last wraps)."""
    tokens = jax.random.randint(
        key, (n, batch["sequences"], batch["seq_len"]), 0, cfg["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=2)


def units_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def expected_rows(cfg: dict, batch: dict) -> float:
    """Rows a step routes to the held experts of one layer under uniform
    routing: tokens x experts per token x held / scored."""
    return (units_per_step(batch) * cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / cfg["published"]["moe_num_primary_experts"])


def live_pairs(seq_len: int, window) -> int:
    """(row, column) pairs a causal layer scores over one sequence:
    S (S + 1) / 2 where it sees the whole past, sum over t of min(t + 1, W)
    under a window of W."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_pairs(cfg: dict, batch: dict) -> int:
    """Live pairs of one step, all layers and sequences (a head's)."""
    window_layout, _ = layouts(cfg)
    return batch["sequences"] * sum(
        live_pairs(batch["seq_len"], cfg["sliding_window_size"] if windowed else None)
        for windowed in window_layout)


def attention_flops(cfg: dict, batch: dict) -> float:
    """QK^T and PV forward, dV, dP, dQ, dK backward over the live pairs: six
    products of 2 D FLOPs a pair and query head. The scores the backward
    builds again, and the dead half of an edge tile, do not count."""
    return 12.0 * cfg["num_attention_heads"] * cfg["head_dim"] * attention_pairs(cfg, batch)


def matmul_params(cfg: dict, batch: dict) -> float:
    """Parameters that multiply a token, the held experts at the share of a
    token's slots they are expected to get (the embedding is a gather)."""
    d, width = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = 2 * d * heads * width + 2 * d * kv_heads * width
    slots = expected_rows(cfg, batch) / units_per_step(batch)
    layer = (attention + d * cfg["published"]["moe_num_primary_experts"]
             + slots * 3 * d * cfg["moe_ffn_hidden_size"])
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip, forward and backward."""
    return (6.0 * matmul_params(cfg, batch) * units_per_step(batch)
            + attention_flops(cfg, batch))


def check_inputs(batch_of_rank):
    """What both forwards below are given: the first sequence of a batch."""
    return batch_of_rank[0][:1]


# Share of an expert layer's (token, slot) choices that the free-running
# program must share with the plain forward. The program scores in float32
# from a bfloat16 normed input where the reference has float32 throughout; the
# 6th and 7th of 64 softmax logits lie ~0.1 apart at the seeded weights and
# the rounding moves a logit by ~1e-3, so about one choice in a hundred
# flips: 0.998 in layer 0, 0.994, 0.988-0.989 and 0.9816-0.9830 in layers 1-3
# on the chip, seven seeds (PERF.md section 6, PR 32). Query heads that read
# the wrong k/v head share 0.83, 0.66 and 0.46 in layers 1-3.
CHOICE_FLOOR = 0.95


def system_logits(cfg: dict, params, model_state, tokens):
    """The program's own forward (flash kernels with grouped heads and the
    window, grouped products, compute dtype): the logits of the last
    positions, ``[1, CHECK_POSITIONS, V]``. It is given the experts the plain
    forward chose for every token: a forward in another precision picks
    another 6th expert wherever the 6th and 7th scores nearly tie, which moves
    that token's logits by a discrete amount that says nothing of the
    arithmetic. The choice itself is held to ``CHOICE_FLOOR``: the share of
    choices on which the free-running program agrees with the plain forward
    is printed by layer, with what its logits then differ by, and where a
    layer's share is under the floor the logits returned are not finite, so
    the check fails."""
    from bluefog_tpu.models import moe_choices

    del model_state  # empty: no routing bias
    net = model(cfg)
    last = lambda logits: logits[:1, -CHECK_POSITIONS:]
    out, state = net.apply({"params": params}, tokens, mutable=["intermediates"])
    free = last(out)
    want, plain_choices = plain_forward(cfg, params, tokens)
    agree = jnp.stack([jnp.mean((a[..., :, None] == b[..., None, :]).any(-1))
                       for a, b in zip(moe_choices(state["intermediates"]), plain_choices)])
    jax.debug.print(
        "free-running routing: choices shared with the plain forward, by expert layer {}; "
        "logits_rel_err {}", agree, jnp.max(jnp.abs(free - want)) / jnp.max(jnp.abs(want)))
    forced = last(net.apply({"params": params}, tokens, choices=plain_choices))
    return jnp.where(jnp.min(agree) >= CHOICE_FLOOR, forced, jnp.nan)


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


def _masked_attention(q, k, v, window, block=512):
    """softmax(q k^T / sqrt(d)) v over the columns s of row t with s <= t and,
    under a window, t - s < window; a block of queries at a time so that
    [H, block, S] scores are all that is held. q [S, Hq, d]; k, v [S, Hkv, d],
    each repeated for the Hq / Hkv query heads that read it (query head g reads
    k/v head g // group)."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(block, s)
    columns = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        behind = (start + jnp.arange(block))[:, None] - columns[None, :]
        allowed = behind >= 0
        if window is not None:
            allowed = allowed & (behind < window)
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one, jnp.arange(0, s, block))
    return out.reshape(s, heads, d)


def _attention(cfg, p, h, window, rotary):
    s = h.shape[0]
    width = cfg["head_dim"]
    q = (h @ p["q"]["kernel"]).reshape(s, cfg["num_attention_heads"], width)
    k = (h @ p["k"]["kernel"]).reshape(s, cfg["num_key_value_heads"], width)
    v = (h @ p["v"]["kernel"]).reshape(s, cfg["num_key_value_heads"], width)
    if rotary:
        q, k = _rope_halves(q, cfg["rope_theta"]), _rope_halves(k, cfg["rope_theta"])
    return _masked_attention(q, k, v, window).reshape(s, -1) @ p["o"]["kernel"]


def _expert_layer(cfg, p, logits, u):
    """sum over the chosen experts held here of w_e E_e(u), E a ReGLU unit
    (``expert_act``: the gate's activation), each held expert evaluated on
    every token under its mask; w the softmax of the router's logits
    renormalised over the chosen ones. Returns it and the chosen ids [S, k]."""
    lo, hi = held_range(cfg)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[cfg["expert_act"]]
    scores = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(scores, cfg["moe_num_active_primary_experts"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True)

    def one(total, expert):
        e, gate, up, down = expert
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)      # [S]
        return total + mask[:, None] * ((act(u @ gate) * (u @ up)) @ down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(lo, hi), p["gate"], p["up"], p["down"]))
    return routed, ids


def _block(cfg, p, x, window, rotary):
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["attn_norm"]["scale"], eps)
    logits = h @ p["router"]                    # the router reads the block's input
    x = x + _attention(cfg, p["attn"], h, window, rotary)
    out, ids = _expert_layer(cfg, p["ffn"], logits, _rms_norm(x, p["ffn_norm"]["scale"], eps))
    return x + out, ids


def plain_forward(cfg: dict, params, tokens, positions: int = CHECK_POSITIONS):
    """(logits [1, positions, V] of the last ``positions`` positions of the
    first sequence, the ids each layer chose as [1, S, k]) in float32 at the
    highest matmul precision."""
    window_layout, rope_layout = layouts(cfg)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens[0]]                    # [S, d]
        for i in range(cfg["num_hidden_layers"]):
            x, ids = _block(cfg, params[f"layer_{i}"], x,
                            cfg["sliding_window_size"] if window_layout[i] else None,
                            bool(rope_layout[i]))
            choices.append(ids[None])
        x = _rms_norm(x[-positions:], params["final_norm"]["scale"], cfg["rms_norm_eps"])
        logits = x @ params["lm_head"]["kernel"]
    return logits[None], choices


def plain_loss(cfg: dict, params, model_state, batch):
    """The training loss of one rank's ``(tokens, targets)`` from the plain
    forward, a sequence at a time: the mean cross-entropy of the next token."""
    del model_state
    tokens, targets = batch

    def one(sequence):
        logits, _ = plain_forward(cfg, params, sequence[0][None], tokens.shape[1])
        logp = jax.nn.log_softmax(logits[0], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, sequence[1][:, None], axis=-1))

    return jnp.mean(jax.lax.map(one, (tokens, targets)))
