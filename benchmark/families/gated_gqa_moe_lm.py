"""Family ``gated_gqa_moe_lm``: ``bf.models.ConfigLM`` at the sizes of an AFMoE style
``config.json`` (arcee-ai's Trinity) -- grouped-query attention with an RMSNorm over each
head's q and k and a sigmoid gate on its output, layers that alternate by the published
``layer_types`` between a sliding causal window with rotary by halves and global attention
without rotary (NoPE), leading dense SwiGLU layers, then expert layers that score by sigmoid,
choose the top-k of score plus a choice-only bias moved by the auxiliary-loss-free rule, weigh
the chosen by their scores normalised and scaled, and add a shared expert; four norms a layer
(sandwich norms) and the embedding's output scaled by sqrt(hidden) where ``mup_enabled``.

The configuration's file gives the chip's share of a stated deployment: ``num_experts`` is how
many experts are held here (ids ``[share * held, (share + 1) * held)`` of the ``published``
count, which the router keeps), ``num_dense_layers`` and ``num_hidden_layers`` the layers run,
``vocab_size`` the slice of the vocabulary, and ``layer_types`` the published list, of which the
first ``num_hidden_layers`` entries are used. What the absent experts would add is left out, in
the program and in the reference alike.

``plain_forward`` is the forward pass again in plain float32 ``jax.numpy``, written from the
equations (PERF.md section 4) and sharing no code with ``bluefog_tpu``: K and V repeated for
their group's query heads, the masks made from positions, attention a block of queries at a
time, each held expert evaluated densely on every token under its mask. ``plain_loss`` is the
training loss from it, for the CPU tests' ``jax.grad``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

THROUGHPUT_METRIC = "tokens_per_s_per_chip"
# logits of this many last positions of one sequence are compared with the plain forward:
# they see the whole context, and every one of them has rows inside and behind the window
CHECK_POSITIONS = 256
SLIDING = "sliding_attention"


def held_range(cfg: dict):
    held = cfg["num_experts"]
    share = cfg["deployment"]["share"]
    return share * held, (share + 1) * held


def layouts(cfg: dict):
    """(sliding_window_layout, rope_layout) of the layers that are run: a ``sliding_attention``
    layer has the window and rotary, a ``full_attention`` one neither."""
    sliding = tuple(int(kind == SLIDING) for kind in cfg["layer_types"][:cfg["num_hidden_layers"]])
    return sliding, sliding


def embedding_scale(cfg: dict) -> float:
    return math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0


def lm_config(cfg: dict):
    from bluefog_tpu.models import LMConfig

    if not cfg["route_norm"] or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the expert layer normalises the chosen scores and has no group step")
    window_layout, rope_layout = layouts(cfg)
    return LMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"], intermediate_size=cfg["intermediate_size"],
        attention="grouped", num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], sliding_window_layout=window_layout,
        rope_layout=rope_layout, rope_theta=cfg["rope_theta"], rope_interleave=False,
        rms_norm_eps=cfg["rms_norm_eps"], first_k_dense_replace=cfg["num_dense_layers"],
        n_routed_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"], scoring_func=cfg["score_func"],
        routed_scaling_factor=cfg["route_scale"], experts_held=held_range(cfg),
        expert_act=cfg["hidden_act"], bias_update_speed=cfg["load_balance_coeff"],
        sandwich_norms=True, qk_norm=True, attn_output_gate=True,
        embedding_scale=embedding_scale(cfg))


def model(cfg: dict):
    import bluefog_tpu as bf
    from bluefog_tpu.parallel.flash import flash_attention

    interpret = cfg.get("interpret_kernels", False)  # the CPU tests' toy cell
    return bf.models.ConfigLM(
        lm_config(cfg), dtype=jnp.dtype(cfg["compute_dtype"]), interpret=interpret,
        attn_fn=partial(flash_attention, causal=True, interpret=interpret))


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this. The model state is the
    expert layers' routing biases, which no gradient moves.

    The model's own initialisers, and then the gains of the two output norms of every layer
    (``attn_out_norm``, ``ffn_out_norm``) set to 1 / sqrt(2 x layers run): what writes into the
    residual stream is scaled by depth, as GPT-2 and Megatron scale the output projection --
    which a norm after it would undo, so the scale goes on the norm's gain. On uniform random
    tokens attention at seeded weights is flat, so what it adds to every token is nearly one
    vector, which every router after the first layer sees; with unit gains it is as long as a
    token's own embedding (unit RMS after the sqrt(hidden) scale) and the routers' choice
    follows it (the configuration's file, ``assumed``, has the measurement)."""
    tokens = jnp.zeros((1, batch["seq_len"]), jnp.int32)
    variables = model(cfg).init(key, tokens)
    gain = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])

    def scaled(path, x):
        names = tuple(k.key for k in path)
        return x * gain if names[-2:] in (("attn_out_norm", "scale"),
                                          ("ffn_out_norm", "scale")) else x

    return jax.tree_util.tree_map_with_path(scaled, variables["params"]), variables["routing"]


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form): the mean
    cross-entropy of the next token; the expert layers' counters ride in ``metrics["aux"]``."""
    from bluefog_tpu.models import next_token_loss

    return next_token_loss(model(cfg)), {"with_model_state": True}


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: tokens uniform over the held slice of the vocabulary, targets
    one position on (the last wraps)."""
    tokens = jax.random.randint(
        key, (n, batch["sequences"], batch["seq_len"]), 0, cfg["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=2)


def units_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expected_rows(cfg: dict, batch: dict) -> float:
    """Rows a step routes to the held experts of one layer under uniform routing: tokens x
    experts per token x held / scored."""
    return (units_per_step(batch) * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def live_pairs(seq_len: int, window) -> int:
    """(row, column) pairs a causal layer scores over one sequence: S (S + 1) / 2 where it
    sees the whole past, sum over t of min(t + 1, W) under a window of W."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_pairs(cfg: dict, batch: dict) -> int:
    """Live pairs of one step, all layers and sequences (a head's)."""
    window_layout, _ = layouts(cfg)
    return batch["sequences"] * sum(
        live_pairs(batch["seq_len"], cfg["sliding_window"] if windowed else None)
        for windowed in window_layout)


def attention_flops(cfg: dict, batch: dict) -> float:
    """QK^T and PV forward, dV, dP, dQ, dK backward over the live pairs: six products of 2 D
    FLOPs a pair and query head. The scores the backward builds again, and the dead half of an
    edge tile, do not count."""
    return 12.0 * cfg["num_attention_heads"] * cfg["head_dim"] * attention_pairs(cfg, batch)


def attention_params(cfg: dict) -> int:
    """A layer's attention matrices: q, the gate and o at the query heads, k and v at the
    k/v heads."""
    d, width = cfg["hidden_size"], cfg["head_dim"]
    return 3 * d * cfg["num_attention_heads"] * width + 2 * d * cfg["num_key_value_heads"] * width


def matmul_params(cfg: dict, batch: dict) -> float:
    """Parameters that multiply a token, the held experts at the share of a token's slots they
    are expected to get (the embedding is a gather)."""
    d = cfg["hidden_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    slots = expected_rows(cfg, batch) / units_per_step(batch)
    expert_layer = (d * cfg["published"]["num_experts"]
                    + (cfg["num_shared_experts"] + slots) * expert)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["num_dense_layers"] * 3 * d * cfg["intermediate_size"]
            + expert_layers(cfg) * expert_layer + d * cfg["vocab_size"])


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip, forward and backward."""
    return (6.0 * matmul_params(cfg, batch) * units_per_step(batch)
            + attention_flops(cfg, batch))


def check_inputs(batch_of_rank):
    """What both forwards below are given: the first sequence of a batch."""
    return batch_of_rank[0][:1]


# Share of an expert layer's (token, slot) choices that the free-running program must share
# with the plain forward. The program scores in float32 from a bfloat16 normed input where the
# reference has float32 throughout, so where the 8th and 9th of 128 sigmoid scores plus bias
# nearly tie a rounding flips the choice: 0.9906-0.9954 by layer on the chip, twelve readings.
# A program whose matrices are rounded to float8 (e4m3) shares 0.926-0.944 of the honest
# reference's choices (PERF.md section 6); a forgotten bias (normal(0, 0.02)) reads
# 0.92-0.94 at the CPU tests' toy size.
CHOICE_FLOOR = 0.97


def system_logits(cfg: dict, params, routing, tokens):
    """The program's own forward (flash kernels with grouped heads and the window, grouped
    products, compute dtype): the logits of the last positions, ``[1, CHECK_POSITIONS, V]``.
    It is given the experts the plain forward chose for every token: a forward in another
    precision picks another 8th expert wherever the 8th and 9th scores nearly tie, which moves
    that token's logits by a discrete amount that says nothing of the arithmetic. The choice
    itself is held to ``CHOICE_FLOOR``: the share of choices on which the free-running program
    agrees with the plain forward is printed by expert layer, with what its logits then differ
    by, and where a layer's share is under the floor the logits returned are not finite, so the
    check fails."""
    from bluefog_tpu.models import moe_choices

    net = model(cfg)
    variables = {"params": params, "routing": routing}
    last = lambda logits: logits[:1, -CHECK_POSITIONS:]
    out, state = net.apply(variables, tokens, mutable=["intermediates"])
    free = last(out)
    want, plain_choices = plain_forward(cfg, params, routing, tokens)
    agree = jnp.stack([jnp.mean((a[..., :, None] == b[..., None, :]).any(-1))
                       for a, b in zip(moe_choices(state["intermediates"]), plain_choices)])
    jax.debug.print(
        "free-running routing: choices shared with the plain forward, by expert layer {}; "
        "logits_rel_err {}", agree, jnp.max(jnp.abs(free - want)) / jnp.max(jnp.abs(want)))
    forced = last(net.apply(variables, tokens, choices=plain_choices))
    return jnp.where(jnp.min(agree) >= CHOICE_FLOOR, forced, jnp.nan)


def plain_logits(cfg: dict, params, routing, tokens):
    return plain_forward(cfg, params, routing, tokens)[0]


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
    """softmax(q k^T / sqrt(d)) v over the columns s of row t with s <= t and, under a window,
    t - s < window; a block of queries at a time so that [H, block, S] scores are all that is
    held. q [S, Hq, d]; k, v [S, Hkv, d], each repeated for the Hq / Hkv query heads that read
    it (query head g reads k/v head g // group)."""
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
    """q/k-normed grouped attention of the normed input h [S, d], its output gated by
    sigmoid(h W_gate) before the output projection."""
    s, width, eps = h.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    q = (h @ p["q"]["kernel"]).reshape(s, cfg["num_attention_heads"], width)
    k = (h @ p["k"]["kernel"]).reshape(s, cfg["num_key_value_heads"], width)
    v = (h @ p["v"]["kernel"]).reshape(s, cfg["num_key_value_heads"], width)
    q, k = _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps)
    if rotary:
        q, k = _rope_halves(q, cfg["rope_theta"]), _rope_halves(k, cfg["rope_theta"])
    o = _masked_attention(q, k, v, window).reshape(s, -1)
    return (o * jax.nn.sigmoid(h @ p["gate"]["kernel"])) @ p["o"]["kernel"]


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def _expert_layer(cfg, p, bias, u):
    """shared(u) + sum over the chosen experts held here of w_e E_e(u), E a SwiGLU unit, each
    held expert evaluated on every token under its mask; s the sigmoid scores of the router's
    logits, the choice the top-k of s + bias, w = route_scale s / (sum of the chosen s).
    Returns it and the chosen ids [S, k]."""
    lo, hi = held_range(cfg)
    scores = jax.nn.sigmoid(u @ p["router"])
    _, ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = cfg["route_scale"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    def one(total, expert):
        e, gate, up, down = expert
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)      # [S]
        return total + mask[:, None] * ((jax.nn.silu(u @ gate) * (u @ up)) @ down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(lo, hi), p["gate"], p["up"], p["down"]))
    return _swiglu(u, p["shared"]) + routed, ids


def _block(cfg, p, bias, x, window, rotary):
    """x += n_pa(Attn(n_in(x))); x += n_pm2(FFN(n_pm(x))), FFN a SwiGLU (``bias`` None) or
    the expert layer."""
    eps = cfg["rms_norm_eps"]
    a = _attention(cfg, p["attn"], _rms_norm(x, p["attn_norm"]["scale"], eps), window, rotary)
    x = x + _rms_norm(a, p["attn_out_norm"]["scale"], eps)
    u = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    m, ids = (_swiglu(u, p["ffn"]), None) if bias is None else _expert_layer(cfg, p["ffn"], bias, u)
    return x + _rms_norm(m, p["ffn_out_norm"]["scale"], eps), ids


def plain_forward(cfg: dict, params, routing, tokens, positions: int = CHECK_POSITIONS):
    """(logits [1, positions, V] of the last ``positions`` positions of the first sequence, the
    ids each expert layer chose as [1, S, k]) in float32 at the highest matmul precision.
    ``routing`` holds the expert layers' biases, by layer as ``params`` holds their weights."""
    window_layout, rope_layout = layouts(cfg)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens[0]] * embedding_scale(cfg)  # [S, d]
        for i in range(cfg["num_hidden_layers"]):
            name = f"layer_{i}"
            bias = routing[name]["ffn"]["bias"] if name in routing else None
            x, ids = _block(cfg, params[name], bias, x,
                            cfg["sliding_window"] if window_layout[i] else None,
                            bool(rope_layout[i]))
            choices += [] if ids is None else [ids[None]]
        x = _rms_norm(x[-positions:], params["final_norm"]["scale"], cfg["rms_norm_eps"])
        logits = x @ params["lm_head"]["kernel"]
    return logits[None], choices


def plain_loss(cfg: dict, params, routing, batch):
    """The training loss of one rank's ``(tokens, targets)`` from the plain forward, a
    sequence at a time: the mean cross-entropy of the next token."""
    tokens, targets = batch

    def one(sequence):
        logits, _ = plain_forward(cfg, params, routing, sequence[0][None], tokens.shape[1])
        logp = jax.nn.log_softmax(logits[0], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, sequence[1][:, None], axis=-1))

    return jnp.mean(jax.lax.map(one, (tokens, targets)))
