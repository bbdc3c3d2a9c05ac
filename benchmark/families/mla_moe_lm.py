"""Family ``mla_moe_lm``: ``bf.models.ConfigLM`` at the sizes of a DeepSeek-V3
style ``config.json`` -- multi-head latent attention, a leading dense SwiGLU
layer, expert layers with sigmoid top-k routing, a choice-only bias and a
shared expert, and multi-token-prediction modules that share the embedding
and the head.

The configuration's file gives the chip's share of a stated deployment:
``n_routed_experts`` is how many experts are held here (ids
``[share * held, (share + 1) * held)`` of the ``published`` count, which the
router keeps), ``vocab_size`` the slice of the vocabulary. What the absent
experts would add is left out, in the program and in the reference alike.

``plain_forward`` is the forward pass again in plain float32 ``jax.numpy``,
written from the equations (PERF.md section 4) and sharing no code with
``bluefog_tpu``: attention a block of queries at a time, each held expert
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
# plain forward, the main head's and each MTP module's: they see the whole context
CHECK_POSITIONS = 256


def held_range(cfg: dict):
    held = cfg["n_routed_experts"]
    share = cfg["deployment"]["share"]
    return share * held, (share + 1) * held


def lm_config(cfg: dict):
    from bluefog_tpu.models import LMConfig

    return LMConfig.from_dict(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=held_range(cfg),
        bias_update_speed=cfg.get("routing_bias_update_speed", 0.0))


def model(cfg: dict):
    import bluefog_tpu as bf
    from bluefog_tpu.parallel.flash import flash_attention

    interpret = cfg.get("interpret_kernels", False)  # the CPU tests' toy cell
    return bf.models.ConfigLM(
        lm_config(cfg), dtype=jnp.dtype(cfg["compute_dtype"]), interpret=interpret,
        attn_fn=partial(flash_attention, causal=True, interpret=interpret))


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this. The model
    state is the expert layers' routing biases, which no gradient moves."""
    tokens = jnp.zeros((1, batch["seq_len"]), jnp.int32)
    variables = model(cfg).init(key, tokens)
    return variables["params"], variables["routing"]


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form)."""
    from bluefog_tpu.models import next_token_loss

    return (next_token_loss(model(cfg), mtp_weight=cfg["mtp_loss_weight"]),
            {"with_model_state": True})


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: tokens uniform over the held slice of the
    vocabulary, next-token and next-next-token targets."""
    tokens = jax.random.randint(
        key, (n, batch["sequences"], batch["seq_len"]), 0, cfg["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=2), jnp.roll(tokens, -2, axis=2)


def units_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def attention_widths(cfg: dict):
    """(heads, q.k width, v width)."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def attention_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expert_layers(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def expected_rows(cfg: dict, batch: dict) -> float:
    """Rows a step routes to the held experts of one layer under uniform
    routing: tokens x experts per token x held / scored."""
    return (units_per_step(batch) * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def matmul_params(cfg: dict, batch: dict) -> float:
    """Parameters that multiply a token, the held experts at the share of a
    token's slots they are expected to get (the embedding is a gather)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    attention = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * (nope + rot)
                 + d * (cfg["kv_lora_rank"] + rot) + cfg["kv_lora_rank"] * heads * (nope + dv)
                 + heads * dv * d)
    expert = 3 * d * cfg["moe_intermediate_size"]
    slots = expected_rows(cfg, batch) / units_per_step(batch)
    expert_layer = (d * cfg["published"]["n_routed_experts"]
                    + (cfg["n_shared_experts"] + slots) * expert)
    heads_out = (1 + cfg["num_nextn_predict_layers"]) * d * cfg["vocab_size"]
    return (attention_layers(cfg) * attention
            + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
            + expert_layers(cfg) * expert_layer
            + cfg["num_nextn_predict_layers"] * 2 * d * d + heads_out)


def attention_flops(cfg: dict, batch: dict) -> float:
    """QK^T and PV forward, dV, dP, dQ, dK backward, causal: 3 B S^2 H (d_qk + d_v)
    a layer. The scores the backward kernels build again do not count."""
    heads, dqk, dv = attention_widths(cfg)
    b, s = batch["sequences"], batch["seq_len"]
    return 3.0 * attention_layers(cfg) * b * s * s * heads * (dqk + dv)


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip, forward and backward."""
    return (6.0 * matmul_params(cfg, batch) * units_per_step(batch)
            + attention_flops(cfg, batch))


def check_inputs(batch_of_rank):
    """What both forwards below are given: the first sequence of a batch."""
    return batch_of_rank[0][:1]


def _stacked(out):
    """The model's output (logits, or logits and the MTP modules') as
    [1 + MTP modules, CHECK_POSITIONS, V] of the first sequence."""
    every = (out[0],) + tuple(out[1]) if isinstance(out, tuple) else (out,)
    return jnp.stack([x[0, -CHECK_POSITIONS:] for x in every])


# Share of an expert layer's (token, slot) choices that the free-running
# program must share with the plain forward. bfloat16 against float32 at the
# seeded weights reads 0.977-0.988 on the chip (PERF.md section 6, PR 27): the
# 8th and 9th of 256 scores lie ~0.007 apart, so a rounding flips one choice
# in 50. A forgotten or misplaced bias (normal(0, 0.02)) reads under 0.8.
CHOICE_FLOOR = 0.95


def system_logits(cfg: dict, params, routing, tokens):
    """The program's own forward (flash kernels, grouped products, compute
    dtype): the main head's and every MTP module's logits of the last
    positions, stacked. It is given the experts the plain forward chose for
    every token: a forward in another precision picks another 8th expert
    wherever the 8th and 9th scores nearly tie, which moves that token's logits
    by a discrete amount that says nothing of the arithmetic. The choice
    itself is held to ``CHOICE_FLOOR``: the share of choices on which the
    free-running program agrees with the plain forward is printed by expert
    layer, with what its logits then differ by, and where a layer's share is
    under the floor the logits returned are not finite, so the check fails."""
    from bluefog_tpu.models import moe_choices

    net = model(cfg)
    variables = {"params": params, "routing": routing}
    out, state = net.apply(variables, tokens, mutable=["intermediates"])
    free = _stacked(out)
    want, plain_choices = plain_forward(cfg, params, routing, tokens)
    agree = jnp.stack([jnp.mean((a[..., :, None] == b[..., None, :]).any(-1))
                       for a, b in zip(moe_choices(state["intermediates"]), plain_choices)])
    jax.debug.print(
        "free-running routing: choices shared with the plain forward, by expert layer {}; "
        "logits_rel_err {}", agree, jnp.max(jnp.abs(free - want)) / jnp.max(jnp.abs(want)))
    forced = _stacked(net.apply(variables, tokens, choices=plain_choices))
    return jnp.where(jnp.min(agree) >= CHOICE_FLOOR, forced, jnp.nan)


def plain_logits(cfg: dict, params, routing, tokens):
    return plain_forward(cfg, params, routing, tokens)[0]


# --- the plain reference: float32 jax.numpy, nothing of bluefog_tpu ---------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """Rotation of the pairs (2i, 2i+1) of x [S, H, D] by position x theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v, block=512):
    """softmax(q k^T / sqrt(d_qk)) v with a causal mask, a block of queries at a
    time so that [H, block, S] scores are all that is held. q, k [S, H, d_qk],
    v [S, H, d_v]."""
    s, _, d = q.shape
    block = min(block, s)
    positions = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        mask = (start + jnp.arange(block))[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one, jnp.arange(0, s, block))
    return out.reshape(s, *v.shape[1:])


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def _latent_attention(cfg, p, h):
    heads, s = cfg["num_attention_heads"], h.shape[0]
    nope, rot, eps = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["rms_norm_eps"]
    c_q = _rms_norm(h @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(s, heads, nope + rot)
    kv_a = h @ p["kv_a"]["kernel"]
    c_kv = _rms_norm(kv_a[:, :cfg["kv_lora_rank"]], p["kv_a_norm"]["scale"], eps)
    k_rot = _rope_pairs(kv_a[:, None, cfg["kv_lora_rank"]:], cfg["rope_theta"])
    kv = (c_kv @ p["kv_b"]["kernel"]).reshape(s, heads, nope + cfg["v_head_dim"])
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rot, (s, heads, rot))], -1)
    return _causal_attention(q, k, kv[..., nope:]).reshape(s, -1) @ p["o"]["kernel"]


def _expert_layer(cfg, p, bias, h):
    """shared(h) + sum over the chosen experts held here of w_e E_e(h), each
    held expert evaluated on every token under its mask. Returns it and the
    chosen ids [S, k]."""
    lo, hi = held_range(cfg)
    scores = jax.nn.sigmoid(h @ p["router"])
    _, ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = cfg["routed_scaling_factor"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)

    def one(total, expert):
        e, gate, up, down = expert
        mask = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)      # [S]
        return total + mask[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (jnp.arange(lo, hi), p["gate"], p["up"], p["down"]))
    return _swiglu(h, p["shared"]) + routed, ids


def _block(cfg, p, bias, x):
    """One block; a dense one has no ``bias``."""
    eps = cfg["rms_norm_eps"]
    x = x + _latent_attention(cfg, p["attn"], _rms_norm(x, p["attn_norm"]["scale"], eps))
    h = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    if bias is None:
        return x + _swiglu(h, p["ffn"]), None
    out, ids = _expert_layer(cfg, p["ffn"], bias, h)
    return x + out, ids


def plain_forward(cfg: dict, params, routing, tokens, positions: int = CHECK_POSITIONS):
    """(logits [1 + MTP modules, positions, V] of the last ``positions``
    positions of the first sequence, the ids each expert layer chose as
    [1, S, k]) in float32 at the highest matmul precision. ``routing`` holds
    the expert layers' biases, by block as ``params`` holds their weights."""
    eps = cfg["rms_norm_eps"]
    bias = lambda block: routing[block]["ffn"]["bias"] if block in routing else None
    head = lambda x, scale: _rms_norm(x[-positions:], scale, eps) @ params["lm_head"]["kernel"]
    choices = []
    with jax.default_matmul_precision("highest"):
        embedding = params["embed"]["embedding"]
        x = embedding[tokens[0]]                                       # [S, d]
        for i in range(cfg["num_hidden_layers"]):
            x, ids = _block(cfg, params[f"layer_{i}"], bias(f"layer_{i}"), x)
            choices += [] if ids is None else [ids[None]]
        logits = [head(x, params["final_norm"]["scale"])]
        for k in range(cfg["num_nextn_predict_layers"]):
            ahead = embedding[jnp.roll(tokens[0], -(k + 1))]
            x = jnp.concatenate(
                [_rms_norm(x, params[f"mtp_{k}_h_norm"]["scale"], eps),
                 _rms_norm(ahead, params[f"mtp_{k}_e_norm"]["scale"], eps)],
                axis=-1) @ params[f"mtp_{k}_proj"]["kernel"]
            x, ids = _block(cfg, params[f"mtp_{k}_block"], bias(f"mtp_{k}_block"), x)
            choices.append(ids[None])
            logits.append(head(x, params[f"mtp_{k}_final_norm"]["scale"]))
    return jnp.stack(logits), choices


def plain_loss(cfg: dict, params, routing, batch):
    """The training loss of one rank's ``(tokens, targets, mtp_targets)`` from
    the plain forward, a sequence at a time: mean cross-entropy of the next
    token plus ``mtp_loss_weight`` times each MTP module's."""
    tokens, targets, mtp_targets = batch

    def one(sequence):
        logits, _ = plain_forward(cfg, params, routing, sequence[0][None], tokens.shape[1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = lambda lp, t: -jnp.mean(jnp.take_along_axis(lp, t[:, None], axis=-1))
        total = nll(logp[0], sequence[1])
        for k in range(cfg["num_nextn_predict_layers"]):
            total += cfg["mtp_loss_weight"] * nll(logp[1 + k], jnp.roll(sequence[2], -k))
        return total

    return jnp.mean(jax.lax.map(one, (tokens, targets, mtp_targets)))
