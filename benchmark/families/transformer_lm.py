"""Family ``transformer_lm``: the repo's decoder-only ``TransformerLM`` at the
sizes of a GPT-NeoX style ``config.json`` (``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_hidden_layers``, ``vocab_size``).

What the harness takes from a family: the model and loss as a user's training
script would write them, the batch maker, the units and model FLOPs of one
step from shapes, and ``plain_logits`` -- the forward pass again in plain
float32 ``jax.numpy``, written from the block's equations and sharing no code
with ``bluefog_tpu.models``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import optax

THROUGHPUT_METRIC = "tokens_per_s_per_chip"
# logits of this many last positions of one sequence are compared with the
# plain forward: they see the whole context, and [256, V] f32 is 50 MB
CHECK_POSITIONS = 256


def model(cfg: dict):
    import bluefog_tpu as bf
    from bluefog_tpu.parallel.flash import flash_attention

    attention = {"flash": partial(flash_attention, causal=True),
                 "dense": None}[cfg["attention"]]  # None: the model's dense default
    return bf.models.TransformerLM(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], dtype=jnp.dtype(cfg["compute_dtype"]),
        attn_fn=attention)


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this."""
    tokens = jnp.zeros((1, batch["seq_len"]), jnp.int32)
    return model(cfg).init(key, tokens)["params"], None


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form)."""
    net = model(cfg)

    def loss_fn(params, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            net.apply({"params": params}, tokens), targets).mean()

    return loss_fn, {}


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: uniform random tokens, next-token targets."""
    tokens = jax.random.randint(
        key, (n, batch["sequences"], batch["seq_len"]), 0, cfg["vocab_size"])
    return tokens, jnp.roll(tokens, -1, axis=2)


def units_per_step(batch: dict) -> int:
    return batch["sequences"] * batch["seq_len"]


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply every token: qkv, out, up, down of each layer
    and the untied head (the embedding is a gather)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f) + d * cfg["vocab_size"]


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip, forward and backward, causal
    (scripts/lm_bench.py's accounting): 6 N_matmul tokens + 12 L B S^2 d / 2.
    Recomputation inside the flash backward is not counted."""
    b, s = batch["sequences"], batch["seq_len"]
    return (6.0 * matmul_params(cfg) * b * s
            + 12.0 * cfg["num_hidden_layers"] * b * s * s * cfg["hidden_size"] * 0.5)


def check_inputs(batch_of_rank):
    """What both forwards below are given: the first sequence of a batch."""
    tokens, _ = batch_of_rank
    return tokens[:1]


def system_logits(cfg: dict, params, model_state, tokens):
    """The program's own forward (flash kernels, compute dtype)."""
    return model(cfg).apply({"params": params}, tokens)[:, -CHECK_POSITIONS:]


def _rms_norm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, base):
    """Rotation by halves over the whole head: x [S, H, D]."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _causal_attention(q, k, v, block=512):
    """softmax(q k^T / sqrt(D)) v with a causal mask, a block of queries at a
    time so that [H, block, S] scores are all that is held. q, k, v [S, H, D]."""
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
    return out.reshape(s, *q.shape[1:])


def plain_logits(cfg: dict, params, model_state, tokens):
    """Plain reference forward in float32 at the highest matmul precision:
    pre-norm block with RMSNorm, fused qkv without bias, rotary by halves, causal
    softmax attention, tanh-GELU MLP, sequential residuals, untied head."""
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens[0]]  # [S, d]
        s, d = x.shape
        for i in range(layers):
            blk = params[f"block_{i}"]
            h = _rms_norm(x, blk["RMSNorm_0"]["scale"])
            q, k, v = jnp.split(h @ blk["qkv"]["kernel"], 3, axis=-1)
            q, k, v = (t.reshape(s, heads, d // heads) for t in (q, k, v))
            q, k = _rope(q, cfg["rotary_emb_base"]), _rope(k, cfg["rotary_emb_base"])
            x = x + _causal_attention(q, k, v).reshape(s, d) @ blk["out"]["kernel"]
            h = _rms_norm(x, blk["RMSNorm_1"]["scale"])
            h = jax.nn.gelu(h @ blk["up"]["kernel"], approximate=True)
            x = x + h @ blk["down"]["kernel"]
        x = _rms_norm(x[-CHECK_POSITIONS:], params["final_norm"]["scale"])
        return (x @ params["lm_head"]["kernel"])[None]
