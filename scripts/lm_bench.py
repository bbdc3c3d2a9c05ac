"""Transformer-LM headline benchmark: tokens/s and MFU on the real chip.

The reference's benchmark methodology (examples/pytorch_benchmark.py:
synthetic data, warmup, timed window, throughput printout) applied to the
long-context LM path this framework adds on top of reference parity:
flash-attention forward + flash-attention-2 backward kernels, bf16 compute,
one jitted train step. Reports ms/step, tokens/s, and model FLOPs
utilization against the bf16 peak of the device kind it ran on (bench.PEAKS).
Runs on a TPU only.

FLOPs accounting (PaLM-style model FLOPs, causal):
  matmul params: 6 * N_matmul * tokens   (fwd + bwd)
  attention:     12 * L * B * S^2 * d_model * 0.5

Run: python scripts/lm_bench.py [--seq-len 8192] [--d-model 2048] ...
Prints one JSON line per config, and appends nothing — PERF.md records the
numbers.
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
import optax

import sys, os
import tempfile
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Flight dumps from a bench run land in a tempdir instead of littering
# the CWD (conftest's default for the test suite); an explicit
# BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))

from bench import peaks, require_tpu  # noqa: E402
from bluefog_tpu.models import TransformerLM  # noqa: E402
from bluefog_tpu.parallel.flash import flash_attention  # noqa: E402


def matmul_param_count(params) -> int:
    """Parameters that induce matmul FLOPs: every >=2-D kernel EXCEPT the
    embedding table (a gather, not a matmul; the lm_head projection is a
    separate kernel and is counted)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return sum(
        int(np.prod(p.shape)) for path, p in flat
        if hasattr(p, "shape") and len(p.shape) >= 2
        and "embed" not in jax.tree_util.keystr(path).lower())


def run(seq_len: int, d_model: int, num_layers: int, num_heads: int,
        batch: int, vocab: int, steps: int, warmup: int, remat: bool,
        chunked_ce: bool = False, ce_chunk: int = 1024):
    device = jax.devices()[0]  # the bare jitted step runs on the default one
    stamp = require_tpu([device])
    model = TransformerLM(
        vocab_size=vocab, num_layers=num_layers, num_heads=num_heads,
        d_model=d_model, d_ff=4 * d_model, dtype=jnp.bfloat16,
        attn_fn=partial(flash_attention, causal=True))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq_len),
                                0, vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(lambda k: model.init(k, tokens)["params"])(
        jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, batch_):
        toks, tgts = batch_
        if chunked_ce:
            # exact CE without materializing the [S, V] logits (1 GB at
            # the headline config) — see parallel.chunked_ce_loss
            from bluefog_tpu.parallel import chunked_ce_loss
            return chunked_ce_loss(model, p, toks, tgts, chunk=ce_chunk,
                                   remat_backbone=remat)
        apply = model.apply
        if remat:
            apply = jax.checkpoint(model.apply)
        logits = apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts).mean()

    @jax.jit
    def step(p, s, batch_):
        l, g = jax.value_and_grad(loss_fn)(p, batch_)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, l

    if steps < 1:
        raise ValueError("--steps must be >= 1")
    batch_ = (tokens, targets)
    for _ in range(warmup):
        params, opt_state, l = step(params, opt_state, batch_)
    jax.block_until_ready(l)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, l = step(params, opt_state, batch_)
    jax.block_until_ready(l)  # dispatch is asynchronous: wait for the last step
    dt = (time.perf_counter() - t0) / steps

    n_mat = matmul_param_count(params)
    tokens_per_step = batch * seq_len
    flops = (6 * n_mat * tokens_per_step
             + 12 * num_layers * batch * seq_len ** 2 * d_model * 0.5)
    result = {
        "metric": "lm_tokens_per_s",
        "seq_len": seq_len, "d_model": d_model, "layers": num_layers,
        "batch": batch, "params_m": round(n_mat / 1e6, 1),
        "ms_per_step": round(dt * 1e3, 2),
        "value": round(tokens_per_step / dt),
        "unit": "tokens/s",
        "mfu": round(flops / dt / peaks(device)["bf16_flops"], 3),
        "final_loss": round(float(np.asarray(l)), 3),
        "device": stamp,
    }
    print(json.dumps(result), flush=True)
    return result


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--d-model", type=int, default=2048)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=16)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--remat", action="store_true",
                   help="checkpoint the whole forward (longer S fits)")
    p.add_argument("--chunked-ce", action="store_true",
                   help="chunked vocab projection + CE (no [S, V] logits)")
    p.add_argument("--ce-chunk", type=int, default=1024)
    a = p.parse_args()
    run(a.seq_len, a.d_model, a.num_layers, a.num_heads, a.batch, a.vocab,
        a.steps, a.warmup, a.remat, a.chunked_ce, a.ce_chunk)


if __name__ == "__main__":
    main()
