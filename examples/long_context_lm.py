"""Long-context LM training with ring-attention sequence parallelism.

The sequence dimension is sharded across all chips: each holds S/n tokens,
K/V blocks rotate around the ICI ring (`bluefog_tpu.parallel.ring_attention`),
so the trainable context length scales linearly with the mesh size. This is
the capability the reference framework never had (it predates attention);
here it rides the same ring machinery as `neighbor_allreduce`.

Run (simulated 8-device mesh):
    bfrun --simulate 8 -- python examples/long_context_lm.py --seq-len 512

``--attention flash`` instead trains full-sequence on ONE chip through the
pallas flash kernel (custom VJP, no [S, S] scores in either direction) —
the single-device long-context path for when a mesh isn't available.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

import bluefog_tpu as bf
from bluefog_tpu import parallel as bfp
from bluefog_tpu.models import TransformerLM


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=2)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--attention", default="ring",
                   choices=["ring", "ulysses", "flash"])
    p.add_argument("--interpret", action="store_true",
                   help="run the flash kernels in the Pallas interpreter "
                        "(a CPU mesh has no Mosaic lowering); never the "
                        "default, so a chip run is a compiled-kernel run")
    args = p.parse_args()

    bf.init()
    n = bf.size()
    if args.attention != "flash" and args.seq_len % n:
        raise SystemExit(f"--seq-len must be divisible by {n} chips")

    attn_fn = None
    if args.attention == "flash":
        from functools import partial
        from bluefog_tpu.parallel.flash import flash_attention
        attn_fn = partial(flash_attention, causal=True,
                          interpret=args.interpret)
    model = TransformerLM(
        vocab_size=args.vocab, num_layers=args.num_layers,
        num_heads=args.num_heads, d_model=args.d_model,
        d_ff=4 * args.d_model, dtype=jnp.bfloat16, attn_fn=attn_fn)

    rng = np.random.RandomState(0)
    # synthetic "copy task"-flavored data: next token = current + 1 mod V
    start = rng.randint(0, args.vocab, (args.batch_size, 1))
    tokens = (start + np.arange(args.seq_len)) % args.vocab
    tokens = jnp.asarray(tokens, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    params = model.init(jax.random.PRNGKey(0), tokens[:, : args.seq_len])["params"]
    if args.attention == "flash":
        def loss_fn(p_, batch):
            x, y = batch
            logits = model.apply({"params": p_}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
    else:
        loss_fn = bfp.cp_loss_fn(model, kind=args.attention)
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p_, s_, batch):
        l, g = jax.value_and_grad(loss_fn)(p_, batch)
        updates, s_ = opt.update(g, s_, p_)
        return optax.apply_updates(p_, updates), s_, l

    if args.attention == "flash":
        # no sequence sharding: one chip owns the full context (the kernel,
        # not the mesh, is what makes the length affordable)
        print(f"seq {args.seq_len} full-sequence on one chip, flash attention")
    else:
        print(f"{n} chip(s), seq {args.seq_len} ({args.seq_len // n}/chip), "
              f"{args.attention} attention")
    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, (tokens, targets))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")
    print(f"{args.steps} steps in {time.time() - t0:.1f}s; "
          f"final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
