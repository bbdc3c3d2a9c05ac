"""Throughput benchmark — port of the reference harness.

Mirrors examples/pytorch_benchmark.py of the reference (arg surface at
:52-60; synthetic data; warmup then timed iterations of ``num_batches_per_iter``
batches; img/sec mean ± CI): ResNet on synthetic ImageNet-shaped batches, one
model replica per chip, the chosen distributed optimizer doing the
communication. The dynamic Expo-2 one-peer schedule is on by default exactly
like the reference (``--disable-dynamic-topology`` restores the static graph).

What this keeps is the reference's surface: every ``--dist-optimizer`` mode
behind one command. The img/sec it prints is a count on whatever backend it
ran on (the CPU mesh, in the tests) and goes into no record; the chip's
numbers are the benchmark's (``benchmark/run.py``, PERF_LEDGER.jsonl).

Run (single host, all chips):   python examples/benchmark.py
Simulated 8-device CPU mesh:    bfrun --simulate 8 -- python examples/benchmark.py \
                                    --model mlp --batch-size 8 --num-iters 3
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


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet34", "resnet18", "vgg16",
                            "mlp", "lm"])
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-chip batch size")
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--dist-optimizer", default="neighbor_allreduce",
                   choices=["neighbor_allreduce", "allreduce",
                            "gradient_allreduce", "hierarchical_neighbor_allreduce",
                            "win_put", "push_sum", "pull_get",
                            "sharded_allreduce", "local"])
    p.add_argument("--disable-dynamic-topology", action="store_true",
                   help="use the static topology instead of the one-peer "
                        "dynamic Expo-2 schedule")
    p.add_argument("--image-size", type=int, default=224)
    return p.parse_args()


def make_model(args):
    if args.model == "mlp":
        model = bf.models.MLP(features=(512, 512, 10))
        sample = jax.ShapeDtypeStruct((args.batch_size, 32, 32, 3),
                                      jnp.float32)
        classes = 10
    elif args.model == "lm":
        # LM-shaped param tree — embedding + attention-block + norm
        # leaves — the fixture the sharded-window partition rules are
        # exercised on (opt_matrix_bench --sharded, ISSUE r17)
        model = bf.models.TransformerLM(
            vocab_size=512, num_layers=2, num_heads=4, d_model=128,
            d_ff=512)
        sample = jax.ShapeDtypeStruct((args.batch_size, 32), jnp.int32)
        classes = 512
    else:
        cls = {"resnet50": bf.models.ResNet50, "resnet34": bf.models.ResNet34,
               "resnet18": bf.models.ResNet18, "vgg16": bf.models.VGG16}[args.model]
        model = cls(num_classes=1000, dtype=jnp.bfloat16)
        sample = jax.ShapeDtypeStruct(
            (args.batch_size, args.image_size, args.image_size, 3), jnp.float32)
        classes = 1000
    return model, sample, classes


def main():
    args = parse_args()
    bf.init()
    n = bf.size()
    model, sample, classes = make_model(args)
    rng = jax.random.PRNGKey(0)
    is_lm = args.model == "lm"
    has_bn = args.model not in ("mlp", "lm")
    # one compiled program (eager init is hundreds of one-op compiles)
    init_kw = {} if is_lm else {"train": True}
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros(sample.shape, sample.dtype), **init_kw))(rng)

    if has_bn:
        # Dropout-bearing models (vgg16) train with their standard dropout
        # active, like the reference harness. Folding a traced value into
        # the key keeps mask generation inside the compiled step (a plain
        # closed-over key is a compile-time constant XLA could fold away),
        # so the measured compute matches a real training step.
        use_dropout = args.model == "vgg16"

        def loss_fn(p, ms, batch):
            images, labels = batch
            rngs = {"dropout": jax.random.fold_in(
                jax.random.PRNGKey(1), labels[0])} if use_dropout else None
            logits, updates = model.apply(
                {"params": p, "batch_stats": ms}, images, train=True,
                mutable=["batch_stats"], rngs=rngs)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, (updates["batch_stats"], {})
        kw = {"with_model_state": True}
    elif is_lm:
        def loss_fn(p, batch):
            tokens, labels = batch
            logits = model.apply({"params": p}, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        kw = {}
    else:
        def loss_fn(p, batch):
            images, labels = batch
            logits = model.apply({"params": p}, images)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        kw = {}

    base = optax.sgd(0.01, momentum=0.9)
    opts = {
        "neighbor_allreduce": bf.DistributedNeighborAllreduceOptimizer,
        "allreduce": bf.DistributedAllreduceOptimizer,
        "gradient_allreduce": bf.DistributedGradientAllreduceOptimizer,
        "sharded_allreduce": bf.DistributedShardedAllreduceOptimizer,
        "hierarchical_neighbor_allreduce":
            bf.DistributedHierarchicalNeighborAllreduceOptimizer,
        "win_put": bf.DistributedWinPutOptimizer,
        "pull_get": bf.DistributedPullGetOptimizer,
        "push_sum": bf.DistributedPushSumOptimizer,
        "local": bf.DistributedNeighborAllreduceOptimizer,
    }
    opt = opts[args.dist_optimizer](base, loss_fn, **kw)
    if args.dist_optimizer == "local":
        opt.num_steps_per_communication = 10**9

    state = opt.init(
        variables["params"],
        model_state=variables.get("batch_stats") if has_bn else None)

    if is_lm:
        images = jax.device_put(
            np.random.RandomState(0).randint(
                0, classes, size=(n, *sample.shape)).astype(np.int32),
            bf.rank_sharding(bf.mesh()))
        labels = jax.device_put(
            jnp.zeros((n, *sample.shape), jnp.int32),
            bf.rank_sharding(bf.mesh()))
    else:
        images = jax.device_put(
            np.random.RandomState(0).randn(
                n, *sample.shape).astype(np.float32),
            bf.rank_sharding(bf.mesh()))
        labels = jax.device_put(
            jnp.zeros((n, args.batch_size), jnp.int32),
            bf.rank_sharding(bf.mesh()))
    batch = (images, labels)

    dynamic = (not args.disable_dynamic_topology and
               args.dist_optimizer == "neighbor_allreduce" and n > 1)
    if dynamic:
        gens = [bf.topology_util.GetDynamicSendRecvRanks(bf.load_topology(), r)
                for r in range(n)]

    def set_dynamic():
        sends = {}
        for r, g in enumerate(gens):
            to, _ = next(g)
            sends[r] = to
        recv_from = {r: [] for r in range(n)}
        for s, dsts in sends.items():
            for d in dsts:
                recv_from[d].append(s)
        opt.send_neighbors = sends
        opt.self_weight = {r: 1.0 / (len(recv_from[r]) + 1) for r in range(n)}
        opt.neighbor_weights = {
            r: {s: 1.0 / (len(recv_from[r]) + 1) for s in recv_from[r]}
            for r in range(n)}

    last_metrics = [None]

    def one_step(st):
        if dynamic:
            set_dynamic()
        st, m = opt.step(st, batch)
        last_metrics[0] = m
        return st

    def sync():
        # dispatch is asynchronous: wait for the last step's loss
        jax.block_until_ready(last_metrics[0]["loss"])

    print(f"Model: {args.model}, batch {args.batch_size}/chip, "
          f"{n} chip(s), optimizer={args.dist_optimizer}, "
          f"dynamic_topology={dynamic}")
    for _ in range(args.num_warmup_batches):
        state = one_step(state)
    sync()

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state = one_step(state)
        sync()
        dt = time.perf_counter() - t0
        rate = args.batch_size * args.num_batches_per_iter * n / dt
        img_secs.append(rate)
        print(f"Iter #{i}: {rate:.1f} img/sec total")

    mean = np.mean(img_secs)
    conf = 1.96 * np.std(img_secs)
    print(f"Total img/sec on {n} chip(s): {mean:.1f} +-{conf:.1f}")


if __name__ == "__main__":
    main()
