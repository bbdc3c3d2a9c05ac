"""ResNet training — the full training-loop port of the reference's
examples/pytorch_resnet.py (407 lines: warmup + piecewise LR decay, gradient
accumulation via --batches-per-allreduce, per-batch dynamic topology,
validation accuracy, checkpoint/resume).

TPU-native differences:
  * the dataset is a deterministic synthetic CIFAR-shaped mixture (class-
    conditioned gaussians) so the example is runnable with zero downloads;
    swap :func:`synthetic_dataset` for a real input pipeline in production;
  * the LR schedule is an optax schedule compiled INTO the fused train step
    (the reference mutates param_group["lr"] host-side per batch,
    pytorch_resnet.py:309-325) — same warmup 1x -> size-x ramp over
    ``--warmup-epochs`` then /10 decays at epochs 30/60/80;
  * gradient accumulation uses ``num_steps_per_communication`` (the
    framework's local-step knob, the analog of batches-per-allreduce);
  * checkpoints are orbax directories via bluefog_tpu.checkpoint (the
    reference saves torch .pth.tar from rank 0, :378-385).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bluefog_tpu as bf


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet18",
                   choices=["resnet18", "resnet34", "resnet50"])
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-rank training batch size")
    p.add_argument("--val-batch-size", type=int, default=32)
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="per-rank base learning rate (scaled by size)")
    p.add_argument("--warmup-epochs", type=float, default=5)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=5e-5)
    p.add_argument("--batches-per-allreduce", type=int, default=1,
                   help="local steps per communication round")
    p.add_argument("--dist-optimizer", default="neighbor_allreduce",
                   choices=["neighbor_allreduce", "gradient_allreduce",
                            "allreduce", "win_put"])
    p.add_argument("--disable-dynamic-topology", action="store_true")
    p.add_argument("--checkpoint-format", default=None,
                   help="e.g. /tmp/ckpt-{epoch}; enables save per epoch")
    p.add_argument("--resume-from", default=None,
                   help="checkpoint directory to resume from")
    p.add_argument("--steps-per-epoch", type=int, default=40,
                   help="synthetic-data batches per epoch")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--seed", type=int, default=42)
    return p.parse_args(argv)


def synthetic_dataset(key, n_ranks, batch, steps, image_size, classes,
                      centers=None):
    """Class-conditioned gaussian 'images': learnable, deterministic, tiny.

    Returns rank-stacked arrays [steps, n_ranks, batch, H, W, 3], labels
    [steps, n_ranks, batch], and the class centers — each rank sees a
    disjoint shard, like the reference's DistributedSampler split. Pass the
    TRAIN set's ``centers`` when building the validation set: train and val
    must sample the same class-conditional distribution.
    """
    kc, kx, kl = jax.random.split(key, 3)
    if centers is None:
        centers = jax.random.normal(kc, (classes, 3)) * 2.0
    labels = jax.random.randint(kl, (steps, n_ranks, batch), 0, classes)
    noise = jax.random.normal(kx, (steps, n_ranks, batch,
                                   image_size, image_size, 3))
    images = centers[labels][:, :, :, None, None, :] + noise
    return np.asarray(images, np.float32), np.asarray(labels, np.int32), centers


def make_lr_schedule(args, size, steps_per_epoch):
    """Warmup 1x -> size-x over warmup_epochs, then /10 at ABSOLUTE epochs
    30/60/80 (same boundaries as the reference's adjust_learning_rate,
    pytorch_resnet.py:305-325 — the decay epochs do not shift by warmup).
    """
    warmup_steps = max(int(args.warmup_epochs * steps_per_epoch), 1)
    peak = args.base_lr * size * args.batches_per_allreduce
    warmup = optax.linear_schedule(
        init_value=args.base_lr * args.batches_per_allreduce,
        end_value=peak, transition_steps=warmup_steps)

    def schedule(step):
        step = jnp.asarray(step)
        lr = jnp.where(step < warmup_steps, warmup(step), peak)
        n_decays = ((step >= 30 * steps_per_epoch).astype(jnp.float32)
                    + (step >= 60 * steps_per_epoch)
                    + (step >= 80 * steps_per_epoch))
        return lr * 10.0 ** (-n_decays)

    return schedule


def build(args, devices=None):
    bf.init(devices=devices)
    n = bf.size()
    model_cls = {"resnet18": bf.models.ResNet18,
                 "resnet34": bf.models.ResNet34,
                 "resnet50": bf.models.ResNet50}[args.model]
    model = model_cls(num_classes=args.classes)
    sample = jnp.zeros((args.batch_size, args.image_size, args.image_size, 3),
                       jnp.float32)
    variables = model.init(jax.random.PRNGKey(args.seed), sample, train=True)

    def loss_fn(p, ms, batch):
        images, labels = batch
        logits, updates = model.apply(
            {"params": p, "batch_stats": ms}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, (updates["batch_stats"], {"acc": acc})

    schedule = make_lr_schedule(args, n, args.steps_per_epoch)
    base = optax.chain(
        optax.add_decayed_weights(args.wd),
        optax.sgd(schedule, momentum=args.momentum),
    )
    opts = {
        "neighbor_allreduce": bf.DistributedNeighborAllreduceOptimizer,
        "gradient_allreduce": bf.DistributedGradientAllreduceOptimizer,
        "allreduce": bf.DistributedAllreduceOptimizer,
        "win_put": bf.DistributedWinPutOptimizer,
    }
    opt = opts[args.dist_optimizer](base, loss_fn, with_model_state=True)
    opt.num_steps_per_communication = args.batches_per_allreduce

    state = opt.init(variables["params"], model_state=variables["batch_stats"])
    start_epoch = 0
    if args.resume_from:
        state, step = bf.checkpoint.restore(args.resume_from, template=state)
        start_epoch = int(step)
        print(f"resumed from {args.resume_from} at epoch {start_epoch}")
    return model, opt, state, start_epoch


def evaluate(model, state, images, labels):
    """Validation accuracy of each rank's model, then the rank-mean.

    The reference averages per-rank metrics with allreduce (:291-301).
    """
    params = state.params

    def apply_one(p, ms, x):
        return model.apply({"params": p, "batch_stats": ms}, x, train=False)

    accs = []
    for s in range(images.shape[0]):
        logits = jax.vmap(apply_one)(params, state.model_state,
                                     jnp.asarray(images[s]))
        accs.append(np.asarray(
            (logits.argmax(-1) == jnp.asarray(labels[s])).mean(axis=(1,))))
    per_rank = np.mean(np.stack(accs), axis=0)  # [n]
    return float(per_rank.mean()), per_rank


def train(args, devices=None):
    model, opt, state, start_epoch = build(args, devices)
    n = bf.size()
    key = jax.random.PRNGKey(args.seed)
    tr_images, tr_labels, centers = synthetic_dataset(
        key, n, args.batch_size, args.steps_per_epoch,
        args.image_size, args.classes)
    va_images, va_labels, _ = synthetic_dataset(
        jax.random.PRNGKey(args.seed + 1), n, args.val_batch_size,
        max(args.steps_per_epoch // 4, 1), args.image_size, args.classes,
        centers=centers)

    dynamic = (not args.disable_dynamic_topology and n > 1 and
               args.dist_optimizer == "neighbor_allreduce")
    if dynamic:
        gens = [bf.topology_util.GetDynamicSendRecvRanks(bf.load_topology(), r)
                for r in range(n)]

    sh = bf.rank_sharding(bf.mesh())
    history = []
    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        losses = []
        # double-buffered host->device feeding: the copy of batch s+1 is in
        # flight while step s computes (bf.utils.prefetch_to_device)
        feed = bf.utils.prefetch_to_device(
            ((tr_images[s], tr_labels[s])
             for s in range(args.steps_per_epoch)), size=2, sharding=sh)
        for s in range(args.steps_per_epoch):
            if dynamic:
                sends = {r: next(g)[0] for r, g in enumerate(gens)}
                recv = {r: [] for r in range(n)}
                for src, dsts in sends.items():
                    for d in dsts:
                        recv[d].append(src)
                opt.send_neighbors = sends
                opt.self_weight = {r: 1.0 / (len(recv[r]) + 1)
                                   for r in range(n)}
                opt.neighbor_weights = {
                    r: {s_: 1.0 / (len(recv[r]) + 1) for s_ in recv[r]}
                    for r in range(n)}
            state, metrics = opt.step(state, next(feed))
            losses.append(float(np.asarray(metrics["loss"]).mean()))
        val_acc, _ = evaluate(model, state, va_images, va_labels)
        dt = time.perf_counter() - t0
        print(f"epoch {epoch}: loss {np.mean(losses):.4f} "
              f"val_acc {val_acc:.3f} ({dt:.1f}s)")
        history.append((np.mean(losses), val_acc))
        if args.checkpoint_format:
            path = args.checkpoint_format.format(epoch=epoch + 1)
            bf.checkpoint.save(path, state, step=epoch + 1)
    return history, state


if __name__ == "__main__":
    train(parse_args())
