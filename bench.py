"""Benchmark: ResNet-50 decentralized training throughput.

Port of the reference harness (examples/pytorch_benchmark.py: synthetic
ImageNet batches, 10 warmup batches, then 10 iterations x 10 batches). The
timed window covers all 100 batches and is closed by ONE
``jax.block_until_ready`` on the last step's loss. It runs the flagship fused
step — per-chip grad -> SGD-momentum update -> Expo-2 neighbor averaging —
over all available chips. Baseline for vs_baseline: the reference's published
`Total img/sec on 16 GPU(s): 4310.6` => 269.4 img/sec per V100
(docs/performance.rst:20-24). Batch is 128/chip (the reference uses 64/V100;
128 keeps the v5e MXU fed — 64 leaves ~15% throughput on the table and the
reference's own harness exposes --batch-size for exactly this reason).

Runs on TPU devices only: a CPU timing is not this metric, so any other
platform is an error. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "device"}.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax


# Flight dumps from a bench run land in a tempdir instead of littering
# the CWD (conftest's default for the test suite); an explicit
# BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))

import bluefog_tpu as bf
from bluefog_tpu.models import ResNet50
from bluefog_tpu.utils import prefetch_to_device

BATCH_PER_CHIP = 128
IMAGE = 224
WARMUP = 10
ITERS = 10
BATCHES_PER_ITER = 10
BASELINE_IMG_SEC_PER_DEVICE = 4310.6 / 16  # reference 16xV100 result

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it
# (Google Cloud documentation, "TPU v5e"). The denominators of every MFU and
# roofline figure; a kind that is not here is an error, not a default.
PEAKS = {"TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def peaks(device) -> dict:
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"no peaks recorded for device kind {device.device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def require_tpu(devices) -> dict:
    """The device stamp of a measurement; exits unless every device is a TPU
    (a number taken on another platform is not a device metric)."""
    found = sorted({d.platform for d in devices})
    if found != ["tpu"]:
        raise SystemExit(
            f"this measurement needs TPU devices; JAX found platform(s) "
            f"{found} ({devices[0].device_kind})")
    return {"platform": "tpu", "kind": devices[0].device_kind,
            "count": len(devices)}


def setup(batch_per_chip: int = BATCH_PER_CHIP, synthetic_batch: bool = True):
    """Build the benchmark step: (opt, state, batch). Caller owns
    ``bf.shutdown()``. Shared with scripts/batch_sweep.py so batch-size
    probes measure exactly the benchmarked step. ``synthetic_batch=False``
    skips building the device-resident batch (host-data mode feeds its own
    — no point holding 77 MB/chip of unused HBM)."""
    n = len(jax.devices())
    topo = bf.topology_util.ExponentialTwoGraph(n) if n > 1 else \
        bf.topology_util.FullyConnectedGraph(1)
    bf.init(topology_fn=lambda size: topo)
    require_tpu(list(bf.mesh().devices.flat))

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    # one compiled program (eager init is hundreds of one-op compiles, none
    # long enough for the persistent cache to keep)
    variables = jax.jit(lambda k: model.init(
        k, jnp.zeros((batch_per_chip, IMAGE, IMAGE, 3), jnp.float32),
        train=True))(rng)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(p, ms, batch):
        images, labels = batch
        if images.dtype == jnp.uint8:
            # host-fed path ships uint8 (4x fewer wire bytes than f32, the
            # standard input-pipeline format); normalize on device
            images = images.astype(jnp.float32) / 127.5 - 1.0
        logits, updates = model.apply(
            {"params": p, "batch_stats": ms}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, (updates["batch_stats"], {})

    opt = bf.DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1, momentum=0.9), loss_fn, with_model_state=True)
    state = opt.init(params, model_state=batch_stats)

    batch = None
    if synthetic_batch:
        # each rank's slice is generated on its own device
        sh = bf.rank_sharding(bf.mesh())
        batch = jax.jit(
            lambda k: (jax.random.normal(
                k, (n, batch_per_chip, IMAGE, IMAGE, 3), jnp.float32),
                jnp.zeros((n, batch_per_chip), jnp.int32)),
            out_shardings=(sh, sh))(rng)

    return opt, state, batch


def host_batch_pool(n: int, batch_per_chip: int, pool: int = 4,
                    image: int = IMAGE):
    """Endless cycle over ``pool`` distinct HOST (numpy) uint8 batches —
    the stand-in for a real data loader (the reference cycles a fake
    torchvision dataset the same way, pytorch_benchmark.py)."""
    rng = np.random.default_rng(7)
    batches = [
        (rng.integers(0, 256, (n, batch_per_chip, image, image, 3),
                      dtype=np.uint8),
         rng.integers(0, 1000, (n, batch_per_chip), dtype=np.int32))
        for _ in range(pool)
    ]
    return itertools.cycle(batches)


def main(host_data: bool = False, prefetch: int = 2,
         steps_scale: float = 1.0) -> None:
    opt, state, batch = setup(synthetic_batch=not host_data)
    iters = max(1, round(ITERS * steps_scale))

    if host_data:
        # real host->HBM traffic: uint8 batches from a host pool, device_put
        # kept `prefetch` deep so the copy of batch t+1 overlaps step t
        n = len(jax.devices())
        feed = prefetch_to_device(
            host_batch_pool(n, BATCH_PER_CHIP), size=prefetch,
            sharding=bf.rank_sharding(bf.mesh()))
        metric = "resnet50_train_img_per_sec_per_chip_hostfeed"
    else:
        feed = itertools.repeat(batch)
        metric = "resnet50_train_img_per_sec_per_chip"

    for _ in range(WARMUP):
        state, metrics = opt.step(state, next(feed))
    jax.block_until_ready(metrics["loss"])

    # Dispatch is asynchronous: the window is one run of steps closed by
    # waiting for the last step's loss, which every earlier step precedes.
    t0 = time.perf_counter()
    for _ in range(iters):
        for _ in range(BATCHES_PER_ITER):
            state, metrics = opt.step(state, next(feed))
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    per_device = BATCH_PER_CHIP * BATCHES_PER_ITER * iters / dt
    print(json.dumps({
        "metric": metric,
        "value": round(per_device, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(per_device / BASELINE_IMG_SEC_PER_DEVICE, 3),
        "device": require_tpu(list(bf.mesh().devices.flat)),
    }))


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--host-data", action="store_true",
                   help="feed uint8 batches from host memory through the "
                        "double-buffered prefetcher (real host->HBM traffic)")
    p.add_argument("--prefetch", type=int, default=2,
                   help="in-flight host transfers; note the timed window "
                        "has no per-step sync, so async step dispatch "
                        "already overlaps transfers with queued compute — "
                        "1 vs 2 is a queue-depth knob here, not a clean "
                        "overlap A/B (examples/resnet.py, which syncs per "
                        "step, shows the prefetch effect directly)")
    p.add_argument("--steps-scale", type=float, default=1.0,
                   help="scale the timed iteration count")
    a = p.parse_args()
    main(host_data=a.host_data, prefetch=a.prefetch,
         steps_scale=a.steps_scale)
