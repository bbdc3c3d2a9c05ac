"""Family ``resnet``: the repo's ResNet v1.5 (``bluefog_tpu.models.ResNet``) at
the ``stage_sizes`` / ``num_filters`` / ``num_classes`` of its configuration,
trained on seeded images with batch norm in training mode.

See ``transformer_lm.py`` for what the harness takes from a family.
``plain_logits`` is the network again in plain float32 ``jax.numpy`` and
``lax.conv_general_dilated``, written from the architecture (He et al. 2015,
stride on the 3x3 as torchvision has it) and sharing no code with
``bluefog_tpu.models``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

THROUGHPUT_METRIC = "img_per_s_per_chip"
CHECK_IMAGES = 16  # both forwards see the first 16 images of a batch


def _block(cfg: dict):
    from bluefog_tpu.models import resnet

    return {"bottleneck": resnet.BottleneckBlock, "basic": resnet.BasicBlock}[cfg["block"]]


def model(cfg: dict):
    import bluefog_tpu as bf

    return bf.models.ResNet(
        stage_sizes=cfg["stage_sizes"], block_cls=_block(cfg),
        num_classes=cfg["num_classes"], num_filters=cfg["num_filters"],
        dtype=jnp.dtype(cfg["compute_dtype"]))


def _images(cfg: dict, count: int):
    return (count, cfg["image_size"], cfg["image_size"], 3)


def init(cfg: dict, batch: dict, key):
    """(params, model_state) of one rank; the harness jits this."""
    variables = model(cfg).init(
        key, jnp.zeros(_images(cfg, batch["images"]), jnp.float32), train=True)
    return variables["params"], variables["batch_stats"]


def loss(cfg: dict):
    """(loss_fn, keyword arguments of the bf optimizer that say its form)."""
    net = model(cfg)

    def loss_fn(params, batch_stats, batch):
        images, labels = batch
        logits, updates = net.apply(
            {"params": params, "batch_stats": batch_stats}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        return loss, (updates["batch_stats"], {})

    return loss_fn, {"with_model_state": True}


def make_batch(cfg: dict, batch: dict, key, n: int):
    """One rank-stacked batch: normal images, labels uniform over the classes
    (all-zero labels are learnt in five steps, after which the loss says nothing)."""
    k_img, k_lab = jax.random.split(key)
    images = jax.random.normal(k_img, (n,) + _images(cfg, batch["images"]), jnp.float32)
    labels = jax.random.randint(k_lab, (n, batch["images"]), 0, cfg["num_classes"])
    return images, labels


def units_per_step(batch: dict) -> int:
    return batch["images"]


def _conv_shapes(cfg: dict):
    """(kernel, c_in, c_out, output side) of every convolution, in order."""
    side = cfg["image_size"] // 2
    shapes = [(7, 3, cfg["num_filters"], side)]
    side //= 2  # max pool
    c_in = cfg["num_filters"]
    bottleneck = cfg["block"] == "bottleneck"
    for i, count in enumerate(cfg["stage_sizes"]):
        f = cfg["num_filters"] * 2 ** i
        c_out = 4 * f if bottleneck else f
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out = side // stride
            if bottleneck:
                shapes += [(1, c_in, f, side), (3, f, f, out), (1, f, c_out, out)]
            else:
                shapes += [(3, c_in, f, out), (3, f, f, out)]
            if c_in != c_out or stride != 1:
                shapes.append((1, c_in, c_out, out))
            c_in, side = c_out, out
    return shapes, c_in


def flops_per_step(cfg: dict, batch: dict) -> float:
    """Model FLOPs of one step on one chip: 2 FLOPs a multiply-accumulate over
    every convolution and the head, times 3 for forward, input gradient and
    weight gradient (the first convolution has no input gradient to compute,
    which this rounds up). ResNet-50 at 224: 4.09 GMAC = 8.18 GFLOP forward an
    image, 24.5 GFLOP a training image."""
    shapes, c_last = _conv_shapes(cfg)
    macs = sum(k * k * ci * co * side * side for k, ci, co, side in shapes)
    macs += c_last * cfg["num_classes"]
    return 3.0 * 2.0 * macs * batch["images"]


def check_inputs(batch_of_rank):
    images, _ = batch_of_rank
    return images[:CHECK_IMAGES]


def system_logits(cfg: dict, params, model_state, images):
    """The program's own forward (compute dtype), batch norm on batch statistics."""
    logits, _ = model(cfg).apply(
        {"params": params, "batch_stats": model_state}, images, train=True,
        mutable=["batch_stats"])
    return logits


def _conv(x, kernel, stride=1):
    k = kernel.shape[0]
    pad = ((k - 1) // 2, k // 2)
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), (pad, pad),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def plain_logits(cfg: dict, params, model_state, images):
    """Plain reference forward in float32 at the highest matmul precision."""
    bottleneck = cfg["block"] == "bottleneck"
    name = "BottleneckBlock" if bottleneck else "BasicBlock"
    with jax.default_matmul_precision("highest"):
        x = _conv(images, params["conv_init"]["kernel"], 2)
        x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
        x = _max_pool_3x3_s2(x)
        index = 0
        for i, count in enumerate(cfg["stage_sizes"]):
            for j in range(count):
                p = params[f"{name}_{index}"]
                index += 1
                stride = 2 if i > 0 and j == 0 else 1
                if bottleneck:
                    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"]), p["BatchNorm_0"]))
                    y = jax.nn.relu(_batch_norm(
                        _conv(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"]))
                    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
                else:
                    y = jax.nn.relu(_batch_norm(
                        _conv(x, p["Conv_0"]["kernel"], stride), p["BatchNorm_0"]))
                    y = _batch_norm(_conv(y, p["Conv_1"]["kernel"]), p["BatchNorm_1"])
                if "conv_proj" in p:
                    x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride), p["norm_proj"])
                x = jax.nn.relu(x + y)
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["head"]["kernel"] + params["head"]["bias"]
