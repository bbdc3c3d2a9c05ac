"""Measure whether ResNet-50's weight-gradient convs sit at the HBM roof.

VERDICT r4 #4: PERF.md's roofline argued the weight-gradient conv fusions
(`convert_reduce_fusion`, 21.9 ms/step, the largest trace bucket) are
HBM-bound, but no bytes/s was ever measured. This probe jits each hot
weight-gradient conv shape standalone (the same ``conv_general_dilated``
XLA emits for dW), times it on the real chip, and reports:

  * achieved HBM GB/s  = (activation reads + grad reads + dW writes) / t
  * achieved TFLOP/s   = 2 * B*Ho*Wo*k*k*Cin*Cout / t

against the chip's published roofs (bench.PEAKS, by device kind). A shape whose
bytes/s approaches the HBM roof while its TFLOP/s sits far below the MXU
roof is measured — not argued — to be bandwidth-bound.

Shapes: the B=128 ResNet-50 stage shapes that dominate the r4 trace
(3x3 convs of stages 2-4 and the stride-2 downsamples).

Run on the real chip:  python scripts/convgrad_probe.py
"""

import json
import os
import sys
import tempfile

import numpy as np

import jax
import jax.numpy as jnp


# Flight dumps from a bench run land in a tempdir instead of littering
# the CWD (conftest's default for the test suite); an explicit
# BLUEFOG_FLIGHT_DIR still wins.
os.environ.setdefault("BLUEFOG_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="bf_flight_"))

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import peaks  # noqa: E402
from resnet_profile import device_op_seconds  # noqa: E402

# (name, B, H, W, Cin, Cout, k, stride) — ResNet-50 hot dW shapes at B=128
SHAPES = [
    ("stage1_3x3", 128, 56, 56, 64, 64, 3, 1),
    ("stage2_3x3", 128, 28, 28, 128, 128, 3, 1),
    ("stage3_3x3", 128, 14, 14, 256, 256, 3, 1),
    ("stage4_3x3", 128, 7, 7, 512, 512, 3, 1),
    ("stage3_1x1_expand", 128, 14, 14, 256, 1024, 1, 1),
    ("stage4_1x1_expand", 128, 7, 7, 512, 2048, 1, 1),
]


def weight_grad(x, dy, k, stride):
    """dW of a NHWC conv via conv_general_dilated, as XLA's autodiff emits:
    contract batch+space of x against dy."""
    pad = (k - 1) // 2

    def fwd(w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    w0 = jnp.zeros((k, k, x.shape[-1], dy.shape[-1]), x.dtype)
    _, vjp = jax.vjp(fwd, w0)
    (dw,) = vjp(dy)
    # real training accumulates dW in f32 (the trace's convert_reduce
    # fusions); include the convert so the probe matches the step's bucket
    return dw.astype(jnp.float32)


def main() -> int:
    dev = jax.devices()[0]
    roof = peaks(dev)
    print(f"# device: {dev.device_kind}", file=sys.stderr)
    for name, B, H, W, Cin, Cout, k, stride in SHAPES:
        Ho, Wo = H // stride, W // stride
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, H, W, Cin), jnp.bfloat16)
        dy = jnp.asarray(rng.randn(B, Ho, Wo, Cout), jnp.bfloat16)
        fn = jax.jit(lambda x, dy: weight_grad(x, dy, k, stride))
        out = fn(x, dy)
        jax.block_until_ready(out)  # compile
        # a host clock around 0.1-2 ms kernels mostly times dispatch: read
        # DEVICE time from a profiler trace, like scripts/resnet_profile.py
        reps = 20
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                for _ in range(reps):
                    out = fn(x, dy)
                jax.block_until_ready(out)
            dt = device_op_seconds(td) / reps
        read_bytes = (x.size + dy.size) * 2            # bf16 operands
        write_bytes = k * k * Cin * Cout * 4           # f32 dW
        gbs = (read_bytes + write_bytes) / dt / 1e9
        flops = 2.0 * B * Ho * Wo * k * k * Cin * Cout
        tfs = flops / dt / 1e12
        print(json.dumps({
            "shape": name, "ms": round(dt * 1e3, 3),
            "GBps": round(gbs, 1),
            "hbm_frac": round(gbs * 1e9 / roof["hbm_bytes_per_s"], 3),
            "TFLOPs": round(tfs, 1),
            "mxu_frac": round(tfs * 1e12 / roof["bf16_flops"], 3),
            "intensity_flop_per_byte": round(
                flops / (read_bytes + write_bytes), 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
