"""Average consensus via decentralized neighbor averaging.

TPU-native port of the reference example ``examples/pytorch_average_consensus.py``:
every rank starts with a random vector and repeatedly averages with its graph
neighbors until all ranks agree on the global mean.

Run (CPU-simulated 8-device mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/average_consensus.py
On a real TPU slice just run it plainly: ranks are the local chips.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from bluefog_tpu import topology_util


def main() -> int:
    bf.init(topology_util.ExponentialTwoGraph)
    n = bf.size()
    print(f"ranks: {n} on {bf.mesh().devices.flat[0].platform}")

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, 1000))
    x = bf.shard_rank_stacked(bf.mesh(), x)
    target = jnp.mean(x, axis=0)  # consensus value: per-coordinate rank mean

    for step in range(60):
        x = bf.neighbor_allreduce(x, name=f"consensus.{step}")

    err = float(jnp.max(jnp.abs(x - target[None, :])))
    print(f"max deviation from rank-mean after 60 rounds: {err:.3e}")
    ok = err < 1e-4
    print("CONSENSUS OK" if ok else "CONSENSUS FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
