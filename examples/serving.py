"""Train-while-serve: a gossip trainer publishing versioned snapshots
while a read-only client answers batched inference from them.

The trainer side is one knob: BLUEFOG_SERVE_PUBLISH_EVERY=N makes
controller 0 write its post-gossip model to the control plane as an
immutable, codec-compressed, shard-striped snapshot every N-th
communicating step, committed behind a monotone version fence so a
reader either sees a complete snapshot or the previous one — never a
torn mix (docs/serving.md).

The serving side never imports jax and never joins the mesh: it is a raw
control-plane attachment (the same kind ``bfrun --status`` uses), so it
runs on any host that can reach the control-plane address. Here both
sides share one process for a self-contained example; point
``bf.serve_client`` (or ``bfrun --serve``) at the job's address to run
them on different machines.

Run (CPU-simulated 8-device mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        BLUEFOG_SERVE_PUBLISH_EVERY=1 python examples/serving.py
On a real TPU slice just run it plainly: ranks are the local chips.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("BLUEFOG_SERVE_PUBLISH_EVERY", "1")
os.environ.setdefault("BLUEFOG_SERVE_POLL_S", "0.2")
# single-host runs have no jax coordinator to derive the control-plane
# address from — pin one so rank 0 serves it in-process and the serving
# client below has somewhere to attach
if not os.environ.get("BLUEFOG_CP_HOST"):
    import socket as _socket
    _s = _socket.socket()
    _s.bind(("127.0.0.1", 0))
    os.environ.update({"BLUEFOG_CP_HOST": "127.0.0.1",
                       "BLUEFOG_CP_PORT": str(_s.getsockname()[1]),
                       "BLUEFOG_CP_WORLD": "1", "BLUEFOG_CP_RANK": "0"})
    _s.close()

import numpy as np

import jax.numpy as jnp
import optax

import bluefog_tpu as bf


def main() -> int:
    bf.init()
    print(f"ranks: {bf.size()}")

    # a tiny ridge-regression "model": one weight vector, least squares
    # against a fixed linear target, gossip-averaged every step
    dim = 512
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(dim,)).astype(np.float32)
    xs_train = rng.normal(size=(256, dim)).astype(np.float32)
    ys_train = xs_train @ w_true

    def loss(params, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2) + 1e-4 * jnp.sum(params["w"] ** 2)

    opt = bf.DistributedPushSumOptimizer(optax.adam(1e-2), loss,
                                         window_prefix="example.serve")
    state = opt.init({"w": jnp.zeros((dim,), jnp.float32)})

    # the serving client: model_fn(params, batch) over the SNAPSHOT
    # leaves (numpy, in tree order) — params[0] is "w", rank-stacked
    # (one row per rank; the rows gossip toward consensus, any serves)
    def model_fn(params, xs):
        return xs @ params[0].reshape(-1, dim)[0]

    host = os.environ.get("BLUEFOG_CP_HOST", "127.0.0.1")
    port = int(os.environ["BLUEFOG_CP_PORT"]) \
        if os.environ.get("BLUEFOG_CP_PORT") else None
    sc = bf.serve_client(model_fn,
                         endpoints=[(host, port)] if port else None)

    # train; the publisher hook ships a new snapshot every comm step and
    # the client hot-swaps behind our back
    batch = (jnp.asarray(xs_train), jnp.asarray(ys_train))
    for step in range(1, 21):
        state, metrics_out = opt.step(state, batch)
        if step == 1:
            ok = sc.wait_ready(timeout=30)
            if not ok:
                print("serving: no snapshot within 30 s", file=sys.stderr)
                return 1
        if step % 5 == 0:
            q = rng.normal(size=(4, dim)).astype(np.float32)
            preds = np.stack([sc.infer(q[i], timeout=10) for i in range(4)])
            err = float(np.max(np.abs(preds - q @ w_true)))
            st = sc.stats()
            print(f"step {step:2d}: serving v{st['version']} "
                  f"({st['swaps']} swaps, {st['batches']} batches) "
                  f"max |pred - true| = {err:.3f}")

    final_v = sc.version()
    sc.close()
    opt.free()
    bf.shutdown()
    ok = final_v >= 1
    print("SERVING OK" if ok else "SERVING FAILED (no snapshot version)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
