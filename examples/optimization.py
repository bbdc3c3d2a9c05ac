"""Decentralized optimization algorithms on the TPU-native BlueFog API.

Re-creation of the reference's richest capability demo
(/root/reference/examples/pytorch_optimization.py:178-427): solving a
regularized regression problem whose data is partitioned across ranks with

  * diffusion                  (Sayed, "Adaptive networks", 2014)
  * exact diffusion            (Yuan et al., 2018, Alg. 1)
  * gradient tracking          (Nedic et al., 2017, Alg. 1)
  * push-DIGing                (Nedic et al., 2017, Alg. 2)

and verifying each against the centralized optimum obtained by distributed
gradient descent.  The port is deliberately idiomatic for this framework:
every per-rank quantity is a *rank-stacked* array ``[size, ...]`` and each
communication round is one SPMD program over the device mesh, so "each rank
runs the recursion" becomes plain array code with no per-rank Python loop.

Gradient tracking keeps the reference's signature overlap pattern — two
concurrent nonblocking ``neighbor_allreduce`` calls in flight while the new
local gradient is computed (reference :327-333).  Push-DIGing keeps the
reference's combo-vector trick (u, y, and the push-sum weight travel as one
window tensor so they can never de-synchronize, reference :378-396) and runs
on one-sided ``win_accumulate`` + ``win_update_then_collect``.

Deviation from the reference, on purpose: the l2 regularizer is the smooth
``0.5*rho*||w||^2`` rather than the reference's non-smooth ``0.5*rho*||w||``,
so the global optimum is the unique zero-gradient point and autodiff is
defined at the w=0 start.
"""

from __future__ import annotations

import argparse
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import bluefog_tpu as bf
from bluefog_tpu import topology_util


# ---------------------------------------------------------------------------
# data + objective
# ---------------------------------------------------------------------------

def generate_data(key, size: int, m: int, n: int,
                  task: str = "logistic_regression"):
    """Per-rank synthetic data, rank-stacked: X [size, m, n], y [size, m, 1]."""
    kx, kw, ky = jax.random.split(key, 3)
    X = jax.random.normal(kx, (size, m, n))
    if task == "logistic_regression":
        w0 = jax.random.normal(kw, (size, n, 1))
        p = 1.0 / (1.0 + jnp.exp(X @ w0))
        y = (jax.random.uniform(ky, (size, m, 1)) < p).astype(X.dtype)
        y = 2.0 * y - 1.0
    elif task == "linear_regression":
        x_o = jax.random.normal(kw, (size, n, 1))
        noise = 0.1 * jax.random.normal(ky, (size, m, 1))
        y = X @ x_o + noise
    else:
        raise NotImplementedError(
            "task must be linear_regression or logistic_regression")
    return X, y


def make_grad_fn(X, y, task: str, rho: float) -> Callable:
    """Stacked gradient: [size, n, 1] weights -> [size, n, 1] local grads.

    X/y are pinned to the rank mesh first so every eager recursion step and
    the jitted gradient run on the mesh backend (NOT the default device,
    which may be a different accelerator in mixed-backend environments).
    """
    X, y = bf.shard_rank_stacked(bf.mesh(), (X, y))

    def local_loss(Xr, yr, wr):
        if task == "logistic_regression":
            data = jnp.mean(jnp.log1p(jnp.exp(-yr * (Xr @ wr))))
            reg = 0.5 * rho * jnp.sum(wr * wr)
        else:
            r = Xr @ wr - yr
            data = 0.5 * jnp.mean(r * r)
            reg = 0.5 * rho * jnp.sum(wr * wr)
        return data + reg

    def total(w_stacked):
        return jnp.sum(jax.vmap(local_loss)(X, y, w_stacked))

    return jax.jit(jax.grad(total))


def _zeros(size: int, n: int):
    """Rank-mesh-pinned [size, n, 1] zeros (numpy -> direct mesh placement)."""
    return bf.shard_rank_stacked(bf.mesh(), np.zeros((size, n, 1), np.float32))


# ---------------------------------------------------------------------------
# baseline: distributed gradient descent (the centralized optimum)
# ---------------------------------------------------------------------------

def distributed_grad_descent(grad_fn, size: int, n: int, maxite: int = 500,
                             alpha: float = 1e-1):
    """x^{k+1} = x^k - alpha * allreduce(local_grad); reference :124-164."""
    w = _zeros(size, n)
    for _ in range(maxite):
        g = bf.allreduce(grad_fn(w), average=True, name="gradient")
        w = w - alpha * g
    return w


# ---------------------------------------------------------------------------
# the decentralized algorithms
# ---------------------------------------------------------------------------

def diffusion(grad_fn, w_opt, size: int, n: int, maxite: int = 500,
              alpha: float = 1e-1) -> Tuple[jnp.ndarray, List[float]]:
    """w^{k+1} = neighbor_allreduce(w^k - alpha*grad); reference :178-212."""
    w = _zeros(size, n)
    mse = []
    for _ in range(maxite):
        phi = w - alpha * grad_fn(w)
        w = bf.neighbor_allreduce(phi, name="diffusion.w")
        mse.append(float(jnp.linalg.norm(w[0] - w_opt[0])))
    return w, mse


def _abar_weights(size: int):
    """Recv weights of (A + I)/2 for the current topology, per rank."""
    topo = bf.load_topology()
    self_w: Dict[int, float] = {}
    nbr_w: Dict[int, Dict[int, float]] = {}
    for r in range(size):
        sw, nw = topology_util.GetRecvWeights(topo, r)
        self_w[r] = (sw + 1.0) / 2.0
        nbr_w[r] = {src: v / 2.0 for src, v in nw.items()}
    return self_w, nbr_w


def exact_diffusion(grad_fn, w_opt, size: int, n: int, maxite: int = 500,
                    alpha: float = 1e-1, use_Abar: bool = True):
    """psi/phi/combine recursion of Yuan et al. 2018; reference :232-281.

    With ``use_Abar`` the combination matrix is (A+I)/2, passed as explicit
    per-rank self/neighbor weights.
    """
    if use_Abar:
        self_w, nbr_w = _abar_weights(size)
    else:
        self_w, nbr_w = None, None
    w = _zeros(size, n)
    psi_prev = w
    mse = []
    for _ in range(maxite):
        psi = w - alpha * grad_fn(w)
        phi = psi + w - psi_prev
        w = bf.neighbor_allreduce(
            phi, self_weight=self_w, neighbor_weights=nbr_w,
            name="exact_diffusion.w")
        psi_prev = psi
        mse.append(float(jnp.linalg.norm(w[0] - w_opt[0])))
    return w, mse


def gradient_tracking(grad_fn, w_opt, size: int, n: int, maxite: int = 500,
                      alpha: float = 1e-1):
    """Nedic et al. 2017 Alg. 1; reference :305-347.

    The two neighbor_allreduce calls are launched nonblocking and stay in
    flight while the new local gradient is computed — the same
    communication/compute overlap the reference demonstrates (:327-333).
    """
    w = _zeros(size, n)
    q = grad_fn(w)            # q^0 = grad(w^0)
    grad_prev = q
    mse = []
    for _ in range(maxite):
        w_handle = bf.neighbor_allreduce_nonblocking(w, name="gt.w")
        q_handle = bf.neighbor_allreduce_nonblocking(q, name="gt.q")
        w = bf.synchronize(w_handle) - alpha * q
        grad = grad_fn(w)     # overlaps with the q exchange
        q = bf.synchronize(q_handle) + grad - grad_prev
        grad_prev = grad
        mse.append(float(jnp.linalg.norm(w[0] - w_opt[0])))
    return w, mse


def push_diging(grad_fn, w_opt, size: int, n: int, maxite: int = 500,
                alpha: float = 1e-1):
    """Nedic et al. 2017 Alg. 2 over one-sided windows; reference :364-427.

    u (the iterate), y (the tracked gradient), and the push-sum weight v
    travel as one combo window tensor [size, 2n+1, 1].  Each round every
    rank accumulates w/(2*outdegree) into its out-neighbors' mailboxes,
    keeps w/2 itself (``self_weight=0.5`` — the window analog of the
    reference's in-place ``w.div_(2)``), and collects.
    """
    topo = bf.load_topology()
    out_nbrs = {r: topology_util.out_neighbor_ranks(topo, r)
                for r in range(size)}
    dst_weights = {
        r: {dst: 1.0 / (2.0 * len(out_nbrs[r])) for dst in out_nbrs[r]}
        for r in range(size)
    }

    w = _zeros(size, 2 * n + 1)
    x = _zeros(size, n)
    grad = grad_fn(x)
    w = w.at[:, n:2 * n].set(grad)
    w = w.at[:, -1].set(1.0)
    grad_prev = grad

    bf.win_create(w, name="w_buff", zero_init=True)
    mse = []
    try:
        for _ in range(maxite):
            bf.barrier()
            w = w.at[:, :n].add(-alpha * w[:, n:2 * n])
            bf.win_accumulate(
                w, name="w_buff", self_weight=0.5, dst_weights=dst_weights,
                require_mutex=True)
            bf.barrier()
            w = bf.win_update_then_collect(name="w_buff")

            x = w[:, :n] / w[:, -1:]
            grad = grad_fn(x)
            w = w.at[:, n:2 * n].add(grad - grad_prev)
            grad_prev = grad
            mse.append(float(jnp.linalg.norm(x[0] - w_opt[0])))
        bf.barrier()
        w = bf.win_update_then_collect(name="w_buff")
        x = w[:, :n] / w[:, -1:]
    finally:
        bf.win_free("w_buff")
    return x, mse


ALGORITHMS = {
    "diffusion": diffusion,
    "exact_diffusion": exact_diffusion,
    "gradient_tracking": gradient_tracking,
    "push_diging": push_diging,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def set_example_topology(name: str) -> None:
    size = bf.size()
    if name == "mesh":
        bf.set_topology(topology_util.MeshGrid2DGraph(size), is_weighted=True)
    elif name == "expo2":
        bf.set_topology(topology_util.ExponentialGraph(size))
    elif name == "star":
        bf.set_topology(topology_util.StarGraph(size), is_weighted=True)
    elif name == "ring":
        bf.set_topology(topology_util.RingGraph(size))
    else:
        raise NotImplementedError(
            "topology must be one of mesh, star, ring, expo2")


def run(method: str = "exact_diffusion", task: str = "logistic_regression",
        topology: str = "ring", maxite: int = 500, alpha: float = 1e-1,
        rho: float = 1e-2, m: int = 20, n: int = 5, seed: int = 123417):
    """Build the problem, solve it centrally and decentrally, report both."""
    size = bf.size()
    set_example_topology(topology)

    X, y = generate_data(jax.random.PRNGKey(seed), size, m, n, task=task)
    grad_fn = make_grad_fn(X, y, task, rho)

    w_opt = distributed_grad_descent(grad_fn, size, n, maxite=maxite,
                                     alpha=alpha)
    g_opt = bf.allreduce(grad_fn(w_opt), average=True)
    print(f"[DG] global grad norm: {float(jnp.linalg.norm(g_opt[0])):.3e} "
          f"local grad norm: {float(jnp.linalg.norm(grad_fn(w_opt)[0])):.3e}")

    algo = ALGORITHMS[method]
    w, mse = algo(grad_fn, w_opt, size, n, maxite=maxite, alpha=alpha)

    g = bf.allreduce(grad_fn(w), average=True)
    print(f"[{method}] final ||w - w_opt||: {mse[-1]:.3e} "
          f"global grad norm: {float(jnp.linalg.norm(g[0])):.3e}")
    return w, w_opt, mse


def main() -> None:
    parser = argparse.ArgumentParser(
        description="Decentralized optimization algorithms (TPU-native)")
    parser.add_argument("--method", default="exact_diffusion",
                        choices=sorted(ALGORITHMS))
    parser.add_argument("--task", default="logistic_regression",
                        choices=["logistic_regression", "linear_regression"])
    parser.add_argument("--topology", default="ring",
                        choices=["mesh", "star", "ring", "expo2"])
    parser.add_argument("--max-iter", type=int, default=500)
    parser.add_argument("--lr", type=float, default=1e-1)
    parser.add_argument("--save-plot-file", default=None,
                        help="optional path for a semilogy convergence plot")
    args = parser.parse_args()

    bf.init()
    print(f"ranks: {bf.size()} on {bf.mesh().devices.flat[0].platform}")
    _, _, mse = run(method=args.method, task=args.task,
                    topology=args.topology, maxite=args.max_iter,
                    alpha=args.lr)
    if args.save_plot_file:
        try:
            import matplotlib
            matplotlib.use("Agg")
            import matplotlib.pyplot as plt
            plt.semilogy(np.maximum(np.asarray(mse), 1e-16))
            plt.xlabel("iteration")
            plt.ylabel("|| w - w* ||")
            plt.savefig(args.save_plot_file)
            plt.close()
        except ImportError:
            print("matplotlib unavailable; skipping plot")


if __name__ == "__main__":
    main()
