"""Schedule ``onepeer_expo2``: the paper's dynamic one-peer Expo-2 schedule.

Before every step each rank is given the one peer it sends to
(``GetDynamicSendRecvRanks`` over the Expo-2 graph, clockwise) and the weights
1/(indegree+1) -- a copy of ``examples/benchmark.py``'s ``set_dynamic``, the
reference harness's default. At n=4 the shifts alternate between 1 and 2, so
the optimizer runs two compiled programs in turn.
"""

from __future__ import annotations

import numpy as np


class Schedule:
    def __init__(self, bf, opt) -> None:
        self.opt = opt
        self.n = bf.size()
        if self.n < 2:
            raise SystemExit("schedule onepeer_expo2 needs at least two chips")
        self.generators = [
            bf.topology_util.GetDynamicSendRecvRanks(bf.load_topology(), r)
            for r in range(self.n)]

    def before_step(self) -> np.ndarray:
        """Called before every ``opt.step``; returns that step's W, where
        W[s, r] is the weight rank r gives to what it receives from rank s."""
        sends = {r: next(g)[0] for r, g in enumerate(self.generators)}
        recv_from = {r: [] for r in range(self.n)}
        for s, dsts in sends.items():
            for d in dsts:
                recv_from[d].append(s)
        weight = {r: 1.0 / (len(recv_from[r]) + 1) for r in range(self.n)}
        self.opt.send_neighbors = sends
        self.opt.self_weight = weight
        self.opt.neighbor_weights = {
            r: {s: weight[r] for s in recv_from[r]} for r in range(self.n)}
        W = np.zeros((self.n, self.n))
        for r in range(self.n):
            W[[r] + recv_from[r], r] = weight[r]
        return W
