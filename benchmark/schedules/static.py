"""Schedule ``static``: the topology ``bf.init()`` sets (Expo-2) is used as it
is; nothing is set on the optimizer between steps."""

from __future__ import annotations

import numpy as np


class Schedule:
    def __init__(self, bf, opt) -> None:
        n = bf.size()
        topo = bf.load_topology()
        # W[s, r]: the weight rank r gives to what it receives from rank s,
        # uniform 1/(indegree+1), from the graph's edges alone
        self.W = np.zeros((n, n))
        for r in range(n):
            sources = [s for s in topo.predecessors(r) if s != r]
            self.W[[r] + sources, r] = 1.0 / (len(sources) + 1)

    def before_step(self) -> np.ndarray:
        """Called before every ``opt.step``; returns that step's W."""
        return self.W
