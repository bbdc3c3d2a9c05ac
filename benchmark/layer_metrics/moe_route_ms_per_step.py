"""Layer ``parallel.expert``: device time a step spends under ``bf.moe.route``
-- the router's float32 scores, the top-k, the sort of the chosen slots by held
expert, the gather of their rows into the buffer and the weighted scatter back,
forward and backward -- on the busiest chip (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, "bf.moe.route")
