"""Layer ``parallel.expert``: device time a step spends under
``bf.moe.experts`` -- the grouped products of the held experts (three forward,
six backward a layer, Pallas kernels), the SwiGLU between them and the casts of
their weights -- on the busiest chip (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, "bf.moe.experts")
