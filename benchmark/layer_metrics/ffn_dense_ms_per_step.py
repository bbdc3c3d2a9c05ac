"""Layer ``models``: device time a step spends under ``bf.ffn.dense`` -- the
dense SwiGLU with the norm before it and, under sandwich norms, the norm after
it: the three matmuls forward, recomputed where the layer is, and their six
gradients -- on the busiest chip (``benchmark/scopes.py``: an op counts under
the innermost of the model's scopes on its path). ``None`` for a program
without the scope."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, "bf.ffn.dense")
