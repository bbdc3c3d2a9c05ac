"""Layer ``models``: device time a step spends under ``bf.lm.head`` -- the
final norm, the exit gate, the head's matmul and the cross-entropy, forward,
recomputed and backward, once a pass in a looped model, and the exit
distribution with the objective made of them -- on the busiest chip
(``benchmark/scopes.py``: an op counts under the innermost of the model's
scopes on its path). ``None`` for a program without the scope."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, "bf.lm.head")
