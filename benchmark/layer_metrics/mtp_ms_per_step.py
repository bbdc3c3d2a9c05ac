"""Layer ``models``: device time a step spends under ``bf.mtp`` -- the whole
multi-token-prediction module: its two norms and the 2d -> d projection, its
block (latent attention, flash kernels and expert layer included, so this
overlaps the other scope metrics), its final norm, the shared head's second
use and its cross-entropy, forward and backward -- on the busiest chip
(``benchmark/scopes.py``). Absent for a configuration without the module."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, scopes.MTP)
