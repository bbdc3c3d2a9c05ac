"""Layer ``models``: device time a step spends under ``bf.mla.proj`` -- latent
attention outside its kernels: the five projections (q_a, q_b, kv_a, kv_b, o),
the norms before them and on both latents, rope on the shared 64-wide part,
forward and backward -- on the busiest chip (``benchmark/scopes.py``)."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, "bf.mla.proj")
