"""Layer ``parallel.flash``: device time a step spends in the three flash
kernels at latent attention's widths (q.k 192, v 128) -- forward, dq and dk/dv
of every layer and of the MTP module's block -- on the busiest chip. Found by
their scopes ``bf.flash.*`` (``benchmark/scopes.py``): the step's other Mosaic
kernels are the expert layer's grouped products."""

from benchmark import scopes


def read(run):
    return scopes.ms(run, *scopes.FLASH)
