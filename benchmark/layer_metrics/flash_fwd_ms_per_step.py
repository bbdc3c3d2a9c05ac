"""Layer ``parallel.flash``: device time a step spends in the forward kernel,
the Mosaic ops under the scope ``bf.flash.fwd`` (one ``pallas_call`` a
layer), on the busiest chip (``benchmark/phases.py``). The three kernels' times
add up to ``flash_ms_per_step``."""

from benchmark import phases


def read(run):
    return phases.kernel_ms(run, "bf.flash.fwd")
