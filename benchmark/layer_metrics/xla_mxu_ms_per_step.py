"""Layer ``models``: device time a step spends in the matrix unit's XLA ops --
``convolution`` and ``dot`` ops and the output fusions rooted in one
(``kind=kOutput``; on TPU a dot is a convolution) -- on the busiest chip.
ResNet's convolutions, the LM's matmuls outside the attention kernels (Mosaic
kernels are custom calls and are not in here)."""


def read(run):
    if not run.chips:
        return None
    chip = run.trace.busiest
    return sum(op.seconds for op in chip.ops if op.is_mxu) / run.traced_steps * 1e3
