"""Layer ``parallel.flash``: device time a step spends in the three Mosaic
kernels (forward, dq, dk/dv of every layer), on the busiest chip. The flash
kernels are the step's only Mosaic kernels (``Op.is_mosaic``)."""


def read(run):
    if not run.chips:
        return None
    chip = run.trace.busiest
    return sum(op.seconds for op in chip.ops if op.is_mosaic) / run.traced_steps * 1e3
