"""Layer ``models``: device time a step spends under the scope ``bf.grad`` --
the loss forward and backward of ``build_fused_step``, flash kernels and a
gradient allreduce included -- on the busiest chip. The join of the trace's op
names with the program's own HLO is ``benchmark/phases.py``."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "bf.grad")
