"""Layer ``ops.plan``: device time a step spends under the scope
``bf.combine`` -- all of it: the permutes' start ops, the waits for them and
the weighted accumulate -- on the chip where that is largest
(``benchmark/phases.py``). 0 on one chip, where the combine is the identity."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "bf.combine", worst_chip=True)
