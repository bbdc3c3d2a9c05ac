"""Layer ``device``: device time a step spends in ops the program put under
none of ``bf.grad`` / ``bf.update`` / ``bf.combine`` -- the unstacking and
restacking around them, ops without metadata, ops of programs that are not an
optimizer's step, and op names on which two step programs disagree -- on the
busiest chip (``benchmark/phases.py``). With the three phases it adds up to
the chip's busy time."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "unscoped")
