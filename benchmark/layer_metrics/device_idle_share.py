"""Layer ``device``: the share of the traced window in which no op ran on the
chip, in percent, on the chip that idled most. 100 * (1 - busy union / window),
from the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane."""


def read(run):
    if not run.chips:
        return None
    return 100.0 * max(chip.idle_share for chip in run.chips)
