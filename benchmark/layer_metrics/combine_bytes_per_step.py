"""Layer ``ops.plan``: bytes one chip hands to collectives in a step -- the
operand shapes of every collective op the chip ran in the traced window, read
from the ops' HLO text, over the steps traced. A count: it repeats exactly.
One f32 copy of the parameters (4 N bytes) under a one-peer schedule, exactly 0
on one chip."""


def read(run):
    if not run.chips:
        return None
    per_chip = [
        sum(op.operand_bytes() for op in chip.ops
            if op.collective and not op.opcode.endswith("-done"))
        for chip in run.chips]
    return max(per_chip) / run.traced_steps
