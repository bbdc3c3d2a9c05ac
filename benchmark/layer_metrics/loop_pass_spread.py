"""Layer ``models``: how unevenly a looped model's passes take the device's
time, in percent -- (slowest - fastest) / median of the passes' totals, a
pass's total being the time of the ops under ``bf.grad`` whose path holds
``bf.loop.<t>`` (forward, recomputed and backward, its head and loss
included), on the busiest chip. The passes do equal work, so this reads near
0 unless XLA moves work between them: a weight's gradient is the sum over the
passes' uses, and the fusion that adds the four and rides Adam in its epilogue
has the path of one of them. ``None`` for a program with fewer than two
passes."""

import re
import statistics

from benchmark import phases

_PASS = re.compile(r"(?<![\w.])bf\.loop\.(\d+)(?![\w.])")


def by_pass(run):
    """Milliseconds a step by pass, or ``None`` without a trace and programs."""
    where = phases.of(run)
    if where is None:
        return None
    total = {}
    for op in run.trace.busiest.ops:
        here = where.get(op)
        found = _PASS.search(here.path)
        if found and here.phase == phases.PHASES[0]:
            t = int(found.group(1))
            total[t] = total.get(t, 0.0) + op.seconds / run.traced_steps * 1e3
    return total


def read(run):
    total = by_pass(run)
    if not total or len(total) < 2:
        return None
    print("loop passes, ms a step: " + ", ".join(f"{t}: {total[t]:.3f}" for t in sorted(total)))
    times = list(total.values())
    return 100.0 * (max(times) - min(times)) / statistics.median(times)
