"""Layer ``optimizers`` (host side of ``_FusedOptimizer.step``): the median wall
time of one step on the host -- the schedule's ``before_step`` (weights and send
peers set on the optimizer) and the ``opt.step`` call, which builds the plan
and dispatches the program without waiting for it. A harness span over every
step of the timed window. It matters once it nears the device's step."""

import statistics


def read(run):
    seconds = run.spans.seconds.get("host_step_s")
    return statistics.median(seconds) * 1e3 if seconds else None
