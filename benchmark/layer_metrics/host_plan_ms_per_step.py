"""Layer ``optimizers`` (host side): the median ``<optimizer>.PLAN`` span of the
traced steps -- ``_weights_and_key()``: the weight matrix and the combine plan
rebuilt from the knobs set before the step -- on the profiler's clock, inside
``<optimizer>.STEP``. Dispatch is STEP - PLAN - BUILD."""

from benchmark import phases


def read(run):
    return phases.host_span_ms(run, ".PLAN")
