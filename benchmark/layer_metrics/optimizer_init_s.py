"""Layer ``optimizers``: seconds in the span ``<optimizer>.INIT`` -- ``opt.init``
from single-rank parameters to the rank-stacked state in place on the devices
(it waits for its own result) -- gauge ``opt.init_sec``. Inside the harness's
``opt_init_s``. ``None`` on a program without the span."""

from benchmark import setup_parts


def read(run):
    return setup_parts.gauge("opt.init_sec")
