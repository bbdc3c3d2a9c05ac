"""Layer ``runtime`` / ``optimizers``: seconds from the first ``opt.step`` call
to its loss -- tracing, lowering, and compilation or the load from the
persistent cache. A harness span."""


def read(run):
    seconds = run.spans.seconds.get("first_step_s")
    return seconds[0] if seconds else None
