"""Layer ``ops.plan``: the part of ``combine_device_ms_per_step`` that is plain
compute -- the ops under ``bf.combine`` that neither are nor wait for a
collective: the weighted accumulate of what the permutes brought -- on the chip
where that is largest (``benchmark/phases.py``)."""

from benchmark import phases


def read(run):
    found = phases.of(run)
    if found is None:
        return None
    return phases.seconds(
        run, lambda op: found.get(op).phase == "bf.combine" and op.collective is None,
        worst_chip=True) * 1e3
