"""Layer ``optimizers``: the devices' high-water mark of live arrays at the end
of ``opt.init``, GiB -- gauge ``opt.init_hbm_peak_bytes``: the largest
``peak_bytes_in_use`` over the mesh's local devices with the rank-stacked state
in place. Where it equals the cell's ``peak_hbm_gib``, set-up and not the step
set the peak. ``None`` on a program without the gauge or a backend without
``memory_stats()``."""

from benchmark import setup_parts


def read(run):
    peak = setup_parts.gauge("opt.init_hbm_peak_bytes")
    return peak and peak / setup_parts.GIB
