"""Layer ``optimizers``: device time a step spends under the scope
``bf.update`` -- ``opt.update`` and ``optax.apply_updates`` of the fused step
(Adam over f32 parameters reads four copies and writes three) -- on the
busiest chip (``benchmark/phases.py``)."""

from benchmark import phases


def read(run):
    return phases.phase_ms(run, "bf.update")
