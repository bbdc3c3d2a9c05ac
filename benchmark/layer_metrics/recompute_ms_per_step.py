"""Layer ``models``: device time a step spends in the ops ``jax.checkpoint``
runs again -- the forward of every recomputed layer application (its norms,
projections, rope, the forward flash kernel, the SwiGLU) and the head's logits
with their softmax statistics, made a second time on the way back -- on the
busiest chip. JAX leaves ``rematted_computation`` on the path of what it runs
again (``.../bf.loop.2/checkpoint/rematted_computation/layer_3/...`` in the
compiled step's ``op_name``s at jax 0.9.0; the backward ops proper have
``checkpoint/`` without it), and a fusion has the path of its root, so one
that holds recomputed and backward members counts by its root. ``None`` for a
program that recomputes nothing."""

from benchmark import phases

MARK = "rematted_computation"


def read(run):
    where = phases.of(run)
    if where is None:
        return None
    return phases.seconds(run, lambda op: MARK in where.get(op).path) * 1e3 or None
