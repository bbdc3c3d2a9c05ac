"""Layer ``models``: device time a step spends under ``bf.attn.gate`` -- the sigmoid gate on
grouped-query attention's output, ``o * sigmoid(h W_gate)`` before the output projection: the
gate's projection (``d`` to the query heads' width), the sigmoid and the product in float32,
forward and backward -- on the busiest chip. ``bf.attn.gate`` is a sibling of ``bf.attn.proj``
and not inside it, so this and ``attn_proj_ms_per_step`` add up without counting an op twice.
An op counts where ``bf.attn.gate`` is the innermost of the model's scopes on its path
(``benchmark/scopes.py`` has the rule and the other scopes; its list is fixed, so this reader
joins ops and paths through ``phases.of(run)`` itself). ``None`` for a program without the
scope."""

import re

from benchmark import phases, scopes

SCOPE = "bf.attn.gate"
_SCOPES = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, scopes.INNER + (
    "bf.attn.proj", SCOPE))) + r")(?![\w.])")


def read(run):
    where = phases.of(run)
    if where is None:
        return None
    total = 0.0
    for op in run.trace.busiest.ops:
        here = where.get(op)
        if here.phase != phases.PHASES[0]:
            continue
        found = _SCOPES.findall(here.path)
        if found and found[-1] == SCOPE:
            total += op.seconds / run.traced_steps * 1e3
    return total or None
