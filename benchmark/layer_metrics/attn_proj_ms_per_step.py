"""Layer ``models``: device time a step spends under ``bf.attn.proj`` --
grouped-query attention outside its kernels: the norm before it, the separate
q, k and v projections (28 and 4 + 4 heads wide), rope by halves on the layers
that have it, the output projection, forward and backward -- on the busiest
chip. An op counts where ``bf.attn.proj`` is the innermost of the model's
scopes on its path (``benchmark/scopes.py`` has the rule and the other scopes;
its list is fixed, so this reader joins ops and paths through
``phases.of(run)`` itself). ``None`` for a program without the scope."""

import re

from benchmark import phases, scopes

SCOPE = "bf.attn.proj"
_SCOPES = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, scopes.INNER + (SCOPE,)))
                     + r")(?![\w.])")


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
