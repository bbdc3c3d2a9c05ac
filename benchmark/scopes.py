"""The traced step's device time by the scope the model ran it under.

``bf.models.ConfigLM`` names its parts where they run, as ``jax.named_scope``s
inside ``bf.grad``: ``bf.mla.proj`` (the five projections, the latent norms and
rope), the three ``bf.flash.*`` kernels, ``bf.moe.route`` (scores, top-k, sort,
gather, the weighted scatter back), ``bf.moe.experts`` (the grouped products),
``bf.moe.shared``, ``bf.ffn.dense``, ``bf.lm.head``, and ``bf.mtp`` around a
whole MTP module, whose block runs the same inner scopes again. The join of a
traced op with the path its instruction carries is ``phases.of(run).get(op)``.

An op is counted under the innermost of these scopes on its path (the last in
the path; a fusion has the path of its first member under the phase), so the
inner scopes divide ``bf.grad`` without overlap and what none of them covers is
``other`` (the embedding, residual adds, what XLA moved between them).
``bf.mtp`` is outermost and is read apart: everything with it on the path.

A program without these scopes (another model, a parent commit) gives ``None``
from every reader.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmark import phases

FLASH = phases.KERNELS
INNER = FLASH + ("bf.mla.proj", "bf.moe.route", "bf.moe.experts", "bf.moe.shared",
                 "bf.ffn.dense", "bf.lm.head")
MTP = "bf.mtp"
OTHER = "other"
DETAILED = ("bf.moe.route", "bf.moe.experts", OTHER)  # their largest ops are printed

_INNER = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, INNER)) + r")(?![\w.])")
_MTP = re.compile(r"(?<![\w.])" + re.escape(MTP) + r"(?![\w.])")


def innermost(path: str) -> str:
    """The last of ``INNER`` on a path, or ``other``."""
    found = _INNER.findall(path)
    return found[-1] if found else OTHER


def of(run) -> Optional[Dict[str, float]]:
    """Milliseconds a step under ``bf.grad`` by innermost scope, plus ``bf.mtp``
    (overlapping them): made on first use, reported once, kept on the run.
    ``None`` without a trace, without step programs, or for a program that has
    none of the scopes."""
    if hasattr(run, "scopes"):
        return run.scopes
    run.scopes = None
    where = phases.of(run)
    if where is None:
        return None
    total = {name: 0.0 for name in INNER + (MTP, OTHER)}
    largest: Dict[str, Dict[str, float]] = {name: {} for name in DETAILED}
    for op in run.trace.busiest.ops:
        here = where.get(op)
        if here.phase != phases.PHASES[0]:
            continue
        ms = op.seconds / run.traced_steps * 1e3
        scope = innermost(here.path)
        total[scope] += ms
        if _MTP.search(here.path):
            total[MTP] += ms
        if scope in largest:
            where_from = phases.second_level(here.path) if scope == OTHER else \
                here.path.rsplit(scope, 1)[-1].strip("/")
            label = f"{where_from} {op.opcode} {op.largest_result()}"
            largest[scope][label] = largest[scope].get(label, 0.0) + ms
    if not any(total[name] for name in INNER + (MTP,)):
        return None
    run.scopes = total
    grad = sum(total[name] for name in INNER + (OTHER,))
    print(f"scopes under bf.grad, ms a step (sum {grad:.3f}): "
          + ", ".join(f"{name} {total[name]:.3f}" for name in INNER + (OTHER,))
          + f"; of which under {MTP} {total[MTP]:.3f}")
    for scope, ops in largest.items():
        print(f"largest under {scope}, ms a step: " + "; ".join(
            f"{label} {ms:.3f}" for label, ms in sorted(ops.items(), key=lambda kv: -kv[1])[:8]))
    return total


def ms(run, *names: str) -> Optional[float]:
    """What the scope readers return: the milliseconds a step of ``names``,
    ``None`` where no op ran under them."""
    total = of(run)
    return None if total is None else sum(total[name] for name in names) or None
