"""The traced step's device time by the phase the program ran it under.

The program names its phases where they run, as ``jax.named_scope``s:
``bf.grad`` / ``bf.update`` / ``bf.combine`` in the fused step and
``bf.flash.fwd`` / ``bf.flash.dq`` / ``bf.flash.dkv`` around the three
kernels. XLA keeps the scope of every instruction in its ``op_name`` metadata,
but ``ProfileData`` does not hand out the trace's copy of it, so the join goes
through the program: ``bf.step_programs()`` gives the compiled HLO of the
steps that ran, and its instructions carry the names the trace's ``XLA Ops``
events have (``%fusion.14``).

The phase of an op is the first of the three names in its ``op_name`` path.
Two kinds of op need a rule, and each rule's share of the step is reported:

  mixed      a fusion whose members lie under more than one phase (XLA fuses
             the update into the epilogue of a weight-gradient matmul, and the
             combine's multiply by the self weight into the update). It goes,
             whole, to the earliest of its members' phases in step order: that
             phase's op is why the fusion exists, what comes later rides in
             its epilogue. The time in mixed fusions is the rule's error bar,
             and ``touching`` (time of all ops with a member under a phase) is
             each phase's upper bound.
  inherited  an op XLA put in itself and gave no metadata (a copy between
             memory spaces, an async slice, a custom call of its own). It
             moves one op's result, so it takes the phase of the ops that
             produce its operands, or failing that of the ops that use it.

What is left is ``unscoped``: the program put it under no phase, or no step
program has it. An op name that two registered programs put in different
phases, or give different kernels, is ``ambiguous`` and counts as unscoped.

A program without ``bf.step_programs`` (a parent commit) and a trace without
device planes give ``None`` from every reader.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
import time
from typing import Callable, Dict, FrozenSet, Iterable, List, Optional

from .trace_reduce import Op

PHASES = ("bf.grad", "bf.update", "bf.combine")  # in step order
KERNELS = ("bf.flash.fwd", "bf.flash.dq", "bf.flash.dkv")
UNSCOPED, AMBIGUOUS = "unscoped", "ambiguous"

_NAME = r"([\w.\-]+)"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?" + _NAME + r"\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?" + _NAME + r"\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?" + _NAME)
_REFERENCE = re.compile(r"%" + _NAME)


def _scope(names) -> "re.Pattern":
    """One of ``names`` as a whole scope of a path, bare or wrapped by a
    transform: ``.../bf.grad/...``, ``transpose(bf.grad)``."""
    return re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, names)) + r")(?![\w.])")


_PHASE, _KERNEL = _scope(PHASES), _scope(KERNELS)


def phase_of(path: str) -> str:
    found = _PHASE.search(path)
    return found.group(1) if found else UNSCOPED


def kernel_of(path: str) -> Optional[str]:
    found = _KERNEL.search(path)
    return found.group(1) if found else None


def earliest(phases: Iterable[str]) -> Optional[str]:
    """The first in step order of the phases given (``None`` if none is one)."""
    found = set(phases)
    return next((phase for phase in PHASES if phase in found), None)


def second_level(path: str) -> str:
    """The three scopes below the phase in a path: the transform and the flax
    modules (``jvp(TransformerLM)/TransformerLM.hidden/block_0``). JAX repeats
    the enclosing scope inside a transpose (``bf.grad/transpose(bf.grad)/jvp(..)``);
    that one is skipped."""
    path = path.split(";")[0]
    found = _PHASE.search(path)
    below = path[found.end():].lstrip(")/").split("/") if found else []
    return "/".join([part for part in below if not _PHASE.search(part)][:3]) or "(no metadata)"


@dataclasses.dataclass(frozen=True)
class Where:
    """What one program says of one instruction."""

    path: str                 # its op_name ("" if it has none); where that lies under no
                              # phase, the first fused member's that lies under ``phase``
    phase: str                # one of PHASES, or "unscoped"
    touches: FrozenSet[str]   # the phases of PHASES it or a fused member lies under
    inherited: bool           # no metadata: the phase is its operands' or its users'

    @property
    def kernel(self) -> Optional[str]:
        return None if self.phase == AMBIGUOUS else kernel_of(self.path)

    @property
    def mixed(self) -> bool:
        return len(self.touches) > 1


NOWHERE = Where("", UNSCOPED, frozenset(), False)  # an op no step program has


def parse(hlo_text: str) -> Dict[str, Where]:
    """Instruction name -> ``Where``, for every instruction of every
    computation of one compiled module (names are unique in a module; the
    trace holds those of the entry computation and of loop bodies)."""
    paths: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    calls: Dict[str, str] = {}
    computations: Dict[str, List[str]] = {}
    body: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            body = computations.setdefault(header.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if not found or body is None:
            continue
        name = found.group(1)
        op_name = _OP_NAME.search(line)
        paths[name] = op_name.group(1) if op_name else ""
        body.append(name)
        operands[name] = _REFERENCE.findall(line[found.end():])
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    users: Dict[str, List[str]] = {}
    for name, refs in operands.items():
        for ref in refs:
            users.setdefault(ref, []).append(name)

    def with_members(name: str) -> List[str]:
        return [name] + computations.get(calls.get(name, ""), [])

    touches = {name: frozenset(phase_of(paths[n]) for n in with_members(name)) - {UNSCOPED}
               for name in paths}

    def inherit(name: str, edges: Dict[str, List[str]]) -> Optional[str]:
        """The earliest phase found along ``edges``, walking through the
        instructions that have no metadata either."""
        seen, queue, found = {name}, [name], set()
        while queue:
            for ref in edges.get(queue.pop(), []):
                if ref in seen or ref not in paths:
                    continue
                seen.add(ref)
                if touches[ref]:
                    found |= touches[ref]
                elif not paths[ref]:
                    queue.append(ref)
        return earliest(found)

    where = {}
    for name, path in paths.items():
        phase = earliest(touches[name])
        inherited = phase is None and not path
        if inherited:
            phase = inherit(name, operands) or inherit(name, users)
        if phase and not inherited:
            path = next(paths[n] for n in with_members(name) if phase_of(paths[n]) == phase)
        where[name] = Where(path, phase or UNSCOPED, touches[name], inherited and bool(phase))
    return where


class Phases:
    """The join of the programs' instructions with one run's traced ops."""

    def __init__(self, programs: List[Dict[str, Where]]) -> None:
        self.where: Dict[str, Where] = {}
        self.ambiguous = set()
        for program in programs:
            for name, here in program.items():
                first = self.where.setdefault(name, here)
                if (first.phase, first.kernel) != (here.phase, here.kernel):
                    self.ambiguous.add(name)
        for name in self.ambiguous:
            self.where[name] = dataclasses.replace(self.where[name], phase=AMBIGUOUS)

    def get(self, op: Op) -> Where:
        """Where the programs put the op: its ``phase`` is one of PHASES,
        ``unscoped`` (under none, or in no program) or ``ambiguous``."""
        return self.where.get(op.name, NOWHERE)


def of(run) -> Optional[Phases]:
    """The run's join, made on first use, reported once on standard output and
    kept on the run. ``None`` where there is nothing to join."""
    if not run.chips:
        return None
    if not hasattr(run, "phases"):
        run.phases = _build()
        if run.phases:
            report(run, run.phases)
    return run.phases


def _build() -> Optional[Phases]:
    import bluefog_tpu as bf

    if not hasattr(bf, "step_programs") or not bf.step_programs():
        return None
    programs = bf.step_programs()
    t0 = time.perf_counter()
    texts = [program.hlo_text() for program in programs]
    t1 = time.perf_counter()
    phases = Phases([parse(text) for text in texts])
    print(f"phases: {len(programs)} step program(s) {[p.key for p in programs]}; hlo_text() "
          f"{(t1 - t0) / len(programs):.3f} s each, {sum(map(len, texts))} characters; "
          f"{len(phases.where)} instructions parsed in {time.perf_counter() - t1:.3f} s")
    return phases


def chip_seconds(run, chip, predicate: Callable[[Op], bool]) -> float:
    return sum(op.seconds for op in chip.ops if predicate(op)) / run.traced_steps


def seconds(run, predicate: Callable[[Op], bool], worst_chip: bool = False) -> float:
    """Device seconds a step in the ops ``predicate`` picks: on the busiest
    chip, or on the chip where they take longest (the combine's ops: the chip
    that waits longest for its peer)."""
    if worst_chip:
        return max(chip_seconds(run, chip, predicate) for chip in run.chips)
    return chip_seconds(run, run.trace.busiest, predicate)


def phase_ms(run, phase: str, worst_chip: bool = False) -> Optional[float]:
    """What the phase readers return: milliseconds a step of the ops under
    ``phase`` (``unscoped`` takes the ambiguous ones too)."""
    phases = of(run)
    if phases is None:
        return None
    wanted = (UNSCOPED, AMBIGUOUS) if phase == UNSCOPED else (phase,)
    return seconds(run, lambda op: phases.get(op).phase in wanted, worst_chip) * 1e3


def kernel_ms(run, kernel: str) -> Optional[float]:
    """Milliseconds a step of the Mosaic kernels under the scope ``kernel``."""
    phases = of(run)
    if phases is None:
        return None
    return seconds(run, lambda op: op.is_mosaic and phases.get(op).kernel == kernel) * 1e3


def host_span_ms(run, suffix: str) -> Optional[float]:
    """The median of the host spans named ``*<suffix>`` in the traced steps."""
    if not run.chips:
        return None
    spans = [end - start for name, start, end in run.trace.host if name.endswith(suffix)]
    return statistics.median(spans) * 1e3 if spans else None


def report(run, phases: Phases) -> None:
    """Lines for the run's log: the step by phase, the share of each rule of
    the join, and where the time under ``bf.grad`` and outside every scope goes."""
    chip, where = run.trace.busiest, phases.get

    def per_step(pick, on=chip) -> float:
        return chip_seconds(run, on, pick) * 1e3

    def by(names, pick, on=chip) -> str:
        return ", ".join(f"{name} {per_step(lambda op: pick(op, name), on):.3f}" for name in names)

    in_phase = lambda op, name: where(op).phase == name
    total = per_step(lambda op: True)
    print(f"phases on {chip.plane}, ms a step: {by(PHASES + (UNSCOPED, AMBIGUOUS), in_phase)}; "
          f"sum {total:.3f}, busy {chip.busy_s / run.traced_steps * 1e3:.3f}")
    print("phases, ms a step in ops touching (a fused member under) each: "
          + by(PHASES, lambda op, name: name in where(op).touches))
    print(f"phases: {per_step(lambda op: where(op).mixed):.3f} ms in fusions whose members span "
          f"two phases, {per_step(lambda op: where(op).inherited):.3f} ms in ops without "
          "metadata that took their neighbours' phase, "
          f"{per_step(lambda op: op.name not in phases.where):.3f} ms in ops no program has")
    for other in run.chips:
        if other is not chip:
            print(f"phases on {other.plane}: {by(PHASES, in_phase, other)}")
    print("kernels, ms a step: "
          + by(KERNELS, lambda op, name: op.is_mosaic and where(op).kernel == name))
    _largest("mixed fusions by phase given < phases touched", chip, run.traced_steps,
             lambda op: where(op).mixed,
             lambda op: f"{where(op).phase} < {'+'.join(sorted(where(op).touches))}")
    _largest("under bf.grad by scope", chip, run.traced_steps,
             lambda op: where(op).phase == PHASES[0], lambda op: second_level(where(op).path))
    _largest("unscoped or ambiguous ops", chip, run.traced_steps,
             lambda op: where(op).phase in (UNSCOPED, AMBIGUOUS),
             lambda op: f"{op.name} {op.opcode} {op.largest_result()} "
                        f"[{where(op).path if op.name in phases.where else 'in no program'}]")


def _largest(title: str, chip, steps: int, pick, label, count: int = 10) -> None:
    total: Dict[str, float] = {}
    for op in filter(pick, chip.ops):
        key = label(op)
        total[key] = total.get(key, 0.0) + op.seconds / steps * 1e3
    top = sorted(total.items(), key=lambda kv: -kv[1])[:count]
    print(f"largest {title}, ms a step: " + "; ".join(f"{key} {ms:.3f}" for key, ms in top))
