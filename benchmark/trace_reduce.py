"""From a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. A v5e trace has one
plane ``/device:TPU:<i>`` per chip with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (every op the core ran, named by its HLO text)
and ``Async XLA Ops`` (copies and collectives in flight, start to done), and a
plane ``/host:CPU`` with one line per host thread holding the
``TraceAnnotation`` spans. All share one clock.

The traced window of a chip runs from the start of its first program to the
end of its last. Busy time is the union of the ``XLA Ops`` intervals inside it:
an op that waits (``collective-permute-done``) counts as busy here and is
reported apart as exposed collective time.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import List, Optional, Tuple

Interval = Tuple[float, float]  # start, end in seconds on the trace's clock

# HLO text of an op event: ``%name = <result type> opcode(operands), attributes``
_OPCODE = re.compile(r"(?<=\s)([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-broadcast")


def _elements(dims: str) -> int:
    count = 1
    for d in filter(None, dims.split(",")):
        count *= int(d)
    return count


@dataclasses.dataclass(frozen=True)
class Op:
    name: str      # "fusion.14"
    opcode: str    # "fusion", "convolution", "custom-call", "collective-permute-done", ...
    text: str      # the whole HLO line as the trace names the event
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def is_mxu(self) -> bool:
        """A convolution or dot as XLA:TPU runs it: bare, or as the root of an
        output fusion (``kind=kOutput``; on TPU a dot is a convolution)."""
        return (self.opcode in ("convolution", "dot")
                or (self.opcode == "fusion" and "kind=kOutput" in self.text))

    @property
    def is_mosaic(self) -> bool:
        """A Pallas kernel compiled by Mosaic: a custom call to ``tpu_custom_call``."""
        return self.opcode == "custom-call" and "tpu_custom_call" in self.text

    @property
    def collective(self) -> Optional[str]:
        """The collective this op starts, finishes or is; None for any other op."""
        for c in COLLECTIVES:
            if self.opcode in (c, c + "-start", c + "-done"):
                return c
        return None

    def largest_result(self) -> str:
        """The largest array the op produces, as ``dtype[dims]`` ("" if none)."""
        head = self.text.split(" = ", 1)[-1].split(" " + self.opcode + "(")[0]
        shapes = _SHAPE.findall(head)
        if not shapes:
            return ""
        dtype, dims = max(shapes, key=lambda s: _elements(s[1]))
        return f"{dtype}[{dims}]"

    def operand_bytes(self) -> int:
        """Bytes of the operands, from the shapes in the HLO text."""
        head = self.text.find(self.opcode + "(")
        depth, end = 0, len(self.text)
        for i in range(head + len(self.opcode), len(self.text)):
            depth += {"(": 1, ")": -1}.get(self.text[i], 0)
            if depth == 0:
                end = i
                break
        total = 0
        for dtype, dims in _SHAPE.findall(self.text[head:end]):
            if dtype not in _DTYPE_BYTES:
                raise ValueError(f"element type {dtype!r} of {self.name} has no size here")
            total += _elements(dims) * _DTYPE_BYTES[dtype]
        return total


@dataclasses.dataclass
class Chip:
    plane: str                      # "/device:TPU:0"
    modules: List[Tuple[str, float, float]]  # (name, start, end) of each program run
    ops: List[Op]
    in_flight: List[Op]             # "Async XLA Ops": copies and collectives, start to done

    @property
    def window(self) -> Interval:
        return self.modules[0][1], max(end for _, _, end in self.modules)

    @property
    def window_s(self) -> float:
        start, end = self.window
        return end - start

    @functools.cached_property
    def busy_intervals(self) -> List[Interval]:
        """The union of the op intervals inside the window, as disjoint intervals."""
        lo, hi = self.window
        merged: List[List[float]] = []
        for op in sorted(self.ops, key=lambda o: o.start):
            start, end = max(op.start, lo), min(op.end, hi)
            if end <= start:
                continue
            if merged and start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], end)
            else:
                merged.append([start, end])
        return [(a, b) for a, b in merged]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps(self) -> List[Interval]:
        """The idle stretches of the window, longest first."""
        lo, hi = self.window
        edges = [lo] + [t for iv in self.busy_intervals for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def step_modules(self) -> List[Tuple[str, float, float]]:
        """Runs of the program that took most of the window (a jitted function
        keeps its name and changes its fingerprint: ``jit_per_rank(123)``)."""
        total = {}
        for name, start, end in self.modules:
            base = name.split("(")[0]
            total[base] = total.get(base, 0.0) + end - start
        step = max(total, key=total.get)
        return [m for m in self.modules if m[0].split("(")[0] == step]


@dataclasses.dataclass
class Reduced:
    chips: List[Chip]
    host: List[Tuple[str, float, float]]  # (name, start, end) of host spans, all threads

    @property
    def busiest(self) -> Chip:
        return max(self.chips, key=lambda chip: chip.busy_s)

    def host_span_at(self, t: float) -> str:
        """The innermost host span open at time ``t`` ("" if none)."""
        open_ = [(end - start, name) for name, start, end in self.host if start <= t < end]
        return min(open_)[1] if open_ else ""


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def _op(text: str, start: float, end: float) -> Op:
    name = text.split(" = ", 1)[0].lstrip("%")
    match = _OPCODE.search(text)
    return Op(name, match.group(1) if match else name.rsplit(".", 1)[0], text, start, end)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce(xplane_path: str) -> Reduced:
    """Read one ``.xplane.pb``. A trace without device planes (a CPU run) gives
    ``chips == []``, and every reader then finds nothing to read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    chips, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Modules" not in lines or "XLA Ops" not in lines:
                continue
            modules = sorted(_events(lines["XLA Modules"]), key=lambda m: m[1])
            if not modules:
                continue
            chips.append(Chip(
                plane=plane.name, modules=modules,
                ops=[_op(*e) for e in _events(lines["XLA Ops"])],
                in_flight=[_op(*e) for e in _events(lines["Async XLA Ops"])]
                if "Async XLA Ops" in lines else []))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # "$file.py:12 fn" events are the Python tracer's: frames, not spans
                host += [e for e in _events(line) if not e[0].startswith("$")]
    chips.sort(key=lambda c: c.plane)
    return Reduced(chips, host)
