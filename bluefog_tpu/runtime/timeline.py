"""Chrome-tracing timeline profiler.

Analog of BlueFog's Timeline subsystem (reference: common/timeline.{h,cc}):
named activities streamed through a lock-free queue to a dedicated writer
thread producing catapult/chrome-tracing JSON (load in chrome://tracing or
Perfetto). Enabled by ``BLUEFOG_TIMELINE=<prefix>`` -> one file
``<prefix><process>.json`` (operations.cc:449-458), or programmatically.

Device-side timing on TPU comes from ``jax.profiler`` xplane traces;
:func:`trace_context` bridges the two by emitting a named activity and a
jax.profiler TraceAnnotation for the same span.

When the native host runtime extension is built (csrc/), the writer is backed
by the C++ spsc-queue implementation; this pure-Python writer (daemon thread +
queue.SimpleQueue) is the fallback and the semantics are identical.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import queue
import threading
import time
from typing import Optional

import jax

from .logging import logger

# Counter-event name anchoring each per-process trace to the wall clock;
# scripts/merge_timelines.py keys on it to align files before merging.
CLOCK_SYNC_COUNTER = "bf.clock_sync_us"


class Timeline:
    """Streaming chrome-tracing writer with named activities per (tensor, lane)."""

    _SENTINEL = object()

    def __init__(self, prefix: str, process_index: Optional[int] = None,
                 use_native: bool = True) -> None:
        if process_index is None:
            # The runtime's backend-aware index, not argless
            # jax.process_index(): the DEFAULT backend can be a
            # single-process plugin while the mesh is multi-process, and
            # co-hosted controllers must not share a trace file.
            from .state import _global_state

            st = _global_state()
            pid = st.process_index if st.initialized else jax.process_index()
        else:
            pid = process_index
        self.path = f"{prefix}{pid}.json"
        self._t0 = time.perf_counter_ns()
        self._pid = pid
        self._closed = False
        self._failed = False  # writer died: stop producing so the queue can't grow
        self._native = None
        self._native_lib = None
        # Serializes native event emission against close(): bf_timeline_close
        # frees the C++ writer, so no producer may hold the handle across it.
        self._native_mu = threading.Lock()
        if use_native:
            from . import native as _native_mod

            lib = _native_mod.load()
            if lib is not None:
                handle = lib.bf_timeline_open(self.path.encode(), pid)
                if handle:
                    self._native = handle
                    self._native_lib = lib
        if self._native is None:
            self._q: "queue.SimpleQueue" = queue.SimpleQueue()
            self._writer = threading.Thread(
                target=self._writer_loop, name="bf-timeline-writer", daemon=True
            )
            self._writer.start()
        # Clock-sync anchor: timestamps are a per-process perf_counter
        # origin, useless across processes until anchored to a shared
        # clock. The first event of every trace is a counter carrying the
        # wall-clock microseconds at (approximately) ts=0;
        # scripts/merge_timelines.py shifts each file onto the common
        # wall-clock axis using (value - ts) before concatenating.
        self.counter(CLOCK_SYNC_COUNTER, time.time_ns() // 1000)

    # -- producer side (any thread) ---------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def activity_start(self, tensor_name: str, activity: str, tid: int = 0) -> None:
        if self._failed or self._closed:
            return
        if self._native is not None:
            with self._native_mu:
                if self._native is not None:
                    self._native_lib.bf_timeline_event(
                        self._native, activity.encode(), tensor_name.encode(),
                        b"B", int(self._now_us()), tid)
            return
        self._q.put(
            {"name": activity, "cat": tensor_name, "ph": "B",
             "ts": self._now_us(), "pid": self._pid, "tid": tid}
        )

    def activity_end(self, tensor_name: str, tid: int = 0) -> None:
        if self._failed or self._closed:
            return
        if self._native is not None:
            with self._native_mu:
                if self._native is not None:
                    self._native_lib.bf_timeline_event(
                        self._native, b"", tensor_name.encode(),
                        b"E", int(self._now_us()), tid)
            return
        self._q.put(
            {"ph": "E", "ts": self._now_us(), "pid": self._pid, "tid": tid,
             "cat": tensor_name}
        )

    def instant(self, tensor_name: str, activity: str, tid: int = 0) -> None:
        if self._failed or self._closed:
            return
        if self._native is not None:
            with self._native_mu:
                if self._native is not None:
                    self._native_lib.bf_timeline_event(
                        self._native, activity.encode(), tensor_name.encode(),
                        b"i", int(self._now_us()), tid)
            return
        self._q.put(
            {"name": activity, "cat": tensor_name, "ph": "i", "s": "t",
             "ts": self._now_us(), "pid": self._pid, "tid": tid}
        )

    @contextlib.contextmanager
    def activity(self, tensor_name: str, activity: str, tid: int = 0):
        self.activity_start(tensor_name, activity, tid)
        try:
            yield
        finally:
            self.activity_end(tensor_name, tid)

    # -- counter + flow events (r10 trace correlation) ---------------------

    def counter(self, name: str, value: int, tid: int = 0) -> None:
        """Chrome counter-track sample (``ph: "C"``): mailbox depth,
        push-sum mass, and the clock-sync anchor ride these."""
        if self._failed or self._closed:
            return
        if self._native is not None:
            with self._native_mu:
                if self._native is not None:
                    self._native_lib.bf_timeline_event2(
                        self._native, name.encode(), b"bf", b"C",
                        int(self._now_us()), tid, int(value))
            return
        self._q.put(
            {"name": name, "cat": "bf", "ph": "C", "ts": self._now_us(),
             "pid": self._pid, "tid": tid, "args": {"value": int(value)}}
        )

    def _flow(self, phase: bytes, name: str, flow_id: int, tid: int) -> None:
        if self._failed or self._closed:
            return
        if self._native is not None:
            with self._native_mu:
                if self._native is not None:
                    self._native_lib.bf_timeline_event2(
                        self._native, name.encode(), b"bf.flow", phase,
                        int(self._now_us()), tid, int(flow_id))
            return
        ev = {"name": name, "cat": "bf.flow", "ph": phase.decode(),
              "id": int(flow_id), "ts": self._now_us(), "pid": self._pid,
              "tid": tid}
        if phase == b"f":
            ev["bp"] = "e"  # bind to the enclosing slice
        self._q.put(ev)

    def flow_start(self, name: str, flow_id: int, tid: int = 0) -> None:
        """Open a cross-process flow arrow (``ph: "s"``). The id is the
        binding key: the hosted window plane uses the deposit tag's
        ``(origin << 32) | counter`` sequence, which the draining side
        recovers from the wire, so a ``win_put`` on rank A visually
        connects to its drain inside rank B's ``win_update`` when the
        per-rank trace files are merged."""
        self._flow(b"s", name, flow_id, tid)

    def flow_finish(self, name: str, flow_id: int, tid: int = 0) -> None:
        """Close a flow arrow (``ph: "f"``, bound to the enclosing slice)."""
        self._flow(b"f", name, flow_id, tid)

    # -- writer side -------------------------------------------------------

    def _writer_loop(self) -> None:
        try:
            with open(self.path, "w") as f:
                f.write("[\n")
                first = True
                while True:
                    ev = self._q.get()
                    if ev is Timeline._SENTINEL:
                        break
                    if not first:
                        f.write(",\n")
                    f.write(json.dumps(ev))
                    first = False
                    f.flush()
                f.write("\n]\n")
        except OSError as exc:  # disk full / bad prefix: drop, don't crash train
            self._failed = True
            logger.error("timeline writer failed, disabling timeline: %s", exc)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._native is not None:
            with self._native_mu:
                handle, self._native = self._native, None
            self._native_lib.bf_timeline_close(handle)
            return
        self._q.put(Timeline._SENTINEL)
        self._writer.join(timeout=5.0)


# -- module-level API mirroring bf.timeline_* (basics.py:308-388) -----------

def _timeline() -> Optional[Timeline]:
    from .state import _global_state

    return _global_state().timeline


def timeline_start_activity(tensor_name: str, activity: str, tid: int = 0) -> bool:
    tl = _timeline()
    if tl is None:
        return False
    tl.activity_start(tensor_name, activity, tid)
    return True


def timeline_end_activity(tensor_name: str, tid: int = 0) -> bool:
    tl = _timeline()
    if tl is None:
        return False
    tl.activity_end(tensor_name, tid)
    return True


def timeline_counter(name: str, value, tid: int = 0) -> bool:
    """Sample a chrome counter track (no-op when the timeline is off)."""
    tl = _timeline()
    if tl is None:
        return False
    tl.counter(name, int(value), tid)
    return True


def timeline_instant(tensor_name: str, activity: str, tid: int = 0) -> bool:
    """Emit an instant event (stall warnings, membership transitions)."""
    tl = _timeline()
    if tl is None:
        return False
    tl.instant(tensor_name, activity, tid)
    return True


def timeline_flow_start(name: str, flow_id: int, tid: int = 0) -> bool:
    tl = _timeline()
    if tl is None:
        return False
    tl.flow_start(name, flow_id, tid)
    return True


def timeline_flow_finish(name: str, flow_id: int, tid: int = 0) -> bool:
    tl = _timeline()
    if tl is None:
        return False
    tl.flow_finish(name, flow_id, tid)
    return True


@contextlib.contextmanager
def timeline_context(tensor_name: str, activity: str, tid: int = 0):
    """Named span in the host timeline AND the jax.profiler device trace."""
    tl = _timeline()
    with jax.profiler.TraceAnnotation(f"{tensor_name}.{activity}"):
        if tl is not None:
            tl.activity_start(tensor_name, activity, tid)
        try:
            yield
        finally:
            if tl is not None:
                tl.activity_end(tensor_name, tid)


# -- the BUILD span of a step program, with what JAX reports inside it -------

@dataclasses.dataclass(frozen=True)
class BuildRecord:
    """What building one step program cost: ``StepProgram.build``, filled on
    the step-cache miss. ``total_s`` is the ``<optimizer>.BUILD`` span, which
    holds the jitted closure and the program's first call; ``trace_s``,
    ``lower_s`` and ``compile_s`` are what JAX reported inside it
    (``jax.monitoring``): the loss traced to a jaxpr (the outermost traces
    only: an inner ``jit``'s is part of its caller's), the jaxpr lowered to a
    module, and the backend's part, a compilation or with ``cache_hit`` the
    load from the persistent cache (``cache_load_s`` of it reading the entry;
    ``saved_s`` is what JAX says the hit saved)."""

    step: int          # the optimizer's step counter at the miss
    t_begin_ns: int    # time.perf_counter_ns(), the flight ring's clock
    total_s: float
    trace_s: float
    lower_s: float
    compile_s: float
    cache_hit: bool    # every compile request of the build was a cache hit
    cache_load_s: float
    saved_s: float

    @property
    def dispatch_s(self) -> float:
        """What is left of BUILD: the closure, the arguments' shapes, the
        program's first dispatch."""
        return self.total_s - self.trace_s - self.lower_s - self.compile_s


class _OpenBuild:
    """A BUILD span while it is open on a thread: where the two listeners
    below file events, and after it what the record is made from."""

    def __init__(self) -> None:
        self.t_begin_ns = time.perf_counter_ns()
        self.total_s = 0.0
        self.traces: list = []  # (when it ended, seconds) of the outermost traces
        self.lower_s = self.compile_s = self.cache_load_s = self.saved_s = 0.0
        self.compiles = self.cache_hits = 0

    def record(self, step: int) -> BuildRecord:
        return BuildRecord(
            step, self.t_begin_ns, self.total_s, sum(s for _, s in self.traces),
            self.lower_s, self.compile_s,
            self.compiles > 0 and self.cache_hits >= self.compiles,
            self.cache_load_s, self.saved_s)


_BUILDING = threading.local()  # .open: the thread's _OpenBuild, if any


def _on_build_seconds(event: str, seconds: float, **_) -> None:
    build = getattr(_BUILDING, "open", None)
    if build is None:  # hlo_text(), a user's own jit, a reference's steps
        return
    if event == "/jax/core/compile/jaxpr_trace_duration":
        # an inner jit's trace ends inside its caller's and is part of it
        now = time.perf_counter()
        while build.traces and build.traces[-1][0] > now - seconds:
            build.traces.pop()
        build.traces.append((now, seconds))
    elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        build.lower_s += seconds
    elif event == "/jax/core/compile/backend_compile_duration":
        build.compile_s += seconds
        build.compiles += 1
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        build.cache_load_s += seconds
    elif event == "/jax/compilation_cache/compile_time_saved_sec":
        build.saved_s += seconds


def _on_build_event(event: str, **_) -> None:
    build = getattr(_BUILDING, "open", None)
    if build is not None and event == "/jax/compilation_cache/cache_hits":
        build.cache_hits += 1


# one pair a process, for good: bf.init() registers nothing, and nothing here
# calls clear_event_listeners(), which would take other listeners away
jax.monitoring.register_event_duration_secs_listener(_on_build_seconds)
jax.monitoring.register_event_listener(_on_build_event)


@contextlib.contextmanager
def build_context(tensor_name: str):
    """The span ``<tensor_name>.BUILD`` around the building of a step program
    and its first call. While it is open on this thread, what JAX reports of
    tracing, lowering, compiling and the persistent cache is filed into the
    object it yields, whose ``record(step)`` is the :class:`BuildRecord`
    afterwards. With none open the listeners do nothing."""
    building = _BUILDING.open = _OpenBuild()
    try:
        with timeline_context(tensor_name, "BUILD"):
            yield building
    finally:
        _BUILDING.open = None
        building.total_s = (time.perf_counter_ns() - building.t_begin_ns) / 1e9


def start_timeline(prefix: str) -> bool:
    """Enable the timeline at runtime (reference: basics.py timeline start)."""
    from .state import _global_state

    st = _global_state()
    if st.timeline is not None:
        logger.warning("timeline already running; ignoring start_timeline")
        return False
    st.timeline = Timeline(prefix)
    return True


def stop_timeline() -> bool:
    from .state import _global_state

    st = _global_state()
    if st.timeline is None:
        return False
    st.timeline.close()
    st.timeline = None
    return True
