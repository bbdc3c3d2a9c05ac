"""Set-up as the program reports it from inside, for the seven readers that
take it apart (``bf_import_s``, ``optimizer_init_s``, ``step_trace_s``,
``step_compile_s``, ``step_cache_hit_share``, ``step_program_hbm_gib``,
``optimizer_init_hbm_gib``).

The harness times its own calls into the program (``Spans``: ``import_s``,
``opt_init_s``, ``first_step_s`` ...). What happens inside them the program
keeps itself: the gauges ``import.*_sec`` of the package's ``__init__``,
``opt.init_sec`` and ``opt.init_hbm_peak_bytes`` of the ``<optimizer>.INIT``
span, and one ``build`` record a step program (``bf.step_programs()[i].build``:
the ``<optimizer>.BUILD`` span split into trace, lower, compile or cache load,
and the rest). Everything here goes through ``bluefog_tpu``'s public names and
gives ``None`` on a program that lacks them (a parent commit).
"""

from __future__ import annotations

import itertools
import sys
from typing import Dict, List, Optional

from .harness import CHECK_STEPS

GIB = 2 ** 30


def _gauges() -> Dict[str, float]:
    import bluefog_tpu as bf

    return bf.metrics.snapshot(include_native=False)["gauges"]


def gauge(name: str) -> Optional[float]:
    """A gauge of the program's registry; ``None`` where it was never set."""
    return _gauges().get(name)


def import_seconds() -> Optional[Dict[str, float]]:
    """``import bluefog_tpu`` by import group, and ``"total"``."""
    seconds = {name[len("import."):-len("_sec")]: value
               for name, value in _gauges().items() if name.startswith("import.")}
    return seconds if "total" in seconds else None


def programs(run) -> Optional[List]:
    """The step programs set-up built, oldest first: those whose build record
    has a step of the first step, the checked steps or the warm-up (the
    harness refuses a run that builds inside the window). ``None`` where the
    programs keep no record."""
    import bluefog_tpu as bf

    built = [p for p in getattr(bf, "step_programs", list)()
             if getattr(p, "build", None) is not None]
    last = CHECK_STEPS + run.cell.traffic["warmup_steps"]
    return [p for p in built if p.build.step <= last] or None


def builds(run) -> Optional[List]:
    found = programs(run)
    return found and [p.build for p in found]


def account(run) -> None:
    """One block of lines: each harness span that wraps a call into the
    program against what the program reports inside it, and what neither
    explains."""
    imports, init_s, built = import_seconds(), gauge("opt.init_sec"), builds(run)
    if imports is None or init_s is None or built is None:
        return
    spans = {k: sum(v) for k, v in run.spans.seconds.items() if k != "host_step_s"}
    other_imports = spans.get("import_s", 0.0) - imports["total"]
    around_init = spans["opt_init_s"] - init_s
    total = sum(b.total_s for b in built)
    stepping = spans["first_step_s"] + spans["checked_steps_s"]
    print(f"set-up from inside: the harness's spans sum to {sum(spans.values()):.3f} s; "
          f"import_s {spans.get('import_s', 0.0):.3f} = import bluefog_tpu {imports['total']:.3f} "
          f"+ {other_imports:.3f} of other imports; opt_init_s {spans['opt_init_s']:.3f} = "
          f"INIT {init_s:.3f} + {around_init:.3f} around it; first_step_s + checked_steps_s "
          f"{stepping:.3f} = {len(built)} build(s) {total:.3f} (trace "
          f"{sum(b.trace_s for b in built):.3f}, lower {sum(b.lower_s for b in built):.3f}, "
          f"compile or load {sum(b.compile_s for b in built):.3f}, dispatch "
          f"{sum(b.dispatch_s for b in built):.3f}) + {stepping - total:.3f} of steps and waits")
    if len(built) == 1:
        print(f"first_step_s - build.total_s = {spans['first_step_s'] - total:.4f} s "
              "(one step on the device and the harness's wait)")
    # run.py's T_PROCESS, the spans and the builds' t_begin_ns are all on
    # time.perf_counter(): what lies between process start and the first BUILD
    # and in no span (the manifest, the schedule, PLAN)
    t_process = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
    if t_process is None:
        return
    before = itertools.takewhile(lambda k: k != "first_step_s", spans)
    between = built[0].t_begin_ns / 1e9 - t_process - sum(spans[k] for k in before)
    print(f"unattributed set-up: {between:.3f} s between the spans before the first build, "
          f"{other_imports:.3f} of other imports, {around_init:.3f} around INIT: "
          f"{between + other_imports + around_init:.3f} s")
