"""One cell, once: set-up, a timed window of optimizer steps, optionally a
profiled handful of steps, and the comparison with the plain reference.

The cell is built as a user's training script would build it -- ``bf.init()``,
a model, a loss, ``bf.Distributed*Optimizer(optax...)``, ``opt.init``,
``opt.step`` -- from the files BENCHMARK.json names (``manifest.py``). Nothing
here knows a configuration, a traffic mix, a schedule or a metric by name.
``run.py`` is the only caller outside the tests, and it is what refuses a
machine without the chips.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import statistics
import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import optax

from . import reference, trace_reduce
from .manifest import Manifest
from .peaks import peaks

# The steps compared with the plain reference. A combine at step k shows in the
# loss of step k+1, so three steps show both shift sets of the one-peer
# schedule at four chips in the losses, and the third combine in the parameters.
CHECK_STEPS = 3


@dataclasses.dataclass
class Cell:
    """Everything one cell's files say, and the modules they name."""

    name: str
    entry: dict      # the workloads entry: config, traffic, chips, why
    config: dict
    traffic: dict
    family: Any
    schedule: Any

    @classmethod
    def load(cls, manifest: Manifest, name: str) -> "Cell":
        entry = manifest.cell(name)
        config = manifest.config(entry["config"])
        traffic = manifest.traffic(entry["traffic"])
        return cls(name, entry, config, traffic,
                   manifest.plugin("families", config["family"]),
                   manifest.plugin("schedules", traffic["schedule"]))


class Spans:
    """Wall-clock spans of the harness's own calls into the program, by name."""

    def __init__(self) -> None:
        self.seconds: Dict[str, List[float]] = {}

    def add(self, name: str, seconds: float) -> None:
        self.seconds.setdefault(name, []).append(seconds)

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)


class CompileCounter:
    """Counts, through ``jax.monitoring``, the programs this process compiled or
    fetched from the persistent cache (inside the window both mean a shape was
    not warmed), and how many of its requests the cache answered."""

    def __init__(self) -> None:
        self.count = self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        self.requests += event == "/jax/compilation_cache/compile_requests_use_cache"
        self.hits += event == "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Run:
    """What a run leaves for the result line, the checks and the metric readers."""

    cell: Cell
    spans: Spans
    check_losses: Optional[np.ndarray] = None        # [CHECK_STEPS, n]
    check_weights: Optional[List[np.ndarray]] = None  # W of each checked step
    check_prints: Any = None                          # fingerprints after them
    chunk_seconds: List[float] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    window_compiles: int = 0
    traced_steps: int = 0
    trace: Optional[trace_reduce.Reduced] = None
    peaks: Optional[dict] = None

    @property
    def step_seconds(self) -> float:
        return sum(self.chunk_seconds) / self.attempted

    @property
    def chips(self) -> List[trace_reduce.Chip]:
        """The traced chips; none without a trace or on a backend without device planes."""
        return self.trace.chips if self.trace else []


class Trainer:
    """The cell's model, optimizer, state and batches, set up as a user would."""

    def __init__(self, cell: Cell, seed: int, devices, spans: Spans) -> None:
        import bluefog_tpu as bf

        self.cell = cell
        fam, cfg, traffic = cell.family, cell.config, cell.traffic
        self.key = jax.random.PRNGKey(seed)
        with spans.timed("init_s"):
            bf.init(devices=list(devices))
        n = bf.size()
        self.tx = getattr(optax, cfg["optimizer"]["name"])(**cfg["optimizer"]["args"])
        self.loss_fn, self.loss_form = fam.loss(cfg)

        with spans.timed("model_init_s"):
            # one jitted program from the seed (eager flax init is hundreds of
            # one-op compiles), in the type the parameters are trained in
            self.init_fn = jax.jit(lambda k: fam.init(cfg, traffic["batch"], k))
            params, model_state = self.init_fn(self.key)
        with spans.timed("opt_init_s"):
            self.opt = getattr(bf, traffic["optimizer"])(
                self.tx, self.loss_fn, **self.loss_form, **traffic["optimizer_args"])
            self.state = self.opt.init(params, model_state=model_state)
            del params, model_state  # the rank-stacked copy is the one the step needs
        with spans.timed("batches_s"):
            # every batch of the pool in one program, each rank's slice made on its chip
            keys = jax.random.split(jax.random.fold_in(self.key, 1), traffic["pool"])
            sharding = bf.rank_sharding(bf.mesh())
            self.pool = jax.jit(
                lambda ks: [fam.make_batch(cfg, traffic["batch"], k, n) for k in ks],
                out_shardings=sharding)(keys)
            jax.block_until_ready(self.pool)
        self.schedule = cell.schedule.Schedule(bf, self.opt)
        self.steps_done = 0
        self.losses: List[Any] = []   # every step's [n] loss, still on the device
        self.weights: List[np.ndarray] = []

    def step(self) -> float:
        """One optimizer step on the next batch of the pool, as the window,
        the warm-up and the traced steps all take it. Returns the seconds the
        host spent in it (the program runs on after that)."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.before_step"):
            W = self.schedule.before_step()
        batch = self.pool[self.steps_done % len(self.pool)]
        with jax.profiler.TraceAnnotation("bench.opt_step"):
            self.state, metrics = self.opt.step(self.state, batch)
        host_seconds = time.perf_counter() - t0
        if self.steps_done < CHECK_STEPS:
            self.weights.append(W)
        self.losses.append(metrics["loss"])
        self.steps_done += 1
        return host_seconds

    def wait(self) -> None:
        """Dispatch is asynchronous: the last step's loss ends what was queued."""
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(self.losses[-1])

    def take_losses(self) -> np.ndarray:
        out = np.stack([np.asarray(l, np.float64) for l in self.losses])
        self.losses = []
        return out

    def close(self) -> None:
        """Free the training state (the reference needs the memory)."""
        self.state = self.opt = self.schedule = None


def set_up(cell: Cell, seed: int, devices, run: Run) -> Trainer:
    """Everything before the window: build, the checked first steps, warm-up."""
    spans = run.spans
    trainer = Trainer(cell, seed, devices, spans)
    with spans.timed("first_step_s"):
        trainer.step()
        trainer.wait()
    with spans.timed("checked_steps_s"):
        while trainer.steps_done < CHECK_STEPS:
            trainer.step()
        run.check_prints = jax.device_get(
            reference.fingerprint_stacked(trainer.state.params))
    run.check_losses = trainer.take_losses()
    run.check_weights = trainer.weights
    with spans.timed("warmup_s"):
        for _ in range(cell.traffic["warmup_steps"]):
            trainer.step()
        trainer.wait()
    trainer.take_losses()
    return trainer


def window(trainer: Trainer, seconds: float, run: Run, compiles: CompileCounter) -> None:
    """Whole chunks of steps, dispatched back to back and each closed by one
    ``block_until_ready``, until ``seconds`` are used up."""
    chunk = trainer.cell.traffic["chunk_steps"]
    compiled_before = compiles.count
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds and not run.failed:
        t0 = time.perf_counter()
        try:
            for _ in range(chunk):
                run.attempted += 1
                run.spans.add("host_step_s", trainer.step())
            trainer.wait()
        except Exception as exc:  # a raised step is a failed step, and ends the window
            print(f"step {run.attempted} raised: {exc!r}", flush=True)
            run.failed += 1
            break
        run.chunk_seconds.append(time.perf_counter() - t0)
    run.window_compiles = compiles.count - compiled_before
    losses = trainer.take_losses()
    run.failed += int((~np.isfinite(losses).all(axis=1)).sum())


def traced_steps(trainer: Trainer, run: Run, trace_dir: str) -> None:
    """A handful of steps under ``jax.profiler``, reduced to ``run.trace``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # TraceAnnotations are kept; Python frames slow the host
    options.enable_hlo_proto = False
    steps = trainer.cell.traffic["trace_steps"]
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(steps):
            trainer.step()
        trainer.wait()
    finally:
        jax.profiler.stop_trace()
    trainer.take_losses()
    run.traced_steps = steps
    run.trace = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))


def check(trainer: Trainer, run: Run, devices) -> dict:
    """The plain references, run last so that the device's peak is the step's
    own: the same seeded parameters, the pool's first batches and the weight
    matrices the schedule reported. Returns the comparison (``ok`` and the
    errors it found)."""
    cell = trainer.cell
    init = functools.partial(trainer.init_fn, trainer.key)
    batches = [trainer.pool[k % len(trainer.pool)] for k in range(CHECK_STEPS)]

    params, model_state = init()
    forward = reference.compare_forward(
        cell.family, cell.config, params, model_state,
        cell.family.check_inputs(reference.rank_slice(batches[0], 0)))
    del params, model_state
    steps = reference.compare_steps(
        run.check_losses, run.check_prints,
        reference.run_steps(trainer.loss_fn, trainer.loss_form.get("with_model_state", False),
                            trainer.tx, init, batches, run.check_weights, devices),
        cell.config["param_dtype"])
    return {"ok": forward["ok"] and steps["ok"], "forward": forward, "steps": steps}


def percentiles(values: List[float]) -> dict:
    q = statistics.quantiles(values, n=4) if len(values) > 1 else [values[0]] * 3
    return {"q1": q[0], "median": q[1], "q3": q[2], "n": len(values)}


def end_to_end(run: Run, setup_s: float, peak: int) -> Dict[str, float]:
    units = run.cell.family.units_per_step(run.cell.traffic["batch"])
    return {
        "step_ms": run.step_seconds * 1e3,
        run.cell.family.THROUGHPUT_METRIC: units / run.step_seconds,
        "peak_hbm_gib": peak / 2 ** 30,
        "setup_s": setup_s,
    }


def per_layer(manifest: Manifest, run: Run) -> Dict[str, float]:
    """Every per-layer metric of the cell whose reader found something."""
    out = {}
    for entry in manifest.metrics("per_layer", run.cell.name):
        value = manifest.plugin("layer_metrics", entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = value
    return out


def breakdown(run: Run) -> dict:
    """For the ledger: the ten device ops with most time a step and the five
    longest idle gaps with what the host had open, on the busiest chip."""
    chip = run.trace.busiest
    total: Dict[str, float] = {}
    first: Dict[str, trace_reduce.Op] = {}
    for op in chip.ops:
        total[op.name] = total.get(op.name, 0.0) + op.seconds / run.traced_steps
        first.setdefault(op.name, op)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[_label(first[name]), seconds] for name, seconds in top],
        "idle_gaps": [[run.trace.host_span_at(a) or "no host span", b - a]
                      for a, b in chip.gaps()[:5]],
    }


def _label(op: trace_reduce.Op) -> str:
    """An op's name with what tells it from its neighbours: the opcode, the
    fusion's kind or "mosaic", and the largest array it produces."""
    kind = "mosaic" if op.is_mosaic else op.text.partition("kind=")[2].split(",")[0]
    return " ".join(filter(None, [op.name, op.opcode, kind, op.largest_result()]))


def device_stamp(devices, run: Run, peak: int) -> dict:
    stamp = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(jax.devices()), "memory_peak_bytes": peak}
    if run.chips:
        stamp["busy_s"] = sum(c.busy_s for c in run.chips) / len(run.chips)
        stamp["window_s"] = sum(c.window_s for c in run.chips) / len(run.chips)
    return stamp


def peak_bytes(devices) -> int:
    """The fullest chip's high-water mark, read after the window and before
    anything is freed. ``peak_bytes_in_use`` counts live arrays only; the scratch
    a loaded program works in (activations, gradients, receive buffers) is
    ``bytes_reserved``, held as long as the program is. So the peak is the larger
    of the most that ever was live and what training holds now, scratch included."""
    def one(stats: dict) -> int:
        return max(stats["peak_bytes_in_use"], stats["bytes_in_use"] + stats["bytes_reserved"])
    return max(one(d.memory_stats()) for d in devices)


def run_cell(manifest: Manifest, name: str, seed: int, seconds: float,
             trace_dir: Optional[str], devices, t_process: float,
             spans: Optional[Spans] = None) -> dict:
    """One run of one cell on ``devices``; returns the result line's object.
    With a ``trace_dir`` a handful of steps are profiled into it after the
    window, and the metrics are the per-layer ones. ``t_process`` is
    ``time.perf_counter()`` as the process started: set-up runs from there to
    the window, and ``spans`` holds what the caller timed of it until now."""
    cell = Cell.load(manifest, name)
    run = Run(cell, spans or Spans(), peaks=peaks(devices[0]))
    compiles = CompileCounter()
    trainer = set_up(cell, seed, devices, run)
    setup_s = time.perf_counter() - t_process
    print(f"set-up compiled or loaded {compiles.count} programs; the persistent cache "
          f"answered {compiles.hits} of {compiles.requests} requests", flush=True)
    window(trainer, seconds, run, compiles)
    if run.window_compiles:
        raise SystemExit(f"{run.window_compiles} program(s) compiled inside the timed "
                         "window: a shape was not warmed, so this is no measurement")
    peak = peak_bytes(devices)
    if trace_dir:
        traced_steps(trainer, run, trace_dir)
    trainer.close()
    verdict = check(trainer, run, devices)
    report(run, setup_s, verdict)

    result = {
        "correct": bool(verdict["ok"]) and run.failed == 0,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {},
        "device": device_stamp(devices, run, peak),
    }
    section = "per_layer" if trace_dir else "end_to_end"
    values = per_layer(manifest, run) if trace_dir else end_to_end(run, setup_s, peak)
    for entry in manifest.metrics(section, name):
        if entry["name"] in values:
            result["metrics"][entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"]}
    if run.chips:
        result["breakdown"] = breakdown(run)
    return result


def report(run: Run, setup_s: float, verdict: dict) -> None:
    """The lines before the result line: what a reader of a run's log wants."""
    cell, fam = run.cell, run.cell.family
    flops = fam.flops_per_step(cell.config, cell.traffic["batch"])
    units = fam.units_per_step(cell.traffic["batch"])
    chunks = percentiles(run.chunk_seconds)
    print(f"cell {cell.name}: {run.attempted} steps in {len(run.chunk_seconds)} chunks of "
          f"{cell.traffic['chunk_steps']}, {sum(run.chunk_seconds):.3f} s; chunk seconds "
          f"median {chunks['median']:.4f} (quartiles {chunks['q1']:.4f}..{chunks['q3']:.4f})")
    print(f"step {run.step_seconds * 1e3:.3f} ms, {units / run.step_seconds:.1f} "
          f"{fam.THROUGHPUT_METRIC}, model FLOPs a step {flops:.4g}, MFU "
          f"{flops / run.step_seconds / run.peaks['bf16_flops']:.4f} of "
          f"{run.peaks['bf16_flops']:.3g} FLOP/s")
    print(f"set-up {setup_s:.2f} s of which " + ", ".join(
        f"{k} {sum(v):.2f}" for k, v in run.spans.seconds.items() if k != "host_step_s"))
    host = percentiles(run.spans.seconds["host_step_s"])
    print(f"host side of a step: median {host['median'] * 1e3:.3f} ms "
          f"(quartiles {host['q1'] * 1e3:.3f}..{host['q3'] * 1e3:.3f}, n={host['n']})")
    for chip in run.chips:
        steps = chip.step_modules()
        print(f"trace {chip.plane}: {len(steps)} runs of {steps[0][0].split('(')[0]} for "
              f"{run.traced_steps} steps, median "
              f"{statistics.median(e - s for _, s, e in steps) * 1e3:.3f} ms; window "
              f"{chip.window_s * 1e3:.3f} ms, busy {chip.busy_s * 1e3:.3f} ms, idle share "
              f"{chip.idle_share:.5f}")
    print(f"check losses {run.check_losses.tolist()}")
    print(f"reference: {verdict}", flush=True)
