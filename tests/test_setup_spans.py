"""Set-up seen from inside: the build record of a step program (the BUILD span
with the program's first call in it, split by ``jax.monitoring`` into trace,
lower and compile or cache load), ``StepProgram.memory()``, the
``<optimizer>.INIT`` span with its gauges, and the import stamps.

Everything runs on the 4-device CPU mesh; no number here is a device metric.
"""

import json
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring as jax_monitoring

import bluefog_tpu as bf
from bluefog_tpu import optimizers
from bluefog_tpu.runtime import flight as flight_mod
from bluefog_tpu.runtime import timeline
from bluefog_tpu.runtime.state import _global_state

from conftest import cpu_devices

N = 4
TRACE = "/jax/core/compile/jaxpr_trace_duration"


@pytest.fixture()
def bf4():
    bf.init(devices=cpu_devices(N), local_size=2)
    yield bf
    bf.shutdown()


def quad_loss(p, b):
    return 0.5 * jnp.sum((p["w"] @ p["v"] - b) ** 2)


def params():
    return {"w": jnp.ones((4, 4), jnp.float32), "v": jnp.ones((4,), jnp.float32)}


BATCH = np.ones((N, 4), np.float32)

# the fused family, the sharded one and one window optimizer: each builds its
# step through _FusedOptimizer._compile and its state through .init
FAMILIES = {
    "fused": bf.DistributedNeighborAllreduceOptimizer,
    "sharded": bf.DistributedShardedAllreduceOptimizer,
    "window": bf.DistributedWinPutOptimizer,
}


@pytest.fixture(params=sorted(FAMILIES))
def make_opt(request, bf4):
    made = []

    def make(loss=quad_loss, **kwargs):
        opt = FAMILIES[request.param](optax.sgd(0.1), loss, **kwargs)
        made.append(opt)
        return opt

    yield make
    for opt in made:
        if hasattr(opt, "free"):
            opt.free()


def steps(opt, n, state=None):
    state = opt.init(params()) if state is None else state
    for _ in range(n):
        state, _ = opt.step(state, BATCH)
    return state


def new_programs(before):
    return [p for p in bf.step_programs() if p not in before]


def registry():
    snap = bf.metrics.snapshot(include_native=False)
    return {**snap["counters"], **snap["gauges"]}


def test_a_build_record_holds_its_parts(make_opt):
    before, t0 = bf.step_programs(), time.perf_counter_ns()
    steps(make_opt(), 1)
    t1 = time.perf_counter_ns()
    (program,) = new_programs(before)
    build = program.build
    assert build.step == 1 and t0 < build.t_begin_ns < t1
    assert build.trace_s > 0 and build.lower_s > 0 and build.compile_s > 0
    assert build.trace_s + build.lower_s + build.compile_s <= build.total_s
    assert build.dispatch_s == pytest.approx(
        build.total_s - build.trace_s - build.lower_s - build.compile_s)
    assert build.t_begin_ns + build.total_s * 1e9 <= t1
    assert not build.cache_hit and build.cache_load_s == 0 and build.saved_s == 0
    with pytest.raises(AttributeError):  # a frozen record
        build.step = 2
    found = registry()
    assert found["opt.step_cache_misses"] == 1 and found["opt.build_cache_hits"] == 0
    assert found["opt.build_trace_sec"] == build.trace_s
    assert found["opt.build_lower_sec"] == build.lower_s
    assert found["opt.build_compile_sec"] == build.compile_s


def test_three_steps_on_one_plan_are_one_miss(make_opt):
    before = bf.step_programs()
    steps(make_opt(), 3)
    assert len(new_programs(before)) == 1
    found = registry()
    assert found["opt.step_cache_misses"] == 1 and found["opt.step_cache_size"] == 1
    assert found["opt.step"] == 3


def test_a_second_plan_is_a_second_miss_and_the_first_again_none(bf4):
    before = bf.step_programs()
    opt = bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1), quad_loss)

    def one_peer(shift):
        opt.self_weight, opt.send_neighbors = 0.5, {r: [(r + shift) % N] for r in range(N)}
        opt.neighbor_weights = {r: {(r - shift) % N: 0.5} for r in range(N)}

    one_peer(1)
    state = steps(opt, 3)
    assert registry()["opt.step_cache_misses"] == 1
    one_peer(2)
    state = steps(opt, 1, state)
    assert registry()["opt.step_cache_misses"] == 2 and registry()["opt.step_cache_size"] == 2
    one_peer(1)
    steps(opt, 2, state)
    found = registry()
    assert found["opt.step_cache_misses"] == 2 and found["opt.step_cache_size"] == 2
    first, second = new_programs(before)
    assert (first.build.step, second.build.step) == (1, 4) and first.key != second.key
    assert found["opt.build_compile_sec"] == pytest.approx(
        first.build.compile_s + second.build.compile_s)
    assert first.build.t_begin_ns + first.build.total_s * 1e9 < second.build.t_begin_ns


def test_what_compiles_outside_a_build_is_not_counted(make_opt):
    before = bf.step_programs()
    opt = make_opt()
    state = steps(opt, 1)
    (program,) = new_programs(before)
    record, found = program.build, registry()
    assert "bf.grad" in program.hlo_text()
    program.memory()
    assert float(jax.jit(lambda x: jnp.tanh(x) * 3)(jnp.ones((3,))).sum()) > 0  # a user's own
    steps(opt, 1, state)
    assert program.build is record and new_programs(before) == [program]
    after = registry()
    assert {k: v for k, v in after.items() if k != "opt.step"} == \
        {k: v for k, v in found.items() if k != "opt.step"}
    assert timeline._BUILDING.open is None


def test_a_nested_jit_in_the_loss_is_counted_once(make_opt):
    nap = 0.3

    @jax.jit
    def inner(x):
        time.sleep(nap)  # at trace time: the inner trace takes this long
        return jnp.sin(x)

    def loss(p, b):
        return 0.5 * jnp.sum((inner(p["w"]) @ p["v"] - b) ** 2)

    seen = []

    def listener(event, seconds, **_):
        if event == TRACE:
            seen.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listener)
    before = bf.step_programs()
    opt = make_opt(loss)
    state = opt.init(params())
    try:
        seen.clear()
        opt.step(state, BATCH)
    finally:
        jax_monitoring.unregister_event_duration_listener(listener)
    (program,) = new_programs(before)
    build = program.build
    # JAX reported inner's trace and, containing it, the step's
    assert sum(s >= nap for s in seen) >= 2
    assert nap <= build.trace_s <= sum(seen) - nap
    assert build.trace_s + build.lower_s + build.compile_s <= build.total_s


def test_a_build_that_fails_leaves_nothing_behind(make_opt):
    def loss(p, b):
        raise ValueError("no such loss")

    before, found = bf.step_programs(), registry()
    opt = make_opt(loss)
    state = opt.init(params())
    with pytest.raises(ValueError, match="no such loss"):
        opt.step(state, BATCH)
    assert timeline._BUILDING.open is None and not opt._step_cache
    assert new_programs(before) == []
    assert registry().get("opt.step_cache_misses", 0) == found.get("opt.step_cache_misses", 0)


def test_two_inits_leave_one_listener_pair():
    for _ in range(2):
        bf.init(devices=cpu_devices(N), local_size=2)
    bf.shutdown()
    assert jax_monitoring.get_event_duration_listeners().count(
        timeline._on_build_seconds) == 1
    assert jax_monitoring.get_event_listeners().count(timeline._on_build_event) == 1


def test_events_with_no_build_open_go_nowhere():
    assert getattr(timeline._BUILDING, "open", None) is None
    timeline._on_build_seconds(TRACE, 1.0, fun_name="f")
    timeline._on_build_event("/jax/compilation_cache/cache_hits")


@pytest.mark.parametrize("how", ["persistent cache", "events"])
def test_a_cache_hit_sets_cache_hit(bf4, how, tmp_path):
    before = bf.step_programs()
    if how == "events":
        # what JAX sends when the persistent cache answers, handed over by hand
        building = timeline._BUILDING.open = timeline._OpenBuild()
        try:
            timeline._on_build_event("/jax/compilation_cache/compile_requests_use_cache")
            timeline._on_build_event("/jax/compilation_cache/cache_hits")
            timeline._on_build_seconds("/jax/compilation_cache/compile_time_saved_sec", 30.0)
            timeline._on_build_seconds("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
            timeline._on_build_seconds("/jax/core/compile/backend_compile_duration", 0.75)
        finally:
            timeline._BUILDING.open = None
        build = building.record(7)
        assert build.cache_hit and (build.step, build.t_begin_ns) == (7, building.t_begin_ns)
        assert (build.compile_s, build.cache_load_s, build.saved_s) == (0.75, 0.5, 30.0)
        # one request of two answered is no hit
        building.compiles += 1
        assert not building.record(7).cache_hit
        return
    from jax.experimental.compilation_cache import compilation_cache

    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {name: getattr(jax.config, name) for name in settings}
    try:
        for name, value in settings.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        steps(bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1), quad_loss), 1)
        jax.clear_caches()
        steps(bf.DistributedNeighborAllreduceOptimizer(optax.sgd(0.1), quad_loss), 1)
    finally:
        for name, value in old.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    first, second = new_programs(before)
    assert not first.build.cache_hit
    if not second.build.cache_hit:
        pytest.skip("the CPU backend did not take the persistent cache here")
    assert second.build.cache_load_s > 0 and second.build.compile_s >= second.build.cache_load_s
    found = registry()
    assert found["opt.step_cache_misses"] == 2 and found["opt.build_cache_hits"] == 1


def test_memory_sums_as_defined(make_opt):
    before = bf.step_programs()
    steps(make_opt(), 1)
    (program,) = new_programs(before)
    memory, analysis = program.memory(), program._compiled().memory_analysis()
    assert memory.argument_bytes == analysis.argument_size_in_bytes > 0
    assert memory.output_bytes == analysis.output_size_in_bytes > 0
    assert memory.alias_bytes == analysis.alias_size_in_bytes
    assert memory.temp_bytes == analysis.temp_size_in_bytes
    assert memory.code_bytes == analysis.generated_code_size_in_bytes
    assert memory.resident_bytes == (memory.argument_bytes + memory.output_bytes
                                     - memory.alias_bytes + memory.temp_bytes + memory.code_bytes)


def test_the_build_instant_stands_inside_its_step_in_the_flight_ring(make_opt):
    before = bf.step_programs()
    steps(make_opt(), 2)
    (program,) = new_programs(before)
    snap = flight_mod.recorder().snapshot()
    names, events = snap["names"], snap["events"]
    rows = [(names[n], kind, a, b) for n, kind, a, b in
            zip(events["name"], events["kind"], events["a"], events["b"])
            if names[n] in ("opt.step", "opt.build")]
    assert [(name, kind) for name, kind, _, _ in rows] == [
        ("opt.step", flight_mod.SPAN_B), ("opt.build", flight_mod.INSTANT),
        ("opt.step", flight_mod.SPAN_E), ("opt.step", flight_mod.SPAN_B),
        ("opt.step", flight_mod.SPAN_E)]
    _, _, a, b = rows[1]
    assert a == pytest.approx(program.build.total_s) and b == program.build.step == 1


def lane(path, cat):
    """(name, begin µs, end µs, depth) of every span of one optimizer's lane."""
    with open(path) as f:
        events = [e for e in json.load(f) if e.get("cat") == cat]
    open_, spans = [], []
    for e in events:
        if e["ph"] == "B":
            open_.append((e["name"], e["ts"]))
        elif e["ph"] == "E":
            name, begin = open_.pop()
            spans.append((name, begin, e["ts"], len(open_) + 1))
    assert not open_
    return sorted(spans, key=lambda s: s[1])


def test_init_is_one_span_outside_every_step_and_build_holds_the_first_call(make_opt, tmp_path):
    st = _global_state()
    st.timeline = timeline.Timeline(str(tmp_path / "tl_"), use_native=False)
    before = bf.step_programs()
    try:
        steps(make_opt(name="opt.setup"), 3)
    finally:
        path = st.timeline.path
        bf.stop_timeline()
    spans = lane(path, "opt.setup")
    by_name = {name: [s for s in spans if s[0] == name] for name in ("INIT", "STEP", "BUILD")}
    (init,), (build,) = by_name["INIT"], by_name["BUILD"]
    assert len(by_name["STEP"]) == 3
    assert init[3] == 1 and init[2] <= by_name["STEP"][0][1]     # closed before the first STEP
    first = by_name["STEP"][0]
    assert build[3] == 2 and first[1] <= build[1] <= build[2] <= first[2]
    # what JAX did for the program's first call lies inside BUILD
    (program,) = new_programs(before)
    record = program.build
    assert (record.trace_s + record.lower_s + record.compile_s) * 1e6 <= build[2] - build[1]
    assert (build[2] - build[1]) / 1e6 <= record.total_s
    found = registry()
    assert 0 < found["opt.init_sec"] and (init[2] - init[1]) / 1e6 <= found["opt.init_sec"]
    # the CPU backend keeps no memory_stats(): no gauge, no error
    assert "opt.init_hbm_peak_bytes" not in found


def test_the_hbm_peak_is_the_fullest_local_devices():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    class FakeMesh:
        def __init__(self, *stats):
            self.local_devices = [Device(s) for s in stats]

    assert optimizers._hbm_peak_bytes(FakeMesh(None, None)) is None
    assert optimizers._hbm_peak_bytes(FakeMesh({"bytes_in_use": 3})) is None
    assert optimizers._hbm_peak_bytes(FakeMesh(
        {"peak_bytes_in_use": 5, "bytes_in_use": 3}, {"peak_bytes_in_use": 9}, None)) == 9


def test_the_import_gauges_survive_reset_for_job():
    groups = {"runtime", "ops", "optimizers", "utils", "checkpoint", "models", "parallel",
              "serving"}
    assert set(bf.IMPORT_SECONDS) == groups | {"total"}
    assert all(seconds >= 0 for seconds in bf.IMPORT_SECONDS.values())
    assert sum(bf.IMPORT_SECONDS[g] for g in groups) == pytest.approx(
        bf.IMPORT_SECONDS["total"], rel=0.05)
    for _ in range(2):  # each init zeroes the registry, then writes them again
        bf.init(devices=cpu_devices(N), local_size=2)
        gauges = bf.metrics.snapshot(include_native=False)["gauges"]
        assert gauges["import.total_sec"] == bf.IMPORT_SECONDS["total"] > 0
        for group in groups:
            assert gauges[f"import.{group}_sec"] == bf.IMPORT_SECONDS[group]
    bf.metrics.reset_for_job()
    assert bf.metrics.snapshot(include_native=False)["gauges"]["import.total_sec"] == 0
    bf.init(devices=cpu_devices(N), local_size=2)
    assert bf.metrics.snapshot(include_native=False)["gauges"]["import.total_sec"] == \
        bf.IMPORT_SECONDS["total"]
    bf.shutdown()
    assert "import.total_sec" in bf.metrics.help_for("import.total_sec") or \
        "import bluefog_tpu" in bf.metrics.help_for("import.total_sec")
    assert "import group" in bf.metrics.help_for("import.checkpoint_sec")
