"""The seven set-up readers (``benchmark/setup_parts.py``) on a toy CPU run:
each reads what the program's registry and ``bf.step_programs()`` hold, a
program built after set-up is left out, and a program without build records
(a parent commit's) gives ``None``. No number here is a device metric."""

import dataclasses

import pytest

import bluefog_tpu as bf
from bluefog_tpu import optimizers

from benchmark import harness, setup_parts
from benchmark.manifest import Manifest

from test_harness import run_stages

READERS = ["bf_import_s", "optimizer_init_s", "step_trace_s", "step_compile_s",
           "step_cache_hit_share", "step_program_hbm_gib", "optimizer_init_hbm_gib"]


def read_all(manifest, run):
    return {name: manifest.plugin("layer_metrics", name).read(run) for name in READERS}


@pytest.fixture()
def toy_run(toy_root):
    """A four-rank one-peer toy run; the registry and the programs are the last job's."""
    optimizers._STEP_PROGRAMS.clear()
    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, "toy-lm-onepeer-4")
    assert verdict["ok"], verdict
    return manifest, run


def test_every_reader_is_a_per_layer_entry_without_a_workloads_list():
    entries = {m["name"]: m for m in Manifest().doc["per_layer"]}
    assert [m["name"] for m in Manifest().doc["per_layer"]][-len(READERS):] == READERS
    for name in READERS:
        assert "workloads" not in entries[name]
        assert entries[name]["moves"] == ("peak_hbm_gib" if "hbm" in name else "setup_s")


def test_readers_read_the_registry_and_the_build_records(toy_run, capsys):
    manifest, run = toy_run
    values = read_all(manifest, run)
    gauges = bf.metrics.snapshot()["gauges"]
    programs = bf.step_programs()
    # two shift sets, two programs, built at steps 1 and 2 of set-up
    assert [p.build.step for p in programs] == [1, 2]
    assert values["bf_import_s"] == gauges["import.total_sec"] == bf.IMPORT_SECONDS["total"]
    assert values["optimizer_init_s"] == gauges["opt.init_sec"] > 0
    assert values["optimizer_init_s"] <= sum(run.spans.seconds["opt_init_s"])
    assert values["step_trace_s"] == pytest.approx(
        sum(p.build.trace_s + p.build.lower_s for p in programs))
    assert values["step_trace_s"] == pytest.approx(
        gauges["opt.build_trace_sec"] + gauges["opt.build_lower_sec"])
    assert values["step_compile_s"] == pytest.approx(gauges["opt.build_compile_sec"])
    assert values["step_compile_s"] > 0
    assert values["step_cache_hit_share"] == 0.0  # bf.init() gives a CPU no persistent cache
    assert values["step_program_hbm_gib"] * setup_parts.GIB == max(
        p.memory().resident_bytes for p in programs)
    assert values["optimizer_init_hbm_gib"] is None  # the CPU backend has no memory_stats()
    # the parts bound the whole: builds inside the first and the checked steps
    built = sum(p.build.total_s for p in programs)
    assert values["step_trace_s"] + values["step_compile_s"] <= built
    assert built <= sum(run.spans.seconds["first_step_s"] + run.spans.seconds["checked_steps_s"])
    out = capsys.readouterr().out
    assert "import bluefog_tpu" in out and "checkpoint" in out
    assert "set-up from inside" in out and "2 build(s)" in out
    assert out.count("build of StepProgram(") == 2 and "largest step program" in out
    # the harness leaves out what a reader does not find, and keeps a zero
    layers = harness.per_layer(manifest, run)
    assert set(READERS) - set(layers) == {"optimizer_init_hbm_gib"}
    assert layers["step_cache_hit_share"] == 0.0


def test_a_program_built_after_set_up_is_ignored(toy_run):
    manifest, run = toy_run
    before = read_all(manifest, run)
    late = optimizers.StepProgram("late", ("none",), None, (None,))
    late.build = optimizers.BuildRecord(
        step=harness.CHECK_STEPS + run.cell.traffic["warmup_steps"] + 1, t_begin_ns=0,
        total_s=50.0, trace_s=20.0, lower_s=10.0, compile_s=19.0, cache_hit=True,
        cache_load_s=0.0, saved_s=0.0)
    optimizers._STEP_PROGRAMS.append(late)
    try:
        assert len(setup_parts.programs(run)) == 2
        assert read_all(manifest, run) == before
    finally:
        optimizers._STEP_PROGRAMS.pop()


def test_a_cache_hit_counts(toy_run, monkeypatch):
    manifest, run = toy_run
    first, second = bf.step_programs()
    monkeypatch.setattr(second, "build", dataclasses.replace(second.build, cache_hit=True))
    assert manifest.plugin("layer_metrics", "step_cache_hit_share").read(run) == 50.0


def test_a_parents_program_gives_none(toy_run, monkeypatch):
    """No ``build``, no ``memory()``, none of the gauges: what the parent commit has."""
    manifest, run = toy_run

    class Program:
        key = (True, (1,), False)

        def hlo_text(self):
            return ""

    monkeypatch.setattr(bf, "step_programs", lambda: [Program()])
    registry = bf.metrics.registry()
    monkeypatch.setattr(registry, "_gauges", {
        k: v for k, v in registry._gauges.items()
        if not k.startswith(("import.", "opt.init_", "opt.build_"))})
    assert read_all(manifest, run) == dict.fromkeys(READERS)
    monkeypatch.delattr(bf, "step_programs")
    assert read_all(manifest, run) == dict.fromkeys(READERS)
