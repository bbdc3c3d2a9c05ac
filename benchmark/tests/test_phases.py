"""``benchmark/phases.py``: the parser on a short HLO text in the TPU compiler's
form, the join with synthetic traced ops, the nine readers that rest on it, and
the host spans of a toy cell. No number here is a device metric."""

import pytest

import bluefog_tpu as bf

from benchmark import harness, phases, trace_reduce
from benchmark.manifest import Manifest

from conftest import ROOT

from test_harness import run_stages

NEW_METRICS = ["grad_ms_per_step", "optimizer_update_ms_per_step", "combine_device_ms_per_step",
               "combine_accumulate_ms_per_step", "flash_fwd_ms_per_step", "flash_dq_ms_per_step",
               "flash_dkv_ms_per_step", "unscoped_ms_per_step", "host_plan_ms_per_step"]

P = "jit(per_rank)/shard_map/"
HLO = f'''HloModule jit_per_rank, is_scheduled=true, entry_computation_layout={{(f32[2,2]{{1,0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0.1: f32[8,8], param_1.1: f32[8,8]) -> f32[8,8] {{
  %param_0.1 = f32[8,8]{{1,0}} parameter(0)
  %param_1.1 = f32[8,8]{{1,0}} parameter(1)
  %convolution.2 = f32[8,8]{{1,0}} convolution(%param_0.1, %param_1.1), dim_labels=bf_io->bf, metadata={{op_name="{P}bf.grad/transpose(jvp(Net))/dense/dot_general" source_file="m.py" source_line=3}}
  %constant.5 = f32[] constant(0.9)
  %multiply.7 = f32[8,8]{{1,0}} multiply(%convolution.2, %convolution.2), metadata={{op_name="{P}bf.update/mul"}}
  ROOT %add.9 = f32[8,8]{{1,0}} add(%multiply.7, %param_1.1), metadata={{op_name="{P}bf.update/add"}}
}}

%fused_computation.2 (param_0.2: f32[8,8], param_1.2: f32[2,2]) -> f32[8,8] {{
  %param_0.2 = f32[8,8]{{1,0}} parameter(0)
  %param_1.2 = f32[2,2]{{1,0}} parameter(1)
  %subtract.3 = f32[8,8]{{1,0}} subtract(%param_0.2, %param_0.2), metadata={{op_name="{P}bf.update/sub"}}
  %broadcast.4 = f32[8,8]{{1,0}} broadcast(%param_1.2), metadata={{op_name="{P}bf.combine/jit(_take)/broadcast_in_dim"}}
  ROOT %multiply.8 = f32[8,8]{{1,0}} multiply(%subtract.3, %broadcast.4), metadata={{op_name="{P}bf.combine/mul"}}
}}

%fused_computation.3 (param_0.3: f32[8,8], param_1.3: f32[8,8]) -> f32[8,8] {{
  %param_0.3 = f32[8,8]{{1,0}} parameter(0)
  %param_1.3 = f32[8,8]{{1,0}} parameter(1)
  ROOT %add.11 = f32[8,8]{{1,0}} add(%param_0.3, %param_1.3), metadata={{op_name="{P}bf.combine/add"}}
}}

%fused_computation.4 (param_0.4: f32[1,8,8]) -> f32[8,8] {{
  %param_0.4 = f32[1,8,8]{{2,1,0}} parameter(0)
  ROOT %bitcast.12 = f32[8,8]{{1,0}} bitcast(%param_0.4), metadata={{op_name="jit(per_rank)/shard_map/squeeze"}}
}}

ENTRY %main.40_spmd (param: f32[2,2], param.1: f32[1,8,8]) -> f32[8,8] {{
  %param = f32[2,2]{{1,0}} parameter(0), metadata={{op_name="w"}}
  %param.1 = f32[1,8,8]{{2,1,0}} parameter(1), metadata={{op_name="params[\\'dense\\'][\\'kernel\\']"}}
  %squeeze_fusion = f32[8,8]{{1,0}} fusion(%param.1), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="jit(per_rank)/shard_map/squeeze"}}
  %copy-start.1 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0:S(1)}}, u32[]) copy-start(%squeeze_fusion)
  %copy-done.1 = f32[8,8]{{1,0:S(1)}} copy-done(%copy-start.1)
  %bf.flash.fwd.1 = f32[8,8]{{1,0}} custom-call(%copy-done.1), custom_call_target="tpu_custom_call", metadata={{op_name="{P}bf.grad/jvp(Net)/attn/jit(flash_block)/bf.flash.fwd/pallas_call"}}
  %bf.flash.dq.1 = f32[8,8]{{1,0}} custom-call(%bf.flash.fwd.1), custom_call_target="tpu_custom_call", metadata={{op_name="{P}bf.grad/transpose(bf.grad)/jvp(Net)/attn/jit(flash_block_bwd)/bf.flash.dq/pallas_call"}}
  %bf.flash.dkv.1 = f32[8,8]{{1,0}} custom-call(%bf.flash.fwd.1), custom_call_target="tpu_custom_call", metadata={{op_name="{P}bf.grad/transpose(bf.grad)/jvp(Net)/attn/jit(flash_block_bwd)/bf.flash.dkv/pallas_call"}}
  %copy-start.2 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}, u32[]) copy-start(%bf.flash.dq.1)
  %copy-done.2 = f32[8,8]{{1,0}} copy-done(%copy-start.2)
  %fusion.1 = f32[8,8]{{1,0}} fusion(%copy-done.2, %bf.flash.dkv.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{P}bf.grad/transpose(jvp(Net))/dense/dot_general"}}
  %multiply_subtract_fusion = f32[8,8]{{1,0}} fusion(%fusion.1, %param), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{P}bf.combine/mul"}}
  %collective-permute-start.1 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}, u32[], u32[]) collective-permute-start(%multiply_subtract_fusion), channel_id=1, source_target_pairs={{{{0,1}},{{1,2}},{{2,3}},{{3,0}}}}, metadata={{op_name="{P}bf.combine/ppermute"}}
  %collective-permute-done.1 = f32[8,8]{{1,0}} collective-permute-done(%collective-permute-start.1), metadata={{op_name="{P}bf.combine/ppermute"}}
  %add_fusion = f32[8,8]{{1,0}} fusion(%multiply_subtract_fusion, %collective-permute-done.1), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{P}bf.combine/add"}}
  ROOT %broadcast.20 = f32[1,8,8]{{2,1,0}} broadcast(%add_fusion), dimensions={{1,2}}, metadata={{op_name="jit(per_rank)/shard_map/broadcast_in_dim"}}
}}
'''

# name, opcode tail of the event text, seconds: two traced steps of the program above
STEP = [("squeeze_fusion", "fusion(f32[1,8,8] %param.1), kind=kLoop", 1.0),
        ("copy-start.1", "copy-start(f32[8,8] %squeeze_fusion)", 0.5),
        ("copy-done.1", "copy-done(f32[8,8] %copy-start.1)", 0.5),
        ("bf.flash.fwd.1", 'custom-call(f32[8,8] %c), custom_call_target="tpu_custom_call"', 3.0),
        ("bf.flash.dq.1", 'custom-call(f32[8,8] %c), custom_call_target="tpu_custom_call"', 4.0),
        ("bf.flash.dkv.1", 'custom-call(f32[8,8] %c), custom_call_target="tpu_custom_call"', 5.0),
        ("copy-start.2", "copy-start(f32[8,8] %bf.flash.dq.1)", 0.25),
        ("copy-done.2", "copy-done(f32[8,8] %copy-start.2)", 0.75),
        ("fusion.1", "fusion(f32[8,8] %a), kind=kOutput, calls=%fused_computation.1", 20.0),
        ("multiply_subtract_fusion", "fusion(f32[8,8] %a), kind=kLoop", 6.0),
        ("collective-permute-start.1", "collective-permute-start(f32[8,8] %a), channel_id=1", 0.5),
        ("collective-permute-done.1", "collective-permute-done(f32[8,8] %a)", 7.0),
        ("add_fusion", "fusion(f32[8,8] %a, f32[8,8] %b), kind=kLoop", 2.0),
        ("broadcast.20", "broadcast(f32[8,8] %add_fusion), dimensions={1,2}", 0.5),
        ("convert.77", "convert(f32[8] %x)", 0.125)]  # of a program that is no optimizer's step


def traced(steps=2, scale=1e-3, wait=7.0, planes=("/device:TPU:0",)):
    """A reduced trace of ``steps`` back-to-back runs of STEP on each plane;
    ``wait`` is the second plane's ``collective-permute-done``."""
    chips = []
    for i, plane in enumerate(planes):
        t, ops = 0.0, []
        for _ in range(steps):
            for name, tail, seconds in STEP:
                if name == "collective-permute-done.1" and i:
                    seconds = wait
                ops.append(trace_reduce._op(f"%{name} = f32[8,8]{{1,0}} {tail}", t, t + seconds * scale))
                t = ops[-1].end
        chips.append(trace_reduce.Chip(plane, [("jit_per_rank(1)", 0.0, t)], ops, []))
    host = [("opt.STEP", 0.0, 0.004), ("opt.PLAN", 0.001, 0.002),
            ("opt.STEP", 0.010, 0.013), ("opt.PLAN", 0.0105, 0.0135), ("opt.PLAN", 0.02, 0.0205)]
    return trace_reduce.Reduced(chips, host)


class Program:
    key = (True, (1,), False)

    def __init__(self, text=HLO):
        self.text = text

    def hlo_text(self):
        return self.text


def a_run(monkeypatch, trace, programs=(Program(),), cell="pythia-s8192-onepeer-4chip"):
    monkeypatch.setattr(bf, "step_programs", lambda: list(programs), raising=False)
    manifest = Manifest(ROOT)
    return manifest, harness.Run(harness.Cell.load(manifest, cell), harness.Spans(),
                                 trace=trace, traced_steps=2,
                                 peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


def test_parser_reads_paths_fusions_and_what_has_no_metadata():
    where = phases.parse(HLO)
    # an instruction's own path; the custom calls are named by their scope
    assert where["bf.flash.dq.1"].path.endswith("bf.flash.dq/pallas_call")
    assert [where[f"bf.flash.{k}.1"].kernel for k in ("fwd", "dq", "dkv")] == list(phases.KERNELS)
    assert {where[f"bf.flash.{k}.1"].phase for k in ("fwd", "dq", "dkv")} == {"bf.grad"}
    # an output fusion rooted in the weight gradient with the update in its
    # epilogue: the earliest phase takes it, and it is mixed
    assert where["fusion.1"].phase == "bf.grad" and where["fusion.1"].mixed
    assert where["fusion.1"].touches == {"bf.grad", "bf.update"}
    # a loop fusion of the update with the combine's multiply, rooted in the latter
    assert where["multiply_subtract_fusion"].phase == "bf.update"
    assert where["multiply_subtract_fusion"].touches == {"bf.update", "bf.combine"}
    assert where["multiply_subtract_fusion"].path.endswith("bf.update/sub")  # the member's
    assert where["add_fusion"].phase == "bf.combine" and not where["add_fusion"].mixed
    assert where["collective-permute-start.1"].phase == "bf.combine"
    assert where["collective-permute-done.1"].phase == "bf.combine"
    # outside every scope: unstacking, restacking, the parameters
    for name in ("squeeze_fusion", "broadcast.20", "param", "param.1"):
        assert where[name].phase == phases.UNSCOPED and not where[name].inherited
    # no metadata: the producer's phase, through its start op; with an unscoped
    # producer, the user's
    assert where["copy-done.2"].phase == "bf.grad" and where["copy-done.2"].inherited
    assert where["copy-start.1"].phase == "bf.grad" and where["copy-done.1"].inherited
    # members of fused computations are instructions of the module too
    assert where["convolution.2"].phase == "bf.grad" and where["add.9"].phase == "bf.update"


@pytest.mark.parametrize("path, phase, kernel, below", [
    ("jit(per_rank)/shard_map/bf.grad/jvp(LM)/LM.hidden/block_0/mlp/dot_general", "bf.grad", None,
     "jvp(LM)/LM.hidden/block_0"),
    ("bf.grad/transpose(bf.grad)/jvp(LM)/LM.hidden/block_1/jit(flash_block_bwd)/bf.flash.dkv/pallas_call",
     "bf.grad", "bf.flash.dkv", "jvp(LM)/LM.hidden/block_1"),
    ("jit(per_rank)/shard_map/transpose(bf.update)/mul;bf.combine/add", "bf.update", None, "mul"),
    ("jit(per_rank)/shard_map/bf.gradient/bf.updates/my.bf.combine/x", "unscoped", None, None),
    ("", "unscoped", None, None),
])
def test_phase_and_kernel_of_a_path(path, phase, kernel, below):
    assert phases.phase_of(path) == phase and phases.kernel_of(path) == kernel
    assert phases.second_level(path) == (below or "(no metadata)")


def test_join_adds_up_to_the_busy_time(monkeypatch, capsys):
    manifest, run = a_run(monkeypatch, traced())
    found = phases.of(run)
    assert phases.of(run) is found  # built once
    by_phase = {p: phases.phase_ms(run, p) for p in phases.PHASES + (phases.UNSCOPED,)}
    # grad: copies 1.0 + kernels 12 + copies 1.0 + fusion.1 20; update: the mixed loop fusion
    assert by_phase == pytest.approx({"bf.grad": 34.0, "bf.update": 6.0, "bf.combine": 9.5,
                                      "unscoped": 1.625})
    chip = run.trace.busiest
    assert sum(by_phase.values()) == pytest.approx(chip.busy_s / 2 * 1e3)
    assert found.get(chip.ops[-1]) is phases.NOWHERE and phases.NOWHERE.phase == phases.UNSCOPED
    out = capsys.readouterr().out
    assert out.count("phases on /device:TPU:0") == 1  # reported once
    assert "26.000 ms in fusions whose members span two phases" in out
    assert "2.000 ms in ops without metadata" in out and "0.125 ms in ops no program has" in out
    assert "bf.update 26.000" in out  # touching: the upper bound of the update
    assert "jvp(Net)/attn/jit(flash_block) 3.000; (no metadata) 2.000" in out


def test_the_nine_readers(monkeypatch):
    manifest, run = a_run(monkeypatch, traced(planes=("/device:TPU:0", "/device:TPU:1"), wait=9.0))
    values = harness.per_layer(manifest, run)
    assert set(NEW_METRICS) <= set(values)
    assert values["grad_ms_per_step"] == pytest.approx(34.0)
    assert values["optimizer_update_ms_per_step"] == pytest.approx(6.0)
    # the combine's readers take the chip that waits longest; it is the busiest here too
    assert values["combine_device_ms_per_step"] == pytest.approx(11.5)
    assert values["combine_accumulate_ms_per_step"] == pytest.approx(2.0)
    assert values["combine_device_ms_per_step"] - values["combine_accumulate_ms_per_step"] \
        == pytest.approx(values["combine_exposed_ms_per_step"] + 0.5)  # + the start op
    assert [values[f"flash_{k}_ms_per_step"] for k in ("fwd", "dq", "dkv")] == pytest.approx([3, 4, 5])
    assert sum(values[f"flash_{k}_ms_per_step"] for k in ("fwd", "dq", "dkv")) \
        == pytest.approx(values["flash_ms_per_step"])
    assert values["unscoped_ms_per_step"] == pytest.approx(1.625)
    assert values["host_plan_ms_per_step"] == pytest.approx(1.0)  # the median of 1.0, 3.0, 0.5
    # the four that add up do, on the busiest chip
    busy = run.trace.busiest.busy_s / 2 * 1e3
    assert sum(values[m] for m in ("grad_ms_per_step", "optimizer_update_ms_per_step",
                                   "combine_device_ms_per_step", "unscoped_ms_per_step")) \
        == pytest.approx(busy)


def test_every_new_metric_is_in_the_manifest_with_the_cells_it_reads():
    manifest = Manifest(ROOT)
    entries = {m["name"]: m for m in manifest.doc["per_layer"]}
    flash_cells = entries["flash_ms_per_step"]["workloads"]
    for name in NEW_METRICS:
        entry = entries[name]
        assert (entry["moves"], entry["better"], entry["unit"]) == ("step_ms", "lower", "ms")
        assert entry["source"] == ("program_span" if name.startswith("host_") else "device_trace")
        assert entry.get("workloads") == (flash_cells if name.startswith("flash_") else None)
    assert [m["name"] for m in manifest.doc["per_layer"]][-9:] == NEW_METRICS  # appended, in order


def test_ambiguous_names_count_as_unscoped(monkeypatch):
    # a second program that gives fusion.1 to another phase, and agrees on the rest
    other = HLO.replace("bf.grad/transpose(jvp(Net))/dense/dot_general", "bf.update/dot_general")
    manifest, run = a_run(monkeypatch, traced(), programs=(Program(), Program(other)))
    found = phases.of(run)
    entry = {name for name, _, _ in STEP}
    assert found.ambiguous & entry == {"fusion.1"} and "convolution.2" in found.ambiguous
    (op,) = [o for o in run.trace.busiest.ops[:len(STEP)] if o.name == "fusion.1"]
    assert found.get(op).phase == phases.AMBIGUOUS and found.get(op).kernel is None
    assert phases.phase_ms(run, "bf.grad") == pytest.approx(14.0)
    assert phases.phase_ms(run, phases.UNSCOPED) == pytest.approx(21.625)
    # programs that differ in the permute's pairs alone agree on every name
    pairs = HLO.replace("{{0,1},{1,2},{2,3},{3,0}}", "{{0,2},{1,3},{2,0},{3,1}}")
    manifest, run = a_run(monkeypatch, traced(), programs=(Program(), Program(pairs)))
    assert phases.of(run).ambiguous == set()


@pytest.mark.parametrize("why", ["no device planes", "no trace", "a parent without step_programs",
                                 "no step program built"])
def test_readers_find_nothing_where_there_is_nothing_to_join(monkeypatch, why):
    trace = traced(planes=()) if why == "no device planes" else None if why == "no trace" else traced()
    manifest, run = a_run(monkeypatch, trace, programs=())
    if why == "a parent without step_programs":
        monkeypatch.delattr(bf, "step_programs")
    for name in NEW_METRICS:
        value = manifest.plugin("layer_metrics", name).read(run)
        # the host span needs no program, only a traced chip
        expect_value = name == "host_plan_ms_per_step" and trace is not None and trace.chips
        assert (value is not None) == bool(expect_value), (name, value)


def test_step_span_holds_plan_in_a_toy_cells_trace(toy_root, tmp_path):
    run, verdict = run_stages(Manifest(toy_root), "toy-lm-onepeer-4", trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    by_kind = {kind: sorted((s, e) for name, s, e in run.trace.host if name.endswith("." + kind))
               for kind in ("STEP", "PLAN", "BUILD")}
    steps = run.cell.traffic["trace_steps"]
    assert len(by_kind["STEP"]) == len(by_kind["PLAN"]) == steps and not by_kind["BUILD"]
    for (s0, s1), (p0, p1) in zip(by_kind["STEP"], by_kind["PLAN"]):
        assert s0 <= p0 <= p1 <= s1
    # the toy cell's two programs are registered, and their text has the scopes;
    # a CPU trace has no device plane, so the readers still report nothing
    keys = [p.key for p in bf.step_programs()[-2:]]
    assert keys == [(True, (1,), False), (True, (2,), False)]
    where = phases.parse(bf.step_programs()[-1].hlo_text())
    assert {w.phase for w in where.values()} >= set(phases.PHASES)
    assert phases.host_span_ms(run, ".PLAN") is None and phases.of(run) is None
