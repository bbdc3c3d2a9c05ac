"""The reducer on recorded v5e traces. ``traces/resnet50_r4`` is the repo's own
five-step ResNet-50 trace (the old setup's chip, the same program). These are
readings of a recording, not measurements."""

import os

import pytest

from benchmark import harness, trace_reduce
from benchmark.manifest import Manifest

from conftest import ROOT


@pytest.fixture(scope="module")
def resnet_trace():
    return trace_reduce.reduce(trace_reduce.find_xplane(os.path.join(ROOT, "traces", "resnet50_r4")))


def test_steps_busy_union_and_idle_share(resnet_trace):
    (chip,) = resnet_trace.chips
    assert chip.plane == "/device:TPU:0"
    steps = chip.step_modules()
    assert len(steps) == 5 and all(name.startswith("jit_per_rank(") for name, _, _ in steps)
    for _, start, end in steps:
        assert (end - start) * 1e3 == pytest.approx(46.9, abs=0.01)
    assert chip.window_s * 1e3 == pytest.approx(234.52, abs=0.01)
    assert chip.busy_s * 1e3 == pytest.approx(234.4, abs=0.05)
    assert chip.idle_share == pytest.approx(0.0004, abs=0.0002)
    # the gaps are what the busy union leaves of the window, longest first
    gaps = chip.gaps()
    assert sum(b - a for a, b in gaps) == pytest.approx(chip.window_s - chip.busy_s)
    assert gaps[0][1] - gaps[0][0] == max(b - a for a, b in gaps) < 20e-6


def test_ops_are_parsed_from_their_hlo_text(resnet_trace):
    (chip,) = resnet_trace.chips
    by_name = {op.name: op for op in chip.ops}
    conv = by_name["fusion.14"]
    assert conv.opcode == "fusion" and conv.is_mxu and not conv.is_mosaic
    assert conv.collective is None and conv.largest_result() == "bf16[128,56,56,256]"
    assert by_name["copy-done.26"].opcode == "copy-done" and not by_name["copy-done.26"].is_mxu
    # convolutions (output fusions) are 37.2 of the 46.9 ms, as PERF.md has had it
    mxu = sum(op.seconds for op in chip.ops if op.is_mxu) / 5
    assert mxu * 1e3 == pytest.approx(37.2, abs=0.1)
    assert not any(op.collective for op in chip.ops + chip.in_flight)
    assert harness._label(conv) == "fusion.14 fusion kOutput bf16[128,56,56,256]"


def test_host_spans_attribute_the_gaps(resnet_trace):
    names = {name for name, _, _ in resnet_trace.host}
    assert "DistributedNeighborAllreduceOptimizer.STEP" in names  # timeline_context's
    assert not any(name.startswith("$") for name in names)        # no Python frames
    a, _ = resnet_trace.chips[0].gaps()[0]
    assert resnet_trace.host_span_at(a) != "" and resnet_trace.host_span_at(-1.0) == ""


@pytest.mark.parametrize("text, opcode, collective, operand_bytes", [
    ("%collective-permute-start.3 = (f32[1,2048,8192]{2,1,0:T(8,128)}, f32[1,2048,8192]{2,1,0:T(8,128)}, "
     "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(f32[1,2048,8192]{2,1,0:T(8,128)} %fusion.9), "
     "channel_id=5, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}",
     "collective-permute-start", "collective-permute", 4 * 2048 * 8192),
    ("%collective-permute-done.3 = f32[1,2048,8192]{2,1,0:T(8,128)} collective-permute-done("
     "(f32[1,2048,8192]{2,1,0:T(8,128)}, f32[1,2048,8192]{2,1,0:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) "
     "%collective-permute-start.3)", "collective-permute-done", "collective-permute", 2 * 4 * 2048 * 8192 + 8),
    ("%all-reduce.1 = (bf16[512]{0:T(512)(2,1)}, f32[]) all-reduce(bf16[512]{0:T(512)(2,1)} %a, f32[] %b), "
     "replica_groups={{0,1,2,3}}, to_apply=%add", "all-reduce", "all-reduce", 2 * 512 + 4),
    ("%flash_block_bwd.7 = (f32[16,8192,128]{2,1,0:T(8,128)}) custom-call(bf16[16,8192,128]{2,1,0} %q), "
     "custom_call_target=\"tpu_custom_call\"", "custom-call", None, 2 * 16 * 8192 * 128),
])
def test_collectives_and_their_bytes(text, opcode, collective, operand_bytes):
    op = trace_reduce._op(text, 0.0, 1.0)
    assert (op.opcode, op.collective, op.operand_bytes()) == (opcode, collective, operand_bytes)
    assert op.is_mosaic == ("tpu_custom_call" in text)


def test_readers_on_a_one_chip_trace(resnet_trace):
    """The trace readers of the real cell on the recording: the combine reads
    exactly 0 on one chip, the flash readers find no kernel and report nothing."""
    manifest = Manifest(ROOT)
    cell = harness.Cell.load(manifest, "resnet50-b128-1chip")
    run = harness.Run(cell, harness.Spans(), trace=resnet_trace, traced_steps=5,
                      peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    values = harness.per_layer(manifest, run)
    assert values["combine_bytes_per_step"] == 0 and values["combine_exposed_ms_per_step"] == 0
    assert values["device_idle_share"] == pytest.approx(0.04, abs=0.02)
    assert values["xla_mxu_ms_per_step"] == pytest.approx(37.2, abs=0.1)
    assert "host_step_ms" not in values  # no window was run: the span readers find nothing
    for name in ("flash_ms_per_step", "flash_roofline"):
        assert manifest.plugin("layer_metrics", name).read(run) in (None, 0.0)
    found = harness.breakdown(run)
    assert len(found["device_ops"]) == 10 and len(found["idle_gaps"]) == 5
    assert found["device_ops"][0][0].startswith("fusion.14 ") and found["device_ops"][0][1] > 1e-3
    stamp = harness.device_stamp([type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()],
                                 run, 1)
    assert 0 < stamp["busy_s"] < stamp["window_s"]
