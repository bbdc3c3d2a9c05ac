"""``combine_hidden_share`` on the toy trace of ``test_phases``: a step whose
one ``collective-permute-done`` waits 7 ms (9 on the second chip). No number
here is a device metric."""

import pytest

from benchmark import trace_reduce
from benchmark.manifest import Manifest

from conftest import ROOT

from test_phases import a_run, traced

NAME = "combine_hidden_share"


def in_flight(chip, lead):
    """The chip with one async span a permute: from ``lead`` seconds before
    its ``-done`` op starts to that op's end."""
    spans = [trace_reduce._op(op.text.replace("-done", "-start"), op.start - lead, op.end)
             for op in chip.ops if op.opcode == "collective-permute-done"]
    return trace_reduce.Chip(chip.plane, chip.modules, chip.ops, spans)


def read(monkeypatch, trace):
    manifest, run = a_run(monkeypatch, trace)
    return manifest.plugin("layer_metrics", NAME).read(run)


def test_nothing_in_flight_is_nothing_to_read(monkeypatch):
    assert read(monkeypatch, traced()) is None
    assert read(monkeypatch, trace_reduce.Reduced([], [])) is None


@pytest.mark.parametrize("lead_ms, share", [
    (0.0, 0.0),     # in flight only while it is waited for: nothing hidden
    (21.0, 75.0),   # 28 ms in flight, the last 7 waited for
    # the two steps' spans overlap (a step is 51.125 ms): their union, -21.5 to
    # 99.625 ms, is counted once
    (63.0, 100 * (1 - 14 / 121.125)),
])
def test_share_of_the_time_in_flight_that_is_not_waited_for(monkeypatch, lead_ms, share):
    trace = traced()
    trace.chips[:] = [in_flight(chip, lead_ms * 1e-3) for chip in trace.chips]
    assert read(monkeypatch, trace) == pytest.approx(share)


def test_no_waits_is_all_hidden_and_the_worst_chip_is_reported(monkeypatch):
    # the second chip waits 9 ms of its 30 in flight, the first 7 of 28
    trace = traced(planes=("/device:TPU:0", "/device:TPU:1"), wait=9.0)
    trace.chips[:] = [in_flight(chip, 21e-3) for chip in trace.chips]
    assert read(monkeypatch, trace) == pytest.approx(70.0)
    for chip in trace.chips:  # the transfers stay, nothing waits for them
        chip.ops[:] = [op for op in chip.ops if op.opcode != "collective-permute-done"]
    assert read(monkeypatch, trace) == pytest.approx(100.0)


def test_the_manifest_lists_it_for_the_cell_that_permutes():
    entry = Manifest(ROOT).doc["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "ops.plan", "moves": "step_ms",
                     "workloads": ["pythia-s8192-onepeer-4chip"]}
