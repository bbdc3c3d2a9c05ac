"""Family ``looped_lm``: a toy configuration and cell through the harness's
functions on the CPU (kernels interpreted, every layer application
recomputed), its FLOP count against the issue's arithmetic, the five readers
of what the loop adds on a made-up trace, and the five wrong-model controls of
the forward check. No number here is a device metric."""

import json
import os
import shutil

import pytest

import jax

from benchmark.manifest import Manifest

from conftest import ROOT, TOY, copy_benchmark, write_manifest

import looped_controls
from test_gqa_window_moe import traced
from test_harness import run_stages
from test_mla_moe import HLO as JOYAI_HLO
from test_phases import Program, a_run

CELL = "ouro-s4096-loop4-1chip"
NEW = ["recompute_ms_per_step", "loop_flash_roofline", "lm_head_ms_per_step",
       "ffn_dense_ms_per_step", "loop_pass_spread"]
SHARED = ["flash_fwd_ms_per_step", "flash_dkv_ms_per_step", "attn_proj_ms_per_step"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with this family's toy configuration and cell
    added: new files and new entries, as the real ones were."""
    dst = str(tmp_path_factory.mktemp("toy_ouro"))
    doc = copy_benchmark(dst)
    shutil.copy(os.path.join(TOY, "toy-ouro.json"), os.path.join(dst, "benchmark", "configs"))
    shutil.copy(os.path.join(TOY, "toy-s32.json"), os.path.join(dst, "benchmark", "traffic"))
    doc["configs"].append({"name": "toy-ouro", "source": "test", "reduced": [], "why": "test",
                           "file": "benchmark/configs/toy-ouro.json"})
    doc["workloads"].append({"name": "toy-ouro-1", "config": "toy-ouro", "traffic": "toy-s32",
                             "chips": 1, "why": "test"})
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-ouro-1")
    write_manifest(dst, doc)
    return dst


def test_toy_cell_end_to_end(toy_root, tmp_path, capfd):
    from bluefog_tpu.runtime import metrics

    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, "toy-ouro-1", trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    # float32 on one backend: the optimizer's steps and the plain steps run the
    # same loss; the forward is the kernels, recomputed, against the plain loop
    assert verdict["steps"]["loss_rel_err"] < 1e-6 and verdict["steps"]["print_err"] < 1e-3
    assert verdict["forward"]["logits_rel_err"] < 1e-5
    assert run.attempted > 0 and run.failed == 0 and run.window_compiles == 0
    assert "looped check: gate_rel_err" in capfd.readouterr().out
    gauges = metrics.snapshot(include_native=False)["gauges"]
    assert (gauges["loop.passes"], gauges["loop.layer_applications"],
            gauges["loop.recomputed"]) == (4, 8, 1)
    # a CPU trace has no device plane: the trace readers find nothing
    for name in NEW + SHARED:
        assert manifest.plugin("layer_metrics", name).read(run) is None


def test_flops_of_the_real_cell_are_the_issues_arithmetic():
    manifest = Manifest(ROOT)
    cfg, batch = manifest.config("ouro-2.6b"), manifest.traffic("s4096")["batch"]
    family = manifest.plugin("families", cfg["family"])
    layers = cfg["num_hidden_layers"]
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert layer + 4 * 2048 == 51_388_416                  # with its four norms: the issue's count
    assert family.applications(cfg) == 4 * layers
    assert family.attention_pairs(cfg, batch) == 4 * layers * (4096 * 4097 // 2)
    assert family.attention_flops(cfg, batch) == 12 * 16 * 128 * 4 * layers * 8_390_656
    assert family.matmul_params(cfg) == layers * layer + 2048 * 49152 + 2048
    # every pass runs the stack and the head: no recomputation counted
    assert family.flops_per_step(cfg, batch) == pytest.approx(
        6 * (layers * layer + 2048 * 49153) * 4096 * 4 + family.attention_flops(cfg, batch))
    if layers == 8:
        assert family.flops_per_step(cfg, batch) == pytest.approx(56.9e12, rel=2e-3)
    # what a step holds: the layers, embedding + head + final norm + gate
    assert layers * 51_388_416 + 201_330_689 == {8: 612_438_017, 6: 509_661_185,
                                                 4: 406_884_353}[layers]


P = "jit(per_rank)/shard_map/bf.grad/"
J = "jvp(ConfigLM)/bf.loop.{t}/"
B = "transpose(jvp(ConfigLM))/bf.loop.{t}/bf.grad/jvp(ConfigLM)/bf.loop.{t}/checkpoint/"
R = B + "rematted_computation/"
CALL = 'custom-call(%param), custom_call_target="tpu_custom_call"'
OUT, LOOP = "fusion(%param), kind=kOutput", "fusion(%param), kind=kLoop"
# name, path under bf.grad, the op, milliseconds in each of two traced steps;
# pass 0 and pass 1 alike but for one fusion XLA gave to pass 0
OPS = [(f"{name}.{t}", path.format(t=t), op, ms) for t in (0, 1) for name, path, op, ms in [
    ("fusion.1", J + "layer_0/attn/bf.attn.proj/q/dot_general", OUT, 2.0),
    ("bf.flash.fwd.1", J + "layer_0/attn/jit(flash_block)/bf.flash.fwd/pallas_call", CALL, 3.0),
    ("fusion.2", J + "layer_0/bf.ffn.dense/ffn/gate/dot_general", OUT, 4.0),
    ("fusion.3", J + "bf.lm.head/final_norm/mul", LOOP, 0.5),
    ("fusion.4", "jvp(bf.loop.{t})/bf.lm.head/dot_general", OUT, 5.0),
    ("fusion.5", "transpose(jvp(bf.loop.{t}))/bf.lm.head/bf.grad/jvp(bf.loop.{t})/bf.lm.head/"
                 "checkpoint/rematted_computation/dot_general", OUT, 5.0),
    ("fusion.6", R + "layer_0/attn/bf.attn.proj/q/dot_general", OUT, 2.0),
    ("bf.flash.fwd.2", R + "layer_0/attn/jit(flash_block)/bf.flash.fwd/pallas_call", CALL, 3.0),
    ("fusion.7", R + "layer_0/bf.ffn.dense/ffn/gate/dot_general", OUT, 4.0),
    ("bf.flash.dkv.1", B + "layer_0/attn/jit(flash_block_bwd)/bf.flash.dkv/pallas_call", CALL, 6.0),
    ("fusion.8", B + "layer_0/bf.ffn.dense/ffn/gate/transpose", OUT, 8.0),
]] + [("fusion.9", B.format(t=0) + "layer_0/bf.ffn.dense/ffn/down/add_any", OUT, 4.25)]
HLO = ("HloModule jit_per_rank, is_scheduled=true\n\n"
       "ENTRY %main.1_spmd (param: f32[8,8]) -> f32[8,8] {\n"
       "  %param = f32[8,8]{1,0} parameter(0)\n"
       + "".join(f'  %{name} = f32[8,8]{{1,0}} {op}, metadata={{op_name="{P}{path}"}}\n'
                 for name, path, op, _ in OPS)
       + '  ROOT %update.1 = f32[8,8]{1,0} add(%param, %param), metadata={op_name="jit(per_rank)/shard_map/bf.update/add"}\n}\n')


def read_all(monkeypatch, trace, program, cell=CELL):
    manifest, run = a_run(monkeypatch, trace, programs=(program,), cell=cell)
    return run, {name: manifest.plugin("layer_metrics", name).read(run)
                 for name in NEW + SHARED}


def test_the_readers_on_a_made_up_trace(monkeypatch):
    run, got = read_all(monkeypatch, traced(OPS), Program(HLO))
    assert got["recompute_ms_per_step"] == pytest.approx(2 * (5.0 + 2.0 + 3.0 + 4.0))
    assert got["flash_fwd_ms_per_step"] == pytest.approx(2 * 6.0)   # first run and recomputed
    assert got["flash_dkv_ms_per_step"] == pytest.approx(2 * 6.0)
    assert got["attn_proj_ms_per_step"] == pytest.approx(2 * 4.0)
    assert got["lm_head_ms_per_step"] == pytest.approx(2 * 10.5)
    assert got["ffn_dense_ms_per_step"] == pytest.approx(2 * 16.0 + 4.25)
    # pass 0: 42.5 + 4.25, pass 1: 42.5; the median of two is their mean
    assert got["loop_pass_spread"] == pytest.approx(100 * 4.25 / (42.5 + 4.25 / 2))
    cfg, batch = run.cell.config, run.cell.traffic["batch"]
    family = run.cell.family
    flash = Manifest(ROOT).plugin("layer_metrics", "loop_flash_roofline")
    flops, bytes_ = flash.needs(family, cfg, batch)
    assert flops == family.attention_flops(cfg, batch)
    # q, k, v, o, dO, dq, dk, dv of 16 heads of 128, 2 bytes, an application
    assert bytes_ == 2 * 8 * 4096 * 16 * 128 * family.applications(cfg)
    assert flash.roof_seconds(family, cfg, batch, run.peaks)[1] == "mxu"
    assert got["loop_flash_roofline"] == pytest.approx(100 * flops / 197e12 / 24e-3)


def test_another_models_program_gives_the_new_readers_nothing(monkeypatch):
    """What the driver runs on the parent and in the other cells: the readers of
    the loop return None and do not raise where the program has no
    ``bf.loop.<t>``, recomputes nothing and the configuration is of another
    family (``lm_head_`` and ``ffn_dense_`` read scopes JoyAI has: they are
    listed for this cell alone)."""
    _, got = read_all(monkeypatch, traced(), Program(JOYAI_HLO),
                      cell="joyai-flash-s8192-epshare-1chip")
    assert [got[name] for name in ("recompute_ms_per_step", "loop_flash_roofline",
                                   "loop_pass_spread")] == [None, None, None]


def _named(entries, name):
    return next(entry for entry in entries if entry["name"] == name)


def test_the_manifest_lists_the_cell_and_its_metrics():
    doc = Manifest(ROOT).doc
    assert _named(doc["workloads"], CELL) == {
        "name": CELL, "config": "ouro-2.6b", "traffic": "s4096", "chips": 1,
        "why": _named(doc["workloads"], CELL)["why"]}
    for name in NEW:
        entry = _named(doc["per_layer"], name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "step_ms"
        assert entry["source"] == "device_trace"
    for name in SHARED:
        assert CELL in _named(doc["per_layer"], name)["workloads"]
    assert CELL not in _named(doc["per_layer"], "flash_dq_ms_per_step")["workloads"]
    assert CELL in _named(doc["end_to_end"], "tokens_per_s_per_chip")["workloads"]
    cfg = Manifest(ROOT).config("ouro-2.6b")
    assert _named(doc["configs"], "ouro-2.6b")["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers"]
    # every number of the catalog's row, under its key (the depth apart)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row for row in map(json.loads, f) if row["name"] == "Ouro-2.6B")["config"]
    assert published.pop("num_hidden_layers") == cfg["published"]["num_hidden_layers"] == 48
    assert {key: cfg[key] for key in published} == published
    assert cfg["num_hidden_layers"] in (8, 6, 4) and cfg["total_ut_steps"] == 4
    assert cfg["optimizer"] == {"name": "adamw", "args": {
        "learning_rate": 3e-4, "b2": 0.95, "weight_decay": 0.1}}
    assert cfg["exit_beta"] == 0.1 and cfg["recompute_layers"] is True
    with open(os.path.join(ROOT, "benchmark", "traffic", "s4096.json")) as f:
        traffic = json.load(f)
    assert traffic["batch"] == {"sequences": 1, "seq_len": 4096}
    assert (traffic["pool"], traffic["chunk_steps"], traffic["warmup_steps"],
            traffic["trace_steps"], traffic["schedule"]) == (4, 3, 3, 3, "static")


@pytest.mark.parametrize("control", (None,) + looped_controls.CONTROLS,
                         ids=lambda c: c.__name__ if c else "honest")
def test_the_forward_check_passes_bfloat16_and_fails_the_controls(control, toy_root):
    """``reference.compare_forward`` as the harness calls it, at toy widths in
    bfloat16 (limit 4e-2): the honest program is ``ok``; one that leaves a pass
    out, feeds the state on un-normed, drops the two output norms, reads
    float8 matrices or swaps stay and leave in the exit distribution is not.
    The chip's readings at the real widths are in PERF.md section 6, PR 34."""
    from benchmark import reference

    manifest = Manifest(toy_root)
    cfg = {**manifest.config("toy-ouro"), "compute_dtype": "bfloat16"}
    family = manifest.plugin("families", cfg["family"])
    batch = {"sequences": 1, "seq_len": 256}
    params, state = family.init(cfg, batch, jax.random.PRNGKey(3))
    tokens = family.make_batch(cfg, batch, jax.random.PRNGKey(4), 1)[0][0]
    verdict = reference.compare_forward(looped_controls.under(control, family), cfg, params,
                                        state, tokens)
    assert verdict["tol"] == 4e-2 and verdict["ok"] == (control is None), verdict
