"""Family ``gqa_window_moe_lm``: a toy configuration and cell through the
harness's functions on the CPU (kernels interpreted), its FLOP count against
the issue's arithmetic, the three readers of its scopes on a made-up trace,
and the wrong-model controls of the forward check. No number here is a device
metric."""

import json
import os
import shutil
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.manifest import Manifest

from conftest import ROOT, TOY, copy_benchmark, write_manifest

from test_harness import run_stages
from test_mla_moe import HLO as JOYAI_HLO, traced
from test_phases import Program, a_run

CELL = "smallthinker-s16384-epshare-1chip"
NEW = ["gqa_window_flash_roofline", "attn_proj_ms_per_step", "reglu_experts_roofline"]
SHARED = ["flash_fwd_ms_per_step", "flash_dkv_ms_per_step", "moe_route_ms_per_step",
          "moe_experts_ms_per_step"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with this family's toy configuration and cell
    added: new files and new entries, as the real ones were."""
    dst = str(tmp_path_factory.mktemp("toy_smallthinker"))
    doc = copy_benchmark(dst)
    shutil.copy(os.path.join(TOY, "toy-smallthinker.json"),
                os.path.join(dst, "benchmark", "configs"))
    shutil.copy(os.path.join(TOY, "toy-s32.json"), os.path.join(dst, "benchmark", "traffic"))
    doc["configs"].append({"name": "toy-smallthinker", "source": "test", "reduced": [],
                           "why": "test", "file": "benchmark/configs/toy-smallthinker.json"})
    doc["workloads"].append({"name": "toy-smallthinker-1", "config": "toy-smallthinker",
                             "traffic": "toy-s32", "chips": 1, "why": "test"})
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-smallthinker-1")
    write_manifest(dst, doc)
    return dst


def test_toy_cell_end_to_end(toy_root, tmp_path, capfd):
    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, "toy-smallthinker-1", trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    # float32 on one backend: the optimizer's steps and the plain steps run the
    # same loss; the forward is the kernels against the plain reference
    assert verdict["steps"]["loss_rel_err"] < 1e-6 and verdict["steps"]["print_err"] < 1e-3
    assert verdict["forward"]["logits_rel_err"] < 1e-5
    assert run.attempted > 0 and run.failed == 0 and run.window_compiles == 0
    # in float32 the free-running program picks the reference's experts, all 8 layers
    assert ("choices shared with the plain forward, by expert layer [1. 1. 1. 1. 1. 1. 1. 1.]"
            in capfd.readouterr().out)
    # a CPU trace has no device plane: the scope readers find nothing
    for name in NEW + SHARED:
        assert manifest.plugin("layer_metrics", name).read(run) is None


def test_flops_of_the_real_cell_are_the_issues_arithmetic():
    manifest = Manifest(ROOT)
    cfg, batch = manifest.config("smallthinker-21b-a3b"), manifest.traffic("s16384")["batch"]
    family = manifest.plugin("families", cfg["family"])
    s = 16384
    window = 4096 * 4097 // 2 + (s - 4096) * 4096          # sum over t of min(t + 1, 4096)
    assert (s * (s + 1) // 2, window) == (134_225_920, 58_722_304)
    assert family.attention_pairs(cfg, batch) == s * (s + 1) // 2 + 3 * window
    assert family.attention_flops(cfg, batch) == pytest.approx(13.35e12, rel=2e-3)
    assert family.expected_rows(cfg, batch) == 12288        # 1,536 a held expert
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128    # 20.97 M
    layer = attention + 2560 * 64 + 0.75 * 3 * 2560 * 768   # router, 0.75 slots a token
    want = 4 * layer + 2560 * 18992
    assert family.matmul_params(cfg, batch) == pytest.approx(want)
    assert family.flops_per_step(cfg, batch) == pytest.approx(28.2e12, rel=5e-3)
    assert family.held_range(cfg) == (0, 8)
    assert family.layouts(cfg) == ((0, 1, 1, 1), (0, 1, 1, 1))
    # what a step holds: 370.5 M parameters (the issue's count)
    expert, vocabulary = 8 * 3 * 2560 * 768, 2 * 18992 * 2560
    assert 4 * (attention + 2560 * 64 + expert + 2 * 2560) + vocabulary + 2560 == 370_547_200


P = "jit(per_rank)/shard_map/bf.grad/"
J, T = "jvp(ConfigLM)/", "transpose(jvp(ConfigLM))/"
CALL = 'custom-call(%param), custom_call_target="tpu_custom_call"'
# name, path under bf.grad, the op, milliseconds in each of two traced steps
OPS = [
    ("fusion.1", J + "layer_1/attn/bf.attn.proj/q/dot_general", "fusion(%param), kind=kOutput", 4.0),
    ("fusion.2", T + "layer_1/bf.attn.proj/attn_norm/mul", "fusion(%param), kind=kLoop", 1.0),
    ("bf.flash.fwd.1", J + "layer_1/attn/jit(flash_block)/bf.flash.fwd/pallas_call", CALL, 3.0),
    ("bf.flash.dkv.1", T + "layer_1/attn/jit(flash_block_bwd)/bf.flash.dkv/pallas_call", CALL, 7.0),
    ("fusion.3", T + "layer_1/attn/jit(flash_block_bwd)/reduce_sum", "fusion(%param), kind=kLoop", 0.5),
    ("fusion.4", J + "layer_1/bf.moe.route/dot_general", "fusion(%param), kind=kOutput", 0.25),
    ("sort.1", J + "layer_1/ffn/bf.moe.route/sort", "sort(%param)", 0.75),
    ("experts.1", J + "layer_1/ffn/bf.moe.experts/pallas_call", CALL, 2.0),
    ("fusion.5", J + "bf.lm.head/lm_head/dot_general", "fusion(%param), kind=kOutput", 3.5),
]
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
    assert got["attn_proj_ms_per_step"] == pytest.approx(5.0)
    assert got["flash_fwd_ms_per_step"] == pytest.approx(3.0)
    assert got["flash_dkv_ms_per_step"] == pytest.approx(7.0)    # the kernel, not the group sum
    assert got["moe_route_ms_per_step"] == pytest.approx(1.0)    # the layer's own scores too
    assert got["moe_experts_ms_per_step"] == pytest.approx(2.0)
    cfg, batch = run.cell.config, run.cell.traffic["batch"]
    family = run.cell.family
    flash = Manifest(ROOT).plugin("layer_metrics", "gqa_window_flash_roofline")
    flops, bytes_ = flash.needs(family, cfg, batch)
    assert flops == family.attention_flops(cfg, batch)
    # q, o, dO, dq at 28 heads and k, v, dk, dv at 4, 2 bytes, four layers
    assert bytes_ == 2 * 4 * 16384 * 128 * (4 * 28 + 4 * 4)
    assert flash.roof_seconds(family, cfg, batch, run.peaks)[1] == "mxu"
    assert got["gqa_window_flash_roofline"] == pytest.approx(100 * flops / 197e12 / 10e-3)
    experts = Manifest(ROOT).plugin("layer_metrics", "reglu_experts_roofline")
    flops, bytes_ = experts.needs(family, cfg, batch)
    assert flops == pytest.approx(4 * 18 * 12288 * 2560 * 768)
    assert bytes_ == pytest.approx(4 * 2 * (9 * 8 * 2560 * 768 + 3 * 12288 * (5120 + 2304)))
    assert experts.roof_seconds(family, cfg, batch, run.peaks)[1] == "mxu"
    assert got["reglu_experts_roofline"] == pytest.approx(100 * flops / 197e12 / 2e-3)


def test_another_models_program_gives_the_new_readers_nothing(monkeypatch):
    """What the driver runs on the parent and in the other cells: the three new
    readers return None and do not raise where the program has no ``bf.attn.proj``
    and the configuration is not of this family."""
    _, got = read_all(monkeypatch, traced(), Program(JOYAI_HLO),
                      cell="joyai-flash-s8192-epshare-1chip")
    assert [got[name] for name in NEW] == [None, None, None]
    assert got["moe_experts_ms_per_step"] == pytest.approx(3.0)


def _named(entries, name):
    return next(entry for entry in entries if entry["name"] == name)


def test_the_manifest_lists_the_cell_and_its_metrics():
    doc = Manifest(ROOT).doc
    assert _named(doc["workloads"], CELL) == {
        "name": CELL, "config": "smallthinker-21b-a3b", "traffic": "s16384", "chips": 1,
        "why": _named(doc["workloads"], CELL)["why"]}
    for name in NEW:
        entry = _named(doc["per_layer"], name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "step_ms"
        assert entry["source"] == "device_trace"
    for name in SHARED:
        assert CELL in _named(doc["per_layer"], name)["workloads"]
    assert CELL not in _named(doc["per_layer"], "flash_dq_ms_per_step")["workloads"]
    assert CELL in _named(doc["end_to_end"], "tokens_per_s_per_chip")["workloads"]
    cfg = Manifest(ROOT).config("smallthinker-21b-a3b")
    assert set(_named(doc["configs"], "smallthinker-21b-a3b")["reduced"]) == set(cfg["reduced"])
    assert cfg["optimizer"] == {"name": "adam", "args": {"learning_rate": 1.5e-5}}  # ISSUE 32's rate
    assert cfg["moe_num_primary_experts"] * cfg["deployment"]["chips_sharing_each_layer"] \
        == cfg["published"]["moe_num_primary_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # every number of the catalog's row, under its key (the three cuts apart)
    published = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
                 "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
                 "num_attention_heads": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
                 "rope_theta": 1500000, "sliding_window_size": 4096}
    assert {key: cfg[key] for key in published} == published
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    with open(os.path.join(ROOT, "benchmark", "traffic", "s16384.json")) as f:
        traffic = json.load(f)
    assert traffic["batch"] == {"sequences": 1, "seq_len": 16384}
    assert (traffic["pool"], traffic["chunk_steps"], traffic["warmup_steps"],
            traffic["trace_steps"], traffic["schedule"]) == (4, 5, 3, 4, "static")


def _fp8_weights(cfg, params):
    return cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 1 else x, params)


def _window_dropped(cfg, params):
    return {**cfg, "sliding_window_layout": [0] * len(cfg["sliding_window_layout"])}, params


def _rope_in_the_global_layers(cfg, params):
    return {**cfg, "rope_layout": [1] * len(cfg["rope_layout"])}, params


def _silu_for_relu(cfg, params):
    return {**cfg, "expert_act": "silu"}, params


def _kv_head_by_remainder(cfg, params):
    """Query head h reads k/v head h % Hkv (see the test's monkeypatch)."""
    return cfg, params


@pytest.mark.parametrize("control", [None, _fp8_weights, _window_dropped,
                                     _rope_in_the_global_layers, _silu_for_relu,
                                     _kv_head_by_remainder])
def test_the_forward_check_passes_bfloat16_and_fails_the_controls(control, toy_root, monkeypatch):
    """``reference.compare_forward`` as the harness calls it, at toy widths in
    bfloat16 (limit 4e-2): the honest program is ``ok``; a program whose
    weights were rounded to fp8, that lets every layer see the whole past, that
    turns q and k in the NoPE layers too, whose experts gate by SiLU, or whose
    query heads read k/v head h % Hkv where h // group is meant, is not. The
    chip's readings at the real widths are in PERF.md section 6, PR 32."""
    from benchmark import reference
    from bluefog_tpu.parallel import flash

    if control is _kv_head_by_remainder:
        monkeypatch.setattr(flash, "_kv_head", lambda group: (
            lambda bh: jax.lax.rem(bh, 4 // group)))       # 4 query heads, one sequence
        flash.flash_block.clear_cache()
    manifest = Manifest(toy_root)
    cfg = {**manifest.config("toy-smallthinker"), "compute_dtype": "bfloat16"}
    family = manifest.plugin("families", cfg["family"])
    batch = {"sequences": 1, "seq_len": 256}
    params, state = family.init(cfg, batch, jax.random.PRNGKey(3))
    tokens = family.make_batch(cfg, batch, jax.random.PRNGKey(4), 1)[0][0]
    under_test = family if control is None else types.SimpleNamespace(
        plain_logits=family.plain_logits,
        system_logits=lambda c, p, s, x: family.system_logits(*control(c, p), s, x))
    try:
        verdict = reference.compare_forward(under_test, cfg, params, state, tokens)
    finally:
        flash.flash_block.clear_cache()
    assert verdict["tol"] == 4e-2 and verdict["ok"] == (control is None), verdict
    if control is not None:
        # by the logits with the choice forced, or by the floor on the choice
        assert not np.isfinite(verdict["logits_rel_err"]) or \
            verdict["logits_rel_err"] > 1.5 * verdict["tol"]
