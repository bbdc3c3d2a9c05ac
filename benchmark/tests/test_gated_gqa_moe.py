"""Family ``gated_gqa_moe_lm``: the cut's parameter count and the cell's attention pairs and
FLOPs against the configuration's arithmetic, a toy configuration and cell through the harness's
functions on the CPU (kernels interpreted), the reader of ``bf.attn.gate`` on a made-up trace,
and the wrong-model controls of the forward check. No number here is a device metric."""

import json
import math
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

CELL = "trinity-mini-s8192-epshare-1chip"
NEW = ["attn_gate_ms_per_step"]
SHARED = ["flash_fwd_ms_per_step", "flash_dkv_ms_per_step", "attn_proj_ms_per_step",
          "moe_route_ms_per_step", "moe_experts_ms_per_step", "gqa_window_flash_roofline"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with this family's toy configuration and cell added: new files
    and new entries, as the real ones were."""
    dst = str(tmp_path_factory.mktemp("toy_trinity"))
    doc = copy_benchmark(dst)
    shutil.copy(os.path.join(TOY, "toy-trinity.json"), os.path.join(dst, "benchmark", "configs"))
    shutil.copy(os.path.join(TOY, "toy-s32.json"), os.path.join(dst, "benchmark", "traffic"))
    doc["configs"].append({"name": "toy-trinity", "source": "test", "reduced": [],
                           "why": "test", "file": "benchmark/configs/toy-trinity.json"})
    doc["workloads"].append({"name": "toy-trinity-1", "config": "toy-trinity",
                             "traffic": "toy-s32", "chips": 1, "why": "test"})
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-trinity-1")
    write_manifest(dst, doc)
    return dst


def test_toy_cell_end_to_end(toy_root, tmp_path, capfd):
    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, "toy-trinity-1", trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    assert verdict["steps"]["loss_rel_err"] < 1e-6 and verdict["steps"]["print_err"] < 1e-3
    assert verdict["forward"]["logits_rel_err"] < 1e-5
    assert run.attempted > 0 and run.failed == 0 and run.window_compiles == 0
    # in float32 the free-running program picks the reference's experts, all 4 expert layers
    assert ("choices shared with the plain forward, by expert layer [1. 1. 1. 1.]"
            in capfd.readouterr().out)
    for name in NEW + SHARED:          # a CPU trace has no device plane: nothing to read
        assert manifest.plugin("layer_metrics", name).read(run) is None


def test_the_cut_is_the_configurations_arithmetic():
    manifest = Manifest(ROOT)
    cfg, batch = manifest.config("trinity-mini"), manifest.traffic("s8192")["batch"]
    family = manifest.plugin("families", cfg["family"])
    params, routing = jax.eval_shape(lambda k: family.init(cfg, batch, k), jax.random.PRNGKey(0))
    d, layers = 2048, 5
    attention = 3 * d * 4096 + 2 * d * 512                           # q, gate, o; k, v
    expert = 3 * d * 1024
    matrices = (layers * attention + 3 * d * 6144 + 4 * (d * 128 + expert + 8 * expert)
                + 2 * 25024 * d)
    assert (attention, matrices) == (27_262_976, 504_102_912)
    norms = layers * (4 * d + 2 * 128) + d                          # four a layer, q/k, final
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == matrices + norms
    assert [routing[f"layer_{i}"]["ffn"]["bias"].shape for i in range(1, 5)] == [(128,)] * 4
    assert family.held_range(cfg) == (0, 8)
    assert family.expected_rows(cfg, batch) == 4096                  # 512 a held expert
    # 16 B a parameter held, 24 B at opt.init's high-water mark
    assert (matrices + norms) * 16 / 2 ** 30 == pytest.approx(7.51, abs=5e-3)
    assert (matrices + norms) * 24 / 2 ** 30 == pytest.approx(11.27, abs=5e-3)


def test_the_layer_types_attention_pairs_and_flops():
    manifest = Manifest(ROOT)
    cfg, batch = manifest.config("trinity-mini"), manifest.traffic("s8192")["batch"]
    family = manifest.plugin("families", cfg["family"])
    s, w = 8192, 2048
    assert [kind[0].upper() for kind in cfg["layer_types"][:5]] == list("SSSFS")
    assert family.layouts(cfg) == ((1, 1, 1, 0, 1), (1, 1, 1, 0, 1))
    window = w * (w + 1) // 2 + (s - w) * w
    assert (s * (s + 1) // 2, window) == (33_558_528, 14_681_088)
    assert family.attention_pairs(cfg, batch) == 33_558_528 + 4 * 14_681_088
    assert family.attention_flops(cfg, batch) == pytest.approx(4.54e12, rel=1e-3)
    # 6 N T by part: projections 6.70 TFLOP, of them the gate 2.06, the head
    # 2.52, the dense SwiGLU 1.86, the shared experts 1.24, the routed 0.62, the router 0.05
    six_t = 6.0 * s
    assert six_t * 5 * family.attention_params(cfg) == pytest.approx(6.70e12, rel=2e-3)
    assert six_t * 5 * 2048 * 4096 == pytest.approx(2.06e12, rel=2e-3)
    assert family.flops_per_step(cfg, batch) == pytest.approx(17.5e12, rel=2e-3)
    lm = family.lm_config(cfg)
    assert (lm.first_k_dense_replace, lm.n_routed_experts, lm.experts_held) == (1, 128, (0, 8))
    assert (lm.sliding_window, lm.routed_scaling_factor, lm.bias_update_speed) == (
        2048, 2.826, 0.001)
    assert lm.embedding_scale == pytest.approx(math.sqrt(2048))
    assert lm.qk_norm and lm.attn_output_gate and lm.sandwich_norms


P = "jit(per_rank)/shard_map/bf.grad/"
J, T = "jvp(ConfigLM)/", "transpose(jvp(ConfigLM))/"
CALL = 'custom-call(%param), custom_call_target="tpu_custom_call"'
# name, path under bf.grad, the op, milliseconds in each of two traced steps
OPS = [
    ("fusion.1", J + "layer_1/attn/bf.attn.proj/q/dot_general", "fusion(%param), kind=kOutput", 4.0),
    ("fusion.2", J + "layer_1/attn/bf.attn.gate/gate/dot_general", "fusion(%param), kind=kOutput", 1.5),
    ("fusion.3", T + "layer_1/attn/bf.attn.gate/logistic", "fusion(%param), kind=kLoop", 0.5),
    ("fusion.4", J + "layer_1/attn/bf.attn.proj/q_norm/mul", "fusion(%param), kind=kLoop", 0.25),
    ("bf.flash.fwd.1", J + "layer_1/attn/jit(flash_block)/bf.flash.fwd/pallas_call", CALL, 3.0),
    ("experts.1", J + "layer_1/ffn/bf.moe.experts/pallas_call", CALL, 2.0),
]
HLO = ("HloModule jit_per_rank, is_scheduled=true\n\n"
       "ENTRY %main.1_spmd (param: f32[8,8]) -> f32[8,8] {\n"
       "  %param = f32[8,8]{1,0} parameter(0)\n"
       + "".join(f'  %{name} = f32[8,8]{{1,0}} {op}, metadata={{op_name="{P}{path}"}}\n'
                 for name, path, op, _ in OPS)
       + '  ROOT %update.1 = f32[8,8]{1,0} add(%param, %param), metadata={op_name="jit(per_rank)/shard_map/bf.update/add"}\n}\n')


def read_all(monkeypatch, trace, program, cell=CELL):
    manifest, run = a_run(monkeypatch, trace, programs=(program,), cell=cell)
    return {name: manifest.plugin("layer_metrics", name).read(run) for name in NEW + SHARED}


def test_the_gate_and_the_projections_add_up_on_a_made_up_trace(monkeypatch):
    got = read_all(monkeypatch, traced(OPS), Program(HLO))
    assert got["attn_gate_ms_per_step"] == pytest.approx(2.0)       # its matmul and sigmoid
    assert got["attn_proj_ms_per_step"] == pytest.approx(4.25)      # q and its norm, not the gate
    assert got["flash_fwd_ms_per_step"] == pytest.approx(3.0)
    assert got["moe_experts_ms_per_step"] == pytest.approx(2.0)


def test_another_models_program_gives_the_gate_reader_nothing(monkeypatch):
    """What a parent commit or another cell runs: no ``bf.attn.gate``, so the
    new reader returns None and does not raise."""
    got = read_all(monkeypatch, traced(), Program(JOYAI_HLO), cell="joyai-flash-s8192-epshare-1chip")
    assert got["attn_gate_ms_per_step"] is None
    assert got["moe_experts_ms_per_step"] == pytest.approx(3.0)


def _named(entries, name):
    return next(entry for entry in entries if entry["name"] == name)


def test_the_manifest_lists_the_cell_and_its_metrics():
    doc = Manifest(ROOT).doc
    assert _named(doc["workloads"], CELL) == {
        "name": CELL, "config": "trinity-mini", "traffic": "s8192", "chips": 1,
        "why": _named(doc["workloads"], CELL)["why"]}
    entry = _named(doc["per_layer"], "attn_gate_ms_per_step")
    assert entry["workloads"] == [CELL] and entry["moves"] == "step_ms"
    assert (entry["source"], entry["layer"]) == ("device_trace", "models")
    for name in SHARED:
        assert CELL in _named(doc["per_layer"], name)["workloads"]
    for name in ("flash_dq_ms_per_step", "moe_experts_roofline"):
        assert CELL not in _named(doc["per_layer"], name)["workloads"]
    assert CELL in _named(doc["end_to_end"], "tokens_per_s_per_chip")["workloads"]
    cfg = Manifest(ROOT).config("trinity-mini")
    assert set(_named(doc["configs"], "trinity-mini")["reduced"]) == set(cfg["reduced"])
    assert cfg["num_experts"] * cfg["deployment"]["chips_sharing_each_layer"] \
        == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # every number of the catalog's row, under its key (the four cuts apart)
    published = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 1024, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "num_experts_per_tok": 8, "num_shared_experts": 1,
                 "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_scale": 2.826,
                 "sliding_window": 2048, "load_balance_coeff": 0.001,
                 "max_position_embeddings": 131072, "global_attn_every_n_layers": 4}
    assert {key: cfg[key] for key in published} == published
    assert len(cfg["layer_types"]) == 32 and cfg["layer_types"][3::4] == ["full_attention"] * 8
    with open(os.path.join(ROOT, "benchmark", "traffic", "s8192.json")) as f:
        traffic = json.load(f)
    assert traffic["batch"] == {"sequences": 1, "seq_len": 8192}
    assert (traffic["pool"], traffic["chunk_steps"], traffic["schedule"]) == (4, 5, "static")


def _fp8_weights(cfg, params):
    return cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 1 else x, params)


def _gate_dropped(cfg, params):
    """The gate's kernel at a large positive value: sigmoid 1, the ungated output."""
    return cfg, jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, 1e3) if "gate" in jax.tree_util.keystr(path)
        and "attn" in jax.tree_util.keystr(path) else x, params)


def _rope_in_the_full_layers(cfg, params):
    return {**cfg, "layer_types": ["sliding_attention"] * len(cfg["layer_types"]),
            "sliding_window": 10 ** 6}, params


def _bias_forgotten(cfg, params):
    """The routing biases at zero (see the test): the choice alone moves, so the floor on the
    share of choices must see it."""
    return cfg, params


@pytest.mark.parametrize("control", [None, _fp8_weights, _gate_dropped, _rope_in_the_full_layers,
                                     _bias_forgotten])
def test_the_forward_check_passes_bfloat16_and_fails_the_controls(control, toy_root):
    """``reference.compare_forward`` as the harness calls it, at toy widths in bfloat16 (limit
    4e-2): the honest program is ``ok``; one whose weights were rounded to fp8, whose gate
    passes everything, that turns q and k in every layer under a window no layer reaches, or
    that chooses its experts without the routing bias, is not."""
    from benchmark import reference
    from bluefog_tpu.parallel import flash

    manifest = Manifest(toy_root)
    cfg = {**manifest.config("toy-trinity"), "compute_dtype": "bfloat16"}
    family = manifest.plugin("families", cfg["family"])
    batch = {"sequences": 1, "seq_len": 256}
    params, routing = family.init(cfg, batch, jax.random.PRNGKey(3))
    tokens = family.make_batch(cfg, batch, jax.random.PRNGKey(4), 1)[0][0]
    under_test = family if control is None else types.SimpleNamespace(
        plain_logits=family.plain_logits,
        system_logits=lambda c, p, s, x: family.system_logits(
            *control(c, p), jax.tree_util.tree_map(jnp.zeros_like, s)
            if control is _bias_forgotten else s, x))
    try:
        verdict = reference.compare_forward(under_test, cfg, params, routing, tokens)
    finally:
        flash.flash_block.clear_cache()
    assert verdict["tol"] == 4e-2 and verdict["ok"] == (control is None), verdict
    if control is not None:
        assert not np.isfinite(verdict["logits_rel_err"]) or \
            verdict["logits_rel_err"] > 1.5 * verdict["tol"]
