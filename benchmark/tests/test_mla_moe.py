"""Family ``mla_moe_lm``: a toy configuration and cell through the harness's
functions on the CPU (kernels interpreted), its FLOP count, and the seven
readers of its scopes on a made-up trace. No number here is a device metric."""

import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import scopes
from benchmark.manifest import Manifest

from conftest import ROOT, TOY, copy_benchmark, write_manifest

from test_harness import run_stages
from test_phases import Program, a_run

CELL = "joyai-flash-s8192-epshare-1chip"
METRICS = ["mla_flash_ms_per_step", "mla_flash_roofline", "mla_proj_ms_per_step",
           "moe_route_ms_per_step", "moe_experts_ms_per_step", "moe_experts_roofline",
           "mtp_ms_per_step"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with this family's toy configuration and cell
    added: new files and new entries, as the real ones were."""
    dst = str(tmp_path_factory.mktemp("toy_mla_moe"))
    doc = copy_benchmark(dst)
    shutil.copy(os.path.join(TOY, "toy-mla-moe.json"), os.path.join(dst, "benchmark", "configs"))
    shutil.copy(os.path.join(TOY, "toy-s32.json"), os.path.join(dst, "benchmark", "traffic"))
    doc["configs"].append({"name": "toy-mla-moe", "source": "test", "reduced": [], "why": "test",
                           "file": "benchmark/configs/toy-mla-moe.json"})
    doc["workloads"].append({"name": "toy-mla-moe-1", "config": "toy-mla-moe",
                             "traffic": "toy-s32", "chips": 1, "why": "test"})
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-mla-moe-1")
    write_manifest(dst, doc)
    return dst


def test_toy_cell_end_to_end(toy_root, tmp_path, capfd):
    manifest = Manifest(toy_root)
    run, verdict = run_stages(manifest, "toy-mla-moe-1", trace_dir=str(tmp_path))
    assert verdict["ok"], verdict
    # float32 on one backend: the optimizer's steps and the plain steps run the
    # same loss; the forward is the kernels against the plain reference
    assert verdict["steps"]["loss_rel_err"] < 1e-6 and verdict["steps"]["print_err"] < 1e-3
    assert verdict["forward"]["logits_rel_err"] < 1e-5
    assert run.attempted > 0 and run.failed == 0 and run.window_compiles == 0
    # in float32 the free-running program picks the reference's experts
    assert "choices shared with the plain forward, by expert layer [1. 1. 1.]" in capfd.readouterr().out
    # a CPU trace has no device plane: the scope readers find nothing
    for name in METRICS:
        assert manifest.plugin("layer_metrics", name).read(run) is None


def test_flops_of_the_real_cell_are_the_issues_arithmetic():
    manifest = Manifest(ROOT)
    cfg, batch = manifest.config("joyai-llm-flash"), manifest.traffic("s8192")["batch"]
    family = manifest.plugin("families", cfg["family"])
    # 3 S^2 H (192 + 128) a layer, five layers and the MTP module's block
    assert family.attention_flops(cfg, batch) == pytest.approx(6 * 3 * 8192 ** 2 * 32 * 320)
    assert family.expected_rows(cfg, batch) == 2048    # 256 a held expert
    mla = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    expert_layer = 2048 * 256 + 1.25 * 3 * 2048 * 768  # router, shared, 0.25 slots a token
    want = (6 * mla + 3 * 2048 * 7168 + 5 * expert_layer + 4096 * 2048 + 2 * 2048 * 16160)
    assert family.matmul_params(cfg, batch) == pytest.approx(want)
    assert family.flops_per_step(cfg, batch) == pytest.approx(
        6 * want * 8192 + family.attention_flops(cfg, batch))
    assert family.held_range(cfg) == (0, 8)


P = "jit(per_rank)/shard_map/bf.grad/"
J, T = "jvp(ConfigLM)/", "transpose(jvp(ConfigLM))/"
CALL = 'custom-call(%param), custom_call_target="tpu_custom_call"'
# name, path under bf.grad, the op, milliseconds in each of two traced steps
OPS = [
    ("fusion.1", J + "layer_1/attn/bf.mla.proj/q_b/dot_general", "fusion(%param), kind=kOutput", 4.0),
    ("fusion.2", T + "layer_1/attn/bf.mla.proj/q_a_norm/mul", "fusion(%param), kind=kLoop", 1.0),
    ("bf.flash.fwd.1", J + "layer_1/attn/jit(flash_block)/bf.flash.fwd/pallas_call", CALL, 3.0),
    ("bf.flash.dq.1", T + "layer_1/attn/jit(flash_block_bwd)/bf.flash.dq/pallas_call", CALL, 5.0),
    ("bf.flash.dkv.1", "transpose(jvp(bf.mtp))/mtp_0_block/attn/jit(flash_block_bwd)/bf.flash.dkv/pallas_call",
     CALL, 6.0),
    ("sort.1", J + "layer_1/ffn/bf.moe.route/sort", "sort(%param)", 0.5),
    ("scatter.1", T + "layer_1/ffn/bf.moe.route/scatter-add", "fusion(%param), kind=kLoop", 1.5),
    ("experts.1", J + "layer_1/ffn/bf.moe.experts/pallas_call", CALL, 2.0),
    ("experts.2", J + "bf.mtp/mtp_0_block/ffn/bf.moe.experts/pallas_call", CALL, 1.0),
    ("fusion.3", J + "layer_1/ffn/bf.moe.shared/shared/up/dot_general", "fusion(%param), kind=kOutput", 0.75),
    ("fusion.4", J + "layer_0/bf.ffn.dense/ffn/up/dot_general", "fusion(%param), kind=kOutput", 2.5),
    ("fusion.5", J + "bf.lm.head/lm_head/dot_general", "fusion(%param), kind=kOutput", 3.5),
    ("fusion.6", J + "bf.mtp/mtp_0_proj/dot_general", "fusion(%param), kind=kOutput", 0.25),
    ("fusion.7", J + "embed/jit(_take)/gather", "fusion(%param), kind=kLoop", 0.125),
]
HLO = ("HloModule jit_per_rank, is_scheduled=true\n\n"
       "ENTRY %main.1_spmd (param: f32[8,8]) -> f32[8,8] {\n"
       "  %param = f32[8,8]{1,0} parameter(0)\n"
       + "".join(f'  %{name} = f32[8,8]{{1,0}} {op}, metadata={{op_name="{P}{path}"}}\n'
                 for name, path, op, _ in OPS)
       + '  ROOT %update.1 = f32[8,8]{1,0} add(%param, %param), metadata={op_name="jit(per_rank)/shard_map/bf.update/add"}\n}\n')


def traced(ops=OPS, steps=2):
    from benchmark import trace_reduce

    t, events = 0.0, []
    for _ in range(steps):
        for name, _, op, ms in list(ops) + [("update.1", "", "add(%param, %param)", 10.0)]:
            events.append(trace_reduce._op(f"%{name} = f32[8,8]{{1,0}} {op}", t, t + ms * 1e-3))
            t = events[-1].end
    return trace_reduce.Reduced(
        [trace_reduce.Chip("/device:TPU:0", [("jit_per_rank(1)", 0.0, t)], events, [])], [])


def read_all(monkeypatch, trace, program):
    manifest, run = a_run(monkeypatch, trace, programs=(program,), cell=CELL)
    return run, {name: manifest.plugin("layer_metrics", name).read(run) for name in METRICS}


def test_the_seven_readers_on_a_made_up_trace(monkeypatch, capsys):
    run, got = read_all(monkeypatch, traced(), Program(HLO))
    assert got["mla_flash_ms_per_step"] == pytest.approx(3.0 + 5.0 + 6.0)
    assert got["mla_proj_ms_per_step"] == pytest.approx(5.0)
    assert got["moe_route_ms_per_step"] == pytest.approx(2.0)
    assert got["moe_experts_ms_per_step"] == pytest.approx(3.0)   # the MTP block's too
    assert got["mtp_ms_per_step"] == pytest.approx(6.0 + 1.0 + 0.25)
    # the inner scopes divide bf.grad without overlap
    by_scope = scopes.of(run)
    assert by_scope == pytest.approx({
        "bf.flash.fwd": 3.0, "bf.flash.dq": 5.0, "bf.flash.dkv": 6.0, "bf.mla.proj": 5.0,
        "bf.moe.route": 2.0, "bf.moe.experts": 3.0, "bf.moe.shared": 0.75, "bf.ffn.dense": 2.5,
        "bf.lm.head": 3.5, scopes.OTHER: 0.25 + 0.125, scopes.MTP: 7.25})
    assert sum(by_scope[name] for name in scopes.INNER + (scopes.OTHER,)) == pytest.approx(
        sum(ms for *_, ms in OPS))
    assert "scopes under bf.grad, ms a step (sum 31.125)" in capsys.readouterr().out
    # the rooflines from the real cell's shapes: 12.37 TFLOP of attention, and
    # the held experts' weights' bytes
    cfg, batch = run.cell.config, run.cell.traffic["batch"]
    flash = Manifest(ROOT).plugin("layer_metrics", "mla_flash_roofline")
    assert flash.needs(cfg, batch)[0] == pytest.approx(6 * 3 * 8192 ** 2 * 32 * 320)
    assert flash.roof_seconds(cfg, batch, run.peaks)[1] == "mxu"
    assert got["mla_flash_roofline"] == pytest.approx(
        100 * flash.needs(cfg, batch)[0] / 197e12 / 14e-3)
    experts = Manifest(ROOT).plugin("layer_metrics", "moe_experts_roofline")
    flops, bytes_ = experts.needs(cfg, batch)
    assert flops == pytest.approx(5 * 18 * 2048 * 2048 * 768)
    assert bytes_ == pytest.approx(5 * 2 * (9 * 8 * 2048 * 768 + 3 * 2048 * (4096 + 2304)))
    assert experts.roof_seconds(cfg, batch, run.peaks)[1] == "hbm"
    assert got["moe_experts_roofline"] == pytest.approx(100 * bytes_ / 819e9 / 3e-3)


def test_without_the_module_there_is_no_mtp_metric(monkeypatch):
    ops = [op for op in OPS if "bf.mtp" not in op[1]]
    hlo = "".join(line + "\n" for line in HLO.splitlines() if "bf.mtp" not in line)
    _, got = read_all(monkeypatch, traced(ops), Program(hlo))
    assert got["mtp_ms_per_step"] is None and got["mla_flash_ms_per_step"] == pytest.approx(8.0)


def test_a_program_without_the_scopes_has_nothing_to_read(monkeypatch):
    from test_phases import HLO as PYTHIA_HLO, traced as pythia_traced

    _, got = read_all(monkeypatch, pythia_traced(), Program(PYTHIA_HLO))
    # the three flash kernels are there (equal widths or not, the scopes are the same)
    assert got.pop("mla_flash_ms_per_step") == pytest.approx(12.0)
    assert got.pop("mla_flash_roofline") > 0
    assert set(got.values()) == {None}


def _named(entries, name):
    return next(entry for entry in entries if entry["name"] == name)


def test_the_manifest_lists_the_cell_and_its_metrics():
    doc = Manifest(ROOT).doc
    assert _named(doc["workloads"], CELL)["chips"] == 1
    for name in METRICS:
        entry = _named(doc["per_layer"], name)
        assert CELL in entry["workloads"] and entry["moves"] == "step_ms"
        assert entry["source"] == "device_trace"
    # the three kernels one by one, by the readers the equal-width cells have
    for name in ("flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step"):
        assert CELL in _named(doc["per_layer"], name)["workloads"]
    assert CELL in _named(doc["end_to_end"], "tokens_per_s_per_chip")["workloads"]
    cfg = Manifest(ROOT).config("joyai-llm-flash")
    with open(os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash.json")) as f:
        assert json.load(f) == cfg
    assert set(_named(doc["configs"], "joyai-llm-flash")["reduced"]) == set(cfg["reduced"])
    assert cfg["optimizer"] == {"name": "adam", "args": {"learning_rate": 1e-3}}  # as named
    assert cfg["n_routed_experts"] * cfg["deployment"]["chips_sharing_each_layer"] \
        == cfg["published"]["n_routed_experts"]


def _fp8_weights(cfg, params, routing):
    return cfg, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 1 else x,
        params), routing


def _rope_by_halves(cfg, params, routing):
    return {**cfg, "rope_interleave": False}, params, routing


def _forgotten_bias(cfg, params, routing):
    """The program's top-k leaves the bias out (see the test's monkeypatch)."""
    return cfg, params, routing


@pytest.mark.parametrize("control", [None, _fp8_weights, _rope_by_halves, _forgotten_bias])
def test_the_forward_check_passes_bfloat16_and_fails_the_controls(control, toy_root, monkeypatch):
    """``reference.compare_forward`` as the harness calls it, at toy widths in
    bfloat16 (limit 4e-2): the honest program is ``ok``; a program whose
    weights were rounded to fp8, whose rope pairs halves where the reference
    pairs (2i, 2i+1), or whose routing bias is forgotten is not -- the last
    through the floor on the share of choices, which a forced choice would
    otherwise hide. On the chip at the real widths the first two read 0.24-0.25
    and 0.63-0.69 (PERF.md section 6, PR 27)."""
    import types

    from benchmark import reference
    from bluefog_tpu.parallel import expert

    if control is _forgotten_bias:
        top_k = expert.route_top_k
        monkeypatch.setattr(expert, "route_top_k", lambda scores, bias, *rest: top_k(
            scores, jnp.zeros_like(bias), *rest))
    manifest = Manifest(toy_root)
    cfg = {**manifest.config("toy-mla-moe"), "compute_dtype": "bfloat16"}
    family = manifest.plugin("families", cfg["family"])
    batch = {"sequences": 1, "seq_len": 256}
    params, routing = family.init(cfg, batch, jax.random.PRNGKey(3))
    # top-4 of 32 scores lie further apart than the real top-8 of 256: a bias
    # as large against them as the real one is against its own
    routing = jax.tree_util.tree_map(lambda b: 10 * b, routing)
    tokens = family.make_batch(cfg, batch, jax.random.PRNGKey(4), 1)[0][0]
    under_test = family if control is None else types.SimpleNamespace(
        plain_logits=family.plain_logits,
        system_logits=lambda c, p, s, x: family.system_logits(*control(c, p, s), x))
    verdict = reference.compare_forward(under_test, cfg, params, routing, tokens)
    assert verdict["tol"] == 4e-2 and verdict["ok"] == (control is None), verdict
    if control is _fp8_weights:
        assert verdict["logits_rel_err"] > 2 * verdict["tol"]   # the arithmetic, choice forced
    elif control is not None:
        assert not np.isfinite(verdict["logits_rel_err"])       # the choice
