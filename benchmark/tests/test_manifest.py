"""BENCHMARK.json against the contract's limits, every name resolved to its
file, and a cell, a configuration, a schedule and a per-layer metric each added
to a copy by new files and new entries alone."""

import json
import os
import re
import shutil

import jax

from benchmark import harness
from benchmark.manifest import Manifest

from conftest import ROOT, TOY, copy_benchmark, write_manifest

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_keeps_the_contract():
    doc = Manifest(ROOT).doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmark"] and doc["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [e["name"] for section in ("configs", "workloads", "end_to_end", "per_layer")
             for e in doc[section]]
    assert all(NAME.match(n) for n in names)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        section_names = [e["name"] for e in doc[section]]
        assert len(section_names) == len(set(section_names))
    assert 2 <= len(doc["workloads"]) <= 24
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in doc["workloads"])
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in doc["workloads"]}) == len(doc["workloads"])
    assert {w["config"] for w in doc["workloads"]} == {c["name"] for c in doc["configs"]}
    assert all(len(e["why"]) <= 200 for e in doc["workloads"] + doc["configs"])
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    assert all(0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
               for m in doc["end_to_end"])
    for m in doc["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_every_name_resolves_and_every_cell_reports_enough():
    manifest = Manifest(ROOT)
    for entry in manifest.doc["workloads"]:
        cell = harness.Cell.load(manifest, entry["name"])
        assert cell.config["family"] and hasattr(cell.schedule, "Schedule")
        for needed in ("THROUGHPUT_METRIC", "init", "loss", "make_batch", "units_per_step",
                       "flops_per_step", "check_inputs", "system_logits", "plain_logits"):
            assert hasattr(cell.family, needed), (cell.config["family"], needed)
        e2e = [m["name"] for m in manifest.metrics("end_to_end", cell.name)]
        layers = manifest.metrics("per_layer", cell.name)
        assert "setup_s" in e2e and "step_ms" in e2e and cell.family.THROUGHPUT_METRIC in e2e
        assert layers and all(m["moves"] in e2e for m in layers)
    for config in manifest.doc["configs"]:
        path = os.path.join(ROOT, config["file"])
        assert config["file"].startswith("benchmark/configs/") and os.path.isfile(path)
        held = manifest.config(config["name"])
        assert held["source"] == config["source"] and set(config["reduced"]) == set(held["reduced"])
    for metric in manifest.doc["per_layer"]:
        assert callable(manifest.plugin("layer_metrics", metric["name"]).read)
    for root, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        assert all(re.match(r"^[A-Za-z0-9_.\-]+$", f) for f in files if "__pycache__" not in root)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """One of each, none touching a file that was there: a configuration of an
    existing family, a traffic mix, a schedule, a per-layer metric and the cell
    that uses them all -- and the new cell runs."""
    root = str(tmp_path)
    doc = copy_benchmark(root)
    before = {}
    for base, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                before[os.path.join(base, f)] = fh.read()

    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(TOY, "toy-lm.json"), os.path.join(bench, "configs", "new-lm.json"))
    with open(os.path.join(TOY, "toy-s32-onepeer.json")) as f:
        traffic = json.load(f)
    traffic["schedule"] = "every_other"
    with open(os.path.join(bench, "traffic", "new-mix.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "schedules", "every_other.py"), "w") as f:
        f.write('"""Static topology, communicating on every second step."""\n'
                "import numpy as np\n\n\n"
                "class Schedule:\n"
                "    def __init__(self, bf, opt):\n"
                "        self.n, self.step = bf.size(), 0\n"
                "        opt.num_steps_per_communication = 2\n"
                "        topo = bf.load_topology()\n"
                "        self.W = np.zeros((self.n, self.n))\n"
                "        for r in range(self.n):\n"
                "            src = [s for s in topo.predecessors(r) if s != r]\n"
                "            self.W[[r] + src, r] = 1.0 / (len(src) + 1)\n\n"
                "    def before_step(self):\n"
                "        self.step += 1\n"
                "        return self.W if self.step % 2 == 0 else np.eye(self.n)\n")
    with open(os.path.join(bench, "layer_metrics", "chunk_ms.py"), "w") as f:
        f.write('"""Median seconds of a chunk, in ms."""\nimport statistics\n\n\n'
                "def read(run):\n"
                "    return statistics.median(run.chunk_seconds) * 1e3 if run.chunk_seconds else None\n")
    doc["configs"].append({"name": "new-lm", "source": "test", "reduced": [], "why": "test",
                           "file": "benchmark/configs/new-lm.json"})
    doc["workloads"].append({"name": "new-cell", "config": "new-lm", "traffic": "new-mix",
                             "chips": 4, "why": "test"})
    doc["per_layer"].append({"name": "chunk_ms", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "optimizers", "moves": "step_ms",
                             "workloads": ["new-cell"]})
    doc["end_to_end"].append({"name": "tokens_per_s_per_chip_new", "unit": "tokens/s/chip",
                              "better": "higher", "bound": 0.01, "source": "host_clock",
                              "workloads": ["new-cell"]})
    write_manifest(root, doc)

    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            with open(path, "rb") as fh:
                assert fh.read() == content, path
    manifest = Manifest(root)
    cell = harness.Cell.load(manifest, "new-cell")
    devices = jax.devices()[:4]
    run = harness.Run(cell, harness.Spans())
    trainer = harness.set_up(cell, 2, devices, run)
    harness.window(trainer, 0.2, run, harness.CompileCounter())
    trainer.close()
    verdict = harness.check(trainer, run, devices)
    assert verdict["ok"], verdict  # the reference mixes on every second step too
    layers = harness.per_layer(manifest, run)
    assert layers["chunk_ms"] > 0 and "host_step_ms" in layers and "flash_ms_per_step" not in layers
