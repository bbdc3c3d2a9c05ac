"""The benchmark's own tests (tier-1 does not collect them):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They run on the CPU backend with four virtual devices: toy sizes through the
harness's functions, the reducer on a recorded v5e trace, and the manifest.
No number they produce is a device metric.
"""

import json
import os
import shutil
import sys

_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(_flags + ["--xla_force_host_platform_device_count=4"])
os.environ["JAX_PLATFORMS"] = "cpu"
if "BLUEFOG_FLIGHT_DIR" not in os.environ:
    import tempfile

    os.environ["BLUEFOG_FLIGHT_DIR"] = tempfile.mkdtemp(prefix="bf_flight_benchtests_")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")

# the toy cells: test files laid over a copy of the benchmark, which is also
# how a later PR adds a configuration of an existing family, a traffic mix and
# a cell -- new files and new entries, no edit to a file that is there
TOY_CONFIGS = ["toy-lm", "toy-resnet"]
TOY_TRAFFIC = ["toy-s32", "toy-s32-onepeer", "toy-b8"]
TOY_CELLS = [
    {"name": "toy-lm-1", "config": "toy-lm", "traffic": "toy-s32", "chips": 1, "why": "test"},
    {"name": "toy-lm-onepeer-4", "config": "toy-lm", "traffic": "toy-s32-onepeer", "chips": 4,
     "why": "test"},
    {"name": "toy-resnet-1", "config": "toy-resnet", "traffic": "toy-b8", "chips": 1,
     "why": "test"},
]


def copy_benchmark(dst: str) -> dict:
    """BENCHMARK.json and the directory under ``paths`` (without these tests)
    copied to ``dst``; returns the manifest's document."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return doc


def write_manifest(dst: str, doc: dict) -> None:
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=2)


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    """A copy of the benchmark with the toy configurations, traffic and cells added."""
    dst = str(tmp_path_factory.mktemp("toy_checkout"))
    doc = copy_benchmark(dst)
    for name in TOY_CONFIGS:
        shutil.copy(os.path.join(TOY, name + ".json"), os.path.join(dst, "benchmark", "configs"))
        doc["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                               "file": f"benchmark/configs/{name}.json"})
    for name in TOY_TRAFFIC:
        shutil.copy(os.path.join(TOY, name + ".json"), os.path.join(dst, "benchmark", "traffic"))
    doc["workloads"] += TOY_CELLS
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:  # the toy cells report what their families' real cells do
            metric["workloads"] += [c["name"] for c in TOY_CELLS]
    write_manifest(dst, doc)
    return dst
