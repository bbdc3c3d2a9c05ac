"""The tree names no measuring program that is gone.

``benchmark/run.py`` is the one throughput harness and PERF_LEDGER.jsonl the
one speed record (docs/performance.md). The harnesses it superseded were
deleted; a document, recipe or comment that still sends a reader to one of
them is the fault this file keeps out. Files are found by walking paths, not
with ``git ls-files``: a checkout may have no ``.git``.
"""

import os
import re
from pathlib import Path

import pytest

THIS = Path(__file__).resolve()
REPO = THIS.parent.parent

# what a builder reads or runs; the builder's scratch, the benchmark (only a
# `benchmark` PR may reword it) and the driver's records are not walked
ROOTS = ("README.md", "Makefile", "chip_smoke.py", "docs", "scripts",
         "examples", "bluefog_tpu", "tests", ".claude")

GONE = re.compile(
    r"lm_bench|batch_sweep|resnet_profile|convgrad_probe"
    r"|frontend_overhead_probe"
    # the file `bench.py` itself, not `win_microbench.py` or `serve_bench.py`
    r"|(?<![\w.-])bench\.py"
    # a checked-in directory of recorded traces
    r"|(?<![\w./-])traces/")


def _files(root: str):
    path = REPO / root
    if path.is_file():
        yield path
        return
    for parent, dirs, names in os.walk(path):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if not name.endswith((".pyc", ".so")):
                yield Path(parent) / name


@pytest.mark.parametrize("root", ROOTS)
def test_nothing_names_a_deleted_harness(root):
    assert (REPO / root).exists(), root
    named = []
    for path in _files(root):
        if path == THIS:    # the one file that has to spell the names
            continue
        text = path.read_text(encoding="utf-8", errors="ignore")
        for n, line in enumerate(text.splitlines(), 1):
            m = GONE.search(line)
            if m:
                named.append(f"{path.relative_to(REPO)}:{n}: {m.group(0)}")
    assert not named, "\n".join(named)


def test_makefile_recipes_run_files_that_exist():
    recipes = [line for line in (REPO / "Makefile").read_text().splitlines()
               if line.startswith("\t")]
    ran = {m.group(1) for line in recipes for m in re.finditer(
        r"(?<![\w./-])((?:scripts/)?\w+\.py)\b", line)}
    assert "scripts/perf_gate.py" in ran    # the pattern finds what is there
    missing = sorted(f for f in ran if not (REPO / f).is_file())
    assert not missing, missing
