"""Env-knob registry analyzer.

Walks every Python file in the tree (AST, no imports) plus the native
source and finds each ``BLUEFOG_*`` environment READ:

* ``os.environ.get(name[, default])`` / ``os.getenv`` / ``env.get`` (any
  receiver whose attribute chain mentions ``environ``),
* ``os.environ[name]`` subscripts in Load context,
* ``name in os.environ`` membership probes,
* ``EnvInt("NAME", default)`` / ``EnvSeconds("NAME", default)`` in
  ``csrc/bf_runtime.cc``.

Checks, against ``runtime/config.py``'s ``KNOBS`` registry:

1. every read knob is declared (a typo'd or ad-hoc knob fails the tree),
2. a per-site LITERAL default must agree with the registry default —
   the "four different defaults for one knob" drift class,
3. every declared knob appears in ``docs/env_variables.md``, and the
   generated knob table section matches the registry exactly
   (``python scripts/bfcheck --write-docs`` regenerates it).

Writes (``env[name] = ...``), deletes, and knob names inside plain string
literals are ignored — only reads are classified.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re
import sys
from typing import List, Optional

from . import Diagnostic

CONFIG_PATH = os.path.join("bluefog_tpu", "runtime", "config.py")
DOCS_PATH = os.path.join("docs", "env_variables.md")
CC_PATH = os.path.join("csrc", "bf_runtime.cc")
TABLE_BEGIN = "<!-- bfcheck:knob-table:begin (generated - edit "\
    "runtime/config.py KNOBS and run `python scripts/bfcheck "\
    "--write-docs`) -->"
TABLE_END = "<!-- bfcheck:knob-table:end -->"

PY_ROOTS = ("bluefog_tpu", "scripts", "tests", "__graft_entry__.py")

_CC_ENV_RE = re.compile(
    r'Env(?:Int|Seconds)\(\s*"(BLUEFOG_[A-Z0-9_]+)"\s*,\s*([-0-9.]+)')


def load_registry(root: str):
    """Load the KNOBS table from runtime/config.py by path (stdlib-only
    module; fixture trees supply their own)."""
    path = os.path.join(root, CONFIG_PATH)
    spec = importlib.util.spec_from_file_location("_bfcheck_config", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.modules.pop(spec.name, None)
    return {k.name: k for k in mod.KNOBS}


def iter_py_files(root: str):
    for entry in PY_ROOTS:
        path = os.path.join(root, entry)
        if os.path.isfile(path):
            yield path
        elif os.path.isdir(path):
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", "build")]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def _const_eval(node) -> Optional[object]:
    """Evaluate simple constant expressions (literals and arithmetic over
    them — `8 * 1024 * 1024` style defaults); None when not constant."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.BinOp) and \
            isinstance(node.op, (ast.Mult, ast.Add, ast.Sub, ast.Pow)):
        left, right = _const_eval(node.left), _const_eval(node.right)
        if isinstance(left, (int, float)) and isinstance(right, (int, float)):
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            return left ** right
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_eval(node.operand)
        if isinstance(v, (int, float)):
            return -v
    return None


def _mentions_environ(node) -> bool:
    """True when the attribute/name chain of ``node`` mentions environ."""
    while isinstance(node, ast.Attribute):
        if node.attr == "environ":
            return True
        node = node.value
    return isinstance(node, ast.Name) and node.id == "environ"


class _ReadCollector(ast.NodeVisitor):
    """Collects (knob name, default node or None, line) env reads."""

    def __init__(self) -> None:
        self.reads = []

    @staticmethod
    def _knob_arg(node) -> Optional[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value.startswith("BLUEFOG_"):
            return node.value
        return None

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        name = None
        default = None
        if isinstance(fn, ast.Attribute) and fn.attr in ("get", "getenv") \
                and (_mentions_environ(fn.value)
                     or (isinstance(fn.value, ast.Name)
                         and fn.value.id in ("os", "env"))):
            if node.args:
                name = self._knob_arg(node.args[0])
                if len(node.args) > 1:
                    default = node.args[1]
        if name:
            self.reads.append((name, default, node.lineno))
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if isinstance(node.ctx, ast.Load) and _mentions_environ(node.value):
            name = self._knob_arg(node.slice)
            if name:
                self.reads.append((name, None, node.lineno))
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        if len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                _mentions_environ(node.comparators[0]):
            name = self._knob_arg(node.left)
            if name:
                self.reads.append((name, None, node.lineno))
        self.generic_visit(node)


def _default_matches(knob, value) -> bool:
    """Is a per-site literal default compatible with the registry's?"""
    reg = knob.default
    if knob.type in ("int", "float"):
        try:
            site = float(value)
        except (TypeError, ValueError):
            return False
        return reg is not None and float(reg) == site
    if knob.type == "bool":
        site = value == "1" if isinstance(value, str) else bool(value)
        return bool(reg) == site
    # str / path / spec: empty-string and None both mean "unset"
    return (reg or "") == (value or "")


def render_knob_table(registry) -> str:
    """The generated docs/env_variables.md knob table (between markers)."""
    lines = [TABLE_BEGIN,
             "| Variable | Type | Default | Effect |",
             "|---|---|---|---|"]
    for k in registry.values():
        if k.default is None:
            dflt = "unset"
        elif k.type == "bool":
            dflt = "`1`" if k.default else "`0`"
        elif isinstance(k.default, float) and k.default == int(k.default):
            dflt = f"`{int(k.default)}`"
        else:
            dflt = f"`{k.default}`"
        scope = " *(read by the native layer)*" if k.scope == "native" \
            else ""
        lines.append(f"| `{k.name}` | {k.type} | {dflt} | {k.doc}{scope} |")
    lines.append(TABLE_END)
    return "\n".join(lines) + "\n"


def write_docs(root: str) -> bool:
    """Regenerate the knob table between the markers; True if changed."""
    registry = load_registry(root)
    path = os.path.join(root, DOCS_PATH)
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    begin = text.find(TABLE_BEGIN)
    end = text.find(TABLE_END)
    if begin < 0 or end < 0:
        raise RuntimeError(f"{DOCS_PATH}: knob-table markers not found")
    new = text[:begin] + render_knob_table(registry) + \
        text[end + len(TABLE_END) + 1:]
    if new != text:
        with open(path, "w", encoding="utf-8") as f:
            f.write(new)
        return True
    return False


def check(root: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []

    def bad(path, line, msg):
        out.append(Diagnostic("knobs", os.path.relpath(path, root)
                              if os.path.isabs(path) else path, line, msg))

    try:
        registry = load_registry(root)
    except Exception as exc:  # noqa: BLE001 — any load failure is the finding
        bad(CONFIG_PATH, 1, f"cannot load knob registry: {exc}")
        return out

    # -- Python read sites --------------------------------------------------
    for path in iter_py_files(root):
        rel = os.path.relpath(path, root)
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as exc:
            bad(rel, exc.lineno or 1, f"syntax error: {exc.msg}")
            continue
        col = _ReadCollector()
        col.visit(tree)
        for name, default, line in col.reads:
            k = registry.get(name)
            if k is None:
                bad(rel, line,
                    f"read of undeclared knob {name} — declare it in "
                    f"{CONFIG_PATH} KNOBS (type, default, doc) first")
                continue
            if default is not None:
                value = _const_eval(default)
                if value is not None and not _default_matches(k, value):
                    bad(rel, line,
                        f"per-site default {value!r} for {name} "
                        f"contradicts the registry default "
                        f"{k.default!r} — import it from the registry "
                        "(runtime/config.py knob_env) instead")

    # -- native read sites --------------------------------------------------
    cc = os.path.join(root, CC_PATH)
    if os.path.exists(cc):
        with open(cc, "r", encoding="utf-8") as f:
            cc_text = f.read()
        for m in _CC_ENV_RE.finditer(cc_text):
            name, site_default = m.group(1), m.group(2)
            line = cc_text.count("\n", 0, m.start()) + 1
            k = registry.get(name)
            if k is None:
                bad(CC_PATH, line,
                    f"native read of undeclared knob {name} — declare it "
                    f"in {CONFIG_PATH} KNOBS (scope=\"native\")")
                continue
            if k.default is not None and \
                    float(k.default) != float(site_default):
                bad(CC_PATH, line,
                    f"native default {site_default} for {name} contradicts "
                    f"the registry default {k.default!r}")

    # -- docs coverage ------------------------------------------------------
    docs = os.path.join(root, DOCS_PATH)
    if not os.path.exists(docs):
        bad(DOCS_PATH, 1, "docs/env_variables.md missing")
        return out
    with open(docs, "r", encoding="utf-8") as f:
        doc_text = f.read()
    for name in registry:
        if f"`{name}`" not in doc_text:
            bad(DOCS_PATH, 1,
                f"declared knob {name} is not documented — run "
                "`python scripts/bfcheck --write-docs`")
    begin = doc_text.find(TABLE_BEGIN)
    end = doc_text.find(TABLE_END)
    if begin < 0 or end < 0:
        bad(DOCS_PATH, 1, "knob-table markers missing (the Live-knobs "
                          "table is generated from the registry)")
    else:
        current = doc_text[begin:end + len(TABLE_END)] + "\n"
        if current != render_knob_table(registry):
            line = doc_text.count("\n", 0, begin) + 1
            bad(DOCS_PATH, line,
                "generated knob table is stale — run "
                "`python scripts/bfcheck --write-docs`")
    return out
