"""Pass: counter-registry consistency.

Every counter the stack emits is declared once, literally, in
``src/repro/core/counters_registry.py``.  This pass reads those literal
sets straight out of the registry's AST (no import) and checks, in every
scoped module:

  * every ``note_recovery(..., "<path>")`` literal is a declared
    RECOVERY_PATH (the silent-typo class: a misspelled path ships a
    ledger entry no assertion ever reads);
  * every ``<obj>.stats.<field> += ...`` increment names a declared
    Stats field;
  * every literal section/key built inside a ``data_path_counters()``
    body is declared under its section;
  * every ``tracing.span(<name>, ...)`` names a declared SPAN with a
    string literal (the benchmark's readers find spans by name).

``finalize`` (full-repo runs only) closes the loop in the other
direction: a RECOVERY_PATH or SPAN declared but never emitted anywhere
is a stale registry entry and is flagged too.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, FrozenSet, List, Optional, Set

from tools.analysis.common import Finding, Module, attr_name

RULE = "counter"

REGISTRY_REL = Path("src/repro/core/counters_registry.py")

# overridable for tests (lint.py sets it from --root)
REGISTRY_PATH: Optional[Path] = None


class Registry:
    def __init__(self, counters: Dict[str, FrozenSet[str]],
                 recovery_paths: FrozenSet[str],
                 recovery_line: int, path: str,
                 spans: FrozenSet[str] = frozenset(), spans_line: int = 1):
        self.counters = counters
        self.recovery_paths = recovery_paths
        self.recovery_line = recovery_line
        self.path = path
        self.spans = spans
        self.spans_line = spans_line
        self.stats_keys = frozenset().union(*counters.values()) \
            if counters else frozenset()


_cache: Dict[str, Registry] = {}


def load_registry(root: Optional[Path] = None) -> Registry:
    path = REGISTRY_PATH
    if path is None:
        base = root if root is not None else Path(__file__).parents[3]
        path = base / REGISTRY_REL
    key = str(path)
    if key in _cache:
        return _cache[key]
    tree = ast.parse(path.read_text(), filename=str(path))
    sets: Dict[str, FrozenSet[str]] = {}
    counters: Dict[str, FrozenSet[str]] = {}
    lines: Dict[str, int] = {}
    for node in tree.body:
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        if len(targets) != 1 or not isinstance(targets[0], ast.Name):
            continue
        name, value = targets[0].id, node.value
        if isinstance(value, ast.Call) and attr_name(value.func) \
                == "frozenset" and value.args:
            elems = value.args[0]
            if isinstance(elems, (ast.Set, ast.List, ast.Tuple)):
                lits = {e.value for e in elems.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
                sets[name] = frozenset(lits)
                lines[name] = node.lineno
        elif isinstance(value, ast.Dict) and name == "COUNTERS":
            for k, v in zip(value.keys, value.values):
                if isinstance(k, ast.Constant) and isinstance(v, ast.Name) \
                        and v.id in sets:
                    counters[k.value] = sets[v.id]
    reg = Registry(counters, sets.get("RECOVERY_PATHS", frozenset()),
                   lines.get("RECOVERY_PATHS", 1), str(path),
                   sets.get("SPANS", frozenset()), lines.get("SPANS", 1))
    _cache[key] = reg
    return reg


def _recovery_literal(call: ast.Call) -> Optional[ast.Constant]:
    """The path literal of a note_recovery-style call, if any."""
    name = attr_name(call.func) or ""
    if not name.endswith("note_recovery"):
        return None
    for arg in reversed(call.args):
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg
    return None


def _span_call(call: ast.Call) -> bool:
    """A ``tracing.span(...)`` call."""
    f = call.func
    return isinstance(f, ast.Attribute) and f.attr == "span" \
        and isinstance(f.value, ast.Name) and f.value.id == "tracing"


def _check_counters_fn(mod: Module, fn: ast.FunctionDef,
                       reg: Registry, out: List[Finding]) -> None:
    """Validate literal section/key structure built by a
    data_path_counters() body."""

    def check_section(section: str, value: ast.AST, line: int) -> None:
        declared = reg.counters.get(section)
        if declared is None:
            out.append(Finding(
                RULE, mod.path, line,
                f"counter section '{section}' is not declared in "
                f"counters_registry.COUNTERS"))
            return
        if isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Constant) \
                        and isinstance(k.value, str) \
                        and k.value not in declared:
                    out.append(Finding(
                        RULE, mod.path, k.lineno,
                        f"counter key '{section}.{k.value}' is not "
                        f"declared in counters_registry"))

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            # out["section"] = {...}
            if isinstance(tgt, ast.Subscript) \
                    and isinstance(tgt.slice, ast.Constant) \
                    and isinstance(tgt.slice.value, str):
                check_section(tgt.slice.value, node.value, node.lineno)
            # out = {"section": {...}, ...}
            elif isinstance(node.value, ast.Dict):
                for k, v in zip(node.value.keys, node.value.values):
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str) \
                            and k.value in reg.counters:
                        check_section(k.value, v, k.lineno)


# note_recovery and span literals seen across the whole run (finalize)
_seen_paths: Set[str] = set()
_seen_spans: Set[str] = set()


def run(mod: Module) -> List[Finding]:
    reg = load_registry()
    out: List[Finding] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and _span_call(node):
            arg = node.args[0] if node.args else None
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                out.append(Finding(
                    RULE, mod.path, node.lineno,
                    "tracing.span name must be a string literal declared "
                    "in counters_registry.SPANS"))
                continue
            _seen_spans.add(arg.value)
            if arg.value not in reg.spans:
                out.append(Finding(
                    RULE, mod.path, node.lineno,
                    f"span '{arg.value}' is not declared in "
                    f"counters_registry.SPANS — a typo here ships a span "
                    f"no reader finds"))
        elif isinstance(node, ast.Call):
            lit = _recovery_literal(node)
            if lit is not None:
                _seen_paths.add(lit.value)
                if lit.value not in reg.recovery_paths:
                    out.append(Finding(
                        RULE, mod.path, node.lineno,
                        f"recovery path '{lit.value}' is not declared in "
                        f"counters_registry.RECOVERY_PATHS — a typo here "
                        f"ships a ledger entry no assertion reads"))
        elif isinstance(node, ast.AugAssign) \
                and isinstance(node.target, ast.Attribute) \
                and isinstance(node.target.value, ast.Attribute) \
                and node.target.value.attr == "stats":
            field = node.target.attr
            if field not in reg.stats_keys:
                out.append(Finding(
                    RULE, mod.path, node.lineno,
                    f"stats field '{field}' incremented here is not "
                    f"declared in counters_registry"))
        elif isinstance(node, ast.FunctionDef) \
                and node.name == "data_path_counters":
            _check_counters_fn(mod, node, reg, out)
    return out


def finalize(mods: List[Module]) -> List[Finding]:
    """Full-repo sweep: declared recovery paths and spans nobody emits
    are stale."""
    reg = load_registry()
    out = [Finding(
        RULE, reg.path, reg.recovery_line,
        f"RECOVERY_PATHS entry '{p}' is emitted nowhere in the scoped "
        f"modules — stale registry entries hide real coverage gaps")
        for p in sorted(reg.recovery_paths - _seen_paths)]
    out += [Finding(
        RULE, reg.path, reg.spans_line,
        f"SPANS entry '{s}' is emitted nowhere in the scoped modules — "
        f"a reader of it would find nothing")
        for s in sorted(reg.spans - _seen_spans)]
    return out
