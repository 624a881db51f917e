"""KNOB* — every ``FMT_*`` environment knob is declared once, read through
:mod:`flink_ml_tpu.utils.knobs`, and documented in README.md.

The declaration table is read *statically* (the literal ``Knob(...)``
calls in ``utils/knobs.py``), so this checker needs no imports from the
package under analysis — and it is the code-vs-docs drift gate: a round
once documented 45 of the 50 knobs the code read.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Tuple

from flink_ml_tpu.analysis.core import (
    Finding,
    Project,
    attr_chain,
    import_sources,
)

RULES = {
    "KNOB001": "FMT_* environment variable read directly (os.environ/"
               "os.getenv) instead of through utils/knobs.py",
    "KNOB002": "knobs getter called with an undeclared FMT_* name",
    "KNOB003": "knob declared in utils/knobs.py but never read (dead knob)",
    "KNOB004": "knob declared but not documented in README.md",
    "KNOB005": "FMT_* name referenced in docs but not declared (doc drift)",
    "KNOB006": "knob declared more than once in utils/knobs.py",
}

KNOBS_REL = "flink_ml_tpu/utils/knobs.py"
_GETTERS = ("raw", "get", "knob_bool", "knob_int", "knob_float", "knob_str")
_KNOB_NAME = re.compile(r"FMT_[A-Z0-9_]+")


def _declarations(project: Project) -> Tuple[Dict[str, int], List[Finding]]:
    """Declared knob name -> line, plus duplicate-declaration findings."""
    declared: Dict[str, int] = {}
    findings: List[Finding] = []
    mod = project.by_rel.get(KNOBS_REL)
    if mod is None:
        return declared, findings
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "Knob" and node.args):
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            name = first.value
            if name in declared:
                findings.append(Finding(
                    "KNOB006", KNOBS_REL, node.lineno,
                    f"knob {name!r} already declared at line "
                    f"{declared[name]}"))
            else:
                declared[name] = node.lineno
    return declared, findings


def _os_rooted(chain: List[str], imports: Dict[str, str]) -> List[str]:
    """Normalize import aliases so every spelling of an environment read
    looks os-rooted: ``from os import environ`` / ``getenv`` and
    ``import os as o`` must not evade KNOB001."""
    if not chain:
        return chain
    source = imports.get(chain[0])
    if source == "os.environ":
        return ["os", "environ"] + chain[1:]
    if source == "os.getenv":
        return ["os", "getenv"] + chain[1:]
    if source == "os":
        return ["os"] + chain[1:]
    return chain


def _literal_fmt_arg(call: ast.Call) -> str:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        if (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                and arg.value.startswith("FMT_")):
            return arg.value
    return ""


def check(project: Project) -> Iterator[Finding]:
    declared, dup_findings = _declarations(project)
    yield from dup_findings

    read: Dict[str, str] = {}  # knob name -> "file:line" of first read
    for mod in project.modules:
        imports = import_sources(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            if chain is None:
                continue
            chain = _os_rooted(chain, imports)
            # direct environment reads: os.environ.get/os.getenv/
            # os.environ[...] is handled below (Subscript); calls first
            if chain[:2] == ["os", "environ"] or chain[:2] == ["os",
                                                              "getenv"]:
                name = _literal_fmt_arg(node)
                if name and mod.rel != KNOBS_REL:
                    yield Finding(
                        "KNOB001", mod.rel, node.lineno,
                        f"read of {name!r} bypasses the knob registry — "
                        f"use flink_ml_tpu.utils.knobs instead")
                continue
            # knobs getters: knobs.knob_int("FMT_X") / knobs.raw("FMT_X")
            if (len(chain) >= 2 and chain[-2] == "knobs"
                    and chain[-1] in _GETTERS):
                name = _literal_fmt_arg(node)
                if not name:
                    continue
                read.setdefault(name, f"{mod.rel}:{node.lineno}")
                if name not in declared:
                    yield Finding(
                        "KNOB002", mod.rel, node.lineno,
                        f"knob {name!r} is not declared in {KNOBS_REL}")
        # os.environ["FMT_X"] subscript reads (rare, but a bypass all the
        # same); writes (ast.Store context) are test-setup idiom and fine
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and _os_rooted(attr_chain(node.value) or [], imports)
                    == ["os", "environ"]
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)
                    and node.slice.value.startswith("FMT_")
                    and mod.rel != KNOBS_REL):
                yield Finding(
                    "KNOB001", mod.rel, node.lineno,
                    f"read of {node.slice.value!r} bypasses the knob "
                    f"registry — use flink_ml_tpu.utils.knobs instead")

    doc_names: Dict[str, str] = {}
    for doc_name, text in project.docs.items():
        for match in _KNOB_NAME.finditer(text):
            doc_names.setdefault(match.group(0), doc_name)

    for name, line in sorted(declared.items()):
        if name not in read:
            yield Finding(
                "KNOB003", KNOBS_REL, line,
                f"knob {name!r} is declared but no code reads it — remove "
                f"the declaration or the knob is dead")
        if name not in doc_names:
            yield Finding(
                "KNOB004", KNOBS_REL, line,
                f"knob {name!r} is declared but not documented in README.md")

    for name, doc_name in sorted(doc_names.items()):
        if name not in declared:
            yield Finding(
                "KNOB005", doc_name, 0,
                f"docs reference {name!r} but {KNOBS_REL} does not declare "
                f"it — stale docs or an undeclared knob")
