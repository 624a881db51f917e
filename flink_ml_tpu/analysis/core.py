"""fmtlint core: file walker, finding records, suppression baseline.

A checker is a callable ``check(project) -> iterable[Finding]`` plus a
``RULES`` dict of the rule ids it can emit (id -> one-line description).
Checkers get the whole parsed :class:`Project`, not one file at a time,
because the repo's invariants are cross-file by nature (a knob declared
in ``utils/knobs.py`` is read in ``serve/breaker.py`` and documented in
``README.md``; a metric-name collision is two call sites in two
modules).

Suppressions live in the committed ``analysis/baseline.json``::

    {"suppressions": [
        {"rule": "LOCK002", "file": "flink_ml_tpu/serve/breaker.py",
         "match": "'_state'", "reason": "volatile-style fast-path read; ..."}
    ]}

An entry suppresses every finding with the same rule id, the same
repo-relative file, and ``match`` as a substring of the message —
line-number free on purpose, so an unrelated edit above a suppressed
finding does not resurrect it.  ``reason`` is mandatory and must be
non-empty: an unexplained suppression is itself a finding (META001).
Entries that no longer match anything are reported as warnings so the
baseline shrinks as debt is paid down, but they never fail the run.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: repo root = three levels up from this file (flink_ml_tpu/analysis/core.py)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: documentation files the knob checker cross-references
DOC_FILES = ("README.md",)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at ``file:line``."""

    rule: str
    file: str  # repo-relative, posix separators
    line: int
    message: str
    symbol: str = ""  # enclosing qualname, e.g. "CircuitBreaker.status"

    def format(self) -> str:
        where = f" ({self.symbol})" if self.symbol else ""
        return f"{self.file}:{self.line} {self.rule} {self.message}{where}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Module:
    """One parsed source file."""

    path: str  # absolute
    rel: str  # repo-relative, posix separators
    tree: ast.Module
    source: str


class Project:
    """Every parsed module plus the documentation text, one object."""

    def __init__(self, root: str, modules: Sequence[Module],
                 docs: Dict[str, str]):
        self.root = root
        self.modules = list(modules)
        self.by_rel = {m.rel: m for m in self.modules}
        #: doc file name -> raw text ("" when the file is absent)
        self.docs = dict(docs)


def _rel(root: str, path: str) -> str:
    return os.path.relpath(os.path.abspath(path), root).replace(os.sep, "/")


def load_project(root: Optional[str] = None,
                 extra_paths: Sequence[str] = ()) -> Tuple[
                     "Project", List[Finding]]:
    """Parse the analysis scope and return ``(project, parse_findings)``.

    Scope: every ``*.py`` under ``<root>/flink_ml_tpu`` (skipping
    ``__pycache__``), plus ``extra_paths`` verbatim.  Unparsable files
    are not fatal — they become META002 findings, so a syntax error in
    a scanned file fails ``--check`` with a location instead of a
    traceback.
    """
    root = os.path.abspath(root or REPO_ROOT)
    paths: List[str] = []
    pkg = os.path.join(root, "flink_ml_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith(".py"):
                paths.append(os.path.join(dirpath, name))
    paths.extend(os.path.abspath(p) for p in extra_paths)

    modules: List[Module] = []
    findings: List[Finding] = []
    for path in paths:
        rel = _rel(root, path)
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=rel)
        except (OSError, SyntaxError, ValueError) as exc:
            findings.append(Finding(
                "META002", rel, getattr(exc, "lineno", 0) or 0,
                f"file does not parse: {exc}"))
            continue
        modules.append(Module(path=path, rel=rel, tree=tree, source=source))

    docs = {}
    for name in DOC_FILES:
        doc_path = os.path.join(root, name)
        try:
            with open(doc_path, encoding="utf-8") as fh:
                docs[name] = fh.read()
        except OSError:
            docs[name] = ""
    return Project(root, modules, docs), findings


def run_checkers(project: Project, checkers: Sequence) -> List[Finding]:
    """Run every checker over the project; findings sorted by location."""
    findings: List[Finding] = []
    for checker in checkers:
        findings.extend(checker(project))
    findings.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    return findings


# -- suppression baseline -----------------------------------------------------


BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")


@dataclasses.dataclass(frozen=True)
class Suppression:
    rule: str
    file: str
    match: str
    reason: str


def load_baseline(path: Optional[str] = None) -> Tuple[
        List[Suppression], List[Finding]]:
    """Load suppressions; malformed entries come back as META001 findings."""
    path = path or BASELINE_PATH
    rel = _rel(REPO_ROOT, path)
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
    except FileNotFoundError:
        return [], []
    except (OSError, json.JSONDecodeError) as exc:
        return [], [Finding("META001", rel, 0,
                            f"baseline does not parse: {exc}")]

    entries: List[Suppression] = []
    findings: List[Finding] = []
    raw_entries = data.get("suppressions", [])
    if not isinstance(raw_entries, list):
        return [], [Finding("META001", rel, 0,
                            "'suppressions' must be a list of objects")]
    for i, raw in enumerate(raw_entries):
        if not isinstance(raw, dict):
            findings.append(Finding(
                "META001", rel, 0,
                f"suppression #{i + 1} is not an object "
                f"({type(raw).__name__})"))
            continue
        missing = [k for k in ("rule", "file", "match", "reason")
                   if not isinstance(raw.get(k), str) or not raw.get(k).strip()]
        if missing:
            findings.append(Finding(
                "META001", rel, 0,
                f"suppression #{i + 1} ({raw.get('rule', '?')} in "
                f"{raw.get('file', '?')}) is missing a non-empty "
                f"{'/'.join(missing)} — every suppression must carry a "
                f"written reason"))
            continue
        entries.append(Suppression(rule=raw["rule"], file=raw["file"],
                                   match=raw["match"], reason=raw["reason"]))
    return entries, findings


def apply_baseline(findings: Iterable[Finding],
                   entries: Sequence[Suppression]) -> Tuple[
                       List[Finding], List[Finding], List[Suppression]]:
    """Split findings into ``(kept, suppressed, unused_entries)``."""
    kept: List[Finding] = []
    suppressed: List[Finding] = []
    used = [False] * len(entries)
    for finding in findings:
        haystack = (f"{finding.message} ({finding.symbol})"
                    if finding.symbol else finding.message)
        hit = None
        for i, entry in enumerate(entries):
            if (entry.rule == finding.rule and entry.file == finding.file
                    and entry.match in haystack):
                hit = i
                break
        if hit is None:
            kept.append(finding)
        else:
            used[hit] = True
            suppressed.append(finding)
    unused = [e for i, e in enumerate(entries) if not used[i]]
    return kept, suppressed, unused


# -- shared AST helpers (used by several checkers) ----------------------------


def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` -> ``["a", "b", "c"]``; None when the base isn't a Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def qualname_index(tree: ast.Module) -> Dict[str, ast.AST]:
    """Map ``name`` / ``Class.method`` -> def node for one module."""
    index: Dict[str, ast.AST] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            index[node.name] = node
        elif isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    index[f"{node.name}.{item.name}"] = item
    return index


def import_sources(tree: ast.Module) -> Dict[str, str]:
    """Local name -> dotted module it was imported from.

    ``from flink_ml_tpu.obs import trace`` maps ``trace`` to
    ``flink_ml_tpu.obs.trace``; ``from x.y import f`` maps ``f`` to
    ``x.y.f``; ``import a.b as c`` maps ``c`` to ``a.b``.
    """
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                out[alias.asname or alias.name.split(".")[0]] = alias.name
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            for alias in node.names:
                out[alias.asname or alias.name] = (
                    f"{node.module}.{alias.name}")
    return out
