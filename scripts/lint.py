#!/usr/bin/env python
"""Self-contained style gate (checkstyle analog, reference tools/maven/checkstyle.xml).

CI also runs ruff (see .github/workflows/ci.yml), but ruff is not available in
every build image; this script enforces the core rules with only the stdlib so
the gate runs everywhere the tests run (tests/test_lint.py executes it).

Checks, per Python file under the source roots:
  * syntax errors (ast.parse)
  * unused imports (module scope, including ``from x import y``)
  * duplicate imports of the same binding
  * bare ``except:`` clauses
  * trailing whitespace / tabs in indentation
  * missing final newline
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

ROOTS = ["flink_ml_tpu", "tests", "examples", "scripts", "__graft_entry__.py"]

# Names intentionally imported for re-export or side effects.
REEXPORT_FILES = {"__init__.py", "conftest.py"}


def _used_names(tree: ast.AST) -> set[str]:
    used: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # a.b.c -> record root name via the Name child (handled above)
            pass
    # String annotations / __all__ entries count as uses.
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            used.add(node.value)
    return used


def check_file(path: Path) -> list[str]:
    problems: list[str] = []
    text = path.read_text()
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as exc:
        return [f"{path}:{exc.lineno}: syntax error: {exc.msg}"]

    lines = text.splitlines()
    for i, line in enumerate(lines, 1):
        if line.rstrip() != line:
            problems.append(f"{path}:{i}: trailing whitespace")
        stripped = line.lstrip(" ")
        if stripped.startswith("\t"):
            problems.append(f"{path}:{i}: tab in indentation")
    if text and not text.endswith("\n"):
        problems.append(f"{path}:{len(lines)}: missing final newline")

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            problems.append(f"{path}:{node.lineno}: bare except")

    if path.name not in REEXPORT_FILES:
        used = _used_names(tree)
        seen: dict[str, int] = {}
        # Only module-level imports: function-local imports are often lazy on purpose.
        for node in tree.body:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                if isinstance(node, ast.ImportFrom) and node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = (alias.asname or alias.name).split(".")[0]
                    if bound in seen:
                        problems.append(
                            f"{path}:{node.lineno}: duplicate import of '{bound}' (first at line {seen[bound]})"
                        )
                    seen[bound] = node.lineno
                    if bound not in used and bound != "_":
                        problems.append(f"{path}:{node.lineno}: unused import '{bound}'")
    return problems


def main() -> int:
    repo = Path(__file__).resolve().parent.parent
    problems: list[str] = []
    for root in ROOTS:
        p = repo / root
        if p.is_file():
            problems.extend(check_file(p))
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                problems.extend(check_file(f))
    for line in problems:
        print(line)
    print(f"lint: {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
