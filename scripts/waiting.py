"""A harness module run with the entries that WAIT appended to the benchmark
IN MEMORY (``BENCHMARK.json`` on disk is not touched):

    python scripts/waiting.py --waiting test_dp4_cell.py -- \\
        chipbench.run --workload mnist8m_lr_dp4.sweep --seed 3700000001 \\
        --seconds 30 --trace 1

Standing tests pin the end of ``per_layer`` and some ``workloads`` lists
(``PERF.md`` section 7); the per-layer entries a PR cannot append therefore
wait, written out as ``WAITING``, in its test file under
``tests/chipbench_tests/``.
:func:`overlay` makes ``chipbench.run.load_json`` hand them out as part of
``BENCHMARK.json``; ``scripts/setup_account.py`` uses it too.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def overlay(files) -> None:
    """Append what waits in ``tests/chipbench_tests/<file>``, of every file
    named, to the benchmark as ``chipbench.run.load_json`` reads it."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import run

    modules = []
    for name in files:
        path = os.path.join(ROOT, "tests", "chipbench_tests", name)
        spec = importlib.util.spec_from_file_location("_waiting", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        modules.append(module)
    load_json = run.load_json

    def with_what_waits(*parts):
        loaded = load_json(*parts)
        if parts[-1] == "BENCHMARK.json":
            for module in modules:
                loaded["per_layer"] = loaded["per_layer"] + list(
                    getattr(module, "WAITING", {}).values())
        return loaded

    run.load_json = with_what_waits


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python scripts/waiting.py")
    parser.add_argument("--waiting", action="append", required=True,
                        help="a file of tests/chipbench_tests whose waiting "
                             "entries to append")
    parser.add_argument("module", help="chipbench.run or chipbench.limits")
    parser.add_argument("rest", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    overlay(args.waiting)
    return importlib.import_module(args.module).main(args.rest)


if __name__ == "__main__":
    sys.exit(main())
