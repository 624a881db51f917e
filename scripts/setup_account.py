"""Where a cell's set-up goes: one harness run with the compile events read out.

    chiprun -- python scripts/setup_account.py --workload epsilon_lr.sweep \
        --seed 3500000001 [--seconds 10] [--trace 1] [--cold] [--out DIR]

Runs ``chipbench.run`` in this process as the driver would (TPU only), with
the per-layer entries that wait in
``tests/chipbench_tests/test_setup_compile_readers.py`` (or what waits in
every file named with ``--waiting``, e.g. ``--waiting test_dp4_cell.py``: a
whole cell there, and two entries) appended to the benchmark IN MEMORY
(``scripts/waiting.py``; ``BENCHMARK.json`` on disk is not touched), and reads
what the harness does not print: the registry's ``compile.*`` timings and
counters over set-up and over the window (the harness's own three snapshots),
the same seconds by causing span (``compile.under/<span>``) and the programs
by name from the flight ring.  ``--cold`` points ``JAX_COMPILATION_CACHE_DIR``
at an empty directory under the checkout before JAX is imported; without it
the machine's compile cache is taken as it stands.  Prints one line,
``setup_account: {...}``, and writes it to ``<out>/<cell>-<warm|cold>-<seed>.json``
(default ``chiprun_out/setup_account``).  Not a benchmark: the numbers go to
``PERF.md`` section 5 under the builder's name.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import resource
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("compile.trace", "compile.lower", "compile.backend",
          "compile.cache_read")
EVENT_KEYS = ("program", "span", "trace_s", "lower_s", "backend_s", "cache",
              "cache_read_s")


#: the test files whose waiting entries a run appends, by default
WAITING_IN = ("test_setup_compile_readers.py",)


def _delta(before: dict, after: dict) -> dict:
    """What a phase added under every ``compile.*`` name."""
    out = {}
    for name, t in after["timings"].items():
        if not name.startswith("compile."):
            continue
        b = before["timings"].get(name, {"count": 0, "total_s": 0.0})
        if t["count"] - b["count"]:
            out[name] = {"count": t["count"] - b["count"],
                         "seconds": t["total_s"] - b["total_s"]}
    for name, v in after["counters"].items():
        if name.startswith("compile.") and v - before["counters"].get(name, 0):
            out[name] = v - before["counters"].get(name, 0)
    return out


def _timings(before: dict, after: dict) -> dict:
    """What a phase added under every timing that is no ``compile.*``."""
    out = {}
    for name, t in sorted(after["timings"].items()):
        b = before["timings"].get(name, {"count": 0, "total_s": 0.0})
        if not name.startswith("compile.") and t["count"] - b["count"]:
            out[name] = {"count": t["count"] - b["count"],
                         "seconds": t["total_s"] - b["total_s"]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python scripts/setup_account.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=1)
    parser.add_argument("--cold", action="store_true")
    parser.add_argument("--waiting", action="append", default=None,
                        help="a file of tests/chipbench_tests whose waiting "
                             "entries to append (default: PR 35's two)")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "setup_account"))
    args = parser.parse_args(argv)

    machine_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if args.cold:
        empty = os.path.join(ROOT, ".scratch", "cold_cache",
                             f"{args.workload}-{args.seed}")
        shutil.rmtree(empty, ignore_errors=True)
        os.makedirs(empty)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = empty

    sys.path.insert(0, ROOT)
    from chipbench import program, run

    from waiting import overlay  # scripts/waiting.py, beside this file

    overlay(args.waiting or WAITING_IN)
    marks = []  # the harness's snapshots: start, set-up's end, window's end
    snapshot = program.snapshot

    def marked():
        snap = snapshot()
        marks.append((time.monotonic(), snap))
        return snap

    program.snapshot = marked
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds),
                       "--trace", str(args.trace)])
    lines = printed.getvalue().splitlines()
    record = json.loads(lines[-2].split("chipbench: record ", 1)[1])
    result = json.loads(lines[-1])

    from flink_ml_tpu.obs import flight
    from flink_ml_tpu.utils import compile_cache

    (_t0, start), (t_setup, setup), (t_window, window) = marks
    phases = {"setup": [], "window": [], "check": []}
    for e in flight.events():
        if e["kind"] == "compile":
            phase = ("setup" if e["mono_s"] <= t_setup else
                     "window" if e["mono_s"] <= t_window else "check")
            phases[phase].append({k: e[k] for k in EVENT_KEYS})
    in_setup = _delta(start, setup)
    stages = {s: in_setup.get(s, {"seconds": 0.0})["seconds"] for s in STAGES}
    compile_s = sum(stages[s] for s in STAGES[:3])
    under = {k[len("compile.under/"):]: v["seconds"]
             for k, v in sorted(in_setup.items())
             if k.startswith("compile.under/")}
    account = {
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "cache": "cold" if args.cold else "as it stands",
        "machine_cache_dir": machine_cache,
        "cache_dir": compile_cache.cache_dir(),
        "correct": result["correct"], "compared": result["compared"],
        "setup_s": record["setup_s"],
        "harness_spans": {k: v for k, v in record["spans"].items()
                          if k.startswith("setup.")},
        "compiled_in_setup": record["compiled_in_setup"],
        "compile_s": compile_s, "stages": stages,
        "compiled_truly_s": stages["compile.backend"]
        - stages["compile.cache_read"],
        "cache_hits": in_setup.get("compile.cache_hits", 0),
        "cache_misses": in_setup.get("compile.cache_misses", 0),
        "programs": in_setup.get("compile.backend", {"count": 0})["count"],
        "under": under,
        "under_minus_stages_s": sum(under.values()) - compile_s,
        "left_over_s": record["setup_s"] - compile_s,
        "setup_programs": phases["setup"],
        # all of it has to be empty: nothing compiles in a warm window
        "window_compile_deltas": _delta(setup, window),
        "window_programs": phases["window"],
        "check_programs": len(phases["check"]),  # the reference's own
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "window_fits": record["jobs_done"], "job_s": record["job_s"],
        "device": result["device"], "breakdown": result.get("breakdown"),
        "check_s": record["check_s"],
        # set-up by the program's own spans (pack, host view, placement,
        # dispatch, sync ...): seconds and observations of each
        "setup_spans": _timings(start, setup),
        # the process's largest resident set, KiB on Linux: the host's side
        # of a table that fills a host's chips
        "host_peak_rss_bytes": 1024 * resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss,
    }
    os.makedirs(args.out, exist_ok=True)
    name = (f"{args.workload}-{'cold' if args.cold else 'warm'}-"
            f"{args.seed}.json")
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(account, f, indent=1)
    print("setup_account: " + json.dumps(account), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
