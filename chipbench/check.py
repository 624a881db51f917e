"""The comparison that decides ``correct``.

Once the window has closed, every job's answer is held against the plain
reference that the job kind names, at the timed sizes, in two steps:

* the FIRST answer of every key that the window served (a grid point, a
  fold, a request: whatever the kind cycles) is compared with the reference's
  answer for the same data and parameters, by the kind's own ``gaps``;
* every LATER answer of that key has to equal the first bit for bit (the same
  data, the same parameters, the same program).

So every answer due in the window is judged.  Each number compared has a
limit of its own, read from ``chipbench/limits/<cell>.json``; how each limit
was set (the program's readings over a dozen seeds, the control's and the
planted faults' readings) is in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np

#: the numbers the harness itself compares in every cell, whatever the kind:
#: each has a limit in every cell's limits file, beside the kind's own
HARNESS_NUMBERS = ("repeat_gap", "failed_jobs", "compiles_in_window",
                   "hidden_failures")


def repeat_gap(first, other) -> float:
    """Largest absolute difference between two answers of one key."""
    worst = 0.0
    for name, a in first.items():
        b = other.get(name)
        a, b = np.atleast_1d(np.asarray(a, np.float64)), \
            np.atleast_1d(np.asarray(b, np.float64))
        if a.shape != b.shape:
            return float("inf")
        diff = np.abs(a - b)
        worst = max(worst, float(np.max(np.where(np.isnan(diff), np.inf, diff))))
    return worst


def worst(per_key: list) -> dict:
    names = sorted({n for g in per_key for n in g})
    return {n: max(g[n] for g in per_key if n in g) for n in names}


def compare(generator, jobs, counts: dict) -> dict:
    """{name: value} of every number compared, the worst over the window.

    ``counts`` holds the exact counts taken by the harness (failed jobs,
    compiles inside the window, the must-be-zero counters)."""
    firsts, repeat = {}, 0.0
    for job in jobs:
        if "answer" not in job:
            continue
        first = firsts.setdefault(job["key"], job["answer"])
        if first is not job["answer"]:
            repeat = max(repeat, repeat_gap(first, job["answer"]))
    values = {"answers_checked": float(len(firsts))}
    if firsts:
        refs = generator.references(sorted(firsts))
        values.update(worst([generator.gaps(firsts[k], refs[k])
                             for k in sorted(firsts)]))
    values["repeat_gap"] = repeat
    values.update({k: float(v) for k, v in counts.items()})
    return values


def verdict(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every number within its limit,
    every limit's number present, and at least one answer checked."""
    compared, ok = {}, values.get("answers_checked", 0) >= 1
    for name, entry in limits.items():
        if name.startswith("_"):  # a note on the file, not a number
            continue
        limit = float(entry["limit"])
        value = values.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        compared[name] = {"value": value, "limit": limit}
    return ok, compared
