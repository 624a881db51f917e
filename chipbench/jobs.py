"""The one general job generator.  A traffic mix is a data file,
``chipbench/traffic/<mix>.json``; this module turns it into a window of jobs.

The mix names its job kind (``"job"``), how many callers send jobs
(``"clients"``) and how they send them (``"loop"``: ``closed``, each caller
waits for its job before it sends the next, with no think time).  The kind is
code found by that name, ``chipbench/kinds/<kind>.py``: it makes the data from
the seed and turns job number ``i`` into one call of the system under test
(see ``chipbench/kinds/__init__.py``).  Everything else in the mix is the
kind's to read.  Every seed gives the same set of jobs, in another order.
"""

from __future__ import annotations

import contextlib
import importlib.util
import itertools
import os
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS_DIR = os.path.join(HERE, "kinds")


class Spans:
    """Host spans of the harness: kept in memory for the per-layer readers and
    written into the profiler's trace (``chipbench.<name>``) so that the
    device's idle gaps can be attributed to what the host was doing."""

    def __init__(self):
        self.records = []  # (name, start, end) on time.perf_counter()

    @contextlib.contextmanager
    def span(self, name):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench." + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name, since=0.0):
        return sum(e - s for n, s, e in self.records
                   if n == name and s >= since)


def order(n, seed):
    """The numbers 0..n-1 in an order drawn from the seed."""
    return [int(i) for i in np.random.default_rng(
        np.random.SeedSequence([int(seed), n, 7])).permutation(n)]


def kind(name: str):
    """The module of a job kind, ``kinds/<name>.py``, found by name."""
    path = os.path.join(KINDS_DIR, f"{name}.py")
    if not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(KINDS_DIR)
                       if f.endswith(".py") and not f.startswith("_"))
        raise SystemExit(f"chipbench: no job kind {name!r} "
                         f"(chipbench/kinds/{name}.py); known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.kinds.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def clients_of(mix: dict) -> int:
    """How many callers the mix has; a way of sending that the window does
    not implement is refused."""
    if mix["loop"] != "closed":
        raise SystemExit(f"chipbench: loop {mix['loop']!r} is not "
                         f"implemented (known: 'closed')")
    clients = int(mix["clients"])
    if clients < 1:
        raise SystemExit("chipbench: a mix needs at least 1 client")
    return clients


def make(config, mix, seed, spans):
    """The mix's generator over the configuration's data, from the seed."""
    return kind(mix["job"]).make(config, mix, seed, spans)


def run_window(generator, seconds, clients=1):
    """Closed loop: each of ``clients`` callers starts jobs until ``seconds``
    have passed and lets its last one finish.  Returns (jobs, start, end); a
    job is a dict with ``key``, ``rows``, ``start``, ``end``, ``answer`` — or
    ``error``.  One caller runs on the calling thread."""
    jobs, numbers = [], itertools.count()
    start = time.perf_counter()
    deadline = start + float(seconds)

    def caller():
        while True:
            t0 = time.perf_counter()
            if t0 >= deadline:
                return
            try:
                key, rows, answer = generator.job(next(numbers))
                jobs.append({"key": key, "rows": rows, "start": t0,
                             "end": time.perf_counter(), "answer": answer})
            except Exception as exc:  # noqa: BLE001 - a failed job is counted
                jobs.append({"key": None, "rows": 0, "start": t0,
                             "end": time.perf_counter(), "error": repr(exc)})

    if clients == 1:
        caller()
    else:
        threads = [threading.Thread(target=caller) for _ in range(clients)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        jobs.sort(key=lambda job: job["start"])
    return jobs, start, time.perf_counter()
