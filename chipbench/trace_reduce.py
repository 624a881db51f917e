"""From a profiler trace (``.xplane.pb``) to numbers.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has every operation the chip ran
(containers such as ``%while`` enclose their bodies' operations, so times
nest) and whose line ``XLA Modules`` has one event per executed program,
named ``jit_<function>(<fingerprint>)``; and a plane ``/host:CPU`` whose lines
(threads) hold the harness's own ``chipbench.<name>`` spans, written with
``jax.profiler.TraceAnnotation``.  All of them are on one clock, nanoseconds
from the start of the trace.

``reduce`` cuts everything to the span ``chipbench.window`` and gives:

* ``busy_s`` — the union of the intervals in which an operation ran, averaged
  over the chips; ``window_s`` — the window's length;
* ``programs`` — device seconds and calls of each program;
* ``device_ops`` — operations by SELF time (an enclosing ``%while`` is charged
  only what its body does not cover), the ten largest;
* ``idle_gaps`` — the device's idle time by the innermost harness span that
  covers it (a gap is split where a span starts or ends), the ten largest.

It needs nothing but JAX (``jax.profiler.ProfileData``) and imports nothing of
the program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1][1] = hi
        else:
            merged.append([lo, hi])
    return merged


def _self_times(events):
    """{name: self nanoseconds} of nested events on one line."""
    out, stack = {}, []  # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            stack[-1][2] -= hi - lo
        stack.append([name, hi, hi - lo])
    close(float("inf"))
    return out


def short_name(op: str) -> str:
    """``%fusion.5 = f32[...] fusion(...)`` -> ``fusion.5``; program and span
    names pass unchanged."""
    if op.startswith("%"):
        return op[1:].split(" ", 1)[0]
    return op


def host_spans(data):
    """[(name, start_ns, end_ns)] of every ``chipbench.*`` span."""
    spans = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans += [e for e in _events(line) if e[0].startswith(SPAN_PREFIX)]
    return spans


def reduce(data) -> dict:
    spans = host_spans(data)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    device_planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not device_planes:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    if windows:
        w_lo, w_hi = windows[0][1], windows[0][2]
    else:  # a trace without the harness's window span: take all of it
        every = [e for p in device_planes for line in p.lines
                 if line.name == OPS_LINE for e in _events(line)]
        w_lo, w_hi = min(e[1] for e in every), max(e[2] for e in every)

    busy_ns, self_ns, programs, gaps = [], {}, {}, {}
    owners = _Owners(spans)
    for plane in device_planes:
        for line in plane.lines:
            if line.name == OPS_LINE:
                events = [(n, max(a, w_lo), min(b, w_hi))
                          for n, a, b in _events(line)
                          if min(b, w_hi) > max(a, w_lo)]
                merged = _union([(a, b) for _n, a, b in events])
                busy_ns.append(sum(b - a for a, b in merged))
                for name, ns in _self_times(events).items():
                    self_ns[name] = self_ns.get(name, 0.0) + ns
                edges = [w_lo] + [t for ab in merged for t in ab] + [w_hi]
                for lo, hi in zip(edges[0::2], edges[1::2]):
                    for owner, ns in owners.split(lo, hi):
                        gaps[owner] = gaps.get(owner, 0.0) + ns
            elif line.name == MODULES_LINE:
                for name, a, b in _events(line):
                    if min(b, w_hi) > max(a, w_lo):
                        p = programs.setdefault(name, {"seconds": 0.0,
                                                       "calls": 0})
                        p["seconds"] += (min(b, w_hi) - max(a, w_lo)) / 1e9
                        p["calls"] += 1
    n = len(busy_ns)
    return {
        "chips": n,
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "programs": {k: {"seconds": v["seconds"] / n, "calls": v["calls"] / n}
                     for k, v in programs.items()},
        "device_ops": _top({short_name(k): v / n / 1e9
                            for k, v in self_ns.items()}),
        "idle_gaps": _top({k: v / n / 1e9 for k, v in gaps.items()}),
    }


class _Owners:
    """Which harness span owns a moment: the shortest ``chipbench.*`` span
    that covers it, the window itself apart."""

    def __init__(self, spans):
        self.cuts = sorted({t for _n, a, b in spans for t in (a, b)})
        self.names = ["outside_any_span"]  # owner before the first cut
        for lo, hi in zip(self.cuts, self.cuts[1:] + [float("inf")]):
            mid = lo if hi == float("inf") else (lo + hi) / 2
            best, best_len = "outside_any_span", float("inf")
            for name, a, b in spans:
                if a <= mid < b and name != WINDOW_SPAN and b - a < best_len:
                    best, best_len = name[len(SPAN_PREFIX):], b - a
            self.names.append(best)

    def split(self, lo, hi):
        """[(owner, nanoseconds)] of the pieces of the gap ``lo..hi``, cut
        where a harness span starts or ends."""
        i = bisect.bisect_right(self.cuts, lo)
        out = []
        while lo < hi:
            nxt = self.cuts[i] if i < len(self.cuts) else float("inf")
            out.append((self.names[i], min(nxt, hi) - lo))
            lo, i = min(nxt, hi), i + 1
        return out


def _top(totals: dict):
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:TOP]]


def program_seconds(reduced: dict, prefix: str):
    """(device seconds, calls) summed over the programs whose name starts with
    ``prefix`` — ``(0.0, 0)`` when none ran."""
    seconds = calls = 0.0
    for name, p in reduced["programs"].items():
        if name.startswith(prefix):
            seconds += p["seconds"]
            calls += p["calls"]
    return seconds, calls
