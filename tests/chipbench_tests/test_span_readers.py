"""Tests of the per-layer readers that read the program's spans (PR 24):
each against a ``Context`` built by hand, with and without the span (or the
programs of a trace), giving the value reckoned by hand or ``None``.  No JAX.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
EMPTY = {"counters": {}, "timings": {}}
FITS = 4


def _snap(timings=None, counters=None):
    return {"counters": dict(counters or {}),
            "timings": {k: {"count": c, "total_s": s}
                        for k, (s, c) in (timings or {}).items()}}


def _ctx(setup=None, window=None, trace=None):
    """What set-up and the window each added to the registry (a reader sees
    only such differences), and the reduced trace."""
    return run.Context(snapshots={"setup": (EMPTY, setup or EMPTY),
                                  "window": (EMPTY, window or EMPTY)},
                       trace=trace)


def _metric(name):
    (metric,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    return metric


def _read(name, ctx):
    metric = _metric(name)
    return run.reader("layers", name)(ctx, metric)


#: one fit's spans in the window, seconds over FITS fits, and a first fit in
#: set-up that placed the table
WINDOW = _snap({
    "fit.wall": (0.520, FITS), "fit.prepare": (0.004, FITS),
    "slab_pool.lookup": (0.002, FITS), "train.place_params": (0.006, FITS),
    "train.dispatch": (0.001, FITS), "train.sync": (0.488, FITS),
    "train.demux": (0.0016, FITS), "train.health": (0.0004, FITS),
    "fit.finish": (0.001, FITS), "fit.report": (0.008, FITS)})
SETUP = _snap({"place.host_view": (2.5, 1), "place.h2d": (0.8, 1),
               "slab_pool.build": (3.4, 1), "slab_pool.lookup": (3.5, 1),
               "train.sync": (9.0, 9)},
              {"slab_pool.bytes_placed": 3.2e9})
TRACE = {"programs": {
    "jit_bundled(123)": {"seconds": 0.30, "calls": 3.0},
    "jit_bundled(456)": {"seconds": 0.18, "calls": 1.0},
    "jit_copy(7)": {"seconds": 1e-5, "calls": 8.0},
    "jit_broadcast_in_dim(8)": {"seconds": 1e-5, "calls": 8.0}}}
ONLY_FITS = {"programs": {"jit_bundled(123)": {"seconds": 0.4, "calls": 4.0}}}
NO_FITS = {"programs": {"jit_copy(7)": {"seconds": 1e-5, "calls": 8.0}}}

CASES = [
    # metric, context, the value reckoned by hand
    ("pool.lookup_ms", _ctx(SETUP, WINDOW), 1e3 * 0.002 / FITS),
    ("pool.lookup_ms", _ctx(SETUP), None),
    ("place.host_view_s", _ctx(SETUP, WINDOW), 2.5),
    ("place.host_view_s", _ctx(window=WINDOW), None),
    ("place.h2d_enqueue_s", _ctx(SETUP, WINDOW), 0.8),
    ("place.h2d_enqueue_s", _ctx(window=WINDOW), None),
    ("place.h2d_enqueue_s",
     _ctx(_snap({"place.h2d": (0.25, 2)})), 0.25),  # two leaves, one sum
    ("train.place_params_ms", _ctx(SETUP, WINDOW), 1e3 * 0.006 / FITS),
    ("train.place_params_ms", _ctx(SETUP), None),
    ("train.extra_programs_per_fit", _ctx(SETUP, WINDOW, TRACE), 16.0 / 4.0),
    ("train.extra_programs_per_fit", _ctx(SETUP, WINDOW, ONLY_FITS), 0.0),
    ("train.extra_programs_per_fit", _ctx(SETUP, WINDOW, NO_FITS), None),
    ("train.extra_programs_per_fit", _ctx(SETUP, WINDOW), None),
    ("fetch.readback_ms", _ctx(SETUP, WINDOW, TRACE),
     1e3 * (0.488 / FITS - 0.48 / 4.0)),
    ("fetch.readback_ms", _ctx(SETUP, WINDOW, NO_FITS), None),
    ("fetch.readback_ms", _ctx(SETUP, None, TRACE), None),  # no fit timed
    ("fetch.readback_ms", _ctx(SETUP, WINDOW), None),  # no trace
    ("fetch.demux_ms", _ctx(SETUP, WINDOW), 1e3 * (0.0016 + 0.0004) / FITS),
    ("fetch.demux_ms", _ctx(SETUP), None),
    ("fit.report_ms", _ctx(SETUP, WINDOW), 1e3 * 0.008 / FITS),
    ("fit.report_ms", _ctx(SETUP), None),
    ("fit.unattributed_ms", _ctx(SETUP, WINDOW),
     1e3 * (0.520 - (0.004 + 0.002 + 0.006 + 0.001 + 0.488 + 0.0016 + 0.0004
                     + 0.001 + 0.008)) / FITS),
    ("fit.unattributed_ms", _ctx(SETUP), None),
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_span_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    got = _read(name, ctx)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, rel=1e-9, abs=1e-12)


def test_every_span_reader_has_its_cases_and_its_entry():
    """The nine readers this file is about are the per-layer metrics with a
    reader file of their own beyond PR 23's eight; each has a case with a
    value and one without, and lists both sweeps."""
    new = {name for name, _c, _e in CASES}
    assert len(new) == 9
    assert len(BENCH["per_layer"]) >= 17
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in new}
    for name in new:
        metric = _metric(name)
        assert metric["layer"] in layers, name  # letter for letter
        assert metric["workloads"] == ["epsilon_lr.sweep", "mnist8m_lr.sweep"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "layers",
                                           name + ".py")), name
        values = [e for n, _c, e in CASES if n == name]
        assert None in values and any(v is not None for v in values), name
