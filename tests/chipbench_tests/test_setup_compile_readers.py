"""Tests of the two per-layer readers PR 35 adds for set-up's compiles
(``setup.compile_s``, ``setup.cache_hit_share``): each against a ``Context``
built by hand, giving the value reckoned by hand or ``None``, over set-up's
snapshots alone; and their entries, written out and waiting.  No JAX.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = ["epsilon_lr.sweep", "mnist8m_lr.sweep", "criteo_sparse_lr.sweep",
         "mnist8m_kmeans.restarts", "url_ragged_lr.sweep"]

# The per-layer entries these readers are for.  They WAIT outside
# BENCHMARK.json, as nine others do since PR 27: test_onepass_reader.py holds
# train.onepass_share to be the last entry of per_layer, the driver takes an
# entry put ahead of it for a change to it, and both files are a `benchmark`
# PR's to edit.  That PR loosens the assertion and appends these two as they
# stand here.
_ENTRY = {"workloads": CELLS, "moves": "setup_s",
          "layer": "fused train program (lib/common.py _build_fused_train_fn)"}
WAITING = {
    "setup.compile_s": dict(_ENTRY, name="setup.compile_s", unit="s",
                            better="lower", source="program_span"),
    "setup.cache_hit_share": dict(_ENTRY, name="setup.cache_hit_share",
                                  unit="%", better="higher",
                                  source="program_counter"),
}

EMPTY = {"counters": {}, "timings": {}}


def _snap(timings=None, counters=None):
    return {"counters": dict(counters or {}),
            "timings": {k: {"count": c, "total_s": s}
                        for k, (s, c) in (timings or {}).items()}}


def _ctx(setup=None, window=None, before=None):
    """The registry at the start of set-up (``before``), at its end and at
    the window's end: a reader sees only the differences."""
    before, setup = before or EMPTY, setup or before or EMPTY
    return run.Context(snapshots={"setup": (before, setup),
                                  "window": (setup, window or setup)})


#: a warm cache: thirteen programs traced, lowered and read
WARM = _snap({"compile.trace": (0.61, 13), "compile.lower": (0.42, 13),
              "compile.backend": (0.37, 13), "compile.cache_read": (0.31, 13),
              "compile.under/train.dispatch": (0.9, 24),
              "compile.under/fit.prepare": (0.5, 15),
              "train.dispatch": (1.2, 9)},
             {"compile.cache_hits": 13})
#: an empty one: every program compiled and written
COLD = _snap({"compile.trace": (0.61, 13), "compile.lower": (0.42, 13),
              "compile.backend": (7.25, 13)},
             {"compile.cache_misses": 13})
#: a change of code: twelve read, one compiled
ONE_NEW = _snap({"compile.trace": (0.5, 13), "compile.lower": (0.25, 13),
                 "compile.backend": (1.0, 13),
                 "compile.cache_read": (0.25, 12)},
                {"compile.cache_hits": 12, "compile.cache_misses": 4})
#: the cache off: the stages, and neither counter
OFF = _snap({"compile.trace": (0.5, 2), "compile.lower": (0.25, 2),
             "compile.backend": (2.0, 2)})
#: a program without the listeners (the parent): its spans, nothing else
PARENT = _snap({"train.dispatch": (7.9, 9), "fit.prepare": (0.8, 9)},
               {"train.compile_runs": 8})
#: a compile INSIDE the window (what ``correct`` forbids): set-up's
#: snapshots stay as they were
LEAKED = _snap({"compile.trace": (9.61, 14), "compile.lower": (9.42, 14),
                "compile.backend": (9.37, 14),
                "compile.cache_read": (0.31, 13)},
               {"compile.cache_hits": 13, "compile.cache_misses": 1})
#: what the process compiled BEFORE set-up's first snapshot does not count
EARLIER = _snap({"compile.trace": (0.11, 3), "compile.lower": (0.02, 3),
                 "compile.backend": (0.07, 3)}, {"compile.cache_hits": 3})
LATER = _snap({"compile.trace": (0.72, 16), "compile.lower": (0.44, 16),
               "compile.backend": (0.44, 16)},
              {"compile.cache_hits": 15, "compile.cache_misses": 1})

CASES = [
    # metric, context, the value reckoned by hand
    ("setup.compile_s", _ctx(WARM), 0.61 + 0.42 + 0.37),
    ("setup.compile_s", _ctx(COLD), 0.61 + 0.42 + 7.25),
    ("setup.compile_s", _ctx(ONE_NEW), 1.75),
    ("setup.compile_s", _ctx(OFF), 2.75),
    ("setup.compile_s", _ctx(PARENT), None),  # no such timing: nothing
    ("setup.compile_s", _ctx(), None),
    ("setup.compile_s", _ctx(WARM, LEAKED), 0.61 + 0.42 + 0.37),
    ("setup.compile_s", _ctx(PARENT, LEAKED), None),  # the window's alone
    ("setup.compile_s", _ctx(LATER, before=EARLIER),
     (0.72 - 0.11) + (0.44 - 0.02) + (0.44 - 0.07)),
    ("setup.compile_s", _ctx(EARLIER, before=EARLIER), None),
    ("setup.cache_hit_share", _ctx(WARM), 100.0),
    ("setup.cache_hit_share", _ctx(COLD), 0.0),  # asked, and nothing served
    ("setup.cache_hit_share", _ctx(ONE_NEW), 75.0),
    ("setup.cache_hit_share", _ctx(OFF), None),  # never asked
    ("setup.cache_hit_share", _ctx(PARENT), None),
    ("setup.cache_hit_share", _ctx(), None),
    ("setup.cache_hit_share", _ctx(WARM, LEAKED), 100.0),
    ("setup.cache_hit_share", _ctx(PARENT, LEAKED), None),
    ("setup.cache_hit_share", _ctx(LATER, before=EARLIER),
     100.0 * 12 / 13),
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_compile_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    metric = WAITING[name]
    assert ctx.phase(metric) == "setup"
    got = run.reader("layers", name)(ctx, metric)
    if expected is None:
        assert got is None  # never 0
    else:
        assert got == pytest.approx(expected, rel=1e-12, abs=1e-12)


def test_the_seconds_by_span_sum_to_what_the_reader_gives():
    # compile.under/* is the same seconds again, by the span that caused
    # them: the reader must not add them in
    under = sum(s for k, (s, _c) in {
        "compile.under/train.dispatch": (0.9, 24),
        "compile.under/fit.prepare": (0.5, 15)}.items())
    got = run.reader("layers", "setup.compile_s")(
        _ctx(WARM), WAITING["setup.compile_s"])
    assert got == pytest.approx(under, rel=1e-12)


@pytest.mark.parametrize("name", sorted(WAITING))
def test_an_entry_that_waits_is_ready_to_move_over(name):
    metric = WAITING[name]
    assert sorted(metric) == ["better", "layer", "moves", "name", "source",
                              "unit", "workloads"]
    assert metric["name"] == name
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # not yet
    # every cell the benchmark has when this was written, by name, each known
    assert metric["workloads"] == CELLS
    assert set(CELLS) <= {c["name"] for c in BENCH["workloads"]}
    # a layer the benchmark already names, letter for letter
    assert metric["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    # every cell reports the end-to-end metric the entry should move
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert all(c in moved.get("workloads", CELLS) for c in CELLS)
    assert metric["source"] in ("program_span", "program_counter")
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_a_cell_with_the_entries_appended_reads_them_over_set_up():
    # what the builder's working copy did on the chip: the two entries at
    # the end of per_layer, nothing else touched
    bench = dict(BENCH, per_layer=BENCH["per_layer"] + list(WAITING.values()))
    for cell in CELLS:
        names = [m["name"] for m in run.metrics_of(
            bench, run.find_cell(bench, cell), "per_layer")]
        assert names[-2:] == list(WAITING), cell
