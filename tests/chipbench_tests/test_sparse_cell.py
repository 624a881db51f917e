"""Tests of what PR 27 adds to the yardstick for the sparse cell
(``criteo_sparse_lr.sweep``): the seeded click-log generator, the plain sparse
reference against NumPy in float64, the sizing arithmetic of
``work_sparse.py``, the three new readers on a ``Context`` built by hand, and
the cell's entries in ``BENCHMARK.json``.  CPU only, tiny sizes.  The
parametrised tests of ``test_chipbench.py`` pick the cell itself up from
``BENCHMARK.json`` (files found, limits named, rehearsal ``correct``, faults
and control not).
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data_sparse, jobs, references, run  # noqa: E402
from chipbench import work_sparse  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELL = "criteo_sparse_lr.sweep"
CONFIG = run.load_json(BENCH_DIR, "configs", "criteo_sparse_lr.json")
DATA = CONFIG["data"]
SEED = 2**31 + 12345


# -- the generator -------------------------------------------------------------


def test_sparse_data_is_a_function_of_the_seed_alone(monkeypatch):
    a = data_sparse.make_rows(DATA, 3000, 1_000_000, SEED)
    b = data_sparse.make_rows(DATA, 3000, 1_000_000, SEED)
    c = data_sparse.make_rows(DATA, 3000, 1_000_000, SEED + 1)
    monkeypatch.setattr(data_sparse, "THREADS", 1)  # whatever the cores
    d = data_sparse.make_rows(DATA, 3000, 1_000_000, SEED)
    for x, y, z in zip(a, b, d):
        assert np.array_equal(x, y) and np.array_equal(x, z)
    assert not np.array_equal(a[1], c[1])
    with pytest.raises(SystemExit):
        data_sparse.make_rows(DATA, 30, 100, 1, dtype="bfloat16")


def test_sparse_rows_keep_the_sources_shape():
    n, dim = 20_000, 1_000_000
    indptr, indices, values, y = data_sparse.make_rows(DATA, n, dim, SEED)
    assert data_sparse.entries_per_row(DATA) == 39 == CONFIG["nnz_per_row"]
    assert len(DATA["categorical_cardinalities"]) == 26
    assert indptr.dtype == np.int64 and np.array_equal(
        indptr, np.arange(n + 1) * 39)  # 39 entries a row, one a field
    assert indices.dtype == np.int32 and indices.shape == (n * 39,)
    assert indices.min() >= 0 and indices.max() < dim
    rows = indices.reshape(n, 39)
    assert (np.diff(rows, axis=1) >= 0).all()  # ascending, as LIBSVM's
    assert values.dtype == np.float32
    assert np.all(values == np.float32(1 / np.sqrt(39)))  # unit-length rows
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert abs(y.mean() - 0.256) < 1e-3
    # skew: the most frequent slot holds far more than its even share
    counts = np.bincount(indices, minlength=dim)
    assert counts.max() / indices.size > 100 * 39 / dim
    # and the tail is wide: most distinct slots are seen once or twice
    seen = counts[counts > 0]
    assert len(seen) > 20_000 and np.median(seen) <= 2


def test_the_hash_is_fixed_and_fills_its_range():
    category = np.arange(200_000, dtype=np.int64)
    a = data_sparse.hash_slots(3, category, 1000)
    assert a.dtype == np.int32 and a.min() == 0 and a.max() == 999
    assert np.array_equal(a, data_sparse.hash_slots(3, category, 1000))
    assert not np.array_equal(a, data_sparse.hash_slots(4, category, 1000))
    even = np.bincount(a, minlength=1000)
    assert even.min() > 100 and even.max() < 300  # 200 a slot, evenly
    # pinned: the same bytes in every later PR
    assert data_sparse.hash_slots(0, np.array([0, 1, 2]), 10**6).tolist() \
        == [611519, 390724, 956704]
    assert data_sparse.hash_slots(38, np.array([10131226]), 10**6).tolist() \
        == [950815]
    _p, indices, _v, y = data_sparse.make_rows(DATA, 3000, 10**6, SEED)
    assert indices[:5].tolist() == [67952, 79566, 87544, 90353, 101665]
    assert int(y.sum()) == 767


# -- the reference -------------------------------------------------------------


def _numpy_fit(idx, vals, y, dim, batch, lr, reg, epochs):
    ww, bb, losses = np.zeros(dim), 0.0, []
    vals = vals.astype(np.float64)
    for _ in range(epochs):
        tot = 0.0
        for lo in range(0, len(y), batch):
            ib, vb, yb = idx[lo:lo + batch], vals[lo:lo + batch], \
                y[lo:lo + batch]
            z = (vb * ww[ib]).sum(-1) + bb
            tot += np.sum(np.logaddexp(0, z) - yb * z)
            err = 1 / (1 + np.exp(-z)) - yb
            g = np.zeros(dim)
            np.add.at(g, ib.reshape(-1), (err[:, None] * vb).reshape(-1))
            ww = ww - lr * (g / len(yb) + reg * ww)
            bb = bb - lr * err.mean()
        losses.append(tot / len(y))
    return ww, bb, losses


def test_sparse_reference_matches_plain_numpy_in_float64():
    rng = np.random.default_rng(3)
    n, width, dim = 3000, 9, 200
    idx = np.sort(rng.integers(0, dim, (n, width), dtype=np.int32), axis=1)
    assert (np.diff(idx, axis=1) == 0).any()  # an index twice in a row
    vals = (rng.random((n, width)) + 0.5).astype(np.float32)
    y = (rng.random(n) < 0.4).astype(np.float32)
    reference = references.load("sparse_glm_sgd")
    got = reference.Table(idx, vals, y, dim, 512).fit(0.2, 1e-3, 2)
    ww, bb, losses = _numpy_fit(idx, vals, y, dim, 512, 0.2, 1e-3, 2)
    assert np.linalg.norm(got["coef"] - ww) / np.linalg.norm(ww) < 1e-5
    assert abs(got["intercept"] - bb) < 1e-6
    assert np.allclose(got["losses"], losses, rtol=1e-5)
    assert reference.gaps(got, {"coef": ww, "intercept": bb,
                                "losses": np.asarray(losses)})["coef_gap"] \
        < 1e-5
    # the control and the faults are other answers
    for variant in reference.CONTROLS.values():
        bad = reference.Table(idx, vals, y, dim, 512).fit(0.2, 1e-3, 2,
                                                          **variant)
        assert reference.gaps(bad, got)["coef_gap"] > 1e-4, variant


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("withIntercept", False)])
def test_the_sparse_reference_refuses_what_it_does_not_compute(key, value):
    reference = references.load(CONFIG["reference"])
    assert reference.precision_of(CONFIG) == "f32"
    assert reference.NUMBERS == references.load("glm_sgd").NUMBERS
    with pytest.raises(SystemExit):
        reference.precision_of(dict(CONFIG, **{key: value}))


# -- the configuration and the work --------------------------------------------


def test_sparse_work_matches_the_sizing_arithmetic_at_the_published_shape():
    # the quarter ISSUE 27 reckoned first (a fit of it lasted 13.3 s) ...
    work = work_sparse.fit_work(dict(CONFIG, rows=45_840_617 // 4))
    assert work["rows"] == 11_460_154
    assert work["steps_per_epoch"] == 350 and work["epochs"] == 1
    assert work["nnz_pad"] == 32768 * 39 == 1_277_952
    assert work["nnz_pad"] % work_sparse.PAD_MULTIPLE == 0
    assert work["entries_per_epoch"] == 11_460_154 * 39 == 446_946_006
    ints = 350 * 2 * 1_277_952 * 4
    floats = 350 * (1_277_952 + 65_536) * 4
    assert work["resident_bytes"] == ints + floats
    assert (round(ints / 1e9, 2), round(floats / 1e9, 2)) == (3.58, 1.88)
    assert round(work["resident_bytes"] / 1e9, 2) == 5.46  # 32% of 16.91 GB
    # an epoch reads every entry (index and value) and every label once
    assert work["bytes"] == 446_946_006 * 8 + 11_460_154 * 4
    assert work["flops"] == 4 * 446_946_006
    # ... and the fallback it states, which the cell runs: a v5e-8 pod's
    # share
    half = work_sparse.fit_work(CONFIG)
    assert CONFIG["rows"] == 45_840_617 // 8 == 5_730_077
    assert half["steps_per_epoch"] == 175 and half["nnz_pad"] == 1_277_952
    assert half["entries_per_epoch"] == 223_473_003
    assert half["resident_bytes"] == (ints + floats) // 2
    assert round(half["resident_bytes"] / 1e9, 2) == 2.73  # 16%
    assert half["bytes"] == 223_473_003 * 8 + 5_730_077 * 4


def test_the_configuration_states_the_deployment():
    mnist = run.load_json(BENCH_DIR, "configs", "mnist8m_lr.json")
    assert CONFIG["guarantees"] == mnist["guarantees"]  # word for word
    assert CONFIG["architecture"] is None  # a deployment, no catalog model
    assert CONFIG["published"] == {"rows": 45840617, "features": 1000000,
                                   "nnz_per_row": 39}
    assert CONFIG["reduced"] == ["rows"] and CONFIG["maxIter"] == 1
    assert CONFIG["numFeatures"] == CONFIG["features"] == 1_000_000
    assert CONFIG["env"] == {}  # no FMT_* switch: the default route
    mix = run.load_json(BENCH_DIR, "traffic", "sweep_sparse.json")
    assert mix["job"] == "refit_sparse" and jobs.clients_of(mix) == 1
    assert len(mix["grid"]["learningRate"]) * len(mix["grid"]["reg"]) == 4
    with pytest.raises(SystemExit):
        jobs.make(dict(CONFIG, **CONFIG["rehearsal"]),
                  dict(mix, input="standardised"), SEED, jobs.Spans())


def test_the_half_batch_fault_keeps_the_first_half_of_every_batch():
    from chipbench import program_sparse

    small = dict(CONFIG, **CONFIG["rehearsal"])
    indptr, indices, values, y = data_sparse.make_rows(
        DATA, 1100, small["numFeatures"], SEED)
    faults = jobs.kind("refit_sparse").planted_faults(small)
    assert sorted(faults) == ["answer_altered", "half_batch",
                              "state_unchanged"]
    (_mod, name, half_table), (_m, _n, half_logreg) = faults["half_batch"]
    assert name == "table" and _mod is program_sparse
    table = half_table(small["numFeatures"], indptr, indices, values, y)
    keep = (np.arange(1100) % 512) < 256
    column = table.col("features")
    assert len(column) == keep.sum() == 256 + 256 + 76
    assert np.array_equal(column.indices,
                          indices.reshape(1100, 39)[keep].reshape(-1))
    assert np.array_equal(np.asarray(table.col("label")), y[keep])
    assert half_logreg(small, 0.1, 0.0).get_global_batch_size() == 256


# -- the three new readers, on a Context built by hand -------------------------

# The per-layer entries these readers are for.  They WAIT outside
# BENCHMARK.json: test_onepass_reader.py holds train.onepass_share to be the
# last entry of per_layer, the driver takes an entry put ahead of it for a
# change to it, and both files are a `benchmark` PR's to edit.  That PR
# loosens the assertion and appends these three as they stand here.
_ENTRY = {"workloads": ["criteo_sparse_lr.sweep"], "moves": "fit_rows_per_s"}
WAITING = {
    "pack_sparse.host_s": dict(
        _ENTRY, name="pack_sparse.host_s", unit="s", better="lower",
        source="program_span", moves="setup_s",
        layer="ingest and pack (table/, native/, lib/common.py "
              "pack_minibatches)"),
    "sparse.entries_per_s": dict(
        _ENTRY, name="sparse.entries_per_s", unit="M/s", better="higher",
        source="device_trace", layer="kernels (XLA programs on the chip)"),
    "sparse.pad_share": dict(
        _ENTRY, name="sparse.pad_share", unit="%", better="lower",
        source="program_counter",
        layer="fused train program (lib/common.py _build_fused_train_fn)"),
}

EMPTY = {"counters": {}, "timings": {}}
TRACE = {"programs": {"jit_bundled(1)": {"seconds": 6.0, "calls": 2.0},
                      "jit_bundled(2)": {"seconds": 2.0, "calls": 1.0},
                      "jit_copy(7)": {"seconds": 1e-5, "calls": 8.0}}}
NO_FITS = {"programs": {"jit_copy(7)": {"seconds": 1e-5, "calls": 8.0}}}
ENTRIES, SLOTS = 223_473_003, 175 * 1_277_952


def _snap(timings=None, counters=None):
    return {"counters": dict(counters or {}),
            "timings": {k: {"count": c, "total_s": s}
                        for k, (s, c) in (timings or {}).items()}}


def _ctx(setup=None, window=None, trace=None):
    return run.Context(snapshots={"setup": (EMPTY, setup or EMPTY),
                                  "window": (EMPTY, window or EMPTY)},
                       trace=trace)


FITS = _snap(counters={"train.sparse_fits": 3,
                       "train.sparse_entries": 3 * ENTRIES,
                       "train.sparse_slots": 3 * SLOTS})
PACKED = _snap({"phase.pack_sparse": (6.5, 1),
                "phase.pack_sparse/pack_csr": (6.4, 1)})
CASES = [
    ("pack_sparse.host_s", _ctx(PACKED, FITS), 6.5),
    ("pack_sparse.host_s", _ctx(window=PACKED), None),  # not in set-up
    ("pack_sparse.host_s", _ctx(_snap({"phase.pack_dense": (3.0, 1)})), None),
    ("sparse.entries_per_s", _ctx(PACKED, FITS, TRACE),
     3 * ENTRIES / 8.0 / 1e6),
    ("sparse.entries_per_s", _ctx(PACKED, FITS), None),  # no trace
    ("sparse.entries_per_s", _ctx(PACKED, FITS, NO_FITS), None),
    ("sparse.entries_per_s", _ctx(PACKED, None, TRACE), None),  # the parent
    ("sparse.pad_share", _ctx(PACKED, FITS), 100 * (1 - ENTRIES / SLOTS)),
    ("sparse.pad_share", _ctx(PACKED, _snap(counters={
        "train.sparse_entries": 750, "train.sparse_slots": 1000})), 25.0),
    ("sparse.pad_share", _ctx(PACKED), None),  # a program without counters
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_sparse_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    got = run.reader("layers", name)(ctx, WAITING[name])
    if expected is None:
        assert got is None  # never 0
    else:
        assert got == pytest.approx(expected, rel=1e-12) and got > 0


# -- the cell's entries --------------------------------------------------------


def test_the_cell_is_listed_where_no_standing_test_pins_the_list():
    # no assertion here pins a list's end or its whole content: a later PR
    # appends its own cell and metrics without an edit to this file
    entry = run.find_cell(BENCH, CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "sweep_sparse"
    assert entry["config"] in {c["name"] for c in BENCH["configs"]}
    reported = {m["name"] for m in run.metrics_of(BENCH, entry, "end_to_end")}
    assert reported >= {"fit_rows_per_s", "fit_p95_ms", "setup_s"}
    layers = {m["name"] for m in run.metrics_of(BENCH, entry, "per_layer")}
    assert layers >= {"pool.hit_share", "train.dispatch_ms",
                      "train_program_roofline", "mfu.fit", "fetch.sync_ms",
                      "device.idle_share.sweep"}
    assert not layers & {"train.onepass_share", "pack.host_s",
                         "place.host_view_s", "place.h2d_gb_per_s"}  # dense


@pytest.mark.parametrize("name", sorted(WAITING))
def test_an_entry_that_waits_is_ready_to_move_over(name):
    metric = WAITING[name]
    assert sorted(metric) == ["better", "layer", "moves", "name", "source",
                              "unit", "workloads"]
    assert metric["name"] == name and metric["workloads"] == [CELL]
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # not yet
    # a layer the benchmark already names, letter for letter
    assert metric["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    # the cell reports the end-to-end metric the entry should move
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1 and CELL in moved[0].get("workloads", [CELL])
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_every_seed_gives_the_same_sparse_jobs_in_another_order():
    small = dict(dict(CONFIG, **CONFIG["rehearsal"]), rows=600)
    mix = run.load_json(BENCH_DIR, "traffic", "sweep_sparse.json")
    made = [jobs.make(small, mix, seed, jobs.Spans())
            for seed in (SEED, SEED, SEED + 1, SEED + 2, SEED + 3)]
    assert made[0].points == [(0.1, 0.0), (0.1, 0.0001), (0.5, 0.0),
                              (0.5, 0.0001)]
    for job in made:
        assert job.points == made[0].points and job.keys == [0, 1, 2, 3]
        assert sorted(job.order) == job.keys
        assert job.rows_per_job == 600 * small["maxIter"]
    assert made[0].order == made[1].order
    assert len({tuple(job.order) for job in made}) > 1
