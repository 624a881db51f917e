"""Tests of what PR 33 adds to the yardstick for the ragged sparse cell
(``url_ragged_lr.sweep``): the seeded generator of ragged bag-of-words rows,
the plain CSR reference against NumPy in float64, the sizing arithmetic of
``work_ragged.py``, the two new readers on a ``Context`` built by hand, and
the cell's entries in ``BENCHMARK.json``.  CPU only, small sizes.  The
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

from chipbench import check, data_ragged, jobs, references, run  # noqa: E402
from chipbench import work_ragged  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELL = "url_ragged_lr.sweep"
CONFIG = run.load_json(BENCH_DIR, "configs", "url_ragged_lr.json")
LIMITS = run.load_json(BENCH_DIR, "limits", CELL + ".json")
DATA = CONFIG["data"]
SMALL = dict(CONFIG, **CONFIG["rehearsal"])
SEED = 2**31 + 12345
ROWS, DIM = CONFIG["rows"], CONFIG["numFeatures"]


# -- the generator -------------------------------------------------------------


def test_ragged_data_is_a_function_of_the_seed_alone(monkeypatch):
    a = data_ragged.make_rows(DATA, 3000, 50_000, SEED)
    b = data_ragged.make_rows(DATA, 3000, 50_000, SEED)
    c = data_ragged.make_rows(DATA, 3000, 50_000, SEED + 1)
    monkeypatch.setattr(data_ragged, "THREADS", 1)  # whatever the cores
    d = data_ragged.make_rows(DATA, 3000, 50_000, SEED)
    for x, y, z in zip(a, b, d):
        assert np.array_equal(x, y) and np.array_equal(x, z)
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[3], c[3])
    with pytest.raises(SystemExit):
        data_ragged.make_rows(DATA, 30, 5000, 1, dtype="bfloat16")
    with pytest.raises(SystemExit):  # fewer binary ids than a row may store
        data_ragged.make_rows(DATA, 30, 500, 1)
    # pinned: the same bytes in every later PR
    indptr, indices, values, y = a
    assert indptr[:4].tolist() == PINNED["indptr"]
    assert indices[:6].tolist() == PINNED["indices"]
    assert int(y.sum()) == PINNED["positives"]
    assert float(values[0]) == pytest.approx(PINNED["value0"], rel=1e-6)


PINNED = {"indptr": [0, 152, 247, 342], "indices": [0, 9, 10, 11, 12, 14],
          "positives": 999, "value0": 0.016916170716285706}


def test_the_widths_law_stores_the_published_total_at_full_size():
    """The law at the configuration's own size: one draw of 2.4 M widths (no
    row is made).  The total within 0.5% of the published 277,058,644, every
    width inside 24-512, the widest about four times the mean, the day's
    mean rising by a tenth."""
    widths = data_ragged.row_widths(DATA, ROWS, DIM, SEED)
    assert widths.shape == (ROWS,) and ROWS == 2_396_130
    assert abs(widths.sum() / CONFIG["published"]["entries"] - 1) < 0.005
    assert abs(widths.mean() - 115.6) < 0.5
    assert widths.min() >= 24 and widths.max() <= 512
    assert 3.8 * widths.mean() < widths.max()
    edges = data_ragged.day_edges(DATA, ROWS)
    assert len(edges) == 122 and edges[0] == 0 and edges[-1] == ROWS
    assert np.diff(edges).min() >= 19_802  # about 20,000 URLs a day
    first, last = widths[:edges[1]].mean(), widths[edges[-2]:].mean()
    assert 1.08 < last / first < 1.12
    # another seed, another table of the same total (the law's spread)
    other = data_ragged.row_widths(DATA, ROWS, DIM, SEED + 1)
    assert not np.array_equal(widths, other)
    assert abs(other.sum() / widths.sum() - 1) < 0.001


def test_ragged_rows_keep_the_sources_shape():
    n, dim = SMALL["rows"], 400_000
    indptr, indices, values, y = data_ragged.make_rows(DATA, n, dim, SEED)
    widths = np.diff(indptr)
    assert indptr.dtype == np.int64 and indptr[0] == 0
    assert np.array_equal(widths, data_ragged.row_widths(DATA, n, dim, SEED))
    assert widths.min() >= 24 and widths.max() <= 512
    assert abs(widths.mean() / 115.63 - 1) < 0.005  # the published mean
    assert indices.dtype == np.int32 and len(indices) == indptr[-1]
    assert indices.min() == 0 and indices.max() < dim
    # ascending and DISTINCT within a row, as the LIBSVM format has them
    row = np.repeat(np.arange(n), widths)
    inside = np.diff(row) == 0
    assert (np.diff(indices.astype(np.int64))[inside] > 0).all()
    # 64 real-valued features, each in half the rows, values in (0, 1] before
    # the scaling; the rest binary: one value a row
    real = indices < 64
    assert abs(real.sum() / n - 32) < 0.2
    assert values.dtype == np.float32 and values.min() > 0
    norms = np.sqrt(np.bincount(row, weights=values.astype(np.float64) ** 2))
    assert np.abs(norms - 1).max() < 1e-6  # unit Euclidean length
    binary = np.where(real, np.float32(0), values)
    top = np.maximum.reduceat(binary, indptr[:-1])
    # every binary entry of a row holds the row's one scale
    assert np.all((binary == 0) | (binary == top[row]))
    assert len(np.unique(top)) > n // 2  # and it differs from row to row
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert abs(y.mean() - 1 / 3) < 1e-3
    # the vocabulary grows with the days: the first day's ids stay inside a
    # fifth of the binary ids, the last day reaches nearly all of them
    edges = data_ragged.day_edges(DATA, n)
    alive = data_ragged.vocabulary(DATA, dim)
    assert alive[0] == int(0.2 * (dim - 64)) and alive[-1] == dim - 64
    assert np.all(np.diff(alive) > 0)
    for day in (0, 60, 120):
        ids = indices[indptr[edges[day]]:indptr[edges[day + 1]]]
        assert ids.max() < 64 + alive[day]
        assert ids.max() > 64 + 0.5 * alive[day]
    # the hot set stays: the most frequent binary feature is in nearly every
    # row, and the tail is wide
    counts = np.bincount(indices, minlength=dim)
    assert counts[64] > 0.95 * n and counts[64:].argmax() == 0
    seen = counts[64:][counts[64:] > 0]
    assert len(seen) > 50_000 and np.median(seen) <= 2


# -- the reference -------------------------------------------------------------


def _numpy_fit(indptr, indices, values, y, dim, batch, lr, reg, epochs):
    ww, bb, losses = np.zeros(dim), 0.0, []
    vals = values.astype(np.float64)
    row = np.repeat(np.arange(len(y)), np.diff(indptr))
    for _ in range(epochs):
        tot = 0.0
        for lo in range(0, len(y), batch):
            hi = min(lo + batch, len(y))
            e = slice(indptr[lo], indptr[hi])
            z = np.bincount(row[e] - lo, weights=vals[e] * ww[indices[e]],
                            minlength=hi - lo) + bb
            yb = y[lo:hi]
            tot += np.sum(np.logaddexp(0, z) - yb * z)
            err = 1 / (1 + np.exp(-z)) - yb
            g = np.zeros(dim)
            np.add.at(g, indices[e], err[row[e] - lo] * vals[e])
            ww = ww - lr * (g / (hi - lo) + reg * ww)
            bb = bb - lr * err.mean()
        losses.append(tot / len(y))
    return ww, bb, losses


def test_csr_reference_matches_plain_numpy_in_float64():
    n, dim, batch = 3000, 4000, 512
    indptr, indices, values, y = data_ragged.make_rows(DATA, n, dim, SEED)
    assert np.diff(indptr).min() < np.diff(indptr).max()  # ragged
    reference = references.load("csr_glm_sgd")
    table = reference.Table(indptr, indices, values, y, dim, batch)
    # its own layout: chunks of steps, each padded to its own fullest step
    assert len(table.chunks) == 1 and table.chunks[0][0].shape[0] == 6
    got = table.fit(0.5, 1e-3, 2)
    ww, bb, losses = _numpy_fit(indptr, indices, values, y, dim, batch, 0.5,
                                1e-3, 2)
    assert np.linalg.norm(got["coef"] - ww) / np.linalg.norm(ww) < 1e-5
    assert abs(got["intercept"] - bb) < 1e-6
    assert np.allclose(got["losses"], losses, rtol=1e-5)
    assert reference.gaps(got, {"coef": ww, "intercept": bb,
                                "losses": np.asarray(losses)})["coef_gap"] \
        < 1e-5
    # the control and the faults are other answers
    for variant in reference.CONTROLS.values():
        bad = table.fit(0.5, 1e-3, 2, **variant)
        assert reference.gaps(bad, got)["coef_gap"] > 1e-4, variant


def test_the_reference_lays_every_chunk_to_its_own_fullest_step(monkeypatch):
    reference = references.load("csr_glm_sgd")
    monkeypatch.setattr(reference, "CHUNK_STEPS", 2)
    monkeypatch.setattr(reference, "PAD_MULTIPLE", 8)
    n, dim, batch = 1100, 4000, 256
    indptr, indices, values, y = data_ragged.make_rows(DATA, n, dim, SEED)
    table = reference.Table(indptr, indices, values, y, dim, batch)
    assert [c[0].shape[0] for c in table.chunks] == [2, 2, 1]
    pads = [c[0].shape[1] for c in table.chunks]
    assert len(set(pads)) > 1  # a shape a chunk, not one for the table
    stored = 0
    for (idx, row, vals, yp, mask), lo in zip(table.chunks, (0, 512, 1024)):
        idx, row, vals, mask = (np.asarray(a) for a in (idx, row, vals, mask))
        steps = idx.shape[0]
        counts = [int(indptr[min(lo + (s + 1) * batch, n)]
                      - indptr[min(lo + s * batch, n)]) for s in range(steps)]
        assert idx.shape[1] == -(-max(counts) // 8) * 8
        for s, count in enumerate(counts):
            assert (row[s, :count] < batch).all()
            assert (np.diff(row[s, :count]) >= 0).all()  # table order
            assert (row[s, count:] == batch).all()  # pads: past the batch
            assert (vals[s, count:] == 0).all()
        stored += sum(counts)
        assert mask.sum() == min(lo + steps * batch, n) - lo
    assert stored == indptr[-1]
    # the smaller chunks give the same answer as one
    whole = references.load("csr_glm_sgd")
    monkeypatch.undo()
    one = whole.Table(indptr, indices, values, y, dim, batch).fit(0.5, 0, 1)
    got = table.fit(0.5, 0.0, 1)
    assert np.allclose(got["coef"], one["coef"], rtol=0, atol=1e-7)


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("withIntercept", False)])
def test_the_csr_reference_refuses_what_it_does_not_compute(key, value):
    reference = references.load(CONFIG["reference"])
    assert reference.precision_of(CONFIG) == "f32"
    assert reference.NUMBERS == references.load("glm_sgd").NUMBERS
    assert reference.CONTROLS is references.load("glm_sgd").CONTROLS
    assert reference.gaps is references.load("sparse_glm_sgd").gaps
    with pytest.raises(SystemExit):
        reference.precision_of(dict(CONFIG, **{key: value}))


# -- the configuration and the work --------------------------------------------


def test_ragged_work_matches_the_sizing_arithmetic_at_the_published_shape():
    widths = data_ragged.row_widths(DATA, ROWS, DIM, SEED)
    indptr = np.concatenate([[0], np.cumsum(widths)])
    work = work_ragged.fit_work(CONFIG, indptr)
    entries = int(widths.sum())
    assert work["rows"] == 2_396_130 and work["epochs"] == 1
    assert work["steps_per_epoch"] == 74
    assert ROWS - 73 * 32768 == 4_066  # the last step's rows
    assert work["entries_per_epoch"] == entries
    # the fullest step (the last days' rows, a tenth wider than the first)
    # sets the padded width: 3.97 M (ISSUE 33 reckoned 4.0-4.2 M)
    # rounded up to an ODD multiple of 512, as the program's pack rounds it
    assert 3.9e6 < work["nnz_pad"] < 4.1e6 and work["nnz_pad"] % 1024 == 512
    fullest = max(int(indptr[min((s + 1) * 32768, ROWS)] - indptr[s * 32768])
                  for s in range(74))
    assert 0 <= work["nnz_pad"] - fullest < 1024
    assert 3.75e6 < entries / 73.124 < 3.83e6  # 3.79 M a step on average
    slots = 74 * work["nnz_pad"]
    assert 289e6 < slots < 303e6 and 0.05 < 1 - entries / slots < 0.08
    ints = 74 * 2 * work["nnz_pad"] * 4
    floats = 74 * (work["nnz_pad"] + 65_536) * 4
    assert work["resident_bytes"] == ints + floats
    assert 2.3e9 <= ints <= 2.45e9 and 1.15e9 <= floats <= 1.25e9
    assert 3.5e9 < work["resident_bytes"] < 3.7e9  # 21% of 16.91 GB
    # an epoch reads every entry (index and value) and every label once
    assert work["bytes"] == entries * 8 + 2_396_130 * 4
    assert work["flops"] == 4 * entries
    # the layout rule's declined side, with room: a row-regular step would
    # walk 32768 x the widest row
    assert work["widest_row"] == widths.max()
    assert work["ell_slots"] == 32768 * widths.max()
    assert work["ell_slots"] > 2 * 1.75 * work["nnz_pad"]


def test_the_configuration_states_the_deployment():
    criteo = run.load_json(BENCH_DIR, "configs", "criteo_sparse_lr.json")
    assert CONFIG["guarantees"] == criteo["guarantees"]  # letter for letter
    assert CONFIG["architecture"] is None  # a deployment, no catalog model
    assert CONFIG["published"] == {"rows": 2396130, "features": 3231961,
                                   "entries": 277058644}
    assert "entries" not in CONFIG  # the count drawn is data
    assert CONFIG["reduced"] == [] and CONFIG["maxIter"] == 1
    assert CONFIG["rows"] == CONFIG["published"]["rows"]
    assert CONFIG["numFeatures"] == CONFIG["features"] == 3_231_961
    assert CONFIG["globalBatchSize"] == 32768 and CONFIG["tol"] == 0.0
    assert CONFIG["env"] == {}  # no FMT_* switch: the default route
    entry = next(c for c in BENCH["configs"] if c["name"] == "url_ragged_lr")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) < 200
    assert entry["source"] not in {c["source"] for c in BENCH["configs"]
                                   if c is not entry}
    mix = run.load_json(BENCH_DIR, "traffic", "sweep_ragged.json")
    assert mix["job"] == "refit_ragged" and jobs.clients_of(mix) == 1
    assert mix["grid"] == {"learningRate": [0.1, 0.5], "reg": [0.0001]}
    with pytest.raises(SystemExit):
        jobs.make(SMALL, dict(mix, input="hashed"), SEED, jobs.Spans())


def test_the_kind_counts_its_work_from_the_table_the_seed_made(capsys):
    mix = run.load_json(BENCH_DIR, "traffic", "sweep_ragged.json")
    small = dict(SMALL, rows=1500)
    made = [jobs.make(small, mix, seed, jobs.Spans())
            for seed in (SEED, SEED, SEED + 1, SEED + 2, SEED + 3)]
    said = capsys.readouterr().err
    for job in made:
        assert job.points == [(0.1, 0.0001), (0.5, 0.0001)]
        assert job.keys == [0, 1] and sorted(job.order) == job.keys
        assert job.rows_per_job == 1500
        work = job.work()
        assert work["entries_per_epoch"] == job.indptr[-1] == len(job.indices)
        assert work["bytes"] == 8 * len(job.indices) + 4 * 1500
        assert f"{len(job.indices)} stored entries" in said
    assert made[0].order == made[1].order
    assert np.array_equal(made[0].indices, made[1].indices)
    assert made[0].work() == made[1].work() != made[2].work()
    assert len({tuple(job.order) for job in made}) > 1
    kind = jobs.kind("refit_ragged")
    assert sorted(kind.planted_faults(small)) == [
        "answer_altered", "half_batch", "state_unchanged"]
    assert kind.numbers(small) == ("coef_gap", "loss_gap")
    assert sorted(kind.controls(small)) == [
        "control_bf16", "fault_half_batch", "fault_unchanged"]


def test_a_program_without_the_steady_pack_is_refused_before_any_data(
        monkeypatch):
    from chipbench import program_ragged
    from flink_ml_tpu.lib import common

    program_ragged.require_steady_pack()  # this program has it
    assert common.padded_nnz(1025, 512) % 1024 == 512
    mix = run.load_json(BENCH_DIR, "traffic", "sweep_ragged.json")
    monkeypatch.delattr(common, "padded_nnz")  # the parent's program
    monkeypatch.setattr(data_ragged, "make_rows", None)  # never reached
    with pytest.raises(SystemExit) as refused:
        jobs.make(SMALL, mix, SEED, jobs.Spans())
    assert "padded_nnz" in str(refused.value)
    assert "refusing to run" in str(refused.value)


def test_the_half_batch_fault_cuts_a_ragged_table_by_its_row_bounds():
    from chipbench import program_sparse

    indptr, indices, values, y = data_ragged.make_rows(
        DATA, 1100, SMALL["numFeatures"], SEED)
    (_mod, name, half_table), (_m, _n, half_logreg) = \
        jobs.kind("refit_ragged").planted_faults(SMALL)["half_batch"]
    assert name == "table" and _mod is program_sparse
    table = half_table(SMALL["numFeatures"], indptr, indices, values, y)
    keep = (np.arange(1100) % 512) < 256
    column = table.col("features")
    assert len(column) == keep.sum() == 256 + 256 + 76
    assert np.array_equal(np.diff(column.indptr), np.diff(indptr)[keep])
    assert np.array_equal(
        column.indices, indices[np.repeat(keep, np.diff(indptr))])
    assert np.array_equal(np.asarray(table.col("label")), y[keep])
    assert half_logreg(SMALL, 0.1, 0.0).get_global_batch_size() == 256


# -- the two new readers, on a Context built by hand ---------------------------

# The per-layer entries these readers are for.  They WAIT outside
# BENCHMARK.json: test_onepass_reader.py holds train.onepass_share to be the
# last entry of per_layer, the driver takes an entry put ahead of it for a
# change to it, and both files are a `benchmark` PR's to edit.  That PR
# loosens the assertion and appends these two as they stand here.
_ENTRY = {"workloads": [CELL], "moves": "fit_rows_per_s", "better": "lower"}
WAITING = {
    "sparse.segment_ns_per_slot": dict(
        _ENTRY, name="sparse.segment_ns_per_slot", unit="ns",
        source="device_trace", layer="kernels (XLA programs on the chip)"),
    "sparse.slot_ratio": dict(
        _ENTRY, name="sparse.slot_ratio", unit="ratio",
        source="program_counter",
        layer="fused train program (lib/common.py _build_fused_train_fn)"),
}

EMPTY = {"counters": {}, "timings": {}}
TRACE = {"programs": {"jit_bundled(1)": {"seconds": 18.0, "calls": 2.0},
                      "jit_bundled(2)": {"seconds": 9.0, "calls": 1.0},
                      "jit_copy(7)": {"seconds": 1e-5, "calls": 8.0}}}
NO_FITS = {"programs": {"jit_copy(7)": {"seconds": 1e-5, "calls": 8.0}}}
SLOTS, RECKONED = 74 * 4_100_096, 74 * 32768 * 492


def _snap(counters=None):
    return {"counters": dict(counters or {}), "timings": {}}


def _ctx(window=None, trace=None):
    return run.Context(snapshots={"setup": (EMPTY, EMPTY),
                                  "window": (EMPTY, window or EMPTY)},
                       trace=trace)


FITS = _snap({"train.sparse_fits": 3, "train.sparse_ell_declined": 3,
              "train.sparse_slots": 3 * SLOTS,
              "train.sparse_ell_slots_reckoned": 3 * RECKONED})
# the parent's program: the slots, and no counter of what the rule reckoned
PARENT = _snap({"train.sparse_fits": 3, "train.sparse_slots": 3 * SLOTS})
# a pack that was not asked (hot/cold, a 2-D mesh): the counter is there, at 0
NOT_ASKED = _snap({"train.sparse_fits": 3, "train.sparse_slots": 3 * SLOTS,
                   "train.sparse_ell_slots_reckoned": 0})
CASES = [
    ("sparse.segment_ns_per_slot", _ctx(FITS, TRACE),
     27.0e9 / (3 * SLOTS)),
    ("sparse.segment_ns_per_slot", _ctx(PARENT, TRACE),
     27.0e9 / (3 * SLOTS)),  # reads on the parent too
    ("sparse.segment_ns_per_slot", _ctx(FITS), None),  # no trace
    ("sparse.segment_ns_per_slot", _ctx(FITS, NO_FITS), None),
    ("sparse.segment_ns_per_slot", _ctx(None, TRACE), None),  # no sparse fit
    ("sparse.slot_ratio", _ctx(FITS), 32768 * 492 / 4_100_096),
    ("sparse.slot_ratio", _ctx(_snap({
        "train.sparse_slots": 1000,
        "train.sparse_ell_slots_reckoned": 1000})), 1.0),  # one width
    ("sparse.slot_ratio", _ctx(PARENT), None),  # a program without it
    ("sparse.slot_ratio", _ctx(NOT_ASKED), None),
    ("sparse.slot_ratio", _ctx(), None),
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_ragged_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    got = run.reader("layers", name)(ctx, WAITING[name])
    if expected is None:
        assert got is None  # never 0
    else:
        assert got == pytest.approx(expected, rel=1e-12) and got > 0
    if name == "sparse.slot_ratio" and expected and expected > 1:
        assert expected > 2 * 1.75  # the rule's declined side, with room


@pytest.mark.parametrize("name,expected", [
    ("sparse.pad_share", 100 * (1 - 276_972_675 / SLOTS)),
    ("sparse.entries_per_s", 3 * 276_972_675 / 27.0 / 1e6)])
def test_the_standing_sparse_readers_read_in_this_cell_as_they_are(
        name, expected):
    window = _snap(dict(FITS["counters"],
                        **{"train.sparse_entries": 3 * 276_972_675}))
    got = run.reader("layers", name)(_ctx(window, TRACE), {"name": name})
    assert got == pytest.approx(expected, rel=1e-12)
    assert 6 < 100 * (1 - 276_972_675 / SLOTS) < 11  # the pad share reckoned


# -- the cell's entries --------------------------------------------------------


def test_the_cell_is_listed_where_no_standing_test_pins_the_list():
    # no assertion here pins a list's end or its whole content: a later PR
    # appends its own cell and metrics without an edit to this file
    entry = run.find_cell(BENCH, CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "sweep_ragged"
    assert entry["config"] == "url_ragged_lr"
    assert "slowest of 4 fits" in entry["why"]
    reported = {m["name"] for m in run.metrics_of(BENCH, entry, "end_to_end")}
    assert reported >= {"fit_rows_per_s", "fit_p95_ms", "setup_s"}
    layers = {m["name"] for m in run.metrics_of(BENCH, entry, "per_layer")}
    assert layers >= {"pool.hit_share", "train.dispatch_ms",
                      "train_program_roofline", "mfu.fit", "fetch.sync_ms",
                      "device.idle_share.sweep"}
    assert not layers & {"train.onepass_share", "pack.host_s",
                         "place.host_view_s", "place.h2d_gb_per_s"}  # dense
    # 5 of 24 cells, none on four chips; what stood keeps its place
    assert [c["name"] for c in BENCH["workloads"]][:4] == [
        "epsilon_lr.sweep", "mnist8m_lr.sweep", "criteo_sparse_lr.sweep",
        "mnist8m_kmeans.restarts"]
    assert all(c["chips"] == 1 for c in BENCH["workloads"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            at = m["workloads"].index(CELL)
            assert m["workloads"][at - 1] == "mnist8m_kmeans.restarts"


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


def test_the_limits_say_where_they_came_from():
    assert set(LIMITS) - {"_readings"} == \
        {"coef_gap", "loss_gap"} | set(check.HARNESS_NUMBERS)
    assert "PR 33" in LIMITS["_readings"]
    reference = references.load(CONFIG["reference"])
    for name in reference.NUMBERS:
        entry = LIMITS[name]
        assert entry["lower"] < entry["limit"] < entry["upper"]
        # the smallest reading of the control and the faults
        assert entry["upper"] == min(entry[label]
                                     for label in reference.CONTROLS)
    for label in reference.CONTROLS:  # each fails one limit with room
        assert max(LIMITS[n][label] / LIMITS[n]["limit"]
                   for n in reference.NUMBERS) > 3, label
    for name in check.HARNESS_NUMBERS:
        assert LIMITS[name]["limit"] == 0
