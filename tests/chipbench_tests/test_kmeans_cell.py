"""Tests of what PR 31 adds to the yardstick for the centroid fit's cell
(``mnist8m_kmeans.restarts``): the seeded mixture generator, the plain
reference against NumPy in float64, the sizing arithmetic of
``work_kmeans.py``, the kind's jobs, control and planted faults in a CPU
rehearsal, the two new readers on a ``Context`` built by hand, and the cell's
entries in ``BENCHMARK.json``.  CPU only, tiny sizes.  The parametrised tests
of ``test_chipbench.py`` pick the cell itself up from ``BENCHMARK.json``.
"""

import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, data_mixture, jobs, references, run  # noqa: E402
from chipbench import work, work_kmeans  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELL = "mnist8m_kmeans.restarts"
CONFIG = run.load_json(BENCH_DIR, "configs", "mnist8m_kmeans.json")
SMALL = dict(CONFIG, **CONFIG["rehearsal"])
MIX = run.load_json(BENCH_DIR, "traffic", "restarts.json")
LIMITS = run.load_json(BENCH_DIR, "limits", CELL + ".json")
DATA = CONFIG["data"]
SEED = 2**31 + 12345
REFERENCE = references.load("kmeans_lloyd")
FAULTS = ["answer_altered", "half_table", "one_row_init", "state_unchanged"]


# -- the generator -------------------------------------------------------------


def test_mixture_data_is_a_function_of_the_seed_alone(monkeypatch):
    a = data_mixture.make_rows(DATA, 9000, 784, SEED)
    b = data_mixture.make_rows(DATA, 9000, 784, SEED)
    c = data_mixture.make_rows(DATA, 9000, 784, SEED + 1)
    monkeypatch.setattr(data_mixture, "THREADS", 1)  # whatever the cores
    d = data_mixture.make_rows(DATA, 9000, 784, SEED)
    for x, y, z in zip(a, b, d):
        assert np.array_equal(x, y) and np.array_equal(x, z)
    assert not np.array_equal(a[0], c[0])
    with pytest.raises(SystemExit):
        data_mixture.make_rows(DATA, 30, 784, 1, dtype="bfloat16")


def test_mixture_rows_keep_the_sources_shape_and_structure():
    n = 20_000
    X, style = data_mixture.make_rows(DATA, n, 784, SEED)
    assert X.shape == (n, 784) and X.dtype == np.float32
    assert X.min() == 0.0 and X.max() == 255.0  # clipped as pixels are
    assert 0.15 < (X > 0).mean() < 0.25  # about a fifth non-zero
    # real values, not whole numbers: bfloat16 would be another table
    inside = X[(X > 0) & (X < 255)]
    assert np.mean(inside != np.round(inside)) > 0.99
    # more components than centroids, of unequal weight
    components = DATA["classes"] * DATA["styles_per_class"]
    assert components == 300 > CONFIG["k"]
    shares = np.bincount(style, minlength=components) / n
    assert (shares > 0).sum() > 250 and shares.max() > 5 * np.median(shares)
    # classes lie far apart, the styles of a class overlap: a row is closer
    # to its own style's mean than to another class's, and often closer to a
    # neighbouring style's than to its own
    plant = data_mixture.planted(DATA, 784, SEED)
    means = np.clip(plant["means"], 0, 255).astype(np.float64)
    rows = X[:400].astype(np.float64)
    d = ((rows ** 2).sum(1)[:, None] - 2 * rows @ means.T
         + (means ** 2).sum(1))
    nearest = d.argmin(axis=1)
    same_class = plant["style_class"][nearest] == \
        plant["style_class"][style[:400]]
    assert same_class.mean() > 0.99
    assert 0.04 < (nearest != style[:400]).mean() < 0.8


# -- the reference -------------------------------------------------------------


def _numpy_lloyd(X, init, iterations):
    X, c, costs, trail = X.astype(np.float64), init.astype(np.float64), [], []
    for _ in range(iterations):
        trail.append(c.copy())
        d = ((X ** 2).sum(1)[:, None] - 2 * X @ c.T + (c ** 2).sum(1))
        nearest = d.argmin(axis=1)
        costs.append(d.min(axis=1).sum())
        for j in range(len(c)):
            if (nearest == j).any():
                c[j] = X[nearest == j].mean(axis=0)
    return c, np.asarray(costs), np.asarray(trail, np.float32)


def test_reference_matches_plain_numpy_in_float64():
    X, _style = data_mixture.make_rows(SMALL["data"], 2500, 24, SEED)
    table = REFERENCE.Table(X)
    got = table.fit(3, 6, 4)
    rows = REFERENCE.plus_plus_rows(X, 6, 3)
    assert len(set(rows.tolist())) == 6
    c, costs, trail = _numpy_lloyd(X, X[rows], 4)
    assert got["epochs"] == 4 and got["centroids"].shape == (6, 24)
    assert got["trail"].shape == (4, 6, 24) and got["trail"].dtype == np.float32
    assert np.array_equal(got["trail"][0], X[rows])  # the init leads the trail
    assert np.linalg.norm(got["centroids"] - c) / np.linalg.norm(c) < 1e-5
    assert np.allclose(got["costs"], costs, rtol=1e-5)
    assert np.allclose(got["trail"], trail, rtol=1e-5, atol=1e-3)
    # the plain NumPy answer, judged as an answer: one reference iteration
    # from each centroid matrix of its trail
    plain = {"centroids": c, "costs": costs, "epochs": 4, "trail": trail}
    gaps = REFERENCE.gaps(plain, got)
    assert gaps["centroid_gap"] < 1e-5 and gaps["cost_gap"] < 1e-5
    assert REFERENCE.gaps(got, got) == {"centroid_gap": 0.0, "cost_gap": 0.0}
    after, step_costs = table.steps(got["trail"])
    assert np.allclose(after[:-1], got["trail"][1:], rtol=1e-6, atol=1e-3)
    assert np.allclose(after[-1], got["centroids"], rtol=1e-6, atol=1e-3)
    assert np.allclose(step_costs, got["costs"], rtol=1e-6)
    # an answer of another length is no answer
    short = dict(got, costs=got["costs"][:3])
    assert REFERENCE.gaps(short, got)["cost_gap"] == float("inf")
    # another init is caught at the trail's head, whatever follows it
    other = table.fit(4, 6, 4)
    assert REFERENCE.gaps(other, got)["centroid_gap"] > 0.01
    assert REFERENCE.gaps(other, other)["centroid_gap"] == 0.0
    # the control and the faults are other answers
    for label, variant in REFERENCE.CONTROLS.items():
        bad = table.fit(3, 6, 4, **variant)
        worst = REFERENCE.gaps(bad, got)
        assert max(worst.values()) > 1e-4, label


def test_d2_sampling_follows_the_squared_distances():
    # 3 far points and a crowd at the origin: the second centre is a far
    # point almost surely, whatever the first
    crowd = np.zeros((300, 2), np.float32)
    far = np.array([[100.0, 0.0], [0.0, 100.0], [-100.0, 0.0]], np.float32)
    X = np.concatenate([crowd, far])
    picks = [REFERENCE.plus_plus_rows(X, 2, seed) for seed in range(12)]
    assert all(max(p) >= 300 for p in picks)
    assert len({tuple(p) for p in picks}) > 3  # and the seed decides which
    assert REFERENCE.sample_rows(90_000, 5).tolist() == list(range(90_000))
    over = REFERENCE.sample_rows(250_000, 5)
    assert len(over) == len(set(over.tolist())) == REFERENCE.SAMPLE_CAP
    assert np.array_equal(over, REFERENCE.sample_rows(250_000, 5))


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("dtype", "float64")])
def test_the_kmeans_reference_refuses_what_it_does_not_compute(key, value):
    assert REFERENCE.precision_of(CONFIG) == "f32"
    with pytest.raises(SystemExit):
        REFERENCE.precision_of(dict(CONFIG, **{key: value}))


# -- the configuration and the work --------------------------------------------


def test_kmeans_work_matches_the_sizing_arithmetic():
    w = work_kmeans.fit_work(CONFIG)
    assert (w["rows"], w["k"], w["iterations"]) == (2_025_000, 100, 20)
    assert w["bytes_per_iteration"] == 2_025_000 * 784 * 4  # 6.35 GB
    assert round(w["bytes_per_iteration"] / 1e9, 2) == 6.35
    assert w["flops_per_iteration"] == \
        2 * 2_025_000 * 784 * 100 + 2_025_000 * 784
    assert round(w["flops_per_iteration"] / 1e9, 1) == 319.1
    assert w["bytes"] == 20 * w["bytes_per_iteration"]
    assert w["flops"] == 20 * w["flops_per_iteration"]
    assert w["resident_bytes"] == 2_025_000 * 785 * 4
    # 37% of the chip, over the 4.00 GiB asked of a new cell
    assert w["resident_bytes"] / 2**30 > 4.0
    # the bytes bound it at the chip's published peaks: 7.75 ms an iteration
    least, bound = work.least_seconds(w, work.peak("TPU v5 lite"))
    assert bound == "hbm"
    assert round(1e3 * least / 20, 2) == 7.75 and round(least, 3) == 0.155


def test_the_configuration_states_the_deployment():
    mnist = run.load_json(BENCH_DIR, "configs", "mnist8m_lr.json")
    # the same table under another estimator: a source of its own, which
    # names the data set and the documented estimator (at most 200 characters)
    assert CONFIG["source"].startswith(mnist["source"] + " ")
    assert "flink-ml" in CONFIG["source"] and "kmeans" in CONFIG["source"]
    assert len(CONFIG["source"]) <= 200
    assert CONFIG["published"] == mnist["published"]
    assert CONFIG["rows"] == mnist["rows"] == 8_100_000 // 4
    assert CONFIG["architecture"] is None  # a deployment, no catalog model
    assert CONFIG["reduced"] == ["rows"]  # no width and no k is cut
    assert (CONFIG["k"], CONFIG["maxIter"], CONFIG["tol"]) == (100, 20, 0.0)
    assert CONFIG["dtype"] == "float32" and CONFIG["env"] == {}
    assert CONFIG["guarantees"][0] == mnist["guarantees"][0]  # word for word
    assert CONFIG["guarantees"][-1] == mnist["guarantees"][-1]
    assert len(CONFIG["guarantees"]) == 4 and len(CONFIG["assumed"]) >= 6
    assert MIX["job"] == "refit_kmeans" and jobs.clients_of(MIX) == 1
    assert MIX["grid"] == {"seed": [1, 2, 3, 4]}
    with pytest.raises(SystemExit):
        jobs.make(SMALL, dict(MIX, input="standardised"), SEED, jobs.Spans())
    with pytest.raises(SystemExit):
        jobs.make(dict(SMALL, tol=1e-4), MIX, SEED, jobs.Spans())


def test_every_seed_gives_the_same_kmeans_jobs_in_another_order():
    small = dict(SMALL, rows=400)
    made = [jobs.make(small, MIX, seed, jobs.Spans())
            for seed in (SEED, SEED, SEED + 1, SEED + 2, SEED + 3)]
    for job in made:
        assert job.points == [1, 2, 3, 4] and job.keys == [0, 1, 2, 3]
        assert sorted(job.order) == job.keys
        assert job.rows_per_job == 400 * small["maxIter"]
        assert job.work() == work_kmeans.fit_work(small)
    assert made[0].order == made[1].order
    assert len({tuple(job.order) for job in made}) > 1
    assert not np.array_equal(made[0].X, made[2].X)  # and other data


# -- the control and the planted faults, in a CPU rehearsal ---------------------


def _rehearsal(monkeypatch=None, fault=None):
    import jax

    kind = jobs.kind(MIX["job"])
    if fault is not None:
        for target, name, replacement in kind.planted_faults(SMALL)[fault]:
            monkeypatch.setattr(target, name, replacement)
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5, trace=0)
    return run.run_cell(args, BENCH, run.find_cell(BENCH, CELL), SMALL, MIX,
                        LIMITS, jax.devices())


def test_the_kind_declares_four_faults_and_the_references_controls():
    kind = jobs.kind(MIX["job"])
    assert sorted(kind.planted_faults(SMALL)) == FAULTS
    assert kind.numbers(CONFIG) == ("centroid_gap", "cost_gap")
    assert sorted(kind.controls(CONFIG)) == [
        "control_bf16", "fault_half_table", "fault_one_row_init",
        "fault_unchanged"]
    assert kind.controls(CONFIG)["control_bf16"] == {"precision": "bf16"}


def test_a_sound_rehearsal_is_correct_and_every_fit_a_pool_hit():
    result, record = _rehearsal()
    assert result["correct"] is True, result["compared"]
    assert record["values"]["answers_checked"] >= 1
    assert record["values"]["repeat_gap"] == 0.0
    # four seeds, one program: whatever set-up compiled, the window none
    assert record["values"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_comes_out_not_correct(fault, monkeypatch):
    result, _record = _rehearsal(monkeypatch, fault)
    assert result["correct"] is False
    over = {n for n, p in result["compared"].items()
            if p["value"] is None or p["value"] > p["limit"]}
    assert over & {"centroid_gap", "cost_gap"}, result["compared"]


@pytest.mark.parametrize("label", sorted(REFERENCE.CONTROLS))
def test_each_control_fails_the_cells_limits(label):
    generator = jobs.make(SMALL, MIX, 2**31 + 5, jobs.Spans())
    keys = generator.keys[:2]
    refs = generator.references(keys)
    bad = generator.references(keys, **REFERENCE.CONTROLS[label])
    values = check.worst([generator.gaps(bad[k], refs[k]) for k in keys])
    values.update({n: 0.0 for n in check.HARNESS_NUMBERS},
                  answers_checked=float(len(keys)))
    correct, compared = check.verdict(values, LIMITS)
    assert correct is False, compared


# -- the two new readers, on a Context built by hand ----------------------------

# The per-layer entries these readers are for.  They WAIT outside
# BENCHMARK.json as the sparse cell's do (test_sparse_cell.py):
# test_onepass_reader.py holds train.onepass_share to be the last entry of
# per_layer, and both files are a `benchmark` PR's to edit.  That PR loosens
# the assertion and appends these two as they stand here.
_ENTRY = {"workloads": [CELL], "moves": "fit_rows_per_s"}
WAITING = {
    "kmeans.init_ms": dict(
        _ENTRY, name="kmeans.init_ms", unit="ms", better="lower",
        source="program_span", layer="whole fit (entry point to result)"),
    "kmeans.row_iters_per_s": dict(
        _ENTRY, name="kmeans.row_iters_per_s", unit="M/s", better="higher",
        source="device_trace", layer="kernels (XLA programs on the chip)"),
}

EMPTY = {"counters": {}, "timings": {}}
TRACE = {"programs": {"jit_bundled(1)": {"seconds": 4.0, "calls": 8.0},
                      "jit__lambda_(3)": {"seconds": 0.4, "calls": 8.0}}}
NO_FITS = {"programs": {"jit__lambda_(3)": {"seconds": 0.4, "calls": 8.0}}}
ROW_ITERS = 2_025_000 * 20


def _snap(timings=None, counters=None):
    return {"counters": dict(counters or {}),
            "timings": {k: {"count": c, "total_s": s}
                        for k, (s, c) in (timings or {}).items()}}


def _ctx(setup=None, window=None, trace=None):
    return run.Context(snapshots={"setup": (EMPTY, setup or EMPTY),
                                  "window": (EMPTY, window or EMPTY)},
                       trace=trace)


FITS = _snap({"kmeans.init": (0.48, 8), "fit.wall": (5.0, 8)},
             {"train.kmeans_fits": 8, "train.kmeans_row_iters": 8 * ROW_ITERS})
GLM_FITS = _snap({"fit.wall": (5.0, 8)}, {"train.fused_runs": 8})
CASES = [
    ("kmeans.init_ms", _ctx(FITS, FITS), 60.0),
    ("kmeans.init_ms", _ctx(FITS), None),  # in set-up only: not the window's
    ("kmeans.init_ms", _ctx(GLM_FITS, GLM_FITS), None),  # no such span
    ("kmeans.row_iters_per_s", _ctx(FITS, FITS, TRACE),
     8 * ROW_ITERS / 4.0 / 1e6),
    ("kmeans.row_iters_per_s", _ctx(FITS, FITS), None),  # no trace
    ("kmeans.row_iters_per_s", _ctx(FITS, FITS, NO_FITS), None),
    ("kmeans.row_iters_per_s", _ctx(GLM_FITS, GLM_FITS, TRACE), None),
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_kmeans_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    got = run.reader("layers", name)(ctx, WAITING[name])
    if expected is None:
        assert got is None  # never 0
    else:
        assert got == pytest.approx(expected, rel=1e-12) and got > 0


# -- the cell's entries --------------------------------------------------------


def test_the_cell_is_listed_where_no_standing_test_pins_the_list():
    entry = run.find_cell(BENCH, CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "restarts"
    assert entry["config"] == "mnist8m_kmeans"
    reported = {m["name"] for m in run.metrics_of(BENCH, entry, "end_to_end")}
    assert reported == {"fit_rows_per_s", "fit_p95_ms", "setup_s"}
    layers = {m["name"] for m in run.metrics_of(BENCH, entry, "per_layer")}
    assert layers == {"pool.hit_share", "train.dispatch_ms",
                      "train_program_roofline", "mfu.fit", "fetch.sync_ms",
                      "device.idle_share.sweep"}
    # appended, nothing that stood moved: the cell is last in each list
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "mnist8m_kmeans"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


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
        {"centroid_gap", "cost_gap"} | set(check.HARNESS_NUMBERS)
    for name in ("centroid_gap", "cost_gap"):
        entry = LIMITS[name]
        assert entry["lower"] < entry["limit"] < entry["upper"]
        # the control's smallest reading; a fault that leaves a number as it
        # is fails by the other
        assert entry["upper"] == entry["control_bf16"]
        assert set(REFERENCE.CONTROLS) <= set(entry) and entry["why"]
    for label in REFERENCE.CONTROLS:
        assert max(LIMITS[n][label] / LIMITS[n]["limit"]
                   for n in ("centroid_gap", "cost_gap")) > 3, label
    for name in check.HARNESS_NUMBERS:
        assert LIMITS[name]["limit"] == 0
