"""The centroid fit (PR 31): ``KMeans.fit`` on its normal path against the
plain reference ``chipbench/references/kmeans_lloyd.py`` on seeded mixtures,
and what the fit's program is made of:

* centroids, cost history and iterations run agree with the reference at two
  shapes; the program's k-means++ and the reference's choose the same rows;
* bundled and unbundled fits return the same values; the row-tiled iteration
  equals the whole-table one on a table whose row count is no multiple of
  the tile; a table wide enough to be packed lane-aligned gives the answer
  of its own width;
* one warm fit observes every span of the fit path once, counts its rows,
  and its program is ``jit_bundled`` with the two ``fmt.train.kmeans.*``
  scopes; a program that holds the one-read kernel (PR 32; its own tests are
  ``tests/test_lloyd_kernel.py``) has ``fmt.train.kmeans.onepass`` too, and
  a fit through it agrees with the reference as the XLA tiles' does.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data_mixture, references  # noqa: E402
from flink_ml_tpu import obs  # noqa: E402
from flink_ml_tpu.lib import clustering, common  # noqa: E402
from flink_ml_tpu.lib.clustering import KMeans  # noqa: E402
from flink_ml_tpu.table.schema import DataTypes, Schema  # noqa: E402
from flink_ml_tpu.table.table import Table  # noqa: E402
from flink_ml_tpu.utils.environment import MLEnvironmentFactory  # noqa: E402

REFERENCE = references.load("kmeans_lloyd")
DATA = {"classes": 4, "styles_per_class": 6, "deform_rank": 4,
        "class_scale": 50.0, "deform_scale": 17.0, "deform": 0.8,
        "noise": 40.0, "shift": -66.0, "weight_concentration": 1.0,
        "clip": [0.0, 255.0]}
#: fit.wall's direct children: the GLM fit's, and the init between lookup
#: and the train call
CHILDREN = ("fit.prepare", "slab_pool.lookup", "kmeans.init",
            "train.place_params", "train.dispatch", "train.sync",
            "train.demux", "train.health", "fit.finish", "fit.report")
MISS_ONLY = ("slab_pool.build", "place.h2d")
KMEANS_SCOPES = {"fmt.train", "fmt.train.kmeans.assign",
                 "fmt.train.kmeans.update", "fmt.train.bundle"}


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import flink_ml_tpu.obs.report as report_mod

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    monkeypatch.setenv("FMT_TRACE_DIR", str(tmp_path / "traces"))
    obs.disable()
    obs.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


def _rows(n, dim, seed=2**31 + 9):
    X, _style = data_mixture.make_rows(DATA, n, dim, seed)
    return X


def _table(X):
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X})


def _kmeans(k, iters, seed=1):
    return (KMeans().set_vector_col("features").set_prediction_col("c")
            .set_k(k).set_max_iter(iters).set_seed(seed))


def _answer(model):
    return {"centroids": np.asarray(model.centroids(), np.float64),
            "costs": np.asarray(model.train_costs_, np.float64),
            "epochs": int(model.train_epochs_),
            "trail": np.asarray(model.train_centroids_, np.float32)}


# -- against the plain reference ------------------------------------------------


@pytest.mark.parametrize("n,dim,k,iters,seed,kernel", [
    (3001, 24, 8, 6, 1, False), (1500, 40, 5, 4, 7, False),
    (2900, 784, 8, 4, 3, True)],
    ids=["3001x24-k8", "1500x40-k5", "2900x784-k8-through-the-kernel"])
def test_fit_agrees_with_the_plain_reference(n, dim, k, iters, seed, kernel,
                                             monkeypatch):
    if kernel:  # packed 896 wide; the kernel on the interpreter
        monkeypatch.setattr(clustering, "_lloyd_kernel_platform",
                            lambda mesh: True)
        obs.enable()
    X = _rows(n, dim)
    model = _kmeans(k, iters, seed).fit(_table(X))
    if kernel:
        counted = obs.registry().snapshot()["counters"]
        assert counted["train.kmeans_onepass_fits"] == 1
    got = _answer(model)
    ref = REFERENCE.Table(X).fit(seed, k, iters)
    assert got["epochs"] == ref["epochs"] == iters
    assert got["centroids"].shape == (k, dim)
    assert len(got["costs"]) == iters and model.train_cost_ == got["costs"][-1]
    gaps = REFERENCE.gaps(got, ref)
    assert gaps["centroid_gap"] < 1e-5 and gaps["cost_gap"] < 1e-5, gaps
    assert np.all(np.diff(got["costs"]) <= 0)  # the cost never rises
    # the trail: the centroids every iteration started from, the init first
    trail = model.train_centroids_
    assert trail.shape == (iters, k, dim) and trail.dtype == np.float32
    sample = X[REFERENCE.sample_rows(n, seed)]
    assert np.array_equal(
        trail[0], sample[REFERENCE.plus_plus_rows(sample, k, seed)])
    assert not np.array_equal(trail[-1], trail[0])


@pytest.mark.parametrize("n,dim,k,seed", [
    (700, 9, 12, 1), (700, 9, 12, 2), (2600, 30, 20, 3),
    (150_000, 3, 6, 4)],
    ids=["700-s1", "700-s2", "2600-s3", "over-the-cap"])
def test_k_means_plus_plus_chooses_the_rows_the_reference_chooses(
        n, dim, k, seed):
    X = _rows(n, dim, seed=2**31 + 40 + seed)
    take = REFERENCE.sample_rows(n, seed)
    assert len(take) == min(n, KMeans.INIT_SAMPLE_CAP) == len(set(take))
    sample = X[take]
    want = REFERENCE.plus_plus_rows(sample, k, seed)
    assert len(set(want.tolist())) == k
    assert np.array_equal(clustering.kmeans_plus_plus(sample, k, seed),
                          sample[want])
    # and over the rows of the placed table, gathered on the device
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    n_pad = -(-n // n_dev) * n_dev
    Xp = np.zeros((n_pad, dim), np.float32)
    Xp[:n] = X
    placed = (jnp.asarray(Xp), jnp.ones((n_pad,), jnp.float32))
    on_device = clustering.kmeans_plus_plus_rows(
        placed, take.astype(np.int32), k, seed, mesh)
    assert np.array_equal(on_device, sample[want])


def test_d2_sampling_spreads_and_survives_coinciding_rows():
    far = np.array([[0.0, 0.0], [0.1, 0.0], [10.0, 10.0], [10.1, 10.0]])
    centres = clustering.kmeans_plus_plus(far, 2, 0)
    assert np.linalg.norm(centres[0] - centres[1]) > 5
    same = np.ones((5, 3), np.float32)
    centres = clustering.kmeans_plus_plus(same, 4, 3)
    assert centres.shape == (4, 3) and np.all(centres == 1.0)


# -- the program's parts --------------------------------------------------------


def test_bundled_and_unbundled_fits_return_the_same_values():
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    X = _rows(160 * n_dev, 12)
    w = np.ones((len(X),), np.float32)
    init = (jnp.asarray(X[:6]), jnp.zeros((4, 6, 12), jnp.float32))
    results = []
    for bundle in (True, False):
        fn = clustering.make_kmeans_train_fn(mesh, 6, 4, 0.0, bundle=bundle)
        assert bool(getattr(fn, "bundle_fetch", False)) is bundle
        results.append(common._run_fused_train(fn, init, (X, w), mesh,
                                               n_rows=len(X)))
    a, b = results
    for got, want in zip(a.params, b.params):  # centroids and their trail
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(a.params[1])[0], X[:6])
    assert a.losses == b.losses and a.epochs == b.epochs == 4
    assert a.final_delta == b.final_delta


@pytest.mark.parametrize("rows,tile", [(1000, 96), (1000, 999), (257, 64)])
def test_the_row_tiled_iteration_equals_the_whole_table_one(
        rows, tile, monkeypatch):
    assert rows % tile  # no multiple of the tile: a last, shorter part
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    X = _rows(rows * n_dev, 10)
    w = np.ones((len(X),), np.float32)
    w[-3:] = 0.0  # pad rows count for nothing
    init = (jnp.asarray(X[:7]), jnp.zeros((3, 7, 10), jnp.float32))
    results = []
    for rows_a_tile in (tile, 1 << 30):
        monkeypatch.setattr(clustering, "_LLOYD_TILE_ROWS", rows_a_tile)
        fn = clustering.make_kmeans_train_fn(mesh, 7, 3, 0.0)
        results.append(common._run_fused_train(fn, init, (X, w), mesh,
                                               n_rows=len(X)))
    tiled, whole = results
    for got, want in zip(tiled.params, whole.params):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=1e-4)
    np.testing.assert_allclose(tiled.losses, whole.losses, rtol=2e-6)


def test_the_one_hot_sums_are_the_scatters_sums():
    rng = np.random.default_rng(5)
    x = (rng.standard_normal((512, 7)) * 1000).astype(np.float32)
    assign = rng.integers(0, 5, 512)
    member = jnp.asarray(assign[:, None] == np.arange(5)[None, :],
                         jnp.float32)
    got = np.asarray(clustering._onehot_sums(member, jnp.asarray(x)))
    want = np.zeros((5, 7), np.float64)
    np.add.at(want, assign, x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-3)
    # a float32 value comes through whole, and the product says how
    one = np.asarray(clustering._onehot_sums(
        jnp.ones((1, 1), jnp.float32), jnp.asarray(x[:1])))
    assert np.array_equal(one, x[:1])
    assert "HIGHEST" in jax.make_jaxpr(clustering._onehot_sums)(
        member, jnp.asarray(x)).pretty_print()


@pytest.mark.parametrize("dim,width", [(2, 2), (511, 511), (512, 512),
                                       (513, 640), (784, 896), (2000, 2048)])
def test_wide_rows_are_packed_lane_aligned(dim, width):
    assert clustering.packed_width(dim) == width


def test_a_lane_padded_fit_answers_in_the_tables_own_width():
    X = _rows(900, 520)
    model = _kmeans(4, 3).fit(_table(X))
    got = _answer(model)
    assert got["centroids"].shape == (4, 520)
    ref = REFERENCE.Table(X).fit(1, 4, 3)
    gaps = REFERENCE.gaps(got, ref)
    assert gaps["centroid_gap"] < 1e-5 and gaps["cost_gap"] < 1e-5, gaps


# -- spans, counters, scopes ----------------------------------------------------


def _snapshot():
    snap = obs.registry().snapshot()
    return snap["timings"], snap["counters"]


def test_a_warm_fit_observes_every_span_once_and_counts_its_rows():
    obs.enable()
    n, k, iters = 2048, 5, 3
    table = _table(_rows(n, 16))
    _kmeans(k, iters).fit(table)  # packs, places, compiles
    first, counted = _snapshot()
    for name in CHILDREN + ("fit.wall",) + MISS_ONLY[:1]:
        assert first[name]["count"] == 1, name
    assert counted["train.kmeans_fits"] == counted["train.fused_runs"] == 1
    assert counted["train.kmeans_row_iters"] == n * iters
    assert counted["train.onepass_fits"] == 0
    # the XLA tiles, and not for the table's sake: off the chip
    assert counted["train.kmeans_onepass_fits"] == 0
    assert "train.kmeans_onepass_declined" not in counted

    _kmeans(k, iters).fit(table)  # warm: the cached pack, a pool hit
    warm, counted = _snapshot()
    delta = {name: (v["count"] - first.get(name, {"count": 0})["count"],
                    v["total_s"] - first.get(name, {"total_s": 0.0})["total_s"])
             for name, v in warm.items()}
    for name in CHILDREN + ("fit.wall",):
        assert delta[name][0] == 1, name
    for name in MISS_ONLY:
        assert delta[name][0] == 0, name
    # a warm fit observes the fit path's spans and no other
    assert {name for name, (count, _s) in delta.items() if count} == \
        set(CHILDREN + ("fit.wall",))
    wall, children = delta["fit.wall"][1], sum(delta[c][1] for c in CHILDREN)
    assert wall >= children > 0
    assert wall - children < 0.25 * wall
    assert counted["slab_pool.hits"] == counted["slab_pool.misses"] == 1
    assert counted["train.kmeans_fits"] == 2
    assert counted["train.kmeans_row_iters"] == 2 * n * iters
    assert counted["train.kmeans_onepass_fits"] == 0


def test_another_seed_is_another_init_of_the_same_program():
    obs.enable()
    table = _table(_rows(1200, 16))
    a = _kmeans(6, 2, seed=1).fit(table)  # a (k, maxIter) of this test alone
    builds = obs.registry().snapshot()["counters"]["train.program_builds"]
    b = _kmeans(6, 2, seed=2).fit(table)
    again = _kmeans(6, 2, seed=1).fit(table)
    counters = obs.registry().snapshot()["counters"]
    assert counters["train.program_builds"] == builds
    assert counters["train.compile_runs"] == 1
    assert not np.array_equal(a.centroids(), b.centroids())
    # a repeated fit returns the same bytes
    assert np.array_equal(a.centroids(), again.centroids())
    assert a.train_costs_ == again.train_costs_


@pytest.mark.parametrize("width,kernel_rows,rows", [
    (6, 0, 64), (128, 128, 128), (128, 128, 160)],
    ids=["xla-tiles", "kernel", "kernel-and-a-remainder"])
def test_the_kmeans_program_carries_its_scopes_and_its_name(
        width, kernel_rows, rows):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    fn = clustering.make_kmeans_train_fn(mesh, 4, 3, 0.0,
                                         kernel_rows=kernel_rows)
    assert fn.bundle_fetch and fn.loss_hist_len == 3
    assert getattr(fn, "pallas_interpret", False) is bool(kernel_rows)
    batch = (jnp.zeros((rows * n_dev, width), jnp.float32),
             jnp.ones((rows * n_dev,), jnp.float32))
    (program,) = [c.cell_contents for c in fn.__closure__
                  if hasattr(c.cell_contents, "lower")]
    lowered = program.lower((jnp.zeros((4, width), jnp.float32),
                             jnp.zeros((3, 4, width), jnp.float32)), batch)
    assert lowered.as_text().startswith("module @jit_bundled")
    scopes = set(re.findall(r"fmt\.[a-z_.]+",
                            lowered.as_text(debug_info=True)))
    if not kernel_rows:
        assert scopes == KMEANS_SCOPES
    else:
        # the kernel's call has its own scope; the other two stay on the
        # XLA side: the rows' squared norms and the new centroids, and the
        # rows a whole number of the kernel's tiles leaves
        assert scopes == KMEANS_SCOPES | {"fmt.train.kmeans.onepass"}
    compiled = lowered.compile().as_text()
    assert KMEANS_SCOPES <= set(re.findall(r"fmt\.[a-z_.]+", compiled))
    # the distance product states its precision
    assert "HIGHEST" in jax.make_jaxpr(clustering._pairwise_sq_dists)(
        jnp.zeros((8, 6)), jnp.zeros((4, 6))).pretty_print()
