"""The Lloyd iteration's one-read kernel (``ops/pallas_kernels.py:lloyd_sums``,
PR 32) on the interpreter: its cost, sums and counts against the two XLA
operations it replaces (``lib/clustering.py:_lloyd_pass``'s ``part``) on the
same rows, what its three bfloat16 pieces keep of a float32, and the rule
that selects it.  The estimator's own CPU path keeps the XLA tiles; a test
that wants the kernel says so (``_lloyd_kernel_platform``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from flink_ml_tpu import obs
from flink_ml_tpu.lib import clustering
from flink_ml_tpu.lib.clustering import KMeans
from flink_ml_tpu.ops import pallas_kernels
from flink_ml_tpu.ops.pallas_kernels import lloyd_sums, lloyd_sums_tile
from flink_ml_tpu.parallel.collectives import shard_map
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory


def one_pass(x, w, c, kernel_rows, tile=96):
    """``_lloyd_pass`` over ``x`` on one device: the kernel at
    ``kernel_rows`` rows a step (interpreted), or the XLA tiles at 0."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    k = c.shape[0]

    def local(x, w, c):
        return clustering._lloyd_pass(
            x, w, jnp.sum(x * x, axis=1), c, k, tile, kernel_rows, True)

    cost, sums, counts = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=(P(), P(), P()), check_vma=False,
    ))(jnp.asarray(x), jnp.asarray(w), jnp.asarray(c))
    return float(cost), np.asarray(sums), np.asarray(counts)


def table_of(rows, width, k, seed=0, scale=100.0):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((rows, width)) * scale).astype(np.float32)
    c = x[rng.choice(rows, k, replace=False)] + np.float32(1.0)
    return x, np.ones((rows,), np.float32), c


def assert_same_pass(got, want):
    (cost, sums, counts), (rcost, rsums, rcounts) = got, want
    assert np.array_equal(counts, rcounts)  # the same rows, every cluster
    np.testing.assert_allclose(cost, rcost, rtol=2e-6)
    scale = np.max(np.abs(rsums))
    assert np.max(np.abs(sums - rsums)) <= 2e-6 * scale


@pytest.mark.parametrize("rows,width,k,kernel_rows,pads", [
    (512, 128, 100, 256, ()),                  # whole tiles
    (600, 128, 100, 256, ()),                  # 88 rows left to the XLA part
    (512, 256, 100, 128, (5, 130, 131, 300)),  # pad rows in the middle
    (640, 128, 100, 128, range(600, 640)),     # and at the end, a whole tile
    (300, 128, 3, 256, (299,)),
    (384, 128, 128, 384, ()),                  # every lane a centroid
    (512, 384, 200, 512, (0,)),                # two lane chunks of centroids
], ids=["whole-tiles", "a-remainder", "pads-in-the-middle", "pads-at-the-end",
        "k3", "k128", "k200"])
def test_the_kernel_returns_the_xla_tiles_sums(rows, width, k, kernel_rows,
                                               pads):
    x, w, c = table_of(rows, width, k, seed=rows + k)
    w[list(pads)] = 0.0
    got = one_pass(x, w, c, kernel_rows)
    assert_same_pass(got, one_pass(x, w, c, 0))
    assert got[2].sum() == rows - len(pads)


def test_a_pad_centroid_wins_no_row_however_far_the_real_ones_are():
    """k = 100 is padded to 128 lanes of centroids at distance +inf: rows
    1e18 from every real centroid (squared: beyond float32 for some) still
    go to a real one."""
    x, w, c = table_of(256, 128, 100, seed=3)
    x[:128] *= np.float32(1e16)
    cost, sums, counts = one_pass(x, w, c, 128)
    assert counts.shape == (100,) and counts.sum() == 256
    assert sums.shape == (100, 128)


def test_an_empty_cluster_sums_to_nothing():
    x, w, c = table_of(256, 128, 5, seed=4)
    c[3] = np.float32(1e6)  # far from every row
    cost, sums, counts = one_pass(x, w, c, 128)
    assert counts[3] == 0 and not sums[3].any()
    assert_same_pass((cost, sums, counts), one_pass(x, w, c, 0))
    # and the fit keeps that centroid
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fn = clustering.make_kmeans_train_fn(mesh, 5, 1, 0.0, kernel_rows=128)
    from flink_ml_tpu.lib import common

    result = common._run_fused_train(
        fn, (jnp.asarray(c), jnp.zeros((1, 5, 128), jnp.float32)), (x, w),
        mesh, n_rows=256)
    assert np.array_equal(np.asarray(result.params[0])[3], c[3])
    assert not np.array_equal(np.asarray(result.params[0])[2], c[2])


def test_a_row_between_two_centroids_goes_to_the_lower_index():
    x = np.zeros((128, 128), np.float32)
    x[:, 0] = np.arange(128)
    c = np.zeros((4, 128), np.float32)
    c[:, 0] = [40.0, 10.0, 30.0, 10.0]  # 1 and 3 coincide; 20 lies between
    w = np.ones((128,), np.float32)
    cost, sums, counts = one_pass(x, w, c, 128)
    rcost, rsums, rcounts = one_pass(x, w, c, 0)
    assert counts[3] == 0 and np.array_equal(counts, rcounts)
    # row 20 is 10 from centroid 1 and from centroid 2: to 1
    assert counts[1] == 21 and sums[1, 0] == sum(range(21))
    assert cost == rcost


def test_the_three_pieces_carry_all_24_bits():
    """Rows whose values need every mantissa bit: a cluster of one row sums
    to that row bit for bit, and larger clusters to the float64 scatter's
    sums within the rounding of a float32 total."""
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((256, 128)) * 1000).astype(np.float32)
    x[:, :8] = np.float32(1.0) + np.float32(2.0 ** -23)  # 1 + one ulp
    w = np.ones((256,), np.float32)
    x[:, 8:16] *= np.float32(-1.0)
    pieces = [np.asarray(p) for p in
              pallas_kernels._f32_nearest_pieces(jnp.asarray(x))]
    for p in pieces:  # each a bfloat16 value, and together all of x
        assert not np.any(p.view(np.int32) & 0xFFFF)
    assert np.array_equal(sum(p.astype(np.float64) for p in pieces), x)
    # rounded, not cut: what is left of x is at most half a last place of
    # the piece, either way (cut pieces leave up to a whole one, one way)
    last_place = 2.0 ** (np.floor(np.log2(np.abs(x))) - 7)
    assert np.all(np.abs(x - pieces[0]) <= last_place / 2)
    assert np.any(np.sign(x - pieces[0]) != np.sign(x))
    # every row its own centroid
    cost, sums, counts = one_pass(x[:128], w[:128], x[:128].copy(), 128)
    assert np.array_equal(sums, x[:128]) and np.all(counts == 1)
    # five clusters of many rows
    c = x[[3, 50, 100, 150, 200]]
    cost, sums, counts = one_pass(x, w, c, 256)
    d = ((x[:, None, :].astype(np.float64) - c[None].astype(np.float64)) ** 2
         ).sum(axis=2)
    want = np.zeros((5, 128), np.float64)
    np.add.at(want, d.argmin(axis=1), x.astype(np.float64))
    total = np.zeros((5, 128), np.float64)
    np.add.at(total, d.argmin(axis=1), np.abs(x).astype(np.float64))
    assert np.all(np.abs(sums - want) <= 2.0 ** -22 * total)


@pytest.mark.parametrize("rows,width,k,tile", [
    (2_025_000, 896, 100, 2048), (300, 896, 8, 256), (5000, 512, 256, 2048),
    # wider rows, shorter tiles: the tile's VMEM is reckoned from the shape
    (5000, 2048, 100, 1280), (5000, 4096, 256, 384), (5000, 16384, 256, 0),
    (127, 896, 8, 0), (5000, 784, 8, 0), (5000, 30, 8, 0),
    (5000, 896, 257, 0), (5000, 896, 0, 0)])
def test_the_tile_is_arithmetic_on_the_shape(rows, width, k, tile):
    assert lloyd_sums_tile(rows, width, k) == tile


def test_the_kernel_refuses_a_shape_it_has_no_tile_for():
    x, w, c = table_of(256, 100, 4)
    with pytest.raises(ValueError, match="no row tile"):
        lloyd_sums(jnp.asarray(x), jnp.asarray(w), jnp.ones((256,)),
                   jnp.asarray(c), tile_rows=128, interpret=True)


# -- the route ------------------------------------------------------------------


@pytest.fixture
def counters(tmp_path, monkeypatch):
    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    obs.disable()
    obs.reset()
    obs.enable()
    yield lambda: obs.registry().snapshot()["counters"]
    obs.disable()
    obs.reset()


def _fit(X, k=4, iters=2):
    table = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X})
    return (KMeans().set_vector_col("features").set_prediction_col("c")
            .set_k(k).set_max_iter(iters).set_seed(1)).fit(table)


def test_a_wide_table_takes_the_kernel_and_a_narrow_one_declines(
        counters, monkeypatch):
    monkeypatch.setattr(clustering, "_lloyd_kernel_platform", lambda m: True)
    n_dev = len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)
    rng = np.random.default_rng(1)
    wide = rng.random((160 * n_dev, 784)).astype(np.float32) * 255
    _fit(wide)  # packed 896 wide
    got = counters()
    assert got["train.kmeans_fits"] == got["train.kmeans_onepass_fits"] == 1
    assert "train.kmeans_onepass_declined" not in got
    assert got["train.pallas_interpreted"] == 1  # on the CPU, and it says so
    _fit(rng.random((160 * n_dev, 30)).astype(np.float32))
    got = counters()
    assert got["train.kmeans_fits"] == 2
    assert got["train.kmeans_onepass_fits"] == 1
    assert got["train.kmeans_onepass_declined"] == 1
    assert got["train.pallas_interpreted"] == 1


@pytest.mark.parametrize("why", ["float64", "two-axes", "few-rows", "k"])
def test_the_rule_declines_what_the_kernel_does_not_take(
        why, counters, monkeypatch):
    monkeypatch.setattr(clustering, "_lloyd_kernel_platform", lambda m: True)
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    Xp, k = np.zeros((256 * n_dev, 896), np.float32), 100
    assert clustering._lloyd_kernel_rows(mesh, Xp, k) == 256
    assert "train.kmeans_onepass_declined" not in counters()
    if why == "float64":
        Xp = Xp.astype(np.float64)
    elif why == "two-axes":
        mesh = Mesh(mesh.devices.reshape(-1, 1), ("data", "model"))
    elif why == "few-rows":
        Xp = Xp[:100 * n_dev]
    else:
        k = 300
    assert clustering._lloyd_kernel_rows(mesh, Xp, k) == 0
    assert counters()["train.kmeans_onepass_declined"] == 1


def test_off_the_chip_the_estimator_keeps_the_xla_tiles(counters):
    rng = np.random.default_rng(2)
    n_dev = len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)
    _fit(rng.random((160 * n_dev, 520)).astype(np.float32))
    got = counters()
    assert got["train.kmeans_fits"] == 1
    assert got["train.kmeans_onepass_fits"] == 0
    assert "train.kmeans_onepass_declined" not in got
    assert "train.pallas_interpreted" not in got
