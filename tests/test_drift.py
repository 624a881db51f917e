"""Data-plane observability tests (ISSUE 11): the streaming distribution
sketches (merge associativity, rank-error bounds, NaN/null parity with
the quarantine counters), the DriftMonitor (reference snapshot at
deploy, sidecar-commit persistence, PSI/KS judgment), the tap wiring
(quarantine boundary, fused plan entry, serving demux, the owner rule),
the third SLO (``slo.burning.drift`` -> reason-coded ``/readyz`` ->
``drift_breach`` black box), the OpenMetrics histogram families, and
the ``obs drift`` CLI."""

import json
import os
import urllib.request

import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.obs import drift, flight, slo, telemetry
from flink_ml_tpu.obs.sketch import ColumnSketch, QuantileSketch, ks, psi
from flink_ml_tpu.serve import quarantine
from flink_ml_tpu.serve.breaker import reset_breakers
from flink_ml_tpu.serve.errors import ModelIntegrityError
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table


@pytest.fixture(autouse=True)
def _drift_isolated(monkeypatch, tmp_path):
    """Clean process-global planes per test: registry, flight, breakers,
    quarantine store, the default drift monitor, and every registered
    telemetry source (drift monitors register histogram providers)."""
    monkeypatch.setenv("FMT_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("FMT_TELEMETRY_PORT", raising=False)
    monkeypatch.delenv("FMT_DRIFT", raising=False)
    obs.enable()
    obs.reset()
    flight.reset()
    reset_breakers()
    quarantine.reset()
    drift.reset()
    yield
    drift.reset()
    obs.disable()
    obs.reset()
    flight.reset()
    reset_breakers()
    quarantine.reset()
    with telemetry._SOURCES_LOCK:
        telemetry._READINESS_SOURCES.clear()
        telemetry._STATUS_SOURCES.clear()
        telemetry._HISTOGRAM_SOURCES.clear()


def _rank_err(data, sketch, qs=(0.01, 0.05, 0.25, 0.5, 0.75, 0.95, 0.99)):
    """Worst rank error of the sketch's quantile estimates: where the
    estimate actually sits in the sorted data vs where it should."""
    srt = np.sort(data)
    worst = 0.0
    for q in qs:
        est = sketch.quantile(q)
        rank = np.searchsorted(srt, est) / len(srt)
        worst = max(worst, abs(rank - q))
    return worst


class TestQuantileSketch:
    def test_merge_equals_streaming(self):
        """merge(a, b, c) must hold exactly the points one sketch
        streaming a+b+c saw — window rotation and reference persistence
        both lean on this."""
        rng = np.random.RandomState(0)
        parts = [rng.randn(1000), rng.lognormal(0, 1, 1000),
                 rng.randn(1000) * 5 - 2]
        streamed = QuantileSketch()
        for p in parts:
            streamed.update(p)
        merged = QuantileSketch()
        for p in parts:
            s = QuantileSketch()
            s.update(p)
            merged.merge(s)
        assert merged.count == streamed.count
        assert merged.total == pytest.approx(streamed.total)
        qs = [0.05, 0.25, 0.5, 0.75, 0.95, 0.99]
        assert merged.quantiles(qs) == streamed.quantiles(qs)

    def test_merge_associativity(self):
        rng = np.random.RandomState(1)
        a, b, c = (rng.randn(500), rng.lognormal(0, 2, 500),
                   -rng.pareto(1.5, 500))
        s = [QuantileSketch() for _ in range(3)]
        for sk, d in zip(s, (a, b, c)):
            sk.update(d)

        def clone(sk):
            return QuantileSketch.from_dict(sk.to_dict())

        ab_c = clone(clone(s[0]).merge(s[1])).merge(s[2])
        a_bc = clone(s[0]).merge(clone(s[1]).merge(s[2]))
        qs = [0.1, 0.5, 0.9]
        assert ab_c.quantiles(qs) == a_bc.quantiles(qs)
        assert ab_c.count == a_bc.count

    @pytest.mark.parametrize("name,maker", [
        ("normal", lambda rng: rng.randn(40_000)),
        ("heavy_tail", lambda rng: rng.lognormal(0, 2, 40_000)),
        ("neg_heavy_tail", lambda rng: -rng.lognormal(0, 2, 40_000)),
        ("bimodal", lambda rng: np.concatenate(
            [rng.randn(20_000) - 10, rng.randn(20_000) + 10])),
        ("pareto", lambda rng: rng.pareto(1.2, 40_000) + 1),
    ])
    def test_rank_error_bound_adversarial(self, name, maker):
        """Estimated quantiles must sit within 2% rank of the target on
        adversarial shapes — heavy tails, bimodal gaps, signed data —
        fed in chunks like the serving tap does."""
        rng = np.random.RandomState(7)
        data = maker(rng)
        sketch = QuantileSketch(alpha=0.01)
        for chunk in np.array_split(data, 17):
            sketch.update(chunk)
        assert _rank_err(data, sketch) <= 0.02, name

    def test_constant_distribution_value_exact(self):
        """A constant column (rank error is meaningless — every value IS
        every quantile): the estimate must be within the alpha relative
        bound of the constant."""
        sketch = QuantileSketch(alpha=0.01)
        sketch.update(np.full(10_000, 3.7))
        for q in (0.01, 0.5, 0.99):
            assert sketch.quantile(q) == pytest.approx(3.7, rel=0.02)
        assert sketch.count == 10_000

    def test_relative_error_bound_positive(self):
        """The DDSketch contract on uncollapsed one-sided data: every
        quantile within alpha relative of the true value."""
        rng = np.random.RandomState(3)
        data = rng.pareto(1.2, 30_000) + 1
        sketch = QuantileSketch(alpha=0.01)
        sketch.update(data)
        for q in (0.05, 0.5, 0.95, 0.99):
            true = np.quantile(data, q)
            assert sketch.quantile(q) == pytest.approx(true, rel=0.025)

    def test_fixed_memory_collapse(self):
        """Magnitudes spanning 12 decades under a tight bin budget: the
        bin count must hold at the cap, with the error pushed into the
        near-zero region — the upper quantiles (where drift statistics
        live) keep their relative bound, and the collapsed low end
        degrades toward 0, never upward."""
        rng = np.random.RandomState(5)
        data = 10.0 ** rng.uniform(-6, 6, 50_000)
        sketch = QuantileSketch(alpha=0.01, max_bins=256)
        for chunk in np.array_split(data, 23):
            sketch.update(chunk)
        assert len(sketch.pos) + len(sketch.neg) + 1 <= 256
        for q in (0.9, 0.99):
            true = np.quantile(data, q)
            assert sketch.quantile(q) == pytest.approx(true, rel=0.05)
        # the low tail absorbed the collapse: estimates can only shrink
        assert sketch.quantile(0.05) <= np.quantile(data, 0.05)

    def test_rejects_non_finite(self):
        sketch = QuantileSketch()
        with pytest.raises(ValueError, match="finite"):
            sketch.update(np.array([1.0, np.nan]))
        with pytest.raises(ValueError, match="finite"):
            sketch.update(np.array([np.inf]))

    def test_serialization_round_trip(self):
        rng = np.random.RandomState(9)
        sketch = QuantileSketch()
        sketch.update(rng.randn(5000) * 3 + 1)
        again = QuantileSketch.from_dict(
            json.loads(json.dumps(sketch.to_dict()))
        )
        qs = [0.05, 0.5, 0.95]
        assert again.quantiles(qs) == sketch.quantiles(qs)
        assert again.count == sketch.count

    def test_histogram_export_compacted(self):
        rng = np.random.RandomState(11)
        sketch = QuantileSketch()
        sketch.update(rng.lognormal(0, 2, 20_000))
        bounds, cum = sketch.histogram(max_buckets=16)
        assert len(bounds) <= 16
        assert bounds == sorted(bounds)
        assert cum == sorted(cum)
        assert cum[-1] == sketch.count


class TestColumnSketch:
    def test_nan_null_parity_with_quarantine(self):
        """The sketch's bad-value tallies and the quarantine boundary's
        reason codes must agree: the same NaN/None/Inf rows, counted the
        same way, from the same batch."""
        from flink_ml_tpu.ops.vector import DenseVector

        per_row = [1.0, np.nan, None, 2.0, None, np.inf]
        vectors = np.array(
            [None if v is None else DenseVector(np.array([v]))
             for v in per_row],
            dtype=object,
        )
        table = Table.from_columns(
            Schema.of(("x", DataTypes.DENSE_VECTOR)), {"x": vectors}
        )
        verdict = quarantine.validate_feature_batch(
            table, dim=1, vector_col="x"
        )
        assert verdict is not None
        good, reasons = verdict
        quarantine.emit("parity", table, good, reasons)
        counts = {
            "nan_inf": obs.registry().counter("serve.quarantined.nan_inf"),
            "null": obs.registry().counter("serve.quarantined.null"),
        }
        cs = ColumnSketch()
        cs.update(np.array(per_row, dtype=object))
        # the quarantine validator folds NaN and Inf into one nan_inf
        # reason; the sketch keeps them separate — their sum must match
        assert cs.nans + cs.infs == counts["nan_inf"] == 2
        assert cs.nulls == counts["null"] == 2
        assert cs.n == 2  # the servable rows
        assert cs.rows == len(per_row)

    def test_moments_match_numpy(self):
        rng = np.random.RandomState(2)
        data = rng.randn(10_000) * 4 + 3
        cs = ColumnSketch()
        for chunk in np.array_split(data, 7):
            cs.update(chunk)
        assert cs.mean == pytest.approx(data.mean(), rel=1e-9)
        assert cs.var == pytest.approx(data.var(), rel=1e-9)

    def test_merge_combines_moments_and_tallies(self):
        rng = np.random.RandomState(4)
        a_data, b_data = rng.randn(3000), rng.randn(2000) + 5
        a, b = ColumnSketch(), ColumnSketch()
        a.update(a_data)
        b.update(b_data)
        b.update(np.array([np.nan]))
        a.merge(b)
        both = np.concatenate([a_data, b_data])
        assert a.n == 5000
        assert a.nans == 1
        assert a.mean == pytest.approx(both.mean(), rel=1e-9)
        assert a.var == pytest.approx(both.var(), rel=1e-9)


class TestDriftStatistics:
    def test_psi_stable_vs_shifted(self):
        rng = np.random.RandomState(6)
        ref, same = QuantileSketch(), QuantileSketch()
        shifted, scaled = QuantileSketch(), QuantileSketch()
        ref.update(rng.randn(20_000))
        same.update(rng.randn(20_000))
        shifted.update(rng.randn(20_000) + 2)
        scaled.update(rng.randn(20_000) * 3)
        assert psi(ref, same) < 0.05
        assert psi(ref, shifted) > 1.0
        assert psi(ref, scaled) > 0.5

    def test_ks_bounds_and_detection(self):
        rng = np.random.RandomState(8)
        ref, same, shifted = (QuantileSketch() for _ in range(3))
        ref.update(rng.randn(20_000))
        same.update(rng.randn(20_000))
        shifted.update(rng.randn(20_000) + 2)
        assert 0.0 <= ks(ref, same) < 0.05
        assert 0.5 < ks(ref, shifted) <= 1.0

    def test_constant_reference_degenerate(self):
        ref, live = QuantileSketch(), QuantileSketch()
        ref.update(np.full(1000, 2.0))
        live.update(np.full(1000, 2.0))
        assert psi(ref, live) == pytest.approx(0.0, abs=1e-6)
        moved = QuantileSketch()
        moved.update(np.full(1000, 9.0))
        assert psi(ref, moved) > 1.0

    def test_empty_sketches(self):
        a, b = QuantileSketch(), QuantileSketch()
        assert psi(a, b) == 0.0
        assert ks(a, b) == 0.0


def _features_table(rng, n, shift=0.0, dim=4):
    X = (rng.randn(n, dim) + shift).astype(np.float32)
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X}
    )


_SPEC = {"dim": 4, "vector_col": "features"}


class TestDriftMonitor:
    def _monitor(self, **kw):
        kw.setdefault("ref_target", 100)
        kw.setdefault("threshold", 0.2)
        kw.setdefault("min_window_rows", 32)
        kw.setdefault("window", 3600)
        return drift.DriftMonitor(name="test", **kw)

    def test_reference_fills_then_freezes(self):
        rng = np.random.RandomState(0)
        mon = self._monitor()
        try:
            mon.observe_input(_features_table(rng, 64), _SPEC)
            mon.roll()
            assert not mon.reference_complete
            mon.observe_input(_features_table(rng, 64), _SPEC)
            mon.roll()
            assert mon.reference_complete
            # post-freeze rows land in the live window
            mon.observe_input(_features_table(rng, 50), _SPEC)
            status = mon.status()
            assert status["reference"]["complete"]
            assert status["live_rows"] == 50
            assert status["reference"]["rows"] == 128
        finally:
            mon.close()

    def test_judge_gates_then_detects_shift(self):
        rng = np.random.RandomState(1)
        mon = self._monitor()
        try:
            assert mon.judge() is None  # reference still filling
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            assert mon.judge() is None  # live window below min_rows
            mon.observe_input(_features_table(rng, 16), _SPEC)
            assert mon.judge() is None
            # allow_small (the burning-SLO re-judge) still judges
            assert mon.judge(allow_small=True) is not None
            mon.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
            verdict = mon.judge()
            assert verdict is not None
            assert verdict["burn"] > 1.0
            assert verdict["worst_column"].startswith("features[")
            assert verdict["breaching"]
            worst = verdict["columns"][0]
            assert {"column", "psi", "ks", "ref", "live"} <= set(worst)
        finally:
            mon.close()

    def test_stable_traffic_does_not_burn(self):
        rng = np.random.RandomState(2)
        mon = self._monitor()
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            mon.observe_input(_features_table(rng, 128), _SPEC)
            verdict = mon.judge()
            assert verdict is not None
            assert verdict["burn"] < 1.0
        finally:
            mon.close()

    def test_window_rotation_forgets_old_traffic(self):
        rng = np.random.RandomState(3)
        mon = self._monitor(window=0.0)  # rotate on every roll
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            mon.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
            mon.roll()  # shifted rows -> previous window
            assert mon.judge(allow_small=True)["burn"] > 1.0
            mon.observe_input(_features_table(rng, 64), _SPEC)
            mon.roll()  # shifted window rotated out
            mon.observe_input(_features_table(rng, 64), _SPEC)
            assert mon.judge()["burn"] < 1.0
        finally:
            mon.close()

    def test_window_row_cap_sketches_the_budget_and_counts_the_rest(
            self, monkeypatch):
        """The armed steady state: once a live window holds
        FMT_DRIFT_WINDOW_ROWS sketched rows, further batches are counted
        (``drift.rows_skipped``, the seen-row denominator) and not
        sketched, and every observed row is one or the other."""
        monkeypatch.setenv("FMT_DRIFT_WINDOW_ROWS", "96")
        rng = np.random.RandomState(5)
        mon = self._monitor()
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            assert mon.reference_complete
            reg = obs.registry()
            rows0 = reg.counter("drift.rows")
            for _ in range(5):
                mon.observe_input(_features_table(rng, 48), _SPEC)
            updates = reg.counter("drift.sketch_updates")
            assert reg.counter("drift.rows") - rows0 == 96
            assert reg.counter("drift.rows_skipped") == 3 * 48
            assert mon.status()["live_rows"] == 96
            # past the cap a batch costs the check alone
            mon.observe_input(_features_table(rng, 48), _SPEC)
            assert reg.counter("drift.sketch_updates") == updates
            assert reg.counter("drift.rows_skipped") == 4 * 48
        finally:
            mon.close()

    def test_quarantine_reason_rates(self):
        rng = np.random.RandomState(4)
        mon = self._monitor()
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.observe_reasons({"nan_inf": 2})
            mon.roll()
            mon.observe_input(_features_table(rng, 64), _SPEC)
            mon.observe_reasons({"nan_inf": 32})
            rates = mon.reason_rates()
            assert rates["reference"]["nan_inf"] == pytest.approx(2 / 128)
            assert rates["live"]["nan_inf"] == pytest.approx(32 / 64)
        finally:
            mon.close()

    def test_persist_and_reload(self, tmp_path):
        rng = np.random.RandomState(5)
        model_dir = tmp_path / "model"
        model_dir.mkdir()
        mon = self._monitor(persist_path=str(model_dir))
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            ref_path = model_dir / drift.REFERENCE_FILE
            assert ref_path.exists()
            assert (model_dir / (drift.REFERENCE_FILE
                                 + ".commit.json")).exists()
        finally:
            mon.close()
        # a restart adopts the committed baseline instead of relearning
        mon2 = self._monitor()
        try:
            assert mon2.load_reference(str(model_dir))
            assert mon2.reference_complete
            mon2.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
            assert mon2.judge()["burn"] > 1.0
        finally:
            mon2.close()

    def test_corrupt_reference_raises_integrity_error(self, tmp_path):
        rng = np.random.RandomState(6)
        model_dir = tmp_path / "model"
        model_dir.mkdir()
        mon = self._monitor(persist_path=str(model_dir))
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
        finally:
            mon.close()
        path = model_dir / drift.REFERENCE_FILE
        with open(path, "a") as f:
            f.write("rot")
        mon2 = self._monitor()
        try:
            with pytest.raises(ModelIntegrityError):
                mon2.load_reference(str(model_dir))
        finally:
            mon2.close()

    def test_missing_reference_returns_false(self, tmp_path):
        mon = self._monitor()
        try:
            assert not mon.load_reference(str(tmp_path))
        finally:
            mon.close()

    def test_reset_reference(self):
        rng = np.random.RandomState(7)
        mon = self._monitor()
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            assert mon.reference_complete
            mon.reset_reference()
            assert not mon.reference_complete
            # the new population becomes the new baseline: shifted rows
            # now DEFINE normal instead of breaching
            mon.observe_input(_features_table(rng, 128, shift=4.0), _SPEC)
            mon.roll()
            mon.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
            assert mon.judge()["burn"] < 1.0
        finally:
            mon.close()

    def test_bootstrap_seeds_reference(self):
        rng = np.random.RandomState(8)
        mon = self._monitor(ref_target=64)
        try:
            warm = _features_table(rng, 64)
            mon.bootstrap(warm)
            mon.roll()
            assert mon.reference_complete
        finally:
            mon.close()

    def test_sparse_column_sketches_nnz(self):
        from flink_ml_tpu.ops.vector import SparseVector

        rng = np.random.RandomState(9)
        rows = np.empty(32, dtype=object)
        for i in range(32):
            nnz = rng.randint(1, 6)
            idx = np.sort(rng.choice(50, size=nnz, replace=False))
            rows[i] = SparseVector(50, idx, np.ones(nnz))
        table = Table.from_columns(
            Schema.of(("f", DataTypes.SPARSE_VECTOR)), {"f": rows}
        )
        mon = self._monitor(ref_target=16)
        try:
            mon.observe_input(table, {"dim": 50, "vector_col": "f"})
            mon.roll()
            status = mon.status()
            assert status["reference"]["columns"] == 1
            with mon._lock:
                assert "f.nnz" in mon._ref
        finally:
            mon.close()


class TestDriftTaps:
    """The wiring: taps at the quarantine boundary / fused entry /
    transform exit feed the scoped monitor exactly once per row."""

    def _fitted_pipeline(self, rng, n=512, dim=4):
        from flink_ml_tpu.api.pipeline import Pipeline
        from flink_ml_tpu.lib import LogisticRegression
        from flink_ml_tpu.lib.feature import StandardScaler

        X = rng.randn(n, dim).astype(np.float32)
        w = rng.randn(dim).astype(np.float32)
        y = (X @ w > 0).astype(np.float64)
        t = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),
                      ("label", "double")),
            {"features": X, "label": y},
        )
        model = Pipeline([
            StandardScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.5).set_max_iter(3),
        ]).fit(t)
        return model, t

    def test_transform_taps_once_per_row(self, monkeypatch):
        """A 2-stage pipeline (both stages validate the same column)
        must sketch each row ONCE — the scope owner rule — and the
        produced prediction column must land as a score sketch."""
        rng = np.random.RandomState(0)
        model, t = self._fitted_pipeline(rng)
        monkeypatch.setenv("FMT_DRIFT", "1")
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "100000")
        drift.reset()
        model.transform(t)
        mon = drift.default_monitor()
        status = mon.status()
        assert status["reference"]["rows"] == t.num_rows()
        with mon._lock:
            cols = dict(mon._ref)
        assert "features[0]" in cols
        assert cols["features[0]"].n == t.num_rows()
        assert "pred" in cols
        assert cols["pred"].n == t.num_rows()
        assert "label" not in cols  # input columns are not scores

    def test_zero_sketch_updates_while_off(self):
        """The off-path contract: with FMT_DRIFT unset, a transform
        performs ZERO sketch updates."""
        rng = np.random.RandomState(1)
        model, t = self._fitted_pipeline(rng)
        obs.reset()
        model.transform(t)
        assert obs.registry().counter("drift.sketch_updates") == 0
        assert obs.registry().counter("drift.rows") == 0

    def test_staged_path_taps_match_fused(self, monkeypatch):
        """FMT_FUSE_TRANSFORM=0 (per-stage serving) must sketch the same
        row count as the fused path — the owner rule dedupes the second
        validating stage."""
        rng = np.random.RandomState(2)
        model, t = self._fitted_pipeline(rng)
        monkeypatch.setenv("FMT_DRIFT", "1")
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "100000")
        monkeypatch.setenv("FMT_FUSE_TRANSFORM", "0")
        drift.reset()
        model.transform(t)
        mon = drift.default_monitor()
        with mon._lock:
            cols = dict(mon._ref)
        assert cols["features[0]"].n == t.num_rows()

    def test_server_taps_and_quarantine_rates(self, monkeypatch):
        """Through the ModelServer: live requests fill the reference,
        then the live window; a poison row is quarantined AND counted in
        the monitor's reason rates (not sketched)."""
        from flink_ml_tpu.serving import ModelServer

        rng = np.random.RandomState(3)
        model, t = self._fitted_pipeline(rng)
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "128")
        server = ModelServer(model, drift=True, max_batch=64)
        try:
            mon = server.drift_monitor
            assert mon is not None
            for i in range(4):
                server.submit(t.slice_rows(i * 32, (i + 1) * 32)).result(
                    timeout=60)
            assert mon.reference_complete
            bad = t.slice_rows(0, 8)
            X = np.array(bad.col("features"), dtype=np.float32, copy=True)
            X[3, 1] = np.nan
            bad = Table.from_columns(bad.schema, {
                "features": X, "label": bad.col("label"),
            })
            res = server.submit(bad).result(timeout=60)
            assert res.num_quarantined == 1
            rates = mon.reason_rates()
            assert rates["live"].get("nan_inf", 0) > 0
            status = mon.status()
            assert status["live_rows"] == 7  # survivors only
        finally:
            server.shutdown()

    def test_deploy_resets_reference(self, monkeypatch):
        """A redeploy makes the new version's population the new normal:
        post-deploy shifted traffic must not burn against the OLD
        model's baseline."""
        from flink_ml_tpu.serving import ModelServer

        rng = np.random.RandomState(4)
        model, t = self._fitted_pipeline(rng)
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "64")
        server = ModelServer(model, drift=True, max_batch=64)
        try:
            mon = server.drift_monitor
            for i in range(2):
                server.submit(t.slice_rows(i * 32, (i + 1) * 32)).result(
                    timeout=60)
            assert mon.reference_complete
            server.deploy(model, "v2")
            assert not mon.reference_complete
            assert server.active_version == "v2"
        finally:
            server.shutdown()

    def test_restart_reloads_persisted_reference(self, monkeypatch,
                                                 tmp_path):
        """A path deploy persists its frozen baseline next to the model;
        a second server over the same artifact restarts WITH it instead
        of relearning from (possibly already-shifted) traffic."""
        from flink_ml_tpu.serving import ModelServer

        rng = np.random.RandomState(5)
        model, t = self._fitted_pipeline(rng)
        model_dir = str(tmp_path / "saved")
        model.save(model_dir)
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "64")
        server = ModelServer(path=model_dir, drift=True, max_batch=64)
        try:
            for i in range(2):
                server.submit(t.slice_rows(i * 32, (i + 1) * 32)).result(
                    timeout=60)
            assert server.drift_monitor.reference_complete
        finally:
            server.shutdown()
        assert os.path.exists(os.path.join(model_dir, drift.REFERENCE_FILE))
        server2 = ModelServer(path=model_dir, drift=True, max_batch=64)
        try:
            assert server2.drift_monitor.reference_complete
            assert server2.drift_monitor._loaded_from is not None
        finally:
            server2.shutdown()


class TestDriftSLO:
    def _burning_monitor(self, rng):
        mon = drift.DriftMonitor(name="slo-test", ref_target=100,
                                 threshold=0.2, min_window_rows=32,
                                 window=3600)
        mon.observe_input(_features_table(rng, 128), _SPEC)
        mon.roll()
        mon.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
        return mon

    def test_drift_slo_burns_and_recovers(self, monkeypatch):
        monkeypatch.setenv("FMT_FLIGHT_MIN_S", "0")
        rng = np.random.RandomState(0)
        mon = self._burning_monitor(rng)
        monitor = slo.SLOMonitor(window=3600, drift=mon)
        try:
            assert monitor.armed()
            results = monitor.sample_once()
            assert results[slo.DRIFT_SLO]["burning"]
            assert obs.registry().gauge("slo.burning.drift") == 1.0
            assert obs.registry().gauge("slo.burn_rate.drift") > 1.0
            reasons = monitor.readiness_reasons()
            assert reasons and reasons[0]["reason"] == "drift"
            # recovery: stable traffic replaces the shifted window
            mon.reset_reference()
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
            mon.observe_input(_features_table(rng, 64), _SPEC)
            results = monitor.sample_once()
            assert not results[slo.DRIFT_SLO]["burning"]
            assert obs.registry().gauge("slo.burning.drift") == 0.0
            assert monitor.readiness_reasons() == []
        finally:
            monitor.stop()
            mon.close()

    def test_drift_breach_black_box_names_columns(self, monkeypatch,
                                                  tmp_path):
        """The dump is reason-coded ``drift_breach``; its header names
        the worst column and the ring holds one ``drift.column_breach``
        event per offending column with ref-vs-live quantiles."""
        monkeypatch.setenv("FMT_FLIGHT_MIN_S", "0")
        monkeypatch.setenv("FMT_FLIGHT_DIR", str(tmp_path / "fl"))
        rng = np.random.RandomState(1)
        mon = self._burning_monitor(rng)
        monitor = slo.SLOMonitor(window=3600, drift=mon)
        try:
            monitor.sample_once()
            path = flight.last_dump_path()
            assert path is not None and "drift_breach" in path
            with open(path) as f:
                lines = [json.loads(line) for line in f]
            header = lines[0]
            assert header["reason"] == "drift_breach"
            assert header["slo"] == "drift"
            assert header["worst_column"].startswith("features[")
            col_events = [e for e in lines[1:]
                          if e.get("kind") == "drift.column_breach"]
            assert col_events
            e = col_events[0]
            assert {"column", "psi", "ks", "ref_p50",
                    "live_p50"} <= set(e)
            # the live median really is the shifted one
            assert e["live_p50"] > e["ref_p50"] + 1.0
        finally:
            monitor.stop()
            mon.close()

    def test_min_window_gating_skips_quiet_entry(self):
        rng = np.random.RandomState(2)
        mon = drift.DriftMonitor(name="gate", ref_target=64,
                                 threshold=0.2, min_window_rows=1000,
                                 window=3600)
        monitor = slo.SLOMonitor(window=3600, drift=mon)
        try:
            mon.observe_input(_features_table(rng, 64), _SPEC)
            mon.roll()
            mon.observe_input(_features_table(rng, 64, shift=4.0), _SPEC)
            # 64 shifted live rows < min 1000: no verdict, no gauge flip
            assert monitor.sample_once() == {}
            assert obs.registry().gauge("slo.burning.drift") is None
        finally:
            monitor.stop()
            mon.close()


class TestDriftTelemetrySurfaces:
    def test_histograms_in_metrics_round_trip(self):
        """A monitor's sketches export as OpenMetrics histogram families
        that survive the strict parser, reference and live both."""
        rng = np.random.RandomState(0)
        mon = drift.DriftMonitor(name="metrics", ref_target=64,
                                 window=3600)
        try:
            mon.observe_input(_features_table(rng, 128, dim=2),
                              {"dim": 2, "vector_col": "features"})
            mon.roll()
            mon.observe_input(_features_table(rng, 32, dim=2),
                              {"dim": 2, "vector_col": "features"})
            text = telemetry.render_openmetrics()
            samples = telemetry.parse_openmetrics(text)
            ref_buckets = [k for k in samples
                           if k.startswith("fmt_drift_ref_features_0_")
                           and "_bucket" in k]
            live_buckets = [k for k in samples
                            if k.startswith("fmt_drift_live_features_0_")
                            and "_bucket" in k]
            assert ref_buckets and live_buckets
            inf_key = 'fmt_drift_ref_features_0__bucket{le="+Inf"}'
            assert samples[inf_key] == 128
            assert samples["fmt_drift_ref_features_0__count"] == 128
        finally:
            mon.close()

    def test_statusz_and_readyz_over_http(self, monkeypatch):
        """End-to-end over the real endpoint: /statusz carries the
        per-column drift section, and a burning drift SLO turns /readyz
        503 with the reason-coded ``drift`` entry."""
        rng = np.random.RandomState(1)
        from flink_ml_tpu.serving import ModelServer

        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "64")
        model, t = TestDriftTaps()._fitted_pipeline(rng)
        server = ModelServer(model, drift=True, max_batch=64,
                             telemetry_port=0)
        try:
            for i in range(2):
                server.submit(t.slice_rows(i * 32, (i + 1) * 32)).result(
                    timeout=60)
            Xs = (rng.randn(64, 4) + 5).astype(np.float32)
            shifted = Table.from_columns(t.schema, {
                "features": Xs, "label": np.zeros(64),
            })
            server.submit(shifted).result(timeout=60)
            server._slo.sample_once()

            def get(path):
                url = server.telemetry.url(path)
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        return r.status, r.read().decode()
                except urllib.error.HTTPError as exc:
                    return exc.code, exc.read().decode()

            code, body = get("/statusz")
            assert code == 200
            status = json.loads(body)
            assert status["drift"]["reference"]["complete"]
            assert status["drift"]["columns"]
            code, body = get("/readyz")
            assert code == 503
            reasons = [r["reason"] for r in json.loads(body)["reasons"]]
            assert "drift" in reasons
        finally:
            server.shutdown()


class TestHistogramParserStrictness:
    def _wrap(self, *lines):
        return "\n".join(lines + ("# EOF",)) + "\n"

    def test_valid_histogram_parses(self):
        text = self._wrap(
            "# TYPE h histogram",
            'h_bucket{le="1"} 3',
            'h_bucket{le="2.5"} 7',
            'h_bucket{le="+Inf"} 9',
            "h_count 9",
            "h_sum 14.5",
        )
        samples = telemetry.parse_openmetrics(text)
        assert samples['h_bucket{le="2.5"}'] == 7
        assert samples["h_count"] == 9

    def test_rejects_non_cumulative_buckets(self):
        text = self._wrap(
            "# TYPE h histogram",
            'h_bucket{le="1"} 5',
            'h_bucket{le="2"} 3',
            'h_bucket{le="+Inf"} 5',
            "h_count 5",
        )
        with pytest.raises(ValueError, match="cumulative"):
            telemetry.parse_openmetrics(text)

    def test_rejects_non_ascending_bounds(self):
        text = self._wrap(
            "# TYPE h histogram",
            'h_bucket{le="2"} 3',
            'h_bucket{le="1"} 3',
            'h_bucket{le="+Inf"} 3',
            "h_count 3",
        )
        with pytest.raises(ValueError, match="ascending"):
            telemetry.parse_openmetrics(text)

    def test_rejects_missing_inf_bucket(self):
        text = self._wrap(
            "# TYPE h histogram",
            'h_bucket{le="1"} 3',
        )
        with pytest.raises(ValueError, match=r"\+Inf"):
            telemetry.parse_openmetrics(text)

    def test_rejects_count_mismatch(self):
        text = self._wrap(
            "# TYPE h histogram",
            'h_bucket{le="+Inf"} 3',
            "h_count 4",
        )
        with pytest.raises(ValueError, match="_count"):
            telemetry.parse_openmetrics(text)

    def test_rejects_le_on_summary(self):
        text = self._wrap(
            "# TYPE s summary",
            's{le="1"} 3',
        )
        with pytest.raises(ValueError, match="belong"):
            telemetry.parse_openmetrics(text)

    def test_rejects_bucket_on_counter(self):
        text = self._wrap(
            "# TYPE c counter",
            'c_bucket{le="1"} 3',
        )
        with pytest.raises(ValueError, match="belong"):
            telemetry.parse_openmetrics(text)

    def test_render_parse_round_trip_with_provider(self):
        key = telemetry.register_histograms("rt", lambda: {
            "rt.lat": ([0.5, 1.0, 5.0], [2, 5, 9], 12.5, 9),
        })
        try:
            obs.counter_add("c.x", 3)
            text = telemetry.render_openmetrics()
            samples = telemetry.parse_openmetrics(text)
            assert samples['fmt_rt_lat_bucket{le="0.5"}'] == 2
            assert samples['fmt_rt_lat_bucket{le="+Inf"}'] == 9
            assert samples["fmt_rt_lat_count"] == 9
            assert samples["fmt_rt_lat_sum"] == 12.5
            assert samples["fmt_c_x_total"] == 3
        finally:
            telemetry.unregister_histograms(key)

    def test_empty_provider_histogram(self):
        key = telemetry.register_histograms("empty", lambda: {
            "empty.h": ([], [], 0.0, 0),
        })
        try:
            samples = telemetry.parse_openmetrics(
                telemetry.render_openmetrics())
            assert samples['fmt_empty_h_bucket{le="+Inf"}'] == 0
            assert samples["fmt_empty_h_count"] == 0
        finally:
            telemetry.unregister_histograms(key)

    def test_broken_provider_never_kills_a_scrape(self):
        def boom():
            raise RuntimeError("provider died")

        key = telemetry.register_histograms("boom", boom)
        try:
            obs.counter_add("c.ok", 1)
            samples = telemetry.parse_openmetrics(
                telemetry.render_openmetrics())
            assert samples["fmt_c_ok_total"] == 1
        finally:
            telemetry.unregister_histograms(key)


class TestDriftReportsAndCLI:
    def test_serving_report_carries_drift_and_check_prints_line(
            self, monkeypatch, tmp_path, capsys):
        from flink_ml_tpu.obs.report import drift_runs, load_reports
        from flink_ml_tpu.serving import ModelServer

        reports_dir = str(tmp_path / "reports")
        monkeypatch.setenv("FMT_OBS_REPORTS", reports_dir)
        monkeypatch.setenv("FMT_DRIFT_REF_ROWS", "64")
        rng = np.random.RandomState(0)
        model, t = TestDriftTaps()._fitted_pipeline(rng)
        server = ModelServer(model, drift=True, max_batch=64)
        try:
            for i in range(2):
                server.submit(t.slice_rows(i * 32, (i + 1) * 32)).result(
                    timeout=60)
            Xs = (rng.randn(64, 4) + 5).astype(np.float32)
            server.submit(Table.from_columns(t.schema, {
                "features": Xs, "label": np.zeros(64),
            })).result(timeout=60)
        finally:
            server.shutdown()
        reports = load_reports(reports_dir)
        rows = drift_runs(reports)
        assert rows and rows[0]["kind"] == "serving"
        assert rows[0]["reference_complete"]
        assert rows[0]["breaching"]
        # the CLI renders the same report
        rc = drift.drift_main(["--reports", reports_dir])
        out = capsys.readouterr().out
        assert rc == 0
        assert "BREACH" in out
        assert "features[" in out

    def test_check_json_includes_drift_rows(self, monkeypatch, tmp_path):
        from flink_ml_tpu.obs.report import RunReport, main, \
            write_run_report

        reports_dir = str(tmp_path / "reports")
        report = RunReport(
            kind="serving", name="ModelServer", ts=1.0, git_sha="abc",
            device={"backend": "cpu"},
            extra={"drift": {
                "monitor": "serving", "reference_complete": True,
                "threshold": 0.2, "live_rows": 100,
                "columns": [{"column": "pred", "psi": 0.5, "ks": 0.4,
                             "ref": {"p05": 0, "p50": 1, "p95": 2},
                             "live": {"p05": 2, "p50": 3, "p95": 4}}],
            }},
        )
        write_run_report(report, reports_dir)
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            main(["--reports", reports_dir, "--json"])
        payload = json.loads(buf.getvalue())
        assert payload["drift"]
        assert payload["drift"][0]["worst_column"] == "pred"
        assert payload["drift"][0]["breaching"] is True

    def test_cli_renders_persisted_reference(self, tmp_path, capsys):
        rng = np.random.RandomState(1)
        model_dir = tmp_path / "model"
        model_dir.mkdir()
        mon = drift.DriftMonitor(name="cli", ref_target=64,
                                 persist_path=str(model_dir))
        try:
            mon.observe_input(_features_table(rng, 128), _SPEC)
            mon.roll()
        finally:
            mon.close()
        rc = drift.drift_main(["--ref", str(model_dir)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "features[0]" in out
