"""Unified run-telemetry subsystem tests (ISSUE 1): registry semantics,
off-by-default zero-cost hooks, RunReport JSONL persistence, and the
report summary CLI — plus the hot-path wiring (a tiny fit with obs on
must leave a parseable report with the compile/steady split recorded)."""

import json
import os

import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.obs.report import main as report_main


@pytest.fixture(autouse=True)
def _obs_isolated():
    """Every test starts disabled with a clean registry and leaves no
    global state behind (obs is process-wide by design)."""
    import flink_ml_tpu.obs.report as _report_mod

    obs.disable()
    obs.reset()
    _report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    _report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


class TestRegistry:
    def test_counters_gauges_timings_roundtrip(self):
        obs.enable()
        obs.counter_add("c.a")
        obs.counter_add("c.a", 4)
        obs.gauge_set("g.x", 7.5)
        obs.observe("t.step", 0.25)
        obs.observe("t.step", 0.75)
        snap = obs.registry().snapshot()
        assert snap["counters"]["c.a"] == 5
        assert snap["gauges"]["g.x"] == 7.5
        t = snap["timings"]["t.step"]
        assert t["count"] == 2
        assert t["total_s"] == pytest.approx(1.0)
        assert t["min_s"] == pytest.approx(0.25)
        assert t["max_s"] == pytest.approx(0.75)
        assert t["mean_s"] == pytest.approx(0.5)
        obs.reset()
        assert obs.registry().snapshot() == {
            "counters": {}, "gauges": {}, "timings": {}
        }

    def test_disabled_hooks_record_nothing(self):
        assert not obs.enabled()
        obs.counter_add("c.off")
        obs.gauge_set("g.off", 1.0)
        obs.observe("t.off", 1.0)
        with obs.phase("p.off"):
            pass
        with obs.span("s.off") as s:
            pass
        assert s is None and obs.span("a") is obs.phase("b")
        # the span's call sites along a fit: pack, place, train, report
        self._tiny_fit()
        snap = obs.registry().snapshot()
        assert snap == {"counters": {}, "gauges": {}, "timings": {}}

    @staticmethod
    def _tiny_fit():
        from flink_ml_tpu.lib import LogisticRegression
        from flink_ml_tpu.table.schema import DataTypes, Schema
        from flink_ml_tpu.table.table import Table

        X = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        table = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),
                      ("label", "double")),
            {"features": X, "label": (X[:, 0] > 0).astype(np.float64)})
        (LogisticRegression().set_vector_col("features")
         .set_label_col("label").set_prediction_col("pred")
         .set_max_iter(1).fit(table))

    def test_phase_nesting_builds_paths(self):
        obs.enable()
        with obs.phase("fit"):
            with obs.phase("pack_csr"):
                pass
            with obs.phase("pack_csr"):
                pass
        snap = obs.registry().snapshot()
        assert snap["timings"]["phase.fit"]["count"] == 1
        assert snap["timings"]["phase.fit/pack_csr"]["count"] == 2

    def test_phased_decorator(self):
        calls = []

        @obs.phased("work")
        def work(x):
            calls.append(x)
            return x * 2

        assert work(3) == 6  # disabled: plain passthrough
        obs.enable()
        assert work(4) == 8
        snap = obs.registry().snapshot()
        assert snap["timings"]["phase.work"]["count"] == 1
        assert calls == [3, 4]

    def test_snapshot_is_json_serializable(self):
        obs.enable()
        obs.counter_add("c", 2)
        obs.observe("t", 0.1)
        obs.gauge_set("g", 3.0)
        json.dumps(obs.registry().snapshot())

    def test_timingstat_exporter_fields(self):
        """ISSUE 10: to_dict carries the monotonic count/sum_s and the
        p90 an OpenMetrics summary wants, alongside the existing stats."""
        from flink_ml_tpu.obs.registry import TimingStat

        t = TimingStat()
        for v in range(10):
            t.observe(float(v))
        d = t.to_dict()
        assert d["count"] == 10
        assert d["sum_s"] == d["total_s"] == pytest.approx(45.0)
        # nearest-rank over 0..9: p50 -> 4, p90 -> 8, p99 -> 9
        assert d["p50_s"] == 4.0
        assert d["p90_s"] == 8.0
        assert d["p99_s"] == 9.0

    def test_timingstat_recent_is_the_newest_window(self):
        from flink_ml_tpu.obs.registry import TimingStat

        t = TimingStat()
        for i in range(5):
            t.observe(float(i))
        assert t.recent(3) == [2.0, 3.0, 4.0]
        assert t.recent(100) == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert t.recent(0) == []
        # past the reservoir the ring wraps: recent() must still return
        # the newest-k in arrival order, not a rotated slice
        for i in range(5, t.RESERVOIR + 40):
            t.observe(float(i))
        want = [float(t.RESERVOIR + 40 - k) for k in range(4, 0, -1)]
        assert t.recent(4) == want

    def test_registry_timing_recent_accessor(self):
        obs.enable()
        for i in range(6):
            obs.observe("t.win", float(i))
        assert obs.registry().timing_recent("t.win", 2) == [4.0, 5.0]
        assert obs.registry().timing_recent("t.never", 2) == []


class TestRunReports:
    def test_write_and_load_roundtrip(self, tmp_path):
        obs.enable()
        obs.counter_add("train.epochs", 3)
        path = obs.fit_report(
            "UnitTestEstimator", shape="8x2", extra={"epochs": 3},
            directory=str(tmp_path),
        )
        assert path and os.path.exists(path)
        reports = obs.load_reports(str(tmp_path))
        assert len(reports) == 1
        r = reports[0]
        assert r["kind"] == "fit"
        assert r["name"] == "UnitTestEstimator"
        assert r["git_sha"]
        assert r["device"]["backend"]
        assert r["metrics"]["counters"]["train.epochs"] == 3
        assert r["extra"] == {"epochs": 3}

    def test_fit_reports_carry_per_fit_deltas(self, tmp_path):
        """A process running several fits must not attribute fit 1's
        counters to fit 2's report (the registry is cumulative; the
        reports are scoped)."""
        obs.enable()
        obs.counter_add("train.epochs", 5)
        obs.observe("train.dispatch", 1.0)
        obs.fit_report("FitA", directory=str(tmp_path))
        obs.counter_add("train.epochs", 2)
        obs.observe("train.dispatch", 0.25)
        obs.fit_report("FitB", directory=str(tmp_path))
        obs.fit_report("FitC", directory=str(tmp_path))  # nothing new
        a, b, c = obs.load_reports(str(tmp_path))
        assert a["metrics"]["counters"]["train.epochs"] == 5
        assert b["metrics"]["counters"]["train.epochs"] == 2
        assert b["metrics"]["timings"]["train.dispatch"] == {
            "count": 1, "total_s": 0.25, "mean_s": 0.25,
            # tail quantiles ride along (ISSUE 8, p90 since ISSUE 10),
            # over this fit's own observations (PR 24: the report sorts
            # no whole reservoir inside a fit)
            "p50_s": 0.25, "p90_s": 0.25, "p99_s": 0.25,
        }
        assert c["metrics"]["counters"] == {}
        assert c["metrics"]["timings"] == {}

    def test_fit_delta_survives_registry_reset(self, tmp_path):
        obs.enable()
        obs.counter_add("c", 10)
        obs.fit_report("A", directory=str(tmp_path))
        obs.reset()  # a new workload scope
        obs.counter_add("c", 3)
        obs.fit_report("B", directory=str(tmp_path))
        _, b = obs.load_reports(str(tmp_path))
        # a reset invalidates the previous totals: report the new value,
        # never a negative delta
        assert b["metrics"]["counters"]["c"] == 3

    def test_fit_delta_detects_reset_even_at_equal_totals(self, tmp_path):
        """An obs.reset() between two jobs must not make the later job's
        fit report drop counters whose post-reset totals land exactly on
        the pre-reset ones (one fused fit per job is the COMMON case)."""
        obs.enable()
        obs.counter_add("train.fused_runs")
        obs.fit_report("A", directory=str(tmp_path))
        obs.reset()
        obs.counter_add("train.fused_runs")  # same total as before: 1
        obs.fit_report("B", directory=str(tmp_path))
        _, b = obs.load_reports(str(tmp_path))
        assert b["metrics"]["counters"]["train.fused_runs"] == 1

    def test_fit_report_noop_when_disabled(self, tmp_path):
        assert obs.fit_report("X", directory=str(tmp_path)) is None
        assert obs.load_reports(str(tmp_path)) == []

    def test_tiny_fit_emits_parseable_report(self, tmp_path, monkeypatch):
        """The CI smoke contract: a fit with obs enabled writes one JSONL
        line carrying the registry snapshot with the dispatch/sync split
        and the program-build counter."""
        from flink_ml_tpu.lib import LogisticRegression
        from flink_ml_tpu.table.schema import DataTypes, Schema
        from flink_ml_tpu.table.table import Table

        monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path))
        obs.enable()
        rng = np.random.RandomState(0)
        X = rng.randn(64, 4).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        t = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),
                      ("label", "double")),
            {"features": X, "label": y},
        )
        model = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("p")
                 .set_max_iter(3).fit(t))
        assert model.train_epochs_ >= 1
        reports = obs.load_reports()
        fits = [r for r in reports if r["kind"] == "fit"]
        assert fits, "fit wrote no RunReport"
        r = fits[-1]
        assert r["name"] == "LogisticRegression"
        counters = r["metrics"]["counters"]
        assert counters.get("train.fused_runs", 0) >= 1
        assert counters.get("train.epochs", 0) >= 1
        timings = r["metrics"]["timings"]
        assert "train.dispatch" in timings and "train.sync" in timings
        assert r["step_summary"] is not None


def _fit_reports(tmp_path, names, retried=()):
    """One ``fit`` RunReport per name, in order; those in ``retried``
    carry a retry on their account (a FAULT-ASSISTED fit)."""
    obs.enable()
    d = str(tmp_path / "reports")
    for name in names:
        if name in retried:
            obs.counter_add("fault.retries")
        obs.fit_report(name, directory=d)
    return d


class TestReportCli:
    def test_cli_reports_present_is_not_an_error(self, tmp_path, capsys):
        # reports exist and nothing in them is degraded: the summary
        # prints and --check passes
        d = _fit_reports(tmp_path, ["A"])
        assert report_main(["--reports", d, "--check"]) == 0
        out = capsys.readouterr().out
        assert "1 RunReport(s) read" in out
        assert "FAULT-ASSISTED" not in out

    def test_cli_missing_reports_is_one_line_diagnostic(self, tmp_path,
                                                        capsys):
        """ISSUE 10 satellite: a missing or empty reports dir is an
        operator mistake — --check fails with ONE diagnostic line (no
        traceback, no silently-green summary)."""
        missing = str(tmp_path / "never_written")
        assert report_main(["--reports", missing, "--check"]) == 1
        out = capsys.readouterr().out.strip()
        assert len(out.splitlines()) == 1
        assert "no RunReports" in out and missing in out
        # informational mode stays exit 0, but still prints the diagnostic
        assert report_main(["--reports", missing]) == 0
        assert "no RunReports" in capsys.readouterr().out
        # --json keeps the machine-readable shape
        assert report_main(["--reports", missing, "--check", "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["ok"] is False and "no RunReports" in payload["error"]

    def test_cli_last_bounds_the_summarised_reports(self, tmp_path, capsys):
        """--last N reads only the newest N RunReports — the bound for
        an append-only runs.jsonl that has grown for months."""
        d = _fit_reports(tmp_path, ["Old", "New"], retried=("Old",))
        assert report_main(["--reports", d, "--check"]) == 0
        out = capsys.readouterr().out
        assert "FAULT-ASSISTED fit Old" in out
        assert "2 RunReport(s) read" in out
        # bounded to the newest single report, the old fit drops out
        assert report_main(["--reports", d, "--last", "1"]) == 0
        out = capsys.readouterr().out
        assert "Old" not in out
        assert "1 RunReport(s) read" in out
        report_main(["--reports", d, "--last", "1", "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert payload["reports"] == 1 and payload["fault_assisted"] == []


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


class TestHbmGauges:
    """ISSUE 10 satellite: record_hbm_gauges was exercised nowhere in
    tier-1 (the CPU container's devices usually report no memory stats)
    — pin down both halves of its contract."""

    def test_gauges_appear_under_hbm_prefix(self, monkeypatch):
        import jax

        obs.enable()
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice({"bytes_in_use": 10, "peak_bytes_in_use": 30,
                         "bytes_limit": 100}),
            _FakeDevice({"bytes_in_use": 20, "peak_bytes_in_use": 25,
                         "bytes_limit": 100}),
        ])
        obs.record_hbm_gauges()
        gauges = obs.registry().snapshot()["gauges"]
        # max over local devices, each key under hbm.*
        assert gauges["hbm.bytes_in_use"] == 20
        assert gauges["hbm.peak_bytes_in_use"] == 30
        assert gauges["hbm.bytes_limit"] == 100
        assert all(k.startswith("hbm.") for k in gauges)

    def test_custom_prefix(self, monkeypatch):
        import jax

        obs.enable()
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice({"bytes_in_use": 7}),
        ])
        obs.record_hbm_gauges(prefix="post_spill")
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges == {"post_spill.bytes_in_use": 7}

    def test_noop_when_backend_reports_no_stats(self, monkeypatch):
        import jax

        obs.enable()
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice(None), _FakeDevice({})])
        obs.record_hbm_gauges()  # must not raise
        assert obs.registry().snapshot()["gauges"] == {}

    def test_partial_stats_record_what_exists(self, monkeypatch):
        import jax

        obs.enable()
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice({"bytes_in_use": 5}),  # no peak / limit keys
        ])
        obs.record_hbm_gauges()
        assert obs.registry().snapshot()["gauges"] == {
            "hbm.bytes_in_use": 5}

    def test_real_cpu_backend_never_raises(self):
        obs.enable()
        obs.record_hbm_gauges()  # whatever this backend reports: no error
        gauges = obs.registry().snapshot()["gauges"]
        assert all(k.startswith("hbm.") for k in gauges)

    def test_disabled_is_a_noop(self, monkeypatch):
        import jax

        assert not obs.enabled()
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _FakeDevice({"bytes_in_use": 10})])
        obs.record_hbm_gauges()
        assert obs.registry().snapshot()["gauges"] == {}


class TestHotPathWiring:
    def test_chunked_table_counts_parsed_chunks(self, tmp_path):
        from flink_ml_tpu.table.schema import DataTypes, Schema
        from flink_ml_tpu.table.sources import ChunkedTable, CsvSource

        p = tmp_path / "t.csv"
        p.write_text("".join(f"{i},{i % 2}\n" for i in range(10)))
        schema = Schema.of(("x", DataTypes.DOUBLE), ("label", "double"))
        chunked = ChunkedTable(CsvSource(str(p), schema), chunk_rows=4)
        list(chunked.chunks())  # disabled: no counts
        assert obs.registry().counter("source.chunks_parsed") == 0
        obs.enable()
        n = sum(t.num_rows() for t in chunked.chunks())
        assert n == 10
        assert obs.registry().counter("source.chunks_parsed") == 3
        assert obs.registry().counter("source.rows_parsed") == 10

    def test_pack_phase_recorded(self):
        from flink_ml_tpu.lib.common import pack_minibatches

        obs.enable()
        X = np.zeros((16, 3), dtype=np.float32)
        y = np.zeros((16,), dtype=np.float64)
        pack_minibatches(X, y, 1, 8)
        snap = obs.registry().snapshot()
        assert snap["timings"]["phase.pack_dense"]["count"] == 1
