"""Live telemetry plane tests (ISSUE 10): OpenMetrics rendering + the
strict parser, the embedded endpoint (liveness vs. reason-coded
readiness, statusz), the ModelServer lifecycle wiring, and the SLO
burn-rate monitor (gauges, flight breach dumps, readiness feed)."""

import json
import threading
import urllib.request
from urllib.error import HTTPError, URLError

import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.fault import pressure
from flink_ml_tpu.obs import flight, slo, telemetry
from flink_ml_tpu.obs.telemetry import (
    TelemetryServer,
    family_name,
    parse_openmetrics,
    render_openmetrics,
)
from flink_ml_tpu.serve.breaker import breaker, reset_breakers


@pytest.fixture(autouse=True)
def _telemetry_isolated(monkeypatch, tmp_path):
    """Every test starts with a clean registry, no breakers, no pressure
    state, no registered telemetry sources, and flight dumps routed to a
    throwaway dir — the plane is process-global by design."""
    monkeypatch.setenv("FMT_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.delenv("FMT_TELEMETRY_PORT", raising=False)
    obs.enable()
    obs.reset()
    flight.reset()
    reset_breakers()
    pressure.reset_states()
    yield
    telemetry.stop()
    obs.disable()
    obs.reset()
    flight.reset()
    reset_breakers()
    pressure.reset_states()
    # a test that leaked a source must not poison the next test's probe
    with telemetry._SOURCES_LOCK:
        telemetry._READINESS_SOURCES.clear()
        telemetry._STATUS_SOURCES.clear()
        telemetry._HISTOGRAM_SOURCES.clear()


def _get(server, path):
    try:
        with urllib.request.urlopen(server.url(path), timeout=10) as r:
            return r.status, r.read().decode()
    except HTTPError as exc:
        return exc.code, exc.read().decode()


@pytest.fixture()
def endpoint():
    server = TelemetryServer(port=0).start()
    yield server
    server.stop()


class TestOpenMetricsRendering:
    def test_counter_gauge_summary_families(self):
        obs.counter_add("c.a", 5)
        obs.gauge_set("g.x", 7.5)
        obs.observe("t.step", 0.25)
        obs.observe("t.step", 0.75)
        text = render_openmetrics()
        lines = text.splitlines()
        assert "# TYPE fmt_c_a counter" in lines
        assert "fmt_c_a_total 5" in lines
        assert "# TYPE fmt_g_x gauge" in lines
        assert "fmt_g_x 7.5" in lines
        assert "# TYPE fmt_t_step summary" in lines
        assert 'fmt_t_step{quantile="0.5"} 0.25' in lines
        assert 'fmt_t_step{quantile="0.9"} 0.75' in lines
        assert 'fmt_t_step{quantile="0.99"} 0.75' in lines
        assert "fmt_t_step_count 2" in lines
        assert "fmt_t_step_sum 1" in lines
        assert lines[-1] == "# EOF"
        assert text.endswith("\n")

    def test_name_sanitization(self):
        # fused-plan breaker gauges carry brackets and plus signs
        obs.gauge_set("serve.breaker_state.FusedPlan[A+B]", 1.0)
        text = render_openmetrics()
        assert "fmt_serve_breaker_state_FusedPlan_A_B_ 1" in text
        parse_openmetrics(text)  # and the result is still valid

    def test_total_suffix_never_doubles(self):
        # OpenMetrics reserves _total for the counter SAMPLE: a registry
        # name already ending in _total must not render fam_total_total
        obs.counter_add("rows_total", 3)
        text = render_openmetrics()
        assert "# TYPE fmt_rows counter" in text
        assert "fmt_rows_total 3" in text
        assert "_total_total" not in text

    def test_renders_and_parses_roundtrip(self):
        obs.counter_add("serving.requests", 42)
        obs.counter_add("serving.shed.queue_full", 2)
        obs.gauge_set("pressure.cap.serving.batch", 128)
        for i in range(20):
            obs.observe("serving.request_latency_ms", float(i))
        samples = parse_openmetrics(render_openmetrics())
        assert samples[family_name("serving.requests") + "_total"] == 42
        assert samples[family_name("pressure.cap.serving.batch")] == 128
        fam = family_name("serving.request_latency_ms")
        assert samples[fam + "_count"] == 20
        assert samples[fam + "_sum"] == float(sum(range(20)))
        assert samples[f'{fam}{{quantile="0.9"}}'] >= \
            samples[f'{fam}{{quantile="0.5"}}']

    def test_empty_registry_is_valid(self):
        obs.reset()
        assert parse_openmetrics(render_openmetrics()) == {}


class TestOpenMetricsParser:
    def test_rejects_missing_eof(self):
        with pytest.raises(ValueError, match="EOF"):
            parse_openmetrics("# TYPE a counter\na_total 1\n")

    def test_rejects_sample_without_family(self):
        with pytest.raises(ValueError, match="before any"):
            parse_openmetrics("a_total 1\n# EOF\n")

    def test_rejects_sample_of_wrong_family(self):
        bad = "# TYPE a counter\nb_total 1\n# EOF\n"
        with pytest.raises(ValueError, match="does not belong"):
            parse_openmetrics(bad)

    def test_rejects_gauge_with_total_suffix(self):
        bad = "# TYPE a gauge\na_total 1\n# EOF\n"
        with pytest.raises(ValueError, match="does not belong"):
            parse_openmetrics(bad)

    def test_rejects_duplicate_family(self):
        bad = "# TYPE a counter\na_total 1\n# TYPE a counter\n# EOF\n"
        with pytest.raises(ValueError, match="duplicate family"):
            parse_openmetrics(bad)

    def test_rejects_malformed_sample(self):
        bad = "# TYPE a counter\na_total one\n# EOF\n"
        with pytest.raises(ValueError, match="malformed"):
            parse_openmetrics(bad)


class TestTelemetryServer:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("FMT_TELEMETRY_PORT", raising=False)
        assert telemetry.env_port() is None
        assert telemetry.start() is None  # module-level: a quiet no-op
        with pytest.raises(ValueError, match="not configured"):
            TelemetryServer()

    def test_env_port_parsing(self, monkeypatch):
        monkeypatch.setenv("FMT_TELEMETRY_PORT", "0")
        assert telemetry.env_port() == 0
        monkeypatch.setenv("FMT_TELEMETRY_PORT", "9464")
        assert telemetry.env_port() == 9464
        monkeypatch.setenv("FMT_TELEMETRY_PORT", "nope")
        assert telemetry.env_port() is None

    def test_healthz_liveness(self, endpoint):
        status, body = _get(endpoint, "/healthz")
        assert status == 200
        payload = json.loads(body)
        assert payload["ok"] is True and payload["uptime_s"] >= 0

    def test_metrics_serves_the_registry(self, endpoint):
        obs.counter_add("c.scraped", 7)
        status, body = _get(endpoint, "/metrics")
        assert status == 200
        samples = parse_openmetrics(body)
        assert samples[family_name("c.scraped") + "_total"] == 7

    def test_unknown_path_404(self, endpoint):
        status, body = _get(endpoint, "/nope")
        assert status == 404
        assert "/metrics" in body  # the 404 names the real paths

    def test_readyz_ok_when_clean(self, endpoint):
        status, body = _get(endpoint, "/readyz")
        assert status == 200
        assert json.loads(body) == {"ready": True, "reasons": []}

    def test_readyz_503_on_open_breaker_and_recovers(self, endpoint):
        b = breaker("TelemetryTestMapper")
        for _ in range(3):
            b.record_failure()
        status, body = _get(endpoint, "/readyz")
        assert status == 503
        payload = json.loads(body)
        assert payload["ready"] is False
        (reason,) = payload["reasons"]
        assert reason["reason"] == "breaker_open"
        assert "TelemetryTestMapper" in reason["detail"]
        reset_breakers()
        status, _ = _get(endpoint, "/readyz")
        assert status == 200

    def test_readyz_503_on_pressure_cap_below_floor(self, endpoint):
        # shrink to cap=2, under the default floor of 8
        pressure.state("test.surface").shrink(4, floor=1)
        status, body = _get(endpoint, "/readyz")
        assert status == 503
        (reason,) = json.loads(body)["reasons"]
        assert reason["reason"] == "memory_pressure"
        assert "test.surface" in reason["detail"]
        pressure.reset_states()
        status, _ = _get(endpoint, "/readyz")
        assert status == 200

    def test_readyz_ignores_pressure_cap_above_floor(self, endpoint):
        pressure.state("test.surface").shrink(512, floor=1)  # cap=256
        status, _ = _get(endpoint, "/readyz")
        assert status == 200

    def test_registered_source_feeds_readyz(self, endpoint):
        reasons = [{"reason": "custom_drain", "detail": "draining"}]
        source = lambda: reasons  # noqa: E731
        telemetry.register_readiness(source)
        try:
            status, body = _get(endpoint, "/readyz")
            assert status == 503
            assert json.loads(body)["reasons"] == reasons
        finally:
            telemetry.unregister_readiness(source)
        status, _ = _get(endpoint, "/readyz")
        assert status == 200

    def test_broken_source_fails_closed(self, endpoint):
        def broken():
            raise RuntimeError("probe bug")

        telemetry.register_readiness(broken)
        try:
            status, body = _get(endpoint, "/readyz")
            assert status == 503
            (reason,) = json.loads(body)["reasons"]
            assert reason["reason"] == "probe_error"
        finally:
            telemetry.unregister_readiness(broken)

    def test_statusz_snapshot(self, endpoint):
        breaker("StatuszMapper")  # registered, closed
        pressure.state("s.x").shrink(64, floor=1)
        flight.record("test.event", detail="statusz")
        key = telemetry.register_status("custom", lambda: {"k": "v"})
        try:
            status, body = _get(endpoint, "/statusz")
            assert status == 200
            st = json.loads(body)
            assert st["breakers"] == {"StatuszMapper": 0.0}
            assert st["pressure_caps"] == {"s.x": 32}
            assert st["uptime_s"] >= 0
            assert st["custom"] == {"k": "v"}
            assert any(e["kind"] == "test.event" for e in st["flight_tail"])
        finally:
            telemetry.unregister_status(key)

    def test_stop_is_idempotent_and_frees_the_port(self):
        server = TelemetryServer(port=0).start()
        port = server.port
        server.stop()
        server.stop()
        # the port is genuinely free: a new listener can take it
        server2 = TelemetryServer(port=port).start()
        try:
            assert server2.port == port
        finally:
            server2.stop()

    def test_module_singleton(self, monkeypatch):
        monkeypatch.setenv("FMT_TELEMETRY_PORT", "0")
        first = telemetry.start()
        assert first is not None and first.running
        assert telemetry.start() is first  # idempotent
        assert telemetry.active_server() is first
        telemetry.stop()
        assert telemetry.active_server() is None


def _tiny_model(n=256, dim=5, seed=0):
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(seed)
    X = rng.randn(n, dim).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR),
                  ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(2),
    ]).fit(t)
    return model, t


class TestModelServerWiring:
    def test_no_telemetry_without_opt_in(self, monkeypatch):
        from flink_ml_tpu.serving import ModelServer

        monkeypatch.delenv("FMT_TELEMETRY_PORT", raising=False)
        model, table = _tiny_model()
        with ModelServer(model, max_wait_ms=1.0) as server:
            assert server.telemetry is None

    def test_lifecycle_scrape_status_and_teardown(self):
        from flink_ml_tpu.serving import ModelServer

        model, table = _tiny_model()
        server = ModelServer(model, version="v1", max_wait_ms=1.0,
                             telemetry_port=0)
        try:
            assert server.telemetry is not None and server.telemetry.port
            server.predict(table.slice_rows(0, 8), timeout=60)
            status, body = _get(server.telemetry, "/metrics")
            assert status == 200
            samples = parse_openmetrics(body)
            assert samples[
                family_name("serving.requests") + "_total"] >= 1
            status, body = _get(server.telemetry, "/statusz")
            st = json.loads(body)
            assert st["server"]["active_version"] == "v1"
            assert st["server"]["running"] is True
            assert "slo" in st  # the monitor came up with the server
            url = server.telemetry.url("/healthz")
        finally:
            server.shutdown()
        assert server.telemetry is None
        with pytest.raises((URLError, OSError)):
            urllib.request.urlopen(url, timeout=2)

    def test_scrape_under_load_sits_within_the_registry_snapshots(self):
        """The exporter publishes the registry, not an approximation of
        it: while requests are being served, every counter of one scrape
        lies between the registry snapshots taken around it, and the
        scrape passes the strict parser."""
        from flink_ml_tpu.serving import ModelServer

        model, table = _tiny_model()
        server = ModelServer(model, max_wait_ms=1.0, telemetry_port=0)
        stop = threading.Event()

        def load():
            i = 0
            while not stop.is_set():
                lo = (i * 8) % (table.num_rows() - 8)
                server.predict(table.slice_rows(lo, lo + 8), timeout=60)
                i += 1

        loader = threading.Thread(target=load, daemon=True)
        try:
            server.predict(table.slice_rows(0, 8), timeout=60)
            loader.start()
            checked = []
            for _ in range(5):
                before = obs.registry().snapshot()["counters"]
                status, body = _get(server.telemetry, "/metrics")
                after = obs.registry().snapshot()["counters"]
                assert status == 200
                checked.append(telemetry.counters_within_bounds(
                    before, parse_openmetrics(body), after))
        finally:
            stop.set()
            loader.join(60)
            server.shutdown()
        assert not loader.is_alive()
        assert min(checked) >= 5, checked

    def test_a_counter_outside_the_scrape_window_is_a_violation(self):
        key = family_name("serving.requests") + "_total"
        assert telemetry.counters_within_bounds(
            {"serving.requests": 3}, {key: 4}, {"serving.requests": 4}) == 1
        # a counter the scrape or the later snapshot lacks is not judged
        assert telemetry.counters_within_bounds(
            {"serving.requests": 3, "a.b": 1}, {key: 3},
            {"serving.requests": 3}) == 1
        for exported in (2, 5):
            with pytest.raises(ValueError, match="outside the scrape"):
                telemetry.counters_within_bounds(
                    {"serving.requests": 3}, {key: exported},
                    {"serving.requests": 4})

    def test_env_port_arms_the_server(self, monkeypatch):
        from flink_ml_tpu.serving import ModelServer

        monkeypatch.setenv("FMT_TELEMETRY_PORT", "0")
        model, _ = _tiny_model()
        with ModelServer(model, max_wait_ms=1.0) as server:
            assert server.telemetry is not None
            status, _ = _get(server.telemetry, "/healthz")
            assert status == 200

    def test_readyz_queue_saturated_on_paused_server(self):
        from flink_ml_tpu.serving import ModelServer

        model, table = _tiny_model()
        server = ModelServer(model, max_batch=16, queue_cap=16,
                             max_wait_ms=1.0, telemetry_port=0,
                             start=False)
        try:
            futs = [server.submit(table.slice_rows(i * 8, (i + 1) * 8))
                    for i in range(2)]  # 16 of 16: saturated
            status, body = _get(server.telemetry, "/readyz")
            assert status == 503
            reasons = {r["reason"]
                       for r in json.loads(body)["reasons"]}
            assert "queue_saturated" in reasons
            server.start()
            for f in futs:
                f.result(60)
            status, _ = _get(server.telemetry, "/readyz")
            assert status == 200
        finally:
            server.shutdown()

    def test_readyz_deploy_in_progress(self):
        from flink_ml_tpu.serving import ModelServer

        model, table = _tiny_model()
        model2, _ = _tiny_model(seed=1)
        server = ModelServer(model, version="v1", max_wait_ms=1.0,
                             telemetry_port=0)
        in_deploy = threading.Event()
        release = threading.Event()
        observed = {}

        class GatedModel:
            """Stands in for a slow-warming deploy: transform blocks
            until the test has probed /readyz mid-deploy."""

            stages = model2.stages

            def transform(self, table):
                in_deploy.set()
                release.wait(30)
                return model2.transform(table)

        def deploy():
            server.deploy(GatedModel(), "v2",
                          warmup=table.slice_rows(0, 4))

        t = threading.Thread(target=deploy)
        try:
            t.start()
            assert in_deploy.wait(30)
            status, body = _get(server.telemetry, "/readyz")
            observed["status"], observed["body"] = status, body
        finally:
            release.set()
            t.join(30)
        assert observed["status"] == 503, observed
        reasons = {r["reason"]
                   for r in json.loads(observed["body"])["reasons"]}
        assert "deploy_in_progress" in reasons
        try:
            assert server.active_version == "v2"
            status, _ = _get(server.telemetry, "/readyz")
            assert status == 200
        finally:
            server.shutdown()

    def test_bind_conflict_warns_and_keeps_serving(self):
        from flink_ml_tpu.serving import ModelServer

        blocker = TelemetryServer(port=0).start()
        model, table = _tiny_model()
        try:
            with pytest.warns(RuntimeWarning, match="failed to bind"):
                server = ModelServer(model, max_wait_ms=1.0,
                                     telemetry_port=blocker.port)
            try:
                assert server.telemetry is None
                res = server.predict(table.slice_rows(0, 4), timeout=60)
                assert res.table.num_rows() == 4  # traffic unharmed
            finally:
                server.shutdown()
        finally:
            blocker.stop()


class TestSLOMonitor:
    def test_error_ratio_burn_math(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=0,
                             min_arrivals=5)
        obs.counter_add("serving.requests", 90)
        obs.counter_add("serving.shed", 10)
        res = mon.sample_once()
        verdict = res[slo.ERROR_SLO]
        # 10 bad of 100 arrivals against a 1% budget: 10x burn
        assert verdict["burning"] and verdict["burn_rate"] == \
            pytest.approx(10.0)
        assert verdict["bad"] == 10 and verdict["total"] == 100
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges["slo.burning.shed_error_ratio"] == 1.0
        assert gauges["slo.burn_rate.shed_error_ratio"] == \
            pytest.approx(10.0)
        assert mon.burning() == {slo.ERROR_SLO: pytest.approx(10.0)}

    def test_latency_burn_judges_window_samples(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0, p99_ms=5.0,
                             min_arrivals=10)
        for _ in range(18):
            obs.observe("serving.request_latency_ms", 1.0)
        for _ in range(2):
            obs.observe("serving.request_latency_ms", 50.0)
        res = mon.sample_once()
        verdict = res[slo.LATENCY_SLO]
        # 2 of 20 over target against the 1% p99 budget: 10x burn
        assert verdict["burning"] and verdict["burn_rate"] == \
            pytest.approx(10.0)
        # only NEW observations are judged next window
        for _ in range(20):
            obs.observe("serving.request_latency_ms", 1.0)
        res = mon.sample_once()
        assert not res[slo.LATENCY_SLO]["burning"]
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges["slo.burning.serving_p99_ms"] == 0.0

    def test_small_windows_are_skipped_not_judged(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=0,
                             min_arrivals=10)
        obs.counter_add("serving.shed", 3)  # 3 arrivals, all shed
        assert mon.sample_once() == {}
        assert mon.burning() == {}

    def test_burning_slo_clears_on_a_quiet_window(self):
        """min_arrivals gates ENTERING a breach, never exiting: once
        /readyz degrades the balancer stops routing, so the quiet
        window that follows must clear the burn — not skip it and pin
        the replica unready forever."""
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=5.0,
                             min_arrivals=10)
        obs.counter_add("serving.requests", 50)
        obs.counter_add("serving.shed", 50)
        for _ in range(10):
            obs.observe("serving.request_latency_ms", 50.0)
        mon.sample_once()
        assert set(mon.burning()) == {slo.ERROR_SLO, slo.LATENCY_SLO}
        # a sub-minimum window of CONTINUED bad traffic keeps the error
        # SLO burning; the latency SLO saw nothing this window and clears
        obs.counter_add("serving.shed", 3)
        res = mon.sample_once()
        assert res[slo.ERROR_SLO]["burning"]
        assert not res[slo.LATENCY_SLO]["burning"]
        assert set(mon.burning()) == {slo.ERROR_SLO}
        # the full drought window (zero arrivals): the error SLO recovers
        res = mon.sample_once()
        assert not res[slo.ERROR_SLO]["burning"]
        assert mon.burning() == {}
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges["slo.burning.shed_error_ratio"] == 0.0
        assert gauges["slo.burning.serving_p99_ms"] == 0.0

    def test_disabled_targets_never_judge(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0, p99_ms=0,
                             min_arrivals=1)
        assert not mon.armed()
        obs.counter_add("serving.shed", 100)
        assert mon.sample_once() == {}

    def test_breach_dumps_black_box_with_named_header(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("FMT_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("FMT_FLIGHT_MIN_S", "30")
        flight.reset()
        flight.record("context.event")  # the ring has history to dump
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=0,
                             min_arrivals=5)
        obs.counter_add("serving.requests", 50)
        obs.counter_add("serving.shed", 50)
        res = mon.sample_once()
        path = flight.last_dump_path()
        assert path and str(tmp_path) in path and "slo_breach" in path
        header = json.loads(open(path).readline())
        assert header["reason"] == "slo_breach"
        assert header["slo"] == slo.ERROR_SLO
        assert header["burn_rate"] == round(
            res[slo.ERROR_SLO]["burn_rate"], 4)
        # a second breach inside FMT_FLIGHT_MIN_S is rate-limited: the
        # breach is re-recorded in the ring but no new black box lands
        obs.counter_add("serving.requests", 50)
        obs.counter_add("serving.shed", 50)
        mon.sample_once()
        assert flight.last_dump_path() == path
        breaches = [e for e in flight.events()
                    if e["kind"] == "slo.breach"]
        assert len(breaches) == 2

    def test_recovery_records_and_clears(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=0,
                             min_arrivals=5)
        obs.counter_add("serving.requests", 50)
        obs.counter_add("serving.shed", 50)
        mon.sample_once()
        assert mon.burning()
        obs.counter_add("serving.requests", 10_000)
        res = mon.sample_once()
        assert not res[slo.ERROR_SLO]["burning"]
        assert mon.burning() == {}
        assert any(e["kind"] == "slo.recovered"
                   for e in flight.events())

    def test_registry_reset_between_samples_is_not_a_burn(self):
        mon = slo.SLOMonitor(window=60, err_ratio=0.5, p99_ms=0,
                             min_arrivals=5)
        obs.counter_add("serving.requests", 100)
        mon.sample_once()
        obs.reset()  # totals shrink: deltas must re-anchor, not go negative
        obs.counter_add("serving.requests", 20)
        res = mon.sample_once()
        assert not res[slo.ERROR_SLO]["burning"]

    def test_burning_slo_feeds_readyz(self, endpoint):
        mon = slo.SLOMonitor(window=60, err_ratio=0.01, p99_ms=0,
                             min_arrivals=5).start()
        try:
            obs.counter_add("serving.requests", 50)
            obs.counter_add("serving.shed", 50)
            mon.sample_once()
            status, body = _get(endpoint, "/readyz")
            assert status == 503
            (reason,) = json.loads(body)["reasons"]
            assert reason["reason"] == "slo_burning"
            assert slo.ERROR_SLO in reason["detail"]
        finally:
            mon.stop()
        status, _ = _get(endpoint, "/readyz")
        assert status == 200  # stop() unplugs the readiness source

    def test_sampling_thread_runs_and_stops(self):
        mon = slo.SLOMonitor(window=0.02, err_ratio=0.01, p99_ms=0,
                             min_arrivals=5).start()
        try:
            obs.counter_add("serving.requests", 50)
            obs.counter_add("serving.shed", 50)
            deadline = threading.Event()
            for _ in range(100):
                if mon.burning():
                    break
                deadline.wait(0.02)
            assert mon.burning(), "the sampler thread never judged"
        finally:
            mon.stop()
        assert mon._thread is None


class TestFlightDumpExtra:
    def test_extra_fields_land_in_header(self, tmp_path):
        flight.record("some.event")
        path = flight.dump("unit_test", directory=str(tmp_path),
                           force=True, extra={"slo": "x",
                                              "burn_rate": 2.5})
        header = json.loads(open(path).readline())
        assert header["slo"] == "x" and header["burn_rate"] == 2.5
        assert header["reason"] == "unit_test"

    def test_extra_never_overrides_core_fields(self, tmp_path):
        flight.record("some.event")
        path = flight.dump("unit_test", directory=str(tmp_path),
                           force=True, extra={"reason": "spoofed"})
        header = json.loads(open(path).readline())
        assert header["reason"] == "unit_test"

    def test_extra_is_redacted(self, tmp_path):
        flight.record("some.event")
        path = flight.dump("unit_test", directory=str(tmp_path),
                           force=True, extra={"api_key": "sk-123"})
        header = json.loads(open(path).readline())
        assert header["api_key"] == "<redacted>"
