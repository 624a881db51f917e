"""End-to-end request tracing + flight recorder (ISSUE 8).

The contracts under test:

* **off-by-default** — with ``FMT_TRACE`` off every hook is one
  module-bool check (``span()`` returns the SHARED nullcontext object)
  and nothing is recorded;
* **explicit handoff** — spans attach to the context their thread was
  explicitly handed (dispatcher coalesced batches, ``prefetch_iter``
  producer threads), NEVER to a racing sibling's trace;
* **the request waterfall** — one served request yields one trace whose
  ``submit -> queue_wait -> coalesce -> transform -> fused_dispatch ->
  device_sync -> demux`` spans nest correctly and account within the
  request's measured wall time;
* **black box** — the flight recorder's bounded ring records sheds and
  breaker transitions at near-zero cost, dumps a redacted JSONL file on
  breaker-open, and sheds/quarantines carry the request's ``trace_id``.
"""

import json
import threading
import time

import numpy as np
import pytest

from flink_ml_tpu import obs, serve
from flink_ml_tpu.api.pipeline import Pipeline
from flink_ml_tpu.lib import LogisticRegression
from flink_ml_tpu.lib.feature import StandardScaler
from flink_ml_tpu.obs import flight, trace
from flink_ml_tpu.serve import quarantine
from flink_ml_tpu.serving import ModelServer, ServerOverloadedError
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.prefetch import prefetch_iter

N, D = 192, 5
SCHEMA = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
WAIT = 60  # generous future timeout: a hang fails loudly, not flakily


@pytest.fixture(scope="module")
def dense_table():
    rng = np.random.RandomState(11)
    X = (2.0 * rng.randn(N, D) + 1.0).astype(np.float32)
    w = rng.randn(D).astype(np.float32)
    y = ((X - 1.0) @ w > 0).astype(np.float64)
    return Table.from_columns(SCHEMA, {"features": X, "label": y})


@pytest.fixture(scope="module")
def model(dense_table):
    return Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(dense_table)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Tracing on at sample=1.0, spans to a per-test sink; clean exit."""
    monkeypatch.setenv("FMT_TRACE_DIR", str(tmp_path))
    trace.reset()
    trace.enable(True, sample=1.0)
    yield tmp_path
    trace.enable(False, sample=1.0)
    trace.reset()


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("FMT_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("FMT_FLIGHT_MIN_S", "0")
    flight.reset()
    yield tmp_path / "flight"
    flight.reset()


def _spans_by_name(spans, trace_id):
    return {s["name"]: s for s in spans if s["trace_id"] == trace_id}


# -- core ---------------------------------------------------------------------


class TestTraceCore:
    def test_off_by_default_is_one_shared_nullcontext(self):
        """The disabled hot-path contract, structurally: the SAME shared
        nullcontext object comes back (no allocation, one bool check)."""
        assert not trace.enabled()
        a = trace.span("anything")
        b = trace.span("else", {"k": 1})
        assert a is b
        assert trace.root_span("fit") is a
        assert trace.start_request("r") is None
        assert trace.current() == ()
        trace.record_span((), "x", 0.1)  # no parents: records nothing
        assert trace.recent_spans() == []
        # the program's span (obs.span) with obs off is the same kind of
        # no-op, and with obs on but tracing off it leaves no trace record
        assert not obs.enabled()
        assert obs.span("fit.wall") is obs.span("train.sync")
        obs.enable()
        try:
            with obs.span("train.sync"):
                pass
        finally:
            obs.disable()
            obs.reset()
        assert trace.recent_spans() == []

    def test_enabled_but_no_active_trace_records_nothing(self, traced):
        with trace.span("orphan"):
            pass
        assert trace.recent_spans() == []

    def test_root_and_child_nesting_attrs_and_sink(self, traced):
        with trace.root_span("fit", {"est": "LR"}):
            with trace.span("pack", {"rows": 8}):
                trace.attr("bucket", 32)
        spans = trace.load_spans()
        assert [s["name"] for s in spans] == ["pack", "fit"]
        child, root = spans
        assert child["trace_id"] == root["trace_id"]
        assert child["parent_id"] == root["span_id"]
        assert root["parent_id"] == ""
        assert child["attrs"] == {"rows": 8, "bucket": 32}
        assert root["status"] == "ok" and root["dur_s"] >= child["dur_s"]

    def test_root_span_degrades_to_child_inside_active_trace(self, traced):
        with trace.root_span("outer"):
            with trace.root_span("inner"):
                pass
        spans = trace.load_spans()
        assert len({s["trace_id"] for s in spans}) == 1
        inner = next(s for s in spans if s["name"] == "inner")
        outer = next(s for s in spans if s["name"] == "outer")
        assert inner["parent_id"] == outer["span_id"]

    def test_error_status_and_reraise(self, traced):
        with pytest.raises(ValueError):
            with trace.root_span("fit"):
                raise ValueError("boom")
        (root,) = trace.load_spans()
        assert root["status"] == "error"
        assert root["attrs"]["error"] == "ValueError"

    def test_head_sampling_zero_mints_nothing(self, traced):
        trace.enable(True, sample=0.0)
        assert trace.start_request("r") is None
        assert trace.root_span("fit") is trace.span("x")  # shared null
        assert trace.recent_spans() == []

    def test_fractional_head_sampling_sheds_the_work_not_the_output(
            self, traced):
        """At a rate between 0 and 1 the coin is flipped at mint: about
        that share of requests get a trace, and an unsampled one gets no
        context, so every hook downstream of it stays a no-op."""
        trace.enable(True, sample=0.05)
        minted = [rt for rt in (trace.start_request("r")
                                for _ in range(1000)) if rt is not None]
        # 50 expected; 0 or 200 of 1000 are both beyond 1e-20
        assert 0 < len(minted) < 200
        for rt in minted:
            rt.end()
        roots = [s for s in trace.recent_spans() if s["name"] == "r"]
        assert len(roots) == len(minted)

    def test_fanout_records_one_span_per_parent_trace(self, traced):
        a = trace.start_request("req_a")
        b = trace.start_request("req_b")
        with trace.use((a.ctx, b.ctx)):
            with trace.span("coalesce"):
                pass
        a.end()
        b.end()
        spans = [s for s in trace.recent_spans() if s["name"] == "coalesce"]
        assert {s["trace_id"] for s in spans} == {a.trace_id, b.trace_id}
        # same span identity and timestamps, one per parent trace
        assert len({s["span_id"] for s in spans}) == 1
        assert len({s["ts"] for s in spans}) == 1
        for s in spans:
            parent = a if s["trace_id"] == a.trace_id else b
            assert s["parent_id"] == parent.ctx.span_id

    def test_record_span_explicit_duration(self, traced):
        rt = trace.start_request("req")
        trace.record_span((rt.ctx,), "queue_wait", 0.25, {"n": 1})
        rt.end()
        qw = next(s for s in trace.recent_spans()
                  if s["name"] == "queue_wait")
        assert qw["dur_s"] == pytest.approx(0.25)
        assert qw["parent_id"] == rt.ctx.span_id

    def test_request_trace_end_is_single_shot(self, traced):
        rt = trace.start_request("req")
        rt.end("ok")
        rt.end("error")  # benign double-end: first outcome wins
        roots = [s for s in trace.recent_spans() if s["name"] == "req"]
        assert len(roots) == 1 and roots[0]["status"] == "ok"

    def test_waterfall_renders_nesting_and_orphans(self, traced):
        with trace.root_span("fit"):
            with trace.span("pack"):
                pass
        spans = trace.load_spans()
        tid = spans[0]["trace_id"]
        out = trace.render_waterfall(spans, tid)
        assert "fit" in out and "pack" in out and "ms" in out
        fit_line = next(line for line in out.splitlines()
                        if " fit " in f" {line} ")
        pack_line = next(line for line in out.splitlines() if "pack" in line)
        # children indent under parents
        assert pack_line.index("pack") > fit_line.index("fit")
        assert "no spans" in trace.render_waterfall(spans, "absent")


# -- cross-thread propagation (the satellite) ---------------------------------


class TestCrossThreadPropagation:
    def test_prefetch_producer_attaches_to_consumer_trace(self, traced):
        """The producer thread's spans must land in the CONSUMER's trace
        — even with two racing consumers prefetching concurrently, each
        producer inherits exactly its own consumer's context."""
        barrier = threading.Barrier(2)
        results = {}

        def consumer(name):
            def gen():
                for i in range(4):
                    with trace.span("produce", {"who": name, "i": i}):
                        pass
                    yield i
            with trace.root_span(f"consume_{name}"):
                barrier.wait(timeout=10)
                list(prefetch_iter(gen(), depth=1, name=f"pf-{name}"))
                results[name] = trace.current_trace_ids()[0]

        threads = [threading.Thread(target=consumer, args=(n,))
                   for n in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert set(results) == {"a", "b"}
        assert results["a"] != results["b"]
        produced = [s for s in trace.recent_spans()
                    if s["name"] == "produce"]
        assert len(produced) == 8
        for s in produced:
            # the span's trace is its OWN consumer's, never the sibling's
            assert s["trace_id"] == results[s["attrs"]["who"]], s

    def test_untraced_consumer_prefetch_records_nothing(self, traced):
        def gen():
            for i in range(3):
                with trace.span("produce"):
                    pass
                yield i

        assert list(prefetch_iter(gen(), depth=1)) == [0, 1, 2]
        assert trace.recent_spans() == []

    def test_coalesced_batch_spans_fan_out_per_request(self, traced, model,
                                                       dense_table):
        """Two requests coalesced into ONE dispatcher batch: the batch-
        scope spans appear in BOTH traces; per-request spans stay in
        their own."""
        server = ModelServer(model, max_batch=64, max_wait_ms=50,
                             start=False)
        fa = server.submit(dense_table.slice_rows(0, 3))
        fb = server.submit(dense_table.slice_rows(3, 8))
        server.start()
        ra, rb = fa.result(WAIT), fb.result(WAIT)
        server.shutdown()
        assert ra.num_rows == 3 and rb.num_rows == 5
        spans = trace.load_spans()
        roots = [s for s in spans if s["name"] == "serving.request"]
        assert len(roots) == 2
        (ta, tb) = [r["trace_id"] for r in roots]
        by_a, by_b = _spans_by_name(spans, ta), _spans_by_name(spans, tb)
        for name in ("submit", "queue_wait", "coalesce", "transform",
                     "demux"):
            assert name in by_a and name in by_b, name
        # ONE coalesced dispatch: the shared batch spans are the same
        # span identity recorded into each trace
        assert by_a["coalesce"]["span_id"] == by_b["coalesce"]["span_id"]
        assert by_a["coalesce"]["attrs"]["requests"] == 2
        # per-request spans never cross: each submit carries its own rows
        assert {by_a["submit"]["attrs"]["rows"],
                by_b["submit"]["attrs"]["rows"]} == {3, 5}
        assert by_a["submit"]["span_id"] != by_b["submit"]["span_id"]


# -- the served-request waterfall (acceptance) --------------------------------


class TestServingTrace:
    def test_single_request_waterfall_nests_within_wall(self, traced,
                                                        model, dense_table):
        with ModelServer(model, max_wait_ms=1,
                         warmup=dense_table.slice_rows(0, 4)) as server:
            trace.reset()  # drop the warmup transform's trace
            t0 = time.perf_counter()
            res = server.predict(dense_table.slice_rows(0, 8),
                                 timeout=WAIT)
            wall_s = time.perf_counter() - t0
        assert res.num_rows == 8
        spans = trace.load_spans()
        (root,) = [s for s in spans if s["name"] == "serving.request"]
        mine = _spans_by_name(spans, root["trace_id"])
        for name in ("submit", "queue_wait", "coalesce", "transform",
                     "fused_dispatch", "device_sync", "demux"):
            assert name in mine, (name, sorted(mine))
        for child in ("submit", "queue_wait", "coalesce", "transform",
                      "demux"):
            assert mine[child]["parent_id"] == root["span_id"], child
        assert mine["device_sync"]["parent_id"] == \
            mine["fused_dispatch"]["span_id"]
        # fused_dispatch sits under serve.dispatch inside the transform
        by_id = {s["span_id"]: s
                 for s in spans if s["trace_id"] == root["trace_id"]}
        hops, cur = [], mine["fused_dispatch"]
        while cur["parent_id"]:
            cur = by_id[cur["parent_id"]]
            hops.append(cur["name"])
        assert hops[0] == "serve.dispatch" and "transform" in hops, hops
        # the accounted hops sum within the measured request wall
        accounted = mine["queue_wait"]["dur_s"] + mine["transform"]["dur_s"]
        assert accounted <= wall_s * 1.05
        assert root["dur_s"] <= wall_s * 1.05
        assert root["attrs"]["version"] == "v1"
        assert mine["serve.dispatch"]["attrs"]["retries"] == 0

    def test_shed_carries_trace_id_everywhere(self, traced, flight_dir,
                                              model, dense_table):
        server = ModelServer(model, queue_cap=8, max_wait_ms=1,
                             start=False)
        server.submit(dense_table.slice_rows(0, 8))  # fills the cap
        with pytest.raises(ServerOverloadedError) as ei:
            server.submit(dense_table.slice_rows(8, 16))
        assert ei.value.reason == "queue_full"
        assert ei.value.trace_id  # the error names its trace
        root = next(s for s in trace.recent_spans()
                    if s["name"] == "serving.request"
                    and s["trace_id"] == ei.value.trace_id)
        assert root["status"] == "shed"
        assert root["attrs"]["shed_reason"] == "queue_full"
        shed_events = [e for e in flight.events()
                       if e["kind"] == "serving.shed"]
        assert shed_events and \
            shed_events[-1]["trace_id"] == ei.value.trace_id
        server.shutdown()

    def test_quarantined_rows_stamp_the_request_trace(self, traced, model,
                                                      dense_table):
        rows = np.asarray(dense_table.col("features")[:4],
                          dtype=np.float32).copy()
        rows[2, 0] = np.nan
        bad = Table.from_columns(SCHEMA, {
            "features": rows,
            "label": np.zeros(4, dtype=np.float64),
        })
        with ModelServer(model, max_wait_ms=1) as server:
            trace.reset()
            res = server.predict(bad, timeout=WAIT)
        assert res.num_rows == 3 and res.num_quarantined == 1
        (root,) = [s for s in trace.load_spans()
                   if s["name"] == "serving.request"]
        assert root["attrs"]["quarantined"] == 1
        assert root["attrs"]["quarantine_reasons"] == "nan_inf"
        (side,) = res.quarantine.values()
        assert list(side.col(quarantine.QUARANTINE_TRACE_COL)) == [
            root["trace_id"]
        ]

    def test_cancelled_while_queued_still_ends_its_trace(self, traced,
                                                         model,
                                                         dense_table):
        """Cancellation is a terminal outcome: a sampled request whose
        caller cancels it while queued must still land its root span
        (status ``cancelled``), not leak an unterminated trace."""
        server = ModelServer(model, max_wait_ms=1, start=False)
        fut = server.submit(dense_table.slice_rows(0, 4))
        assert fut.cancel()
        server.start()
        server.shutdown()
        trace.flush()
        roots = [s for s in trace.load_spans()
                 if s["name"] == "serving.request"]
        assert len(roots) == 1
        assert roots[0]["status"] == "cancelled"

    def test_untraced_serving_is_unaffected(self, model, dense_table):
        assert not trace.enabled()
        with ModelServer(model, max_wait_ms=1) as server:
            res = server.predict(dense_table.slice_rows(0, 4),
                                 timeout=WAIT)
        assert res.num_rows == 4
        assert trace.recent_spans() == []


# -- guarded-fit traces -------------------------------------------------------


class TestFitTrace:
    def test_guarded_fit_roots_a_trace_with_train_spans(self, traced,
                                                        dense_table):
        (LogisticRegression().set_vector_col("features")
         .set_label_col("label").set_prediction_col("pred")
         .set_learning_rate(0.5).set_max_iter(2).fit(dense_table))
        spans = trace.load_spans()
        roots = [s for s in spans if s["name"] == "fit"]
        assert roots, [s["name"] for s in spans]
        mine = _spans_by_name(spans, roots[-1]["trace_id"])
        assert "train.dispatch" in mine and "train.sync" in mine
        assert mine["train.dispatch"]["parent_id"] == \
            roots[-1]["span_id"]


# -- flight recorder ----------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self, flight_dir, monkeypatch):
        monkeypatch.setenv("FMT_FLIGHT_EVENTS", "16")
        for i in range(64):
            flight.record("tick", i=i)
        events = flight.events()
        assert len(events) == 16
        assert events[-1]["i"] == 63 and events[0]["i"] == 48
        assert events[-1]["seq"] == 64  # true totals survive the ring

    def test_capacity_zero_disables(self, flight_dir, monkeypatch):
        monkeypatch.setenv("FMT_FLIGHT_EVENTS", "0")
        flight.record("tick")
        assert flight.events() == []
        assert flight.dump("anything", force=True) is None

    def test_redaction_masks_secrets_and_truncates(self, flight_dir):
        flight.record("deploy", api_key="sk-very-secret",
                      detail="x" * 1000, count=3)
        (e,) = flight.events()
        assert e["api_key"] == "<redacted>"
        assert len(e["detail"]) == 256 and e["detail"].endswith("...")
        assert e["count"] == 3

    def test_dump_writes_jsonl_and_rate_limits(self, flight_dir,
                                               monkeypatch):
        monkeypatch.setenv("FMT_FLIGHT_MIN_S", "9999")
        flight.record("tick", i=1)
        path = flight.dump("unit_test")
        assert path and str(flight_dir) in path
        lines = [json.loads(line) for line in open(path)]
        assert lines[0]["kind"] == "flight.dump"
        assert lines[0]["reason"] == "unit_test"
        assert lines[1]["kind"] == "tick"
        assert flight.dump("unit_test") is None  # rate-limited
        assert flight.dump("unit_test", force=True) is not None

    def test_breaker_open_dumps_black_box(self, flight_dir, monkeypatch):
        monkeypatch.setenv("FMT_SERVE_BREAKER_THRESHOLD", "1")
        serve.reset_breakers()
        try:
            serve.breaker("TraceTestMapper").record_failure()
        finally:
            serve.reset_breakers()
        path = flight.last_dump_path()
        assert path and str(flight_dir) in path
        events = [json.loads(line) for line in open(path)][1:]
        opens = [e for e in events if e["kind"] == "breaker.state"
                 and e.get("state") == 1.0
                 and e.get("name") == "TraceTestMapper"]
        assert opens, events

    def test_record_never_raises_on_weird_values(self, flight_dir):
        flight.record("odd", obj=object(), arr=np.arange(3))
        (e,) = flight.events()
        assert isinstance(e["obj"], str) and isinstance(e["arr"], str)


# -- report satellites --------------------------------------------------------


class TestReportSatellites:
    def test_fit_delta_timings_carry_quantiles(self):
        from flink_ml_tpu.obs import report

        obs.enable()
        obs.reset()
        try:
            # consume any pending delta state, then observe fresh samples
            report._fit_delta_snapshot()
            for ms in (1, 2, 3, 4, 100):
                obs.observe("unit.test_stat", ms / 1e3)
            delta = report._fit_delta_snapshot()
        finally:
            obs.reset()
            obs.disable()
        stat = delta["timings"]["unit.test_stat"]
        assert stat["count"] == 5
        assert stat["p50_s"] == pytest.approx(0.003)
        assert stat["p99_s"] == pytest.approx(0.1)

    def test_transform_report_carries_timings_and_trace(self, tmp_path,
                                                        traced):
        from flink_ml_tpu.obs.report import load_reports, transform_report

        obs.enable()
        obs.reset()
        try:
            obs.observe("serve.deadline_ms", 0.004)
            with trace.root_span("pipeline"):
                transform_report("UnitModel", rows=8,
                                 serve_delta={"serve.device_ok": 1},
                                 directory=str(tmp_path))
                tid = trace.current_trace_ids()[0]
        finally:
            obs.reset()
            obs.disable()
        (rep,) = load_reports(str(tmp_path))
        assert rep["extra"]["trace_id"] == tid
        assert rep["extra"]["timings"]["serve.deadline_ms"]["count"] == 1
