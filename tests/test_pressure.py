"""Memory-pressure resilience (ISSUE 9): OOM classification, adaptive
batch bisection, HBM-budget admission, pool pressure eviction, and the
exact-parity recovery contracts on every dispatch surface."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from flink_ml_tpu import fault, obs
from flink_ml_tpu.fault import injection, pressure, retry
from flink_ml_tpu.fault.injection import InjectedFault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OOM_MSG = "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 123456 bytes."


@pytest.fixture(autouse=True)
def _clean_pressure_state(tmp_path, monkeypatch):
    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "_reports"))
    injection.reset()
    pressure.reset_states()
    yield
    injection.reset()
    pressure.reset_states()
    obs.disable()
    obs.reset()


def _dense_table(n=256, dim=5, seed=3):
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(seed)
    X = rng.randn(n, dim).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )


def _logreg(lr=0.5, iters=3, **extra):
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(lr).set_max_iter(iters)
    )
    for k, v in extra.items():
        getattr(est, f"set_{k}")(v)
    return est


class TestOomClassification:
    def test_allocator_messages_are_oom(self):
        for msg in (
            OOM_MSG,
            "Resource exhausted: Failed to allocate request for 2.5GiB",
            "Allocator (TPU_0) ran out of memory trying to allocate 1.2G",
            "RESOURCE_EXHAUSTED: Error allocating device buffer (HBM)",
            "XlaRuntimeError: Out of memory",
        ):
            assert pressure.is_oom(RuntimeError(msg)), msg

    def test_host_memory_error_is_oom(self):
        assert pressure.is_oom(MemoryError())

    def test_quota_exhaustion_stays_transient(self):
        # the satellite-1 contract: RESOURCE_EXHAUSTED without allocator
        # vocabulary is quota/RPC backpressure — a retry plausibly fixes it
        quota = RuntimeError("RESOURCE_EXHAUSTED: quota exceeded for rpc")
        assert not pressure.is_oom(quota)
        assert retry.is_transient(quota)

    def test_non_exhaustion_errors_are_not_oom(self):
        for exc in (
            RuntimeError("UNAVAILABLE: socket closed"),
            ValueError("bad shape"),
            KeyboardInterrupt(),
        ):
            assert not pressure.is_oom(exc)

    def test_injected_oom_point_classified(self):
        injection.configure("fault.oom>10")
        with pytest.raises(InjectedFault) as ei:
            pressure.maybe_oom(11)
        assert pressure.is_oom(ei.value)
        assert not retry.is_transient(ei.value)
        # other injection points keep their transient classification
        assert retry.is_transient(InjectedFault("place.h2d", 1))


#: the two texts a kernel that overflows VMEM produced on a v5e (jax 0.9.0,
#: libtpu 0.0.34 — chip run of PR 21), plus the short form ISSUE 21 quotes
SCOPED_VMEM_MSG = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %tpu_custom_call.1 = f32[2048,2048]{1,0:T(8,128)}"
    " custom-call(%args_0_.1), custom_call_target=\"tpu_custom_call\". Scoped "
    "allocation with size 32.00M and limit 16.00M exceeded scoped vmem limit "
    "by 16.00M. It should not be possible to run out of scoped vmem"
)
VMEM_WINDOW_MSG = (
    "RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed memory "
    "(size=134217728) :: #allocation2 [shape = 'u8[268435456]{0}', "
    "space=vmem, size = 0x10000000, tag = 'input window allocation for "
    "operator input 0.'] :: tpu_custom_call.1"
)
COMPILE_SHAPED = (
    SCOPED_VMEM_MSG, VMEM_WINDOW_MSG,
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
)
#: ...and what the chip's HBM allocator says, which IS memory pressure
HBM_MSG = (
    "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
    "allocate 4.00G. That was not possible. There are 3.75G free.; "
    "(0x0x0_HBM0)"
)


class TestCompileFailureIsNobodysToAbsorb:
    """A kernel that does not fit VMEM carries the allocator's status code
    but is a compile failure: not an OOM (bisecting the batch compiles the
    same kernel), not transient (a retry compiles it again)."""

    def test_compile_shaped_text_is_neither_oom_nor_transient(self):
        for msg in COMPILE_SHAPED:
            for exc in (RuntimeError(msg), ValueError(msg)):
                assert pressure.is_compile_failure(exc), msg
                assert not pressure.is_oom(exc), msg
                assert not retry.is_transient(exc), msg

    def test_hbm_exhaustion_is_still_an_oom(self):
        for exc in (RuntimeError(HBM_MSG), ValueError(HBM_MSG)):
            assert not pressure.is_compile_failure(exc)
            assert pressure.is_oom(exc)
            assert not retry.is_transient(exc)

    def test_propagates_out_of_a_fused_serve_dispatch(self, monkeypatch):
        from flink_ml_tpu.api.pipeline import Pipeline
        from flink_ml_tpu.common.fused import FusedRun
        from flink_ml_tpu.lib.feature import StandardScaler

        t = _dense_table(n=512)
        model = Pipeline([
            StandardScaler().set_selected_col("features"), _logreg(),
        ]).fit(t)
        calls = []

        def refuse(self, *a, **k):
            calls.append(1)
            raise RuntimeError(SCOPED_VMEM_MSG)

        monkeypatch.setattr(FusedRun, "_device_batch", refuse)
        obs.enable()
        obs.reset()
        with pytest.raises(RuntimeError, match="scoped vmem"):
            model.transform(t)
        assert calls == [1]  # not retried, not bisected
        c = obs.registry().snapshot()["counters"]
        for hidden in ("pressure.ooms", "pressure.bisections",
                       "fault.retries", "serve.fallbacks",
                       "serve.dispatch_failures",
                       "pipeline.plan_fallback_batches"):
            assert c.get(hidden, 0) == 0, (hidden, c)

    def test_propagates_out_of_train_glm(self, monkeypatch):
        from flink_ml_tpu.lib import common

        calls = []

        def refuse(*a, **k):
            calls.append(1)
            raise RuntimeError(VMEM_WINDOW_MSG)

        monkeypatch.setattr(common, "_run_fused_train", refuse)
        obs.enable()
        obs.reset()
        with pytest.raises(RuntimeError, match="space=vmem"):
            _logreg(iters=2, global_batch_size=32).fit(_dense_table())
        assert calls == [1]  # no micro-batch fallback
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.ooms", 0) == 0, c
        assert c.get("train.pressure_runs", 0) == 0, c


class TestRetryDeclassification:
    def test_oom_not_retried_same_size(self):
        """The red test for the old behavior: fault/retry.py classified
        every RESOURCE_EXHAUSTED as transient, so a deterministic
        allocator OOM was retried at the identical batch size
        ``FMT_RETRY_ATTEMPTS`` times (failing identically each time,
        tripling the latency) before giving up.  Now it re-raises on the
        FIRST attempt and routes to pressure recovery."""
        attempts = [0]

        def body():
            attempts[0] += 1
            raise RuntimeError(OOM_MSG)

        with pytest.raises(RuntimeError, match="Out of memory"):
            fault.with_retry(body, "test.oom",
                             retry.RetryPolicy(attempts=3, base_delay_s=0.0))
        assert attempts[0] == 1  # the old behavior burned all 3

    def test_transient_exhaustion_still_retried(self):
        attempts = [0]

        def body():
            attempts[0] += 1
            if attempts[0] < 3:
                raise RuntimeError("RESOURCE_EXHAUSTED: quota exceeded")
            return "ok"

        assert fault.with_retry(
            body, "test.quota",
            retry.RetryPolicy(attempts=3, base_delay_s=0.0),
        ) == "ok"
        assert attempts[0] == 3


class TestValueConditionedRules:
    def test_over_threshold_rule_fires_while_value_exceeds(self):
        injection.configure("fault.oom>256")
        pressure.maybe_oom(256)  # boundary: not strictly greater
        pressure.maybe_oom(100)
        with pytest.raises(InjectedFault):
            pressure.maybe_oom(257)
        with pytest.raises(InjectedFault):
            pressure.maybe_oom(512)  # fires EVERY over-threshold call
        assert injection.fire_count("fault.oom") == 2

    def test_no_value_never_fires(self):
        injection.configure("some.point>10")
        injection.maybe_fail("some.point")  # plain hook: no value, no fire
        assert injection.fire_count("some.point") == 0

    def test_mixed_spec_parses(self):
        injection.configure("a@2,b~0.5,c>64")
        with pytest.raises(InjectedFault):
            injection.maybe_fail("c", value=65)

    def test_bad_threshold_rejected(self):
        with pytest.raises(ValueError, match="must be a number"):
            injection.configure("p>abc")
        with pytest.raises(ValueError, match=">= 0"):
            injection.configure("p>-1")


class TestRunBisected:
    def _capacity_fn(self, capacity, log=None):
        def fn(lo, hi):
            if log is not None:
                log.append((lo, hi))
            if hi - lo > capacity:
                raise RuntimeError(OOM_MSG)
            return np.arange(lo, hi)

        return fn

    def test_converges_and_concatenates_exactly(self):
        obs.enable()
        out = pressure.run_bisected(
            self._capacity_fn(100), 1000, surface="t.bisect"
        )
        np.testing.assert_array_equal(out, np.arange(1000))
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.ooms", 0) >= 1
        assert c.get("pressure.bisections", 0) >= 1

    def test_state_remembered_across_runs(self):
        log = []
        fn = self._capacity_fn(100, log)
        pressure.run_bisected(fn, 1000, surface="t.mem")
        log.clear()
        out = pressure.run_bisected(fn, 1000, surface="t.mem")
        np.testing.assert_array_equal(out, np.arange(1000))
        # second run chunks at the remembered cap: zero failing probes
        assert all(hi - lo <= 100 for lo, hi in log), log

    def test_aimd_probe_recovers_full_batch(self, monkeypatch):
        obs.enable()
        fn = self._capacity_fn(100)
        pressure.run_bisected(fn, 1000, surface="t.aimd")
        st = pressure.state("t.aimd")
        assert st.cap is not None
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "0")
        for _ in range(20):
            st.admit(1000)
        assert st.cap is None  # fully recovered
        assert obs.registry().snapshot()["counters"].get(
            "pressure.resizes", 0) >= 1
        # and with capacity restored the next run is ONE unsplit call
        log = []
        pressure.run_bisected(self._capacity_fn(10_000, log), 1000,
                              surface="t.aimd")
        assert log == [(0, 1000)]

    def test_floor_oom_reraises(self):
        def fn(lo, hi):
            raise RuntimeError(OOM_MSG)

        with pytest.raises(RuntimeError, match="Out of memory"):
            pressure.run_bisected(fn, 64, surface="t.floor", floor=8)

    def test_non_oom_raises_through(self):
        def fn(lo, hi):
            raise ValueError("a real bug")

        with pytest.raises(ValueError, match="a real bug"):
            pressure.run_bisected(fn, 64, surface="t.raise")

    def test_dict_and_list_results_concatenate(self):
        def fn(lo, hi):
            if hi - lo > 4:
                raise RuntimeError(OOM_MSG)
            return {"a": np.arange(lo, hi), "b": [str(i) for i in range(lo, hi)]}

        out = pressure.run_bisected(fn, 10, surface="t.dict")
        np.testing.assert_array_equal(out["a"], np.arange(10))
        assert out["b"] == [str(i) for i in range(10)]

    def test_disabled_layer_fails_fast(self, monkeypatch):
        monkeypatch.setenv("FMT_PRESSURE", "0")
        log = []
        with pytest.raises(RuntimeError, match="Out of memory"):
            pressure.run_bisected(self._capacity_fn(100, log), 1000,
                                  surface="t.off")
        assert log == [(0, 1000)]  # one attempt, no recovery


class TestPoolPressureEviction:
    def test_unpinned_dropped_pinned_kept(self):
        from flink_ml_tpu.table import slab_pool

        pool = slab_pool.SlabPool(budget_bytes=1 << 30)
        a = np.arange(1024.0)
        b = np.arange(2048.0)
        va = pool.get_or_build(("a",), lambda: a, nbytes=a.nbytes)
        pool.get_or_build(("b",), lambda: b, nbytes=b.nbytes)
        with pool.pinned(va):
            dropped = pool.evict_for_pressure()
            assert dropped == b.nbytes  # only the unpinned entry
            assert pool._entries  # the pinned one survived
        assert pool.evict_for_pressure() == a.nbytes

    def test_bisection_evicts_before_shrinking(self):
        from flink_ml_tpu.table import slab_pool

        slab_pool.reset_pool()
        big = np.arange(4096.0)
        slab_pool.pool().get_or_build(("victim",), lambda: big,
                                      nbytes=big.nbytes)
        obs.enable()
        calls = {"n": 0}

        def fn(lo, hi):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError(OOM_MSG)
            return np.arange(lo, hi)  # eviction freed enough: same size OK

        out = pressure.run_bisected(fn, 100, surface="t.evict")
        np.testing.assert_array_equal(out, np.arange(100))
        assert calls["n"] == 2  # retried at FULL size after eviction
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.evictions", 0) >= 1
        assert c.get("slab_pool.pressure_evictions", 0) >= 1
        assert pressure.state("t.evict").cap is None  # never shrank
        slab_pool.reset_pool()


class TestFusedBisectionParity:
    def _pipeline_and_table(self, n=512):
        from flink_ml_tpu.api.pipeline import Pipeline
        from flink_ml_tpu.lib.feature import StandardScaler

        t = _dense_table(n=n)
        model = Pipeline([
            StandardScaler().set_selected_col("features"),
            _logreg(),
        ]).fit(t)
        return model, t

    def test_transform_under_ceiling_bit_identical(self):
        model, t = self._pipeline_and_table()
        (ref,) = model.transform(t)
        obs.enable()
        obs.reset()
        injection.configure("fault.oom>64")
        try:
            (out,) = model.transform(t)
        finally:
            injection.configure(None)
        np.testing.assert_array_equal(
            np.asarray(out.col("p")), np.asarray(ref.col("p"))
        )
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.bisections", 0) >= 1, c
        # under pressure the plan dispatches MORE, never fewer, rows
        assert c.get("pipeline.fused_rows", 0) >= t.num_rows()

    def test_transform_returns_to_unsplit_batches_once_the_ceiling_lifts(
            self, monkeypatch):
        """Recovery end to end on the fused serving surface: the caps a
        ceiling taught are probed back up by later transforms (AIMD, the
        probe interval at zero), and after that a transform bisects
        nothing and predicts the same bits."""
        model, t = self._pipeline_and_table(n=192)
        (ref,) = model.transform(t)
        obs.enable()
        injection.configure("fault.oom>64")
        try:
            model.transform(t)
        finally:
            injection.configure(None)
        assert pressure.current_caps(), "the ceiling taught no cap"
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "0")
        for _ in range(40):
            if not pressure.current_caps():
                break
            model.transform(t)
        assert not pressure.current_caps()
        obs.reset()
        (out,) = model.transform(t)
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.bisections", 0) == 0, c
        assert c.get("pressure.ooms", 0) == 0, c
        np.testing.assert_array_equal(
            np.asarray(out.col("p")), np.asarray(ref.col("p"))
        )

    def test_quarantine_offsets_survive_bisection(self):
        from flink_ml_tpu.serve import quarantine
        from flink_ml_tpu.table.table import Table

        model, t = self._pipeline_and_table()
        bad_rows = [7, 300]
        X = np.asarray(t.features_dense("features"), dtype=np.float32).copy()
        for r in bad_rows:
            X[r, 1] = np.nan
        bad_t = Table.from_columns(t.schema, {
            "features": X, "label": t.col("label"),
        })
        quarantine.reset()
        (ref,) = model.transform(bad_t)
        ref_side = quarantine.quarantine_table("StandardScalerModel")
        ref_rows = list(ref_side.col(quarantine.QUARANTINE_ROW_COL))
        quarantine.reset()
        injection.configure("fault.oom>64")
        try:
            (out,) = model.transform(bad_t)
        finally:
            injection.configure(None)
        side = quarantine.quarantine_table("StandardScalerModel")
        assert list(side.col(quarantine.QUARANTINE_ROW_COL)) == ref_rows
        assert sorted(ref_rows) == bad_rows  # original-feed offsets
        np.testing.assert_array_equal(
            np.asarray(out.col("p")), np.asarray(ref.col("p"))
        )
        quarantine.reset()

    def test_staged_apply_chunking_parity(self):
        """KMeans assign + Knn scan (the apply_batched/apply_sharded
        chunking) under the injected ceiling: predictions exact."""
        from flink_ml_tpu.lib import KMeans, Knn

        t = _dense_table(n=300)
        km = (KMeans().set_vector_col("features").set_k(4)
              .set_prediction_col("c").set_max_iter(3).fit(t))
        knn = (Knn().set_vector_col("features").set_label_col("label")
               .set_k(3).set_prediction_col("p").fit(t))
        (km_ref,) = km.transform(t)
        (knn_ref,) = knn.transform(t)
        obs.enable()
        obs.reset()
        injection.configure("fault.oom>32")
        try:
            (km_out,) = km.transform(t)
            (knn_out,) = knn.transform(t)
        finally:
            injection.configure(None)
        np.testing.assert_array_equal(np.asarray(km_out.col("c")),
                                      np.asarray(km_ref.col("c")))
        np.testing.assert_array_equal(np.asarray(knn_out.col("p")),
                                      np.asarray(knn_ref.col("p")))
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.ooms.apply", 0) >= 1, c


class TestServingUnderPressure:
    def _model_and_table(self, n=512):
        from flink_ml_tpu.api.pipeline import Pipeline
        from flink_ml_tpu.lib.feature import StandardScaler

        t = _dense_table(n=n)
        model = Pipeline([
            StandardScaler().set_selected_col("features"),
            _logreg(),
        ]).fit(t)
        return model, t

    def test_coalesced_batches_survive_injected_ceiling(self):
        from flink_ml_tpu.serving import ModelServer

        model, t = self._model_and_table()
        (ref,) = model.transform(t)
        refp = np.asarray(ref.col("p"))
        obs.enable()
        obs.reset()
        injection.configure("fault.oom>64")
        try:
            with ModelServer(model, max_batch=256, max_wait_ms=1) as server:
                futs = [server.submit(t.slice_rows(i * 32, (i + 1) * 32))
                        for i in range(16)]
                for i, f in enumerate(futs):
                    got = np.asarray(f.result(120).table.col("p"))
                    np.testing.assert_array_equal(
                        got, refp[i * 32:(i + 1) * 32],
                        err_msg=f"request {i} diverged under pressure",
                    )
        finally:
            injection.configure(None)
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.bisections", 0) >= 1, c
        assert c.get("serving.failed_requests", 0) == 0, c

    def test_dispatcher_splits_at_request_boundary(self):
        """A model whose TRANSFORM OOMs wholesale (no internal bisection
        available — e.g. a custom stage) forces the dispatcher-level
        split: each caller still gets its exact solo result."""
        from flink_ml_tpu.serving import ModelServer

        class CeilingModel:
            """transform raises allocator OOM for batches over 40 rows."""

            stages = []

            def transform(self, table):
                if table.num_rows() > 40:
                    raise RuntimeError(OOM_MSG)
                return (table,)

        obs.enable()
        obs.reset()
        t = _dense_table(n=128)
        with ModelServer(CeilingModel(), max_batch=128, max_wait_ms=20,
                         start=False) as server:
            futs = [server.submit(t.slice_rows(i * 16, (i + 1) * 16))
                    for i in range(8)]  # coalesces to one 128-row batch
            server.start()
            for i, f in enumerate(futs):
                res = f.result(60)
                np.testing.assert_array_equal(
                    np.asarray(res.table.features_dense("features")),
                    np.asarray(
                        t.slice_rows(i * 16, (i + 1) * 16)
                        .features_dense("features")
                    ),
                )
        c = obs.registry().snapshot()["counters"]
        assert c.get("serving.pressure_splits", 0) >= 1, c
        assert c.get("serving.failed_requests", 0) == 0, c
        # the pressure state caps later coalescing
        assert pressure.state("serving.batch").cap is not None

    def test_bytes_cap_sheds_memory_pressure(self):
        from flink_ml_tpu.serving import ModelServer
        from flink_ml_tpu.serving.errors import (
            SHED_MEMORY_PRESSURE,
            ServerOverloadedError,
        )

        model, t = self._model_and_table(n=512)
        obs.enable()
        obs.reset()
        # features are 512x5 f32 + 512x8 label: one row ~ 28 bytes; cap
        # the queue at ~2 KiB so the third 32-row request cannot fit
        server = ModelServer(model, queue_cap=4096,
                             queue_cap_mb=2.0 / 1024.0, max_wait_ms=1,
                             start=False)
        server.submit(t.slice_rows(0, 32))
        server.submit(t.slice_rows(32, 64))
        with pytest.raises(ServerOverloadedError) as ei:
            server.submit(t.slice_rows(64, 96))
        assert ei.value.reason == SHED_MEMORY_PRESSURE
        c = obs.registry().snapshot()["counters"]
        assert c.get(f"serving.shed.{SHED_MEMORY_PRESSURE}", 0) == 1, c
        server.start()
        server.shutdown()  # drains the two admitted requests

    def test_bytes_cap_off_by_default(self):
        from flink_ml_tpu.serving.admission import ServingConfig

        assert ServingConfig.from_env().queue_cap_bytes == 0
        cfg = ServingConfig.from_env(queue_cap_mb=1.5)
        assert cfg.queue_cap_bytes == int(1.5 * (1 << 20))

    def test_table_nbytes_estimates_schema_width(self):
        from flink_ml_tpu.serving.admission import table_nbytes

        t = _dense_table(n=64, dim=5)
        est = table_nbytes(t)
        # 64 rows x (5 f32 features + 1 f64 label) = 64*(20+8)
        assert est == 64 * (5 * 4 + 8)


class TestTrainingUnderPressure:
    def test_fit_under_ceiling_matches_exactly(self):
        """Injected OOM above the window size: the micro-batch fallback
        streams the identical update schedule — params EXACTLY equal the
        unpressured fit's."""
        t = _dense_table()
        est = lambda: _logreg(iters=4, global_batch_size=32)  # noqa: E731
        m0 = est().fit(t)
        w0 = np.asarray(m0.coefficients())
        b0 = float(m0.intercept())
        from flink_ml_tpu.table import slab_pool

        slab_pool.reset_pool()
        pressure.reset_states()
        obs.enable()
        obs.reset()
        injection.configure("fault.oom>64")
        try:
            m1 = est().fit(t)
        finally:
            injection.configure(None)
        np.testing.assert_array_equal(np.asarray(m1.coefficients()), w0)
        assert float(m1.intercept()) == b0
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.ooms.train.glm", 0) >= 1, c
        assert c.get("train.pressure_runs", 0) >= 1, c
        # the state remembers: a second pressured fit re-bisects nothing
        obs.reset()
        injection.configure("fault.oom>64")
        try:
            m2 = est().fit(t)
        finally:
            injection.configure(None)
        np.testing.assert_array_equal(np.asarray(m2.coefficients()), w0)
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.ooms.train.glm", 0) == 0, c

    def test_single_step_accumulation_deterministic_and_close(self):
        """A ceiling below even one SGD step forces within-step gradient
        accumulation: sum-based, ascending-chunk order — deterministic
        across runs, and numerically within f32 accumulation tolerance
        of the unpressured fit."""
        from flink_ml_tpu.table import slab_pool

        t = _dense_table()
        est = lambda: _logreg(iters=4, global_batch_size=32)  # noqa: E731
        m0 = est().fit(t)
        w0 = np.asarray(m0.coefficients())

        def pressured_fit():
            slab_pool.reset_pool()
            pressure.reset_states()
            injection.configure("fault.oom>16")
            try:
                return est().fit(t)
            finally:
                injection.configure(None)

        obs.enable()
        m1, m2 = pressured_fit(), pressured_fit()
        np.testing.assert_array_equal(
            np.asarray(m1.coefficients()), np.asarray(m2.coefficients())
        )  # bitwise-stable accumulation order
        np.testing.assert_allclose(
            np.asarray(m1.coefficients()), w0, rtol=1e-5, atol=1e-6
        )
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.accum_steps", 0) >= 1, c

    def test_aimd_restores_fused_path(self, monkeypatch):
        from flink_ml_tpu.table import slab_pool

        t = _dense_table()
        est = lambda: _logreg(iters=2, global_batch_size=32)  # noqa: E731
        slab_pool.reset_pool()
        obs.enable()
        injection.configure("fault.oom>64")
        try:
            est().fit(t)
        finally:
            injection.configure(None)
        st = pressure.state("train.glm")
        assert st.cap is not None
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "0")
        for _ in range(20):
            st.admit(1024)
        assert st.cap is None
        obs.reset()
        est().fit(t)  # back on the fused whole-batch program
        c = obs.registry().snapshot()["counters"]
        assert c.get("train.fused_runs", 0) >= 1, c
        assert c.get("train.pressure_runs", 0) == 0, c

    def test_subprocess_fit_under_oom_matches_exactly(self, tmp_path):
        """The satellite contract end-to-end: a fresh process whose
        ENVIRONMENT carries the injected HBM ceiling (configured before
        any flink_ml_tpu import, like production FMT_FAULT_INJECT) fits
        through grad-accumulation windows and prints params BIT-IDENTICAL
        to the fault-free subprocess fit."""
        script = (
            "import numpy as np\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "jax.config.update('jax_enable_x64', True)\n"
            "from flink_ml_tpu.lib import LogisticRegression\n"
            "from flink_ml_tpu.table.schema import DataTypes, Schema\n"
            "from flink_ml_tpu.table.table import Table\n"
            "rng = np.random.RandomState(3)\n"
            "X = rng.randn(256, 5).astype(np.float32)\n"
            "y = (X[:, 0] > 0).astype(np.float64)\n"
            "t = Table.from_columns(Schema.of(('features', "
            "DataTypes.DENSE_VECTOR), ('label', 'double')), "
            "{'features': X, 'label': y})\n"
            "m = (LogisticRegression().set_vector_col('features')"
            ".set_label_col('label').set_prediction_col('p')"
            ".set_learning_rate(0.5).set_max_iter(4)"
            ".set_global_batch_size(32).fit(t))\n"
            "w = list(np.asarray(m.coefficients())) + [float(m.intercept())]\n"
            "print('PARAMS ' + ' '.join(f'{v:.17g}' for v in w))\n"
        )

        def run(spec):
            env = dict(os.environ)
            env.pop("FMT_FAULT_INJECT", None)
            if spec:
                env["FMT_FAULT_INJECT"] = spec
            env["FMT_OBS"] = "0"
            env["JAX_ENABLE_X64"] = "1"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            )
            out = subprocess.run(
                [sys.executable, "-c", script], capture_output=True,
                text=True, timeout=240, env=env, cwd=REPO,
            )
            assert out.returncode == 0, out.stderr[-2000:]
            lines = [ln for ln in out.stdout.splitlines()
                     if ln.startswith("PARAMS")]
            assert lines, out.stdout
            return lines[0]

        clean = run(None)
        pressured = run("fault.oom>64")
        assert pressured == clean, (pressured, clean)


class TestPressureStateUnit:
    def test_shrink_halves_and_admit_probes(self, monkeypatch):
        st = pressure.PressureState("unit")
        assert st.admit(1000) == 1000
        st.shrink(1000)
        assert st.cap == 500
        st.shrink(500)
        assert st.cap == 250
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "3600")
        assert st.admit(1000) == 250  # probe interval not elapsed
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "0")
        assert st.admit(1000) == 375  # +1000//8
        assert st.capped_below(1000)
        assert not st.capped_below(300)

    def test_probe_interval_respected(self, monkeypatch):
        st = pressure.PressureState("unit2")
        st.admit(800)
        st.shrink(800)
        monkeypatch.setenv("FMT_PRESSURE_PROBE_S", "60")
        before = st.cap
        st.admit(800)
        assert st.cap == before  # too soon to probe
        st._last_change = time.monotonic() - 61
        st.admit(800)
        assert st.cap == before + 100  # 800 // 8
