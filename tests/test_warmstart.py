"""Cold-start resilience: compile-cache resolution + warm-artifact store
(ISSUE 18, resolution rewritten in ISSUE 21).

Covers where the persistent compile cache lives (``JAX_COMPILATION_CACHE_DIR``
owned by JAX itself, else ``<repo>/.jax_cache``, ``FMT_COMPILE_CACHE=off``)
and the warm-artifact layer: AOT save/load round trip onto the executable's
own devices, torn-write / corrupt-entry / fingerprint mismatch / call-time
failure -> detected degrade to recompile (counter + flight event, never a
raise), bounded GC, fault-injection points, the fused lookup-before-compile
path, the ladder warmup in ``VersionManager.deploy``, and what a spawned
replica inherits.
"""

import os
import pickle

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from flink_ml_tpu import obs  # noqa: E402
from flink_ml_tpu.serve import integrity  # noqa: E402
from flink_ml_tpu.serving import warmstart  # noqa: E402
from flink_ml_tpu.utils import compile_cache, knobs  # noqa: E402


def _counters():
    return obs.registry().snapshot().get("counters", {})


@pytest.fixture(autouse=True)
def _obs_on():
    obs.enable()
    obs.reset()
    obs.flight.reset()
    yield
    # back off for the file a worker runs next (tests/test_trace.py asserts
    # the default)
    obs.disable()
    obs.reset()


# -- compile-cache resolution -------------------------------------------------


@pytest.fixture
def cache_state(monkeypatch):
    """Isolate the module latch and the environment, and RECORD every
    ``jax.config.update`` the resolution makes instead of applying it."""
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.delenv("FMT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return monkeypatch, updates


class TestCompileCacheResolution:
    def test_knob_declared(self):
        names = {k.name for k in knobs.DECLARATIONS}
        assert "FMT_COMPILE_CACHE" in names
        assert "FMT_WARM_LADDER_MAX" in names
        assert "FMT_WARMSTART" in names
        assert "FMT_WARM_DIR" in names
        assert "FMT_WARM_CACHE_MB" in names

    def test_jax_variable_owns_the_directory(self, cache_state, tmp_path):
        env, updates = cache_state
        d = str(tmp_path / "placed_from_outside")
        env.setenv("JAX_COMPILATION_CACHE_DIR", d)
        assert compile_cache.enable_compilation_cache() == d
        assert compile_cache.cache_dir() == d
        # JAX reads its own variable: the package never sets the directory
        assert "jax_compilation_cache_dir" not in updates
        # ...but still caches every program regardless of size/time
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1

    def test_default_is_fixed_path_in_the_checkout(self, cache_state,
                                                   tmp_path):
        env, updates = cache_state
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
            repo, ".jax_cache")
        d = str(tmp_path / ".jax_cache")
        env.setattr(compile_cache, "DEFAULT_CACHE_DIR", d)
        env.setattr(compile_cache, "_cpu_only", lambda: False)
        assert compile_cache.enable_compilation_cache() == d
        assert updates["jax_compilation_cache_dir"] == d
        assert compile_cache.cache_dir() == d and os.path.isdir(d)
        # idempotent: a second call neither moves nor re-sets it
        updates.clear()
        assert compile_cache.enable_compilation_cache() == d
        assert "jax_compilation_cache_dir" not in updates

    def test_explicit_cpu_run_has_no_default_cache(self, cache_state):
        # jax_platforms is "cpu" under the test harness
        _env, updates = cache_state
        assert compile_cache._cpu_only()
        assert compile_cache.enable_compilation_cache() is None
        assert compile_cache.cache_dir() is None
        assert "jax_compilation_cache_dir" not in updates

    def test_off_disables(self, cache_state, tmp_path):
        env, updates = cache_state
        env.setenv("FMT_COMPILE_CACHE", "off")
        env.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ignored"))
        assert compile_cache.enable_compilation_cache() is None
        assert compile_cache.cache_dir() is None
        assert updates == {"jax_enable_compilation_cache": False}

    def test_directory_value_is_no_longer_a_meaning(self, cache_state,
                                                    tmp_path):
        # FMT_COMPILE_CACHE keeps one meaning, "off": a directory there
        # places nothing (JAX_COMPILATION_CACHE_DIR does that now)
        env, updates = cache_state
        env.setenv("FMT_COMPILE_CACHE", str(tmp_path / "xla"))
        assert compile_cache.enable_compilation_cache() is None
        assert "jax_compilation_cache_dir" not in updates


# -- warm-artifact store (tentpole) -------------------------------------------


def _tiny_compiled():
    x = jnp.arange(8, dtype=jnp.float32)
    s = jnp.float32(2.0)
    f = jax.jit(lambda a, b: a * b + 1.0)
    return f.lower(x, s).compile(), (x, s)


@pytest.fixture
def store(tmp_path):
    st = warmstart.WarmstartStore(str(tmp_path / "warm_aot"))
    yield st


class TestWarmstartStore:
    def test_save_load_roundtrip(self, store):
        compiled, args = _tiny_compiled()
        key = store.entry_key("scaler", 8, 1, "float32", extra="t0")
        assert store.save(key, compiled)
        loaded = store.load(key)
        assert loaded is not None
        np.testing.assert_array_equal(
            np.asarray(loaded(*args)), np.asarray(compiled(*args))
        )
        c = _counters()
        assert c.get("warmstart.saves", 0) >= 1
        assert c.get("warmstart.hits", 0) >= 1
        # entry file + CRC sidecar both on disk
        p = store.entry_path(key)
        assert os.path.exists(p)
        assert os.path.exists(integrity.commit_path(p))

    def test_roundtrip_loads_onto_the_executables_own_devices(self, store):
        # a one-device executable replayed in an 8-device process: without
        # execution_devices jax would load it onto all 8 and refuse the call
        assert jax.device_count() == 8
        compiled, args = _tiny_compiled()
        key = store.entry_key("one_device", 8, 1, "float32")
        assert store.save(key, compiled)
        with open(store.entry_path(key), "rb") as f:
            assert pickle.loads(f.read())["device_ids"] == [
                jax.devices()[0].id]
        loaded = store.load(key)
        np.testing.assert_array_equal(
            np.asarray(loaded(*args)), np.asarray(compiled(*args)))

    def test_call_time_failure_degrades_to_recompile(self, store):
        """A replayed executable that loads but fails when CALLED is a
        degrade like a load-time one — the request is answered by the
        recompiled program, once and for all."""
        compiled, args = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        recompiles = []

        def recompile():
            recompiles.append(1)
            return compiled

        loaded = store.load(key, recompile=recompile)
        loaded._fn = lambda *a: (_ for _ in ()).throw(RuntimeError(
            "INVALID_ARGUMENT: Expected args to execute_sharded_on_local_"
            "devices to have 8 shards, got: [1, 1]"))
        np.testing.assert_array_equal(
            np.asarray(loaded(*args)), np.asarray(compiled(*args)))
        np.testing.assert_array_equal(
            np.asarray(loaded(*args)), np.asarray(compiled(*args)))
        assert recompiles == [1]  # switched for good, not per call
        c = _counters()
        assert c.get("warmstart.degraded.call", 0) == 1
        assert c.get("warmstart.degraded", 0) == 1

    def test_call_time_oom_is_not_the_artifacts_fault(self, store):
        compiled, args = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        loaded = store.load(key, recompile=lambda: compiled)
        loaded._fn = lambda *a: (_ for _ in ()).throw(RuntimeError(
            "RESOURCE_EXHAUSTED: Error allocating device buffer: "
            "Attempting to allocate 4.00G. That was not possible. There "
            "are 3.75G free.; (0x0x0_HBM0)"))
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            loaded(*args)  # re-raised for the pressure layer
        assert _counters().get("warmstart.degraded", 0) == 0

    def test_missing_entry_is_miss(self, store):
        assert store.load(store.entry_key("nope", 1, 1, "float32")) is None
        c = _counters()
        assert c.get("warmstart.misses", 0) >= 1
        assert c.get("warmstart.degraded", 0) == 0

    def test_corrupt_entry_degrades_not_raises(self, store):
        compiled, _ = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        p = store.entry_path(key)
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        with open(p, "wb") as f:
            f.write(bytes(raw))
        assert store.load(key) is None  # degrade, never a wrong answer
        c = _counters()
        assert c.get("warmstart.degraded", 0) >= 1
        assert c.get("warmstart.degraded.corrupt", 0) >= 1
        kinds = [e.get("kind") for e in obs.flight.events()]
        assert "warmstart.degraded" in kinds

    def test_torn_write_detected(self, store):
        compiled, _ = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        p = store.entry_path(key)
        # simulate a torn write: entry landed but the commit record did not
        os.remove(integrity.commit_path(p))
        assert store.load(key) is None
        assert _counters().get("warmstart.degraded.torn", 0) >= 1

    def test_truncated_entry_detected(self, store):
        compiled, _ = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        p = store.entry_path(key)
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
        assert store.load(key) is None
        assert _counters().get("warmstart.degraded.corrupt", 0) >= 1

    def test_fingerprint_mismatch_degrades(self, store):
        key = store.entry_key("k", 8, 1, "float32")
        blob = pickle.dumps({
            "fmt": warmstart.ENTRY_FORMAT,
            "fingerprint": "0" * 12,
            "key": key,
            "payload": b"",
            "in_tree": None,
            "out_tree": None,
        })
        with integrity.AtomicFile(store.entry_path(key)) as f:
            f.write(blob)
        assert store.load(key) is None
        assert _counters().get("warmstart.degraded.fingerprint", 0) >= 1

    def test_gc_evicts_stale_fingerprints(self, store):
        compiled, _ = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        assert store.save(key, compiled)
        stale = os.path.join(store.root, "deadbeef0000")
        os.makedirs(stale, exist_ok=True)
        with open(os.path.join(stale, "old.aot"), "wb") as f:
            f.write(b"x" * 4096)
        evicted = store.gc(max_bytes=1024)
        assert evicted >= 1
        assert not os.path.exists(stale)  # stale fingerprints go first
        assert _counters().get("warmstart.gc_evictions", 0) >= 1

    def test_fault_injection_points(self, store):
        from flink_ml_tpu.fault import injection

        compiled, _ = _tiny_compiled()
        key = store.entry_key("k", 8, 1, "float32")
        injection.configure("warmstart.save@1")
        try:
            assert store.save(key, compiled) is False  # degraded, no raise
        finally:
            injection.reset()
        assert _counters().get("fault.injected.warmstart.save", 0) == 1

        assert store.save(key, compiled)
        injection.configure("warmstart.load@1")
        try:
            assert store.load(key) is None  # falls back to recompile
        finally:
            injection.reset()
        assert _counters().get("fault.injected.warmstart.load", 0) == 1

    def test_manifest_seal(self, store):
        compiled, _ = _tiny_compiled()
        k1 = store.entry_key("a", 8, 1, "float32")
        k2 = store.entry_key("b", 32, 1, "float32")
        store.save(k1, compiled)
        store.save(k2, compiled)
        mp = store.seal_manifest()
        assert mp and os.path.exists(mp)
        man = store.manifest()
        assert man["fingerprint"] == store.fingerprint
        assert set(man["entries"]) == {k1, k2}

    def test_concurrent_writer_tmp_is_unique(self, tmp_path):
        # last-writer-wins coordination relies on per-writer tmp names
        p = str(tmp_path / "e.aot")
        af = integrity.AtomicFile(p, unique_tmp=True)
        assert str(os.getpid()) in af._tmp


# -- fused lookup-before-compile ----------------------------------------------


def _fit_scaler_model(tmp_path):
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(7)
    X = rng.randn(64, 5).astype(np.float32)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X}
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
    ]).fit(t)
    return model, t


class TestLookupBeforeCompile:
    def test_second_plan_hits_warm_artifact(self, tmp_path):
        model, t = _fit_scaler_model(tmp_path)
        warmstart.configure(str(tmp_path / "warm_aot"))
        try:
            out1 = model.transform(t)[0]
            assert _counters().get("warmstart.saves", 0) >= 1
            # a fresh plan (fresh FusedRun, as a respawned replica builds)
            # must load the persisted executable instead of compiling
            d = str(tmp_path / "m")
            model.save(d)
            from flink_ml_tpu.api.pipeline import PipelineModel

            obs.reset()
            m2 = PipelineModel.load(d)
            out2 = m2.transform(t)[0]
            c = _counters()
            assert c.get("warmstart.hits", 0) >= 1
            assert c.get("warmstart.compile_skips", 0) >= 1
            np.testing.assert_array_equal(
                np.asarray(out1.col("features"), dtype=np.float64),
                np.asarray(out2.col("features"), dtype=np.float64),
            )
        finally:
            warmstart.configure(None)

    def test_inactive_store_means_no_counters(self, tmp_path):
        model, t = _fit_scaler_model(tmp_path)
        assert warmstart.active() is None
        model.transform(t)[0].col("features")
        c = _counters()
        assert c.get("warmstart.saves", 0) == 0
        assert c.get("warmstart.hits", 0) == 0


# -- ladder warmup in deploy (satellite 3) ------------------------------------


class TestLadderWarmup:
    def test_deploy_walks_bounded_ladder(self, tmp_path, monkeypatch):
        from flink_ml_tpu.serving.versioning import VersionManager

        monkeypatch.setenv("FMT_WARM_LADDER_MAX", "3")
        model, t = _fit_scaler_model(tmp_path)
        warm = t.slice_rows(0, 8)
        warmstart.configure(str(tmp_path / "warm_aot"))
        try:
            vm = VersionManager()
            vm.deploy(model, "v1", warmup=warm)
            c = _counters()
            # rungs 1 and 32 beyond the 8-row live sample, bounded at 3
            assert c.get("serving.warm_ladder_rungs", 0) == 2
            # the sealed manifest is on disk after the swap
            assert warmstart.active().manifest()["entries"]
        finally:
            warmstart.configure(None)

    def test_ladder_disabled_at_zero(self, tmp_path, monkeypatch):
        from flink_ml_tpu.serving.versioning import VersionManager

        monkeypatch.setenv("FMT_WARM_LADDER_MAX", "0")
        model, t = _fit_scaler_model(tmp_path)
        warmstart.configure(str(tmp_path / "warm_aot"))
        try:
            vm = VersionManager()
            vm.deploy(model, "v1", warmup=t.slice_rows(0, 8))
            assert _counters().get("serving.warm_ladder_rungs", 0) == 0
        finally:
            warmstart.configure(None)


    def test_a_warm_path_deploy_compiles_nothing_and_serves_the_same_bits(
            self, tmp_path, monkeypatch):
        """What a respawned or rolling-deployed replica relies on: a
        path deploy over the store an earlier deploy sealed replays every
        rung, so its compile-ledger delta is EMPTY, nothing degrades, and
        its first response is the cold deploy's bit for bit.  Each deploy
        starts as a fresh process does: no ledgered shape, no active
        store, a model loaded from the directory."""
        from flink_ml_tpu.common import fused
        from flink_ml_tpu.obs import trace
        from flink_ml_tpu.serving.versioning import VersionManager

        monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
        monkeypatch.delenv("FMT_WARM_DIR", raising=False)
        model, t = _fit_scaler_model(tmp_path)
        d = str(tmp_path / "m")
        model.save(d)

        def ledger_lines():
            try:
                with open(trace.compile_ledger_path()) as f:
                    return sum(1 for line in f if line.strip())
            except OSError:
                return 0

        def deploy_and_serve():
            fused.reset_compile_keys()
            trace.reset()
            warmstart.configure(None)
            obs.reset()
            before = ledger_lines()
            vm = VersionManager()
            vm.deploy(d, "v1", warmup=t.slice_rows(0, 8))
            out = vm.active().transform(t.slice_rows(8, 24))
            return (_counters(), ledger_lines() - before,
                    np.asarray(out.col("features")).tobytes())

        try:
            cold, cold_lines, cold_bytes = deploy_and_serve()
            warm, warm_lines, warm_bytes = deploy_and_serve()
        finally:
            warmstart.configure(None)
        assert cold.get("warmstart.saves", 0) > 0    # the cold deploy sealed it
        assert cold_lines > 0
        assert warm.get("warmstart.hits", 0) > 0     # ...and the warm one hit
        assert warm.get("warmstart.degraded", 0) == 0
        assert warm_lines == 0, "the warm deploy compiled something"
        assert warm_bytes == cold_bytes


# -- what a spawned replica inherits -------------------------------------------


class TestSpawnEnvPropagation:
    def test_platform_and_cache_dirs_ride_to_children(self, tmp_path,
                                                      monkeypatch):
        from flink_ml_tpu.serving import replica as replica_mod

        monkeypatch.delenv("FMT_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xla"))
        warmstart.configure(str(tmp_path / "warm_aot"))
        try:
            env = {}
            replica_mod._child_env(env)
            # pinned to the parent's backend: a child that cannot get it
            # dies at boot instead of serving from another platform
            assert env["JAX_PLATFORMS"] == jax.default_backend() == "cpu"
            assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "xla")
            assert env["FMT_WARM_DIR"] == str(tmp_path / "warm_aot")
            assert "FMT_COMPILE_CACHE" not in env
        finally:
            warmstart.configure(None)

    def test_resolved_default_cache_dir_rides_too(self, tmp_path,
                                                  monkeypatch):
        from flink_ml_tpu.serving import replica as replica_mod

        monkeypatch.delenv("FMT_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(compile_cache, "_enabled_dir",
                            str(tmp_path / ".jax_cache"))
        env = {}
        replica_mod._child_env(env)
        assert env["JAX_COMPILATION_CACHE_DIR"] == str(
            tmp_path / ".jax_cache")

    def test_no_cache_no_store_hands_over_neither(self, monkeypatch):
        from flink_ml_tpu.serving import replica as replica_mod

        monkeypatch.setattr(compile_cache, "_enabled_dir", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert warmstart.active() is None
        env = {}
        replica_mod._child_env(env)
        assert "JAX_COMPILATION_CACHE_DIR" not in env
        assert "FMT_WARM_DIR" not in env
