#!/usr/bin/env python
"""Chaos smoke (ISSUE 3): the tier-1-fast fit matrix under seeded fault
injection, asserting convergence parity and nonzero retry accounting.

Legs (all on the virtual 8-device CPU mesh):

  1. **fused GLM** — fit under an injected cold-placement fault plus a
     slab-pool lookup fault; params must EQUAL the fault-free run's
     (retry and fallback are schedule-transparent), with ``fault.retries``
     and ``fault.fallbacks`` nonzero in the fit RunReports.
  2. **streamed out-of-core GLM** — spill-backed fit under an injected
     spill-read corruption plus a placement fault; params must EQUAL the
     fault-free run's (the corrupted epoch rebuilds from source).
  3. **mid-run SIGTERM/resume** — for BOTH paths, a worker subprocess
     receives a real SIGTERM mid-fit, commits an emergency checkpoint,
     exits 0; a resume subprocess completes the run and its params must be
     BIT-IDENTICAL to an uninterrupted run's.
  4. **dead-peer watchdog** — ``agree_max`` against a wedged allgather
     must raise the ``FMT_AGREE_TIMEOUT_S`` diagnostic, not hang.

Run directly (``python scripts/chaos_smoke.py``) or via the CI
``chaos-smoke`` job.  Exit code 0 = all parity and accounting assertions
held.

**Serving mode** (``--serve``, ISSUE 4): the inference-path counterpart.
For each estimator family (GLM, KMeans, Knn, StandardScaler):

  1. **quarantine** — one injected bad row (NaN) per batch must be masked
     out with a reason code in the side-table while every surviving row's
     prediction EQUALS the clean run's;
  2. **breaker + fallback** — under a sticky ``serve.dispatch`` fault the
     per-mapper circuit breaker opens and the NumPy CPU fallback serves,
     with discrete predictions exactly equal to the device run's;
  3. **model integrity** — one corrupted model file per family must raise
     ``ModelIntegrityError`` at load (never wrong predictions);

plus the RunReport accounting: transform reports carry the serve deltas
and ``serve_degraded_runs`` flags the fallback-only transforms (the
``obs --check`` SERVE-DEGRADED line).

**Serving-runtime mode** (``--serving``, ISSUE 7): the request-path
counterpart, against the dynamic micro-batching ``ModelServer``:

  1. **shed under overload** — a paused server with a tiny queue cap must
     reject past-cap submissions with reason-coded
     ``ServerOverloadedError`` (expired-oldest shed first, then
     ``queue_full``), then serve every ADMITTED request correctly once it
     drains — overload loses the rejected requests and nothing else;
  2. **hot swap under load** — a mid-traffic ``deploy`` of a new version
     must serve ZERO failed requests; results span both versions and
     every row matches its version's solo transform;
  3. **corrupt deploy rollback** — deploying a bit-flipped model artifact
     raises ``ModelIntegrityError`` and the previous version keeps
     serving;
  4. **breaker-open shed** — an open circuit breaker sheds at admission
     (``breaker_open``) instead of queueing onto a dead device;

plus the ``serving`` RunReport from shutdown carrying the shed/swap
counters and the request-latency p50/p99.

**Pressure mode** (``--pressure``, ISSUE 9): the memory-pressure
resilience counterpart — a deterministic 256-row HBM ceiling
(``FMT_FAULT_INJECT="fault.oom>256"``) against the serving and training
stacks:

  1. **serving survives the ceiling** — a 2048-row load (32 x 64-row
     requests) through ``ModelServer`` must complete with ZERO failed
     requests, every caller's predictions BIT-IDENTICAL to the
     unpressured run, and ``pressure.ooms``/``pressure.bisections``
     nonzero (the fused plan bisected under the ceiling instead of
     failing);
  2. **AIMD recovery** — once the ceiling lifts, continued traffic must
     probe the cap back up (``pressure.resizes`` > 0) until full batches
     dispatch unsplit again (the surface's cap clears);
  3. **training grad-accumulation parity** — a fit under the ceiling
     must stream micro-batch windows and produce params EXACTLY equal to
     the fault-free fit's;
  4. **memory-pressure admission** — with ``FMT_SERVING_QUEUE_CAP_MB``
     set below the offered load, admission must shed with the
     reason-coded ``memory_pressure`` ``ServerOverloadedError`` and a
     flight-recorder dump must land for it.

**Telemetry mode** (``--telemetry``, ISSUE 10): the live-plane
counterpart — the OpenMetrics exporter and readiness endpoints under
real load and a real degradation:

  1. **scrape under load** — with concurrent request traffic flowing
     through ``ModelServer``, ``GET /metrics`` must parse as valid
     OpenMetrics text (the strict independent parser, not the
     renderer), and every exported counter must sit within the
     ``registry().snapshot()`` bounds taken around the scrape — the
     exporter publishes the registry, not an approximation of it;
  2. **readiness degrades and recovers** — a sticky injected
     ``serve.dispatch`` fault drives the circuit breaker open:
     ``/readyz`` must flip to 503 with the machine-readable
     ``breaker_open`` reason (and ``/statusz`` must show the open
     breaker + the active model version); once the fault clears and
     the cooldown elapses, a served probe closes the breaker and
     ``/readyz`` must return 200;
  3. **SLO burn-rate** — the shed traffic from the open-breaker window
     must drive the ``shed_error_ratio`` SLO monitor into breach
     (``slo.burning.*`` gauge set, a ``slo_breach`` flight dump whose
     header names the SLO and its burn rate), and recover after clean
     traffic;
  4. **lifecycle** — ``shutdown`` must take the endpoint down with the
     server (no orphaned listener).

**Drift mode** (``--drift``, ISSUE 11): the data-plane counterpart —
the full drift-detection loop under an injected distribution shift:

  1. **baseline** — live traffic freezes the deploy-time reference
     distribution (``FMT_DRIFT_REF_ROWS``); the drift SLO judges the
     live window at well under 1x burn and ``/readyz`` stays 200;
  2. **breach** — a 5-sigma covariate shift injected on ONE feature
     column must burn ``slo.burning.drift`` past 1x, flip ``/readyz``
     to 503 with the reason-coded ``drift`` entry, surface the shifted
     column at the top of ``/statusz``'s per-column section, and land a
     ``drift_breach`` black box whose header AND per-column ring events
     name exactly that column with its reference-vs-live quantiles;
  3. **recovery by redeploy** — ``deploy()`` of a new version resets
     the reference; the shifted population becomes the new baseline,
     the burn clears, and ``/readyz`` returns 200;
  4. **CLI** — ``python -m flink_ml_tpu.obs drift`` renders the
     per-column comparison from the shutdown serving report.

**Online mode** (``--online``, ISSUE 14): the continuous-learning
counterpart — an online fitter training beside the live server through
the ``ContinuousLearningController``'s validation gate:

  1. **loop demo** — a clean label stream beside live request traffic
     must swap >= 2 validated candidates through the zero-downtime
     deploy contract with ZERO failed requests;
  2. **poisoned label burst** — hugely mis-scaled labels drive the
     online SGD non-finite; the gate must block the swap reason-coded
     (``numeric_health``/``score_quarantine``) with a black-box dump
     while the OLD model keeps serving BIT-IDENTICALLY with zero
     caller-visible failures, the trainer must reset to the last good
     candidate, and once clean labels resume a later candidate must
     validate and swap again (the self-healing loop);
  3. **post-swap drift burn** — a 5-sigma covariate shift on the live
     request stream inside the probation window must burn
     ``slo.burning.drift`` and the controller must automatically roll
     the server back to the prior version through the
     integrity-verified swap path (``lifecycle.rollbacks``, black box).

**Multi-chip mode** (``--multichip``, ISSUE 15): the SPMD serving
counterpart — the fused mesh path on the 8 fake devices this smoke
already forces:

  1. **sharded path proof** — a dense 2-stage chain AND a categorical
     segment-CSR chain (indexer -> encoder -> sparse LR) must dispatch
     EVERY fused batch through ``shard_map``
     (``fused.shard_map_dispatches == pipeline.fused_dispatches``, zero
     plan fallbacks) — the CSR single-device bypass is gone;
  2. **injected OOM under load** — a 2048-row ``ModelServer`` load under
     a ``fault.oom`` row ceiling must serve ZERO failed requests with
     every caller's predictions BIT-IDENTICAL to the unpressured run,
     the learned ``FusedPlan[...]`` cap must be PER-DEVICE-denominated
     (global limit = cap x 8 within the ceiling — one OOM on the mesh
     must not collapse the cap to a 1-device floor), and once the
     ceiling lifts AIMD must probe every cap back up until full batches
     dispatch unsplit; a pressured segment-CSR transform must
     re-extract its sharded sub-ranges bit-identically too;
  3. **breaker trip on the mesh path** — a sticky ``serve.dispatch``
     fault must open the per-plan breaker ON the sharded path and the
     staged fallback must serve with exact discrete parity.

**Router mode** (``--router``, ISSUE 13): the horizontal-scale-out
counterpart — a 3-replica ``ReplicaRouter`` fleet under sustained
concurrent load:

  1. **replica kill** — ``kill -9`` of one replica mid-traffic must
     complete with ZERO failed client requests (in-flight requests
     retry on the survivors, counted in ``router.retries``), the death
     must be detected and a replacement respawned
     (``router.replica_deaths`` / ``router.respawns``), and the fleet
     must return to 3 ready replicas;
  2. **rolling deploy under load** — ``router.deploy(v2)`` must drain
     and swap one replica at a time with ZERO failed requests and zero
     router sheds, results spanning both versions with per-version
     solo-transform parity, and every replica finishing on v2;
  3. **corrupt deploy** — a bit-flipped artifact must stop the roll at
     the first replica with ``RollingDeployError`` (the replica-side
     swap contract rolled it back), partial per-replica status
     preserved at ``router.deploy_status``, and the whole fleet still
     serving the old version;

plus the ``ReplicaRouter`` RunReport from shutdown carrying the
death/respawn/deploy accounting and request-latency quantiles.

**Trace mode** (``--trace``, ISSUE 8): the observability counterpart —
end-to-end request tracing plus the black-box flight recorder:

  1. **waterfall** — one traced request through ``ModelServer`` must
     yield a single trace whose ``submit -> queue_wait -> coalesce ->
     transform -> (fused_dispatch -> device_sync) -> demux`` spans nest
     correctly, with queue_wait + transform accounting within the
     request's own wall time;
  2. **black box on breaker-open** — a sticky injected dispatch fault
     drives the breaker open; a flight-recorder dump must land
     containing the closed->open breaker transition and the subsequent
     ``breaker_open`` shed IN CAUSAL ORDER (ring sequence numbers), with
     the shed event carrying the shed request's ``trace_id`` (the same
     id stamped on its ``ServerOverloadedError``).

**Fleet-trace mode** (``--fleet-trace``, ISSUE 16): the distributed
counterpart — trace-context propagation across a real router + 2
replica subprocesses:

  1. **tail sampling under load** — 50 routed requests with
     ``FMT_TRACE_TAIL=slow`` keep only the anomalous traces, and at
     least one survivor stitches spans from >= 2 processes with
     router-probed clock offsets on disk;
  2. **retries as siblings** — an injected ``router.dispatch`` fault
     renders the retry as a sibling span under one root (error -> ok);
  3. **the fleet CLI** — ``python -m flink_ml_tpu.obs fleet`` lists and
     renders the stitched multi-process waterfall with its per-phase
     cost rollup.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# environment before jax import: virtual mesh, x64 (match the test suite),
# telemetry on so RunReports carry the fault accounting this smoke asserts
os.environ.setdefault("FMT_COMPILE_CACHE", "off")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_X64", "1")
if "--worker" not in sys.argv:
    # telemetry in the parent only: the SIGTERM workers run fault-free
    # fits of the same estimators, and their clean fit reports would
    # otherwise steal the latest-per-name slot fault_assisted_runs judges
    os.environ["FMT_OBS"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

N, DIM, CHUNK_ROWS = 256, 5, 64


def make_xy():
    rng = np.random.RandomState(17)
    X = rng.randn(N, DIM)
    y = (X @ rng.randn(DIM) > 0).astype(np.float64)
    return X, y


def dense_table():
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    X, y = make_xy()
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X.astype(np.float32), "label": y},
    )


def chunked_table(spill=True):
    from flink_ml_tpu.table.schema import Schema
    from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

    X, y = make_xy()
    rows = [tuple(X[i]) + (y[i],) for i in range(N)]
    schema = Schema([f"f{i}" for i in range(DIM)] + ["label"],
                    ["double"] * (DIM + 1))
    return ChunkedTable(CollectionSource(rows, schema), CHUNK_ROWS,
                        spill=spill)


def fused_est(ckpt=None):
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(4)
    )
    if ckpt:
        est.set_checkpoint_dir(str(ckpt)).set_checkpoint_interval(1)
    return est


def streamed_est(ckpt=None):
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression()
        .set_feature_cols([f"f{i}" for i in range(DIM)])
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(4)
        .set_global_batch_size(32)
    )
    if ckpt:
        est.set_checkpoint_dir(str(ckpt)).set_checkpoint_interval(1)
    return est


def auc(scores, y):
    """Rank-statistic AUC (no sklearn in the image)."""
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    pos = y > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def params_of(model):
    return np.asarray(model.coefficients()), float(model.intercept())


# -- worker modes (SIGTERM legs run in real subprocesses) ---------------------


def worker(mode: str, ckpt: str) -> None:
    if mode.startswith("fused"):
        if mode == "fused-crash":
            # die to a real SIGTERM right after the first snapshot commits
            import flink_ml_tpu.iteration.checkpoint as ck

            orig, seen = ck.save_checkpoint, {"n": 0}

            def killing_save(*a, **kw):
                path = orig(*a, **kw)
                seen["n"] += 1
                if seen["n"] == 1:
                    os.kill(os.getpid(), signal.SIGTERM)
                return path

            ck.save_checkpoint = killing_save
        model = fused_est(ckpt).fit(dense_table())
    else:
        table = chunked_table(spill=False)
        if mode == "ooc-crash":
            served = {"n": 0}
            orig_chunks = type(table).chunks

            def killing_chunks(self):
                for t in orig_chunks(self):
                    served["n"] += 1
                    if served["n"] == N // CHUNK_ROWS + 2:  # mid-epoch 2
                        os.kill(os.getpid(), signal.SIGTERM)
                    yield t

            type(table).chunks = killing_chunks
        model = streamed_est(ckpt).fit(table)
    w, b = params_of(model)
    print("PARAMS " + " ".join(f"{v:.17g}" for v in list(w) + [b]),
          flush=True)


def run_worker(mode, ckpt):
    env = dict(os.environ)
    env.pop("FMT_FAULT_INJECT", None)
    # the SIGTERM workers run fault-FREE fits of the same estimators; with
    # obs on they would append clean fit reports AFTER the chaos fits and
    # steal the latest-per-name slot fault_assisted_runs judges
    env["FMT_OBS"] = "0"
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", mode,
         str(ckpt)],
        capture_output=True, text=True, timeout=300, env=env,
    )


def sigterm_resume_leg(mode: str, tmp: str) -> None:
    plain = run_worker(f"{mode}-run", os.path.join(tmp, f"{mode}-ref"))
    assert plain.returncode == 0, plain.stderr
    ref = [ln for ln in plain.stdout.splitlines() if ln.startswith("PARAMS")]
    assert ref, plain.stdout

    ckpt = os.path.join(tmp, f"{mode}-crash")
    crashed = run_worker(f"{mode}-crash", ckpt)
    assert crashed.returncode == 0, (
        f"{mode}: preempted worker must exit cleanly (0), got "
        f"{crashed.returncode}: {crashed.stderr[-2000:]}"
    )
    assert "PARAMS" not in crashed.stdout, "worker survived its SIGTERM"
    assert os.listdir(ckpt), "no emergency checkpoint committed"

    resumed = run_worker(f"{mode}-run", ckpt)
    assert resumed.returncode == 0, resumed.stderr
    res = [ln for ln in resumed.stdout.splitlines()
           if ln.startswith("PARAMS")]
    assert res == ref, (
        f"{mode}: resumed params are not bit-identical\n{res}\n{ref}"
    )
    print(f"  {mode}: SIGTERM -> emergency checkpoint -> exact resume OK")


def _serve_families(table):
    """(name, fitted model, prediction column, discrete) per estimator
    family — the serving-mode test matrix."""
    from flink_ml_tpu.lib import KMeans, Knn, LogisticRegression, StandardScaler

    lr = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3).fit(table)
    )
    km = (
        KMeans().set_vector_col("features").set_k(4)
        .set_prediction_col("cluster").set_max_iter(3).fit(table)
    )
    knn = (
        Knn().set_vector_col("features").set_label_col("label")
        .set_k(3).set_prediction_col("p").fit(table)
    )
    sc = (
        StandardScaler().set_selected_col("features")
        .set_output_col("scaled").fit(table)
    )
    return [
        ("LogisticRegression", lr, "p", True),
        ("KMeans", km, "cluster", True),
        ("Knn", knn, "p", True),
        ("StandardScaler", sc, "scaled", False),
    ]


def _col_matrix(table, col):
    """A column as a comparable float matrix (vector columns densify)."""
    from flink_ml_tpu.table.schema import DataTypes

    if DataTypes.is_vector(table.schema.type_of(col)):
        return np.asarray(table.features_dense(col), dtype=np.float64)
    return np.asarray(table.col(col), dtype=np.float64).reshape(-1, 1)


def serve_main() -> int:
    """The serving-robustness chaos matrix (``--serve``)."""
    import warnings

    reports_dir = tempfile.mkdtemp(prefix="chaos_serve_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ["FMT_SERVE_BREAKER_THRESHOLD"] = "2"
    os.environ["FMT_RETRY_ATTEMPTS"] = "2"
    os.environ["FMT_RETRY_BASE_S"] = "0.001"
    from flink_ml_tpu import fault, obs, serve
    from flink_ml_tpu.serve import ModelIntegrityError, quarantine
    from flink_ml_tpu.table.table import Table

    table = dense_table()
    X, y = make_xy()
    bad_row = 7  # the injected bad row, one per (single-batch) transform
    Xbad = X.astype(np.float32).copy()
    Xbad[bad_row, 1] = np.nan
    bad_table = Table.from_columns(
        table.schema, {"features": Xbad, "label": y}
    )

    for name, model, pred_col, discrete in _serve_families(table):
        serve_name = type(model).__name__  # the mapper telemetry key
        (clean,) = model.transform(table)
        ref = _col_matrix(clean, pred_col)

        # -- leg 1: one bad row per batch -> quarantined, good rows exact --
        quarantine.reset()
        (q_out,) = model.transform(bad_table)
        assert q_out.num_rows() == N - 1, (
            f"{name}: expected {N - 1} served rows, got {q_out.num_rows()}"
        )
        qt = quarantine.quarantine_table(serve_name)
        assert qt is not None and qt.num_rows() == 1, f"{name}: no quarantine"
        reason = qt.col(quarantine.QUARANTINE_REASON_COL)[0]
        row = int(qt.col(quarantine.QUARANTINE_ROW_COL)[0])
        assert reason == "nan_inf" and row == bad_row, (name, reason, row)
        got = _col_matrix(q_out, pred_col)
        np.testing.assert_array_equal(
            got, np.delete(ref, bad_row, axis=0),
            err_msg=f"{name}: quarantine changed surviving predictions",
        )

        # -- leg 2: sticky dispatch faults -> breaker opens, fallback parity --
        serve.reset_breakers()
        obs.reset()
        fault.configure("serve.dispatch@1+", seed=0)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                model.transform(table)          # breaker absorbs failures
                (fb_out,) = model.transform(table)  # now fully open
        finally:
            fault.configure(None)
        fb = _col_matrix(fb_out, pred_col)
        if discrete:
            np.testing.assert_array_equal(
                fb, ref, err_msg=f"{name}: fallback predictions diverge"
            )
        else:
            np.testing.assert_allclose(
                fb, ref, rtol=1e-5, atol=1e-6,
                err_msg=f"{name}: fallback values diverge",
            )
        counters = obs.registry().snapshot()["counters"]
        assert counters.get("serve.fallbacks", 0) >= 1, (name, counters)
        assert serve.breaker(serve_name).state == 1.0, f"{name}: not open"

        # -- leg 3: corrupted model file -> ModelIntegrityError, never junk --
        stage_dir = os.path.join(tempfile.mkdtemp(prefix="chaos_serve_m_"),
                                 "stage")
        model.save(stage_dir)
        mdf = os.path.join(stage_dir, "model_data.jsonl")
        blob = bytearray(open(mdf, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(mdf, "wb") as f:
            f.write(bytes(blob))
        from flink_ml_tpu.api.core import load_stage

        try:
            load_stage(stage_dir)
            raise AssertionError(f"{name}: corrupted model file loaded")
        except ModelIntegrityError:
            pass
        print(f"  {name}: quarantine + breaker fallback + integrity OK "
              f"(fallbacks={counters.get('serve.fallbacks'):g})")

    # -- leg 4: breaker trips INSIDE a fused plan -> per-stage fallback ------
    # (ISSUE 6): a 3-stage fused pipeline under a sticky dispatch fault
    # must open the per-PLAN breaker, split to the per-stage path, and —
    # since the fault stays sticky there too — bottom out in each mapper's
    # CPU fallback with exact discrete parity
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import (
        LogisticRegression,
        MinMaxScaler,
        StandardScaler,
    )

    pipe = Pipeline([
        StandardScaler().set_selected_col("features").set_output_col("s1"),
        MinMaxScaler().set_selected_col("s1").set_output_col("s2"),
        LogisticRegression().set_vector_col("s2").set_label_col("label")
        .set_prediction_col("p").set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)
    os.environ["FMT_FUSE_TRANSFORM"] = "1"
    (ref_t,) = pipe.transform(table)
    serve.reset_breakers()
    obs.reset()
    fault.configure("serve.dispatch@1+", seed=0)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            pipe.transform(table)            # plan breaker absorbs failures
            (fb_t,) = pipe.transform(table)  # now fully open
    finally:
        fault.configure(None)
    np.testing.assert_array_equal(
        _col_matrix(fb_t, "p"), _col_matrix(ref_t, "p"),
        err_msg="fused plan: per-stage fallback predictions diverge",
    )
    counters = obs.registry().snapshot()["counters"]
    plan_keys = [k for k in counters
                 if k.startswith("serve.fallbacks.FusedPlan[")]
    assert plan_keys, counters
    plan_name = plan_keys[0][len("serve.fallbacks."):]
    assert serve.breaker(plan_name).state == 1.0, f"{plan_name}: not open"
    assert counters.get("pipeline.plan_fallback_batches", 0) >= 1, counters
    print(f"  fused plan: breaker open -> per-stage fallback parity OK "
          f"({plan_name}, "
          f"fallback_batches={counters.get('pipeline.plan_fallback_batches'):g})")

    # -- leg 5: Pallas serving chain under chaos (ISSUE 17) ------------------
    # the same 3-stage pipeline lowered to ONE Pallas kernel per batch:
    # clean pass must be bit-identical to the XLA path with exactly one
    # kernel launch per fused dispatch; a sticky dispatch fault must open
    # the plan breaker and bottom out in the per-stage path — still exact
    # — while the degraded run is flagged PALLAS-DEGRADED in the reports
    os.environ["FMT_SERVE_PALLAS"] = "1"
    try:
        serve.reset_breakers()
        obs.reset()
        (pl_t,) = pipe.transform(table)
        np.testing.assert_array_equal(
            _col_matrix(pl_t, "p"), _col_matrix(ref_t, "p"),
            err_msg="pallas chain: predictions diverge from XLA path",
        )
        counters = obs.registry().snapshot()["counters"]
        n_disp = counters.get("fused.pallas_dispatches", 0)
        assert n_disp >= 1, counters
        assert n_disp == counters.get("pipeline.fused_dispatches"), counters
        assert counters.get("fused.pallas_fallbacks", 0) == 0, counters

        serve.reset_breakers()
        obs.reset()
        fault.configure("serve.dispatch@1+", seed=0)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                pipe.transform(table)            # plan breaker absorbs
                (pfb_t,) = pipe.transform(table)  # now fully open
        finally:
            fault.configure(None)
        np.testing.assert_array_equal(
            _col_matrix(pfb_t, "p"), _col_matrix(ref_t, "p"),
            err_msg="pallas chain: faulted fallback predictions diverge",
        )
        counters = obs.registry().snapshot()["counters"]
        assert counters.get("fused.pallas_fallbacks", 0) >= 1, counters
        assert counters.get("fused.pallas_dispatches", 0) == 0, counters
        from flink_ml_tpu.obs.report import (
            load_reports,
            pallas_degraded_runs,
        )

        pdeg = pallas_degraded_runs(load_reports(reports_dir))
        assert pdeg, "no transform RunReport was flagged PALLAS-DEGRADED"
        print(f"  pallas chain: clean parity ({n_disp:g} kernel launches) "
              f"+ breaker fallback parity OK "
              f"({len(pdeg)} PALLAS-DEGRADED run(s))")
    finally:
        os.environ.pop("FMT_SERVE_PALLAS", None)

    # -- RunReport accounting: fallback-only transforms are SERVE-DEGRADED ---
    from flink_ml_tpu.obs.report import load_reports, serve_degraded_runs

    degraded = serve_degraded_runs(load_reports(reports_dir))
    assert degraded, "no transform RunReport was flagged SERVE-DEGRADED"
    for d in degraded:
        assert d["serve"].get("serve.fallbacks", 0) >= 1, d
    print(f"  RunReports: {len(degraded)} SERVE-DEGRADED transform(s) "
          "flagged")
    print("serving chaos smoke OK")
    return 0


def serving_main() -> int:
    """The serving-runtime chaos matrix (``--serving``)."""
    import threading
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_serving_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    import numpy as np

    from flink_ml_tpu import obs, serve
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.serve import ModelIntegrityError
    from flink_ml_tpu.serving import ModelServer, ServerOverloadedError

    table = dense_table()

    def fit(max_iter):
        return Pipeline([
            StandardScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("p")
            .set_learning_rate(0.5).set_max_iter(max_iter),
        ]).fit(table)

    m1, m2 = fit(3), fit(5)
    solo = {}
    for version, model in (("v1", m1), ("v2", m2)):
        (out,) = model.transform(table)
        solo[version] = np.asarray(out.col("p"))

    # -- leg 1: shed under overload ------------------------------------------
    # a paused server IS an overloaded server: the dispatcher cannot keep
    # up, the queue hits its row cap, and admission must shed predictably
    server = ModelServer(m1, version="v1", queue_cap=40, max_batch=16,
                         max_wait_ms=1, start=False)
    admitted = [server.submit(table.slice_rows(i * 8, (i + 1) * 8))
                for i in range(4)]  # 32 of the 40-row cap
    doomed = server.submit(table.slice_rows(32, 40), deadline_ms=1)  # 40/40
    shed_kinds = set()
    try:
        server.submit(table.slice_rows(40, 56))  # cap + nothing expired yet
        raise AssertionError("past-cap submit was admitted")
    except ServerOverloadedError as exc:
        shed_kinds.add(exc.reason)
    time.sleep(0.01)  # the deadline_ms=1 request expires in the queue
    late = server.submit(table.slice_rows(40, 48))  # expired-oldest shed
    try:
        doomed.result(1)
        raise AssertionError("expired request was served")
    except ServerOverloadedError as exc:
        shed_kinds.add(exc.reason)
    assert shed_kinds == {"queue_full", "deadline_expired"}, shed_kinds
    server.start()  # overload clears: every admitted request serves right
    for i, fut in enumerate(admitted):
        got = np.asarray(fut.result(60).table.col("p"))
        np.testing.assert_array_equal(got, solo["v1"][i * 8:(i + 1) * 8])
    np.testing.assert_array_equal(
        np.asarray(late.result(60).table.col("p")), solo["v1"][40:48])
    server.shutdown()
    c = obs.registry().snapshot()["counters"]
    assert c.get("serving.shed.queue_full", 0) >= 1, c
    assert c.get("serving.shed.deadline_expired", 0) >= 1, c
    print(f"  overload: reason-coded shed {sorted(shed_kinds)}, admitted "
          "requests exact")

    # -- leg 2: hot swap under sustained load --------------------------------
    obs.reset()
    server = ModelServer(m1, version="v1", max_batch=64, max_wait_ms=1)
    results, failures = [], []
    n_req, swap_at = 60, 30
    swap_done = threading.Event()

    def traffic():
        for i in range(n_req):
            lo = (i * 4) % (N - 4)
            try:
                res = server.predict(table.slice_rows(lo, lo + 4),
                                     timeout=60)
                results.append((lo, res))
            except BaseException as exc:  # noqa: BLE001 - the assertion
                failures.append(exc)
            if i == swap_at:
                swap_done.wait(30)

    t = threading.Thread(target=traffic)
    t.start()
    while len(results) < swap_at:
        time.sleep(0.002)
    server.deploy(m2, "v2")  # mid-traffic, warmed from the live sample
    swap_done.set()
    t.join(120)
    server.shutdown()
    assert not failures, f"hot swap failed {len(failures)} requests: " \
                         f"{failures[0]!r}"
    versions = {res.version for _lo, res in results}
    assert versions == {"v1", "v2"}, versions
    for lo, res in results:
        np.testing.assert_array_equal(
            np.asarray(res.table.col("p")),
            solo[res.version][lo:lo + 4],
            err_msg=f"rows {lo}..{lo + 4} diverge from solo {res.version}",
        )
    c = obs.registry().snapshot()["counters"]
    assert c.get("serving.swaps", 0) == 1, c
    print(f"  hot swap: {len(results)} requests across {sorted(versions)}, "
          "zero failures, per-version parity exact")

    # -- leg 3: corrupt deploy -> rollback ------------------------------------
    server = ModelServer(m1, version="v1", max_wait_ms=1,
                         warmup=table.slice_rows(0, 4))
    bad_dir = os.path.join(tempfile.mkdtemp(prefix="chaos_serving_m_"), "v2")
    m2.save(bad_dir)
    mdf = os.path.join(bad_dir, "stage_001", "model_data.jsonl")
    blob = bytearray(open(mdf, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(mdf, "wb") as f:
        f.write(bytes(blob))
    try:
        server.deploy(bad_dir, "v2")
        raise AssertionError("corrupt deploy was accepted")
    except ModelIntegrityError:
        pass
    assert server.active_version == "v1"
    res = server.predict(table.slice_rows(0, 8), timeout=60)
    assert res.version == "v1"
    np.testing.assert_array_equal(np.asarray(res.table.col("p")),
                                  solo["v1"][:8])
    c = obs.registry().snapshot()["counters"]
    assert c.get("serving.deploy_failures", 0) >= 1, c
    print("  corrupt deploy: ModelIntegrityError raised, v1 kept serving")

    # -- leg 4: breaker open -> shed at admission -----------------------------
    serve.reset_breakers()
    os.environ["FMT_SERVE_BREAKER_THRESHOLD"] = "1"
    serve.breaker("LogisticRegressionModel").record_failure()
    try:
        server.submit(table.slice_rows(0, 4))
        raise AssertionError("submit queued onto an open breaker")
    except ServerOverloadedError as exc:
        assert exc.reason == "breaker_open", exc.reason
    finally:
        serve.reset_breakers()
        os.environ.pop("FMT_SERVE_BREAKER_THRESHOLD", None)
    server.shutdown()
    print("  breaker open: shed at admission (breaker_open), no queueing")

    # -- the serving RunReport from shutdown ----------------------------------
    from flink_ml_tpu.obs.report import load_reports

    serving_reports = [r for r in load_reports(reports_dir)
                       if r.get("kind") == "serving"]
    assert serving_reports, "no serving RunReport written at shutdown"
    last = serving_reports[-2]["extra"]  # the hot-swap server's report
    assert last.get("serving.swaps") == 1, last
    assert last.get("latency_p99_ms", 0) > 0, last
    print(f"  RunReports: {len(serving_reports)} serving report(s), "
          f"swap + p99 recorded")
    print("serving chaos smoke OK")
    return 0


def router_main() -> int:
    """The replica-router chaos matrix (``--router``, ISSUE 13)."""
    import glob
    import threading
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_router_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.serving import ReplicaRouter, RollingDeployError

    table = dense_table()

    def fit(max_iter):
        return Pipeline([
            StandardScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("p")
            .set_learning_rate(0.5).set_max_iter(max_iter),
        ]).fit(table)

    m1, m2 = fit(3), fit(5)
    root = tempfile.mkdtemp(prefix="chaos_router_models_")
    v1_dir, v2_dir = os.path.join(root, "v1"), os.path.join(root, "v2")
    m1.save(v1_dir)
    m2.save(v2_dir)
    solo = {}
    for version, model in (("v1", m1), ("v2", m2)):
        (out,) = model.transform(table)
        solo[version] = np.asarray(out.col("p"))

    n_replicas = 3
    router = ReplicaRouter(v1_dir, version="v1", replicas=n_replicas,
                           poll_ms=30)
    assert router.ready_count() == n_replicas, router.replicas
    print(f"  fleet: {n_replicas} replicas up "
          f"(pids {[r['pid'] for r in router.replicas]})")

    failures, results = [], []
    stop = threading.Event()

    def load():
        i = 0
        while not stop.is_set():
            lo = (i * 4) % (N - 4)
            try:
                res = router.predict(table.slice_rows(lo, lo + 4),
                                     timeout=120)
                results.append((lo, res))
            except BaseException as exc:  # noqa: BLE001 - the assertion
                failures.append(exc)
            i += 1
            time.sleep(0.002)  # sustained, not saturating: probes and
            #                    the respawned child need cycles too

    loader = threading.Thread(target=load, daemon=True)
    loader.start()
    while len(results) < 10:
        time.sleep(0.005)

    # -- leg 1: kill -9 one replica under load -> zero failed requests -------
    victim = router.replicas[0]["pid"]
    os.kill(victim, signal.SIGKILL)
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        stats = router.stats()
        if (stats.get("router.respawns", 0) >= 1
                and router.ready_count() >= n_replicas):
            break
        time.sleep(0.1)
    stats = router.stats()
    assert stats.get("router.replica_deaths", 0) >= 1, stats
    assert stats.get("router.respawns", 0) >= 1, stats
    assert router.ready_count() == n_replicas, router.replicas
    assert not failures, (
        f"{len(failures)} requests failed across the kill: "
        f"{failures[0]!r}"
    )
    served_before_deploy = len(results)
    print(f"  kill -9 pid {victim}: {served_before_deploy} requests "
          f"served, zero failures, fleet back to {n_replicas} ready "
          f"(retries={stats.get('router.retries', 0):g}, "
          f"respawns={stats.get('router.respawns'):g})")

    # -- leg 2: rolling deploy under load -> zero failures, all on v2 --------
    sheds_before = router.stats().get("router.shed", 0)
    status = router.deploy(v2_dir, "v2")
    time.sleep(0.3)  # post-deploy traffic lands on v2
    stop.set()
    loader.join(60)
    assert not failures, (
        f"{len(failures)} requests failed across the rolling deploy: "
        f"{failures[0]!r}"
    )
    assert status["ok"] is True, status
    live = [r for r in status["replicas"] if r["outcome"] == "deployed"]
    assert len(live) == n_replicas, status
    assert all(r["active_version"] == "v2" for r in live), status
    assert router.stats().get("router.shed", 0) == sheds_before, (
        "the rolling deploy shed traffic"
    )
    versions = {res.version for _lo, res in results}
    assert versions == {"v1", "v2"}, versions
    for lo, res in results:
        np.testing.assert_array_equal(
            np.asarray(res.table.col("p")), solo[res.version][lo:lo + 4],
            err_msg=f"rows {lo}..{lo + 4} diverge from solo {res.version}",
        )
    print(f"  rolling deploy: {len(results)} requests across "
          f"{sorted(versions)}, zero failures, zero sheds, "
          f"{len(live)}/{n_replicas} replicas on v2, per-version "
          "parity exact")

    # -- leg 3: corrupt deploy -> partial status, fleet keeps serving --------
    bad_dir = os.path.join(root, "bad")
    m2.save(bad_dir)
    mdf = glob.glob(os.path.join(bad_dir, "stage_*",
                                 "model_data.jsonl"))[0]
    blob = bytearray(open(mdf, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(mdf, "wb") as f:
        f.write(bytes(blob))
    try:
        router.deploy(bad_dir, "v3")
        raise AssertionError("corrupt rolling deploy was accepted")
    except RollingDeployError as exc:
        partial = exc.status
    assert partial["ok"] is False, partial
    assert partial["replicas"][0]["outcome"] == "failed", partial
    assert partial["replicas"][0]["error"] == "ModelIntegrityError", partial
    assert router.deploy_status == partial
    assert router.active_version == "v2"
    res = router.predict(table.slice_rows(0, 8), timeout=120)
    assert res.version == "v2", res.version
    np.testing.assert_array_equal(np.asarray(res.table.col("p")),
                                  solo["v2"][:8])
    print("  corrupt deploy: RollingDeployError at replica 1/3 "
          "(ModelIntegrityError), partial status reported, fleet kept "
          "serving v2")

    # -- the ReplicaRouter RunReport from shutdown ---------------------------
    router.shutdown()
    from flink_ml_tpu.obs.report import load_reports

    reports = [r for r in load_reports(reports_dir)
               if r.get("kind") == "serving"
               and r.get("name") == "ReplicaRouter"]
    assert reports, "no ReplicaRouter RunReport written at shutdown"
    extra = reports[-1]["extra"]
    assert extra.get("router.replica_deaths", 0) >= 1, extra
    assert extra.get("router.respawns", 0) >= 1, extra
    assert extra.get("router.rolling_deploys", 0) == 2, extra
    assert extra.get("latency_p99_ms", 0) > 0, extra
    c = obs.registry().snapshot()["counters"]
    assert c.get("router.rolling_deploys", 0) == 2, c
    print(f"  RunReport: deaths/respawns/deploys recorded, p99 "
          f"{extra['latency_p99_ms']:.1f} ms")
    print("router chaos smoke OK")
    return 0


def trace_main() -> int:
    """The tracing + flight-recorder chaos matrix (``--trace``)."""
    import time

    os.environ["FMT_TRACE"] = "1"
    os.environ["FMT_TRACE_DIR"] = tempfile.mkdtemp(prefix="chaos_traces_")
    os.environ["FMT_FLIGHT_DIR"] = tempfile.mkdtemp(prefix="chaos_flight_")
    os.environ["FMT_FLIGHT_MIN_S"] = "0"  # every dump lands (test mode)
    os.environ["FMT_OBS_REPORTS"] = tempfile.mkdtemp(
        prefix="chaos_trace_reports_"
    )
    from flink_ml_tpu import fault, serve
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import flight, trace
    from flink_ml_tpu.serving import ModelServer, ServerOverloadedError

    trace.enable(True, sample=1.0)
    table = dense_table()
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)

    # -- leg 1: one served request -> one correctly-nested waterfall ---------
    trace.reset()
    serve.reset_breakers()
    with ModelServer(model, max_wait_ms=1,
                     warmup=table.slice_rows(0, 4)) as server:
        t0 = time.perf_counter()
        server.predict(table.slice_rows(0, 8), timeout=60)
        wall_s = time.perf_counter() - t0
    spans = trace.load_spans()
    roots = [s for s in spans if s["name"] == "serving.request"]
    assert len(roots) == 1, f"expected 1 request trace, got {len(roots)}"
    tid = roots[0]["trace_id"]
    mine = [s for s in spans if s["trace_id"] == tid]
    by_name = {s["name"]: s for s in mine}
    for want in ("submit", "queue_wait", "coalesce", "transform",
                 "fused_dispatch", "device_sync", "demux"):
        assert want in by_name, f"missing span {want!r}: {sorted(by_name)}"
    root_id = roots[0]["span_id"]
    for child in ("submit", "queue_wait", "coalesce", "transform", "demux"):
        assert by_name[child]["parent_id"] == root_id, (
            child, by_name[child]["parent_id"], root_id)
    # fused_dispatch nests under serve.dispatch, inside the transform tree
    by_id = {s["span_id"]: s for s in mine}
    anc, hops = by_name["fused_dispatch"], []
    while anc["parent_id"]:
        anc = by_id[anc["parent_id"]]
        hops.append(anc["name"])
    assert hops[0] == "serve.dispatch" and "transform" in hops, hops
    assert by_name["device_sync"]["parent_id"] == \
        by_name["fused_dispatch"]["span_id"]
    # the accounted hops sum within the measured request wall time
    accounted = by_name["queue_wait"]["dur_s"] + by_name["transform"]["dur_s"]
    assert accounted <= wall_s * 1.05, (accounted, wall_s)
    assert roots[0]["dur_s"] <= wall_s * 1.05, (roots[0]["dur_s"], wall_s)
    waterfall = trace.render_waterfall(spans, tid)
    assert "fused_dispatch" in waterfall
    print(f"  waterfall: {len(mine)} spans, correct nesting, "
          f"queue_wait+transform {accounted * 1e3:.1f}ms within "
          f"wall {wall_s * 1e3:.1f}ms")
    print("\n".join("    " + line for line in waterfall.splitlines()))

    # -- leg 2: sticky dispatch fault -> breaker opens -> black box ----------
    flight.reset()
    serve.reset_breakers()
    os.environ["FMT_SERVE_BREAKER_THRESHOLD"] = "2"
    os.environ["FMT_SERVE_BREAKER_COOLDOWN_S"] = "60"
    server = ModelServer(model, max_wait_ms=1,
                         warmup=table.slice_rows(0, 4))
    try:
        fault.configure("serve.dispatch@1+", seed=0)
        # every dispatch fails -> CPU fallback still serves -> after the
        # threshold the breaker opens and dumps the black box
        shed_exc = None
        for i in range(8):
            try:
                server.predict(table.slice_rows(i * 4, i * 4 + 4),
                               timeout=120)
            except ServerOverloadedError as exc:
                shed_exc = exc
                break
        assert shed_exc is not None, "breaker never shed at admission"
        assert shed_exc.reason == "breaker_open", shed_exc.reason
        assert shed_exc.trace_id, "shed error carries no trace_id"
    finally:
        fault.configure(None)
        server.shutdown()
        serve.reset_breakers()
        os.environ.pop("FMT_SERVE_BREAKER_THRESHOLD", None)
        os.environ.pop("FMT_SERVE_BREAKER_COOLDOWN_S", None)
    dump_path = flight.last_dump_path()
    assert dump_path and os.path.exists(dump_path), (
        "no flight-recorder dump landed on breaker-open")
    events = [json.loads(line) for line in open(dump_path)]
    header, events = events[0], events[1:]
    assert header["kind"] == "flight.dump"
    opens = [e for e in events
             if e["kind"] == "breaker.state" and e.get("state") == 1.0]
    sheds = [e for e in events
             if e["kind"] == "serving.shed"
             and e.get("reason") == "breaker_open"]
    assert opens, f"no breaker-open transition in the dump: " \
                  f"{sorted({e['kind'] for e in events})}"
    assert sheds, "no breaker_open shed event in the dump"
    assert sheds[-1].get("trace_id") == shed_exc.trace_id, (
        sheds[-1].get("trace_id"), shed_exc.trace_id)
    # causal order: the ring's sequence numbers put the breaker opening
    # BEFORE the shed it caused
    assert opens[0]["seq"] < sheds[-1]["seq"], (
        opens[0]["seq"], sheds[-1]["seq"])
    assert any(e["kind"] == "serve.fallback" for e in events), (
        "no fallback events recorded before the breaker opened")
    print(f"  black box: {len(events)} events in {dump_path}")
    print(f"    breaker open seq={opens[0]['seq']} -> shed "
          f"seq={sheds[-1]['seq']} trace_id={sheds[-1]['trace_id']}")
    print("trace chaos smoke OK")
    return 0


def fleet_trace_main() -> int:
    """The fleet-tracing chaos matrix (``--fleet-trace``, ISSUE 16):
    distributed traces across a real router + 2 replica subprocesses.

      1. **tail sampling under load** — 50 routed requests with
         ``FMT_TRACE_TAIL=slow`` must persist only the anomalous traces
         (the first-compile request is slow in BOTH processes; the
         steady state is not), and at least one survivor must stitch
         spans from >= 2 pids with router-measured clock offsets on
         disk;
      2. **retries as siblings** — an injected ``router.dispatch`` fault
         must render the retry as a SIBLING ``router.dispatch`` span
         under the same root, first attempt status ``error``, last
         ``ok``;
      3. **the fleet CLI** — ``python -m flink_ml_tpu.obs fleet`` over
         the shared trace dir must list and render the stitched
         multi-process waterfall with its per-phase cost rollup.
    """
    tdir = tempfile.mkdtemp(prefix="chaos_fleet_traces_")
    # env BEFORE the router spawns: the replica children inherit the
    # sink dir and the tail policy from it
    os.environ["FMT_TRACE"] = "1"
    os.environ["FMT_TRACE_DIR"] = tdir
    os.environ["FMT_TRACE_TAIL"] = "slow"
    # the first routed request pays the replica's fused compile (~200 ms
    # on the CPU mesh); the steady state is ~10 ms — 100 ms splits them
    os.environ["FMT_TRACE_SLOW_MS"] = "100"
    os.environ["FMT_OBS_REPORTS"] = tempfile.mkdtemp(
        prefix="chaos_fleet_reports_"
    )
    from flink_ml_tpu import fault
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import trace
    from flink_ml_tpu.serving import ReplicaRouter

    trace.enable(True, sample=1.0)
    trace.set_tail("slow")
    table = dense_table()
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)
    v1_dir = os.path.join(tempfile.mkdtemp(prefix="chaos_fleet_models_"),
                          "v1")
    model.save(v1_dir)

    router = ReplicaRouter(v1_dir, version="v1", replicas=2, poll_ms=50)
    try:
        # -- leg 1: 50 requests under FMT_TRACE_TAIL=slow --------------------
        n_req = 50
        for i in range(n_req):
            lo = (i * 4) % (N - 4)
            res = router.predict(table.slice_rows(lo, lo + 4), timeout=120)
            assert res.trace_id, "routed success response carries no trace_id"
        trace.flush()
        spans = trace.load_spans(tdir)
        kept = [s for s in spans if s["name"] == "router.request"]
        assert kept, ("tail sampling dropped every trace — the "
                      "first-compile request must judge slow")
        assert len(kept) < n_req, (
            f"tail sampling kept all {len(kept)}/{n_req} traces — the "
            "steady state should be under FMT_TRACE_SLOW_MS"
        )
        pids_by_trace = {}
        for s in spans:
            pids_by_trace.setdefault(s["trace_id"], set()).add(s["pid"])
        stitched = [t for t, pids in pids_by_trace.items() if len(pids) >= 2]
        assert stitched, "no kept trace spans >= 2 processes"
        offsets = trace.load_clock_offsets(tdir)
        replica_pids = {r["pid"] for r in router.replicas}
        assert replica_pids & set(offsets), (
            f"no clock offset probed for the replicas: {offsets}"
        )
        print(f"  tail: kept {len(kept)}/{n_req} traces, "
              f"{len(stitched)} stitched across >= 2 pids, clock offsets "
              f"for {sorted(set(offsets) & replica_pids)}")

        # -- leg 2: injected dispatch fault -> sibling retry spans -----------
        trace.set_tail("")  # keep the (fast) retried trace in the parent
        fault.configure("router.dispatch@1", seed=0)
        try:
            res = router.predict(table.slice_rows(0, 4), timeout=120)
        finally:
            fault.configure(None)
        trace.flush()
        spans = trace.load_spans(tdir)
        disp = sorted(
            (s for s in spans if s["trace_id"] == res.trace_id
             and s["name"] == "router.dispatch"),
            key=lambda s: s["attrs"].get("attempt", 0),
        )
        assert len(disp) >= 2, f"retry recorded {len(disp)} dispatch span(s)"
        assert len({s["parent_id"] for s in disp}) == 1, (
            "retry attempts are not siblings under one root"
        )
        assert disp[0]["status"] == "error", disp[0]
        assert disp[-1]["status"] == "ok", disp[-1]
        stats = router.stats()
        assert stats.get("router.retries", 0) >= 1, stats
        print(f"  retry: {len(disp)} sibling router.dispatch spans under "
              f"one root (error -> ok), retries="
              f"{stats.get('router.retries'):g}")
    finally:
        router.shutdown()

    # -- leg 3: the fleet CLI over the shared trace dir ----------------------
    assert trace.fleet_main(["--traces", tdir, "--list"]) == 0
    assert trace.fleet_main(["--traces", tdir, stitched[0]]) == 0
    print("fleet-trace chaos smoke OK")
    return 0


def pressure_main() -> int:
    """The memory-pressure chaos matrix (``--pressure``, ISSUE 9)."""
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_pressure_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ["FMT_FLIGHT_DIR"] = tempfile.mkdtemp(prefix="chaos_pflight_")
    os.environ["FMT_FLIGHT_MIN_S"] = "0"
    from flink_ml_tpu import fault, obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.fault import pressure
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import flight
    from flink_ml_tpu.serving import ModelServer, ServerOverloadedError
    from flink_ml_tpu.table import slab_pool
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(11)
    n_rows, req_rows = 2048, 64
    X = rng.randn(n_rows, 8).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(t)
    (ref,) = model.transform(t)
    refp = np.asarray(ref.col("p"))

    # -- leg 1: 2048-row serving load under a 256-row HBM ceiling ------------
    pressure.reset_states()
    obs.reset()
    os.environ["FMT_PRESSURE_PROBE_S"] = "0"  # probe on every admit
    fault.configure("fault.oom>256")
    failures = []
    try:
        with ModelServer(model, max_batch=512, max_wait_ms=1) as server:
            futs = [
                server.submit(t.slice_rows(i * req_rows, (i + 1) * req_rows))
                for i in range(n_rows // req_rows)
            ]
            for i, fut in enumerate(futs):
                try:
                    got = np.asarray(fut.result(120).table.col("p"))
                    np.testing.assert_array_equal(
                        got, refp[i * req_rows:(i + 1) * req_rows],
                        err_msg=f"request {i} diverged under pressure",
                    )
                except BaseException as exc:  # noqa: BLE001 - the assertion
                    failures.append(exc)
            assert not failures, (
                f"{len(failures)} of {len(futs)} requests failed under the "
                f"injected ceiling: {failures[0]!r}"
            )
            c = obs.registry().snapshot()["counters"]
            assert c.get("pressure.ooms", 0) >= 1, c
            assert c.get("pressure.bisections", 0) >= 1, c
            print(f"  ceiling: {len(futs)} x {req_rows}-row requests served, "
                  f"zero failures, bit-identical "
                  f"(ooms={c.get('pressure.ooms'):g}, "
                  f"bisections={c.get('pressure.bisections'):g})")

            # -- leg 2: ceiling lifts -> AIMD probes back to full batch ------
            fault.configure(None)
            deadline = time.monotonic() + 60
            plan_surfaces = [
                name for name in pressure._STATES
                if name.startswith("FusedPlan[")
            ]
            assert plan_surfaces, sorted(pressure._STATES)

            def caps():
                return [pressure.state(s).cap for s in plan_surfaces]

            while any(cap is not None for cap in caps()):
                assert time.monotonic() < deadline, (
                    f"AIMD never recovered: caps={caps()}"
                )
                server.predict(t.slice_rows(0, 512), timeout=120)
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.resizes", 0) >= 1, c
        # recovered: one more transform must dispatch UNSPLIT (bisections
        # stay flat) and stay bit-identical
        before = c.get("pressure.bisections", 0)
        (out,) = model.transform(t)
        np.testing.assert_array_equal(np.asarray(out.col("p")), refp)
        after = obs.registry().snapshot()["counters"].get(
            "pressure.bisections", 0)
        assert after == before, (before, after)
        print(f"  AIMD: caps cleared, resizes={c.get('pressure.resizes'):g}, "
              "full-batch dispatch restored unsplit")
    finally:
        fault.configure(None)
        os.environ.pop("FMT_PRESSURE_PROBE_S", None)

    # -- leg 3: training under the ceiling -> exact grad-accum parity --------
    base = fused_est().set_global_batch_size(32).fit(dense_table())
    w0, b0 = params_of(base)
    slab_pool.reset_pool()
    pressure.reset_states()
    obs.reset()
    fault.configure("fault.oom>64")
    try:
        pressured = fused_est().set_global_batch_size(32).fit(dense_table())
    finally:
        fault.configure(None)
    w1, b1 = params_of(pressured)
    np.testing.assert_array_equal(w1, w0)
    assert b1 == b0
    c = obs.registry().snapshot()["counters"]
    assert c.get("train.pressure_runs", 0) >= 1, c
    assert c.get("pressure.ooms.train.glm", 0) >= 1, c
    print("  training: fit under ceiling streamed micro-batch windows, "
          f"params exact (pressure_runs={c.get('train.pressure_runs'):g})")

    # -- leg 4: bytes-denominated admission sheds memory_pressure -------------
    pressure.reset_states()
    flight.reset()
    obs.reset()
    # one 64-row request is 64 x (8 f32 features + 1 f64 label) = 2560
    # bytes: a 6 KiB cap admits two requests and sheds the third
    server = ModelServer(model, queue_cap=4096,
                         queue_cap_mb=6.0 / 1024.0, max_wait_ms=1,
                         start=False)
    server.submit(t.slice_rows(0, 64))
    server.submit(t.slice_rows(64, 128))
    try:
        server.submit(t.slice_rows(128, 192))
        raise AssertionError("past-bytes-cap submit was admitted")
    except ServerOverloadedError as exc:
        assert exc.reason == "memory_pressure", exc.reason
    dump_path = flight.last_dump_path()
    assert dump_path and os.path.exists(dump_path), (
        "no flight-recorder dump landed on the memory_pressure shed"
    )
    events = [json.loads(line) for line in open(dump_path)]
    sheds = [e for e in events if e.get("kind") == "serving.shed"
             and e.get("reason") == "memory_pressure"]
    assert sheds, sorted({e.get("kind") for e in events})
    server.start()
    server.shutdown()  # drain the two admitted requests
    c = obs.registry().snapshot()["counters"]
    assert c.get("serving.shed.memory_pressure", 0) == 1, c
    print("  admission: bytes cap shed memory_pressure, black-box dump "
          f"landed ({os.path.basename(dump_path)})")
    print("pressure chaos smoke OK")
    return 0


def telemetry_main() -> int:
    """The live-telemetry chaos matrix (``--telemetry``, ISSUE 10)."""
    import threading
    import time
    import urllib.error
    import urllib.request
    import warnings

    os.environ["FMT_OBS_REPORTS"] = tempfile.mkdtemp(
        prefix="chaos_telemetry_reports_"
    )
    os.environ["FMT_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="chaos_telemetry_flight_"
    )
    os.environ["FMT_FLIGHT_MIN_S"] = "0"  # every dump lands (test mode)
    os.environ["FMT_SERVE_BREAKER_THRESHOLD"] = "2"
    os.environ["FMT_SERVE_BREAKER_COOLDOWN_S"] = "0.75"
    from flink_ml_tpu import fault, obs, serve
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import flight, slo, telemetry
    from flink_ml_tpu.serving import ModelServer, ServerOverloadedError

    serve.reset_breakers()
    obs.reset()
    flight.reset()
    table = dense_table()
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)

    server = ModelServer(model, version="v1", max_batch=64,
                         max_wait_ms=1.0, telemetry_port=0,
                         warmup=table.slice_rows(0, 4))
    assert server.telemetry is not None and server.telemetry.port, (
        "telemetry_port=0 did not bind an ephemeral endpoint"
    )

    def get(path):
        try:
            with urllib.request.urlopen(server.telemetry.url(path),
                                        timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    # -- leg 1: scrape under concurrent load ----------------------------------
    stop = threading.Event()
    served = []

    def load():
        i = 0
        while not stop.is_set():
            lo = (i * 8) % (N - 8)
            served.append(
                server.predict(table.slice_rows(lo, lo + 8), timeout=60)
            )
            i += 1

    loader = threading.Thread(target=load)
    loader.start()
    while len(served) < 4:  # traffic genuinely concurrent with the scrape
        time.sleep(0.002)
    snap_before = obs.registry().snapshot()["counters"]
    status, text = get("/metrics")
    snap_after = obs.registry().snapshot()["counters"]
    stop.set()
    loader.join()
    assert status == 200, status
    samples = telemetry.parse_openmetrics(text)  # raises on malformed text
    checked = telemetry.counters_within_bounds(
        snap_before, samples, snap_after)  # raises on an out-of-bounds one
    assert checked >= 5, f"only {checked} counters cross-checked"
    for probe in ("/healthz", "/readyz"):
        status, _ = get(probe)
        assert status == 200, (probe, status)
    print(f"  scrape: {len(samples)} samples parsed under load, "
          f"{checked} counters within snapshot bounds")

    # -- leg 2: sticky dispatch fault -> breaker open -> /readyz 503 ---------
    mon = slo.SLOMonitor(window=60, err_ratio=0.01, min_arrivals=5)
    sheds = 0
    fault.configure("serve.dispatch@1+", seed=0)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for i in range(8):
                try:
                    server.predict(table.slice_rows(i * 4, i * 4 + 4),
                                   timeout=120)
                except ServerOverloadedError as exc:
                    assert exc.reason == "breaker_open", exc.reason
                    sheds += 1
        assert sheds, "sticky dispatch fault never opened the breaker"
        status, body = get("/readyz")
        assert status == 503, (status, body)
        payload = json.loads(body)
        assert payload["ready"] is False, payload
        reasons = {r["reason"] for r in payload["reasons"]}
        assert "breaker_open" in reasons, payload
        status, body = get("/statusz")
        st = json.loads(body)
        assert any(v == 1.0 for v in st["breakers"].values()), st["breakers"]
        assert st["server"]["active_version"] == "v1", st["server"]
        print(f"  readiness: breaker open -> /readyz 503 "
              f"{sorted(reasons)}, statusz shows "
              f"{[k for k, v in st['breakers'].items() if v == 1.0]}")

        # -- leg 3: the shed window burns the error-ratio SLO -----------------
        res = mon.sample_once()
        verdict = res.get("shed_error_ratio")
        assert verdict and verdict["burning"], res
        assert verdict["burn_rate"] > 1.0, verdict
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges.get("slo.burning.shed_error_ratio") == 1.0, gauges
        dump_path = flight.last_dump_path()
        assert dump_path and os.path.exists(dump_path), (
            "no slo_breach flight dump landed")
        header = json.loads(open(dump_path).readline())
        assert header["reason"] == "slo_breach", header
        assert header["slo"] == "shed_error_ratio", header
        assert header["burn_rate"] == round(verdict["burn_rate"], 4), header
        print(f"  slo: shed window burned at "
              f"{verdict['burn_rate']:.1f}x, black box "
              f"{os.path.basename(dump_path)} header names it")
    finally:
        fault.configure(None)

    # -- leg 4: recovery ------------------------------------------------------
    time.sleep(0.8)  # breaker cooldown elapses
    server.predict(table.slice_rows(0, 8), timeout=60)  # probe closes it
    status, body = get("/readyz")
    assert status == 200, (status, body)
    for _ in range(20):  # clean traffic clears the SLO breach
        server.predict(table.slice_rows(0, 4), timeout=60)
    res = mon.sample_once()
    assert not res["shed_error_ratio"]["burning"], res
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges.get("slo.burning.shed_error_ratio") == 0.0, gauges
    print("  recovery: breaker closed -> /readyz 200, SLO burn cleared")

    # -- leg 5: the endpoint dies with the server -----------------------------
    url = server.telemetry.url("/healthz")
    server.shutdown()
    assert server.telemetry is None
    try:
        urllib.request.urlopen(url, timeout=2)
        raise AssertionError("telemetry endpoint survived shutdown")
    except (urllib.error.URLError, ConnectionError, OSError):
        pass
    serve.reset_breakers()
    for var in ("FMT_SERVE_BREAKER_THRESHOLD",
                "FMT_SERVE_BREAKER_COOLDOWN_S", "FMT_FLIGHT_MIN_S"):
        os.environ.pop(var, None)
    print("telemetry chaos smoke OK")
    return 0


def drift_main() -> int:
    """The data-drift chaos matrix (``--drift``, ISSUE 11): the full
    loop — baseline traffic freezes a reference, an injected covariate
    shift on ONE column burns the ``drift`` SLO, ``/readyz`` degrades
    503 with the reason-coded ``drift`` entry, the ``drift_breach``
    black box names the shifted column with reference-vs-live
    quantiles, and a redeploy resets the reference so the shifted
    population becomes the new baseline and the server recovers to
    200."""
    import urllib.error
    import urllib.request

    os.environ["FMT_OBS_REPORTS"] = tempfile.mkdtemp(
        prefix="chaos_drift_reports_"
    )
    os.environ["FMT_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="chaos_drift_flight_"
    )
    os.environ["FMT_FLIGHT_MIN_S"] = "0"  # every dump lands (test mode)
    os.environ["FMT_DRIFT_REF_ROWS"] = "256"
    os.environ["FMT_DRIFT_MIN_ROWS"] = "64"
    from flink_ml_tpu import obs, serve
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import flight, slo
    from flink_ml_tpu.serving import ModelServer

    serve.reset_breakers()
    obs.reset()
    flight.reset()
    rng = np.random.RandomState(23)
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    true_w = rng.randn(DIM).astype(np.float32)

    def traffic(n, shift_col=None, shift=0.0):
        X = rng.randn(n, DIM).astype(np.float32)
        if shift_col is not None:
            X[:, shift_col] += shift
        y = (X @ true_w > 0).astype(np.float64)
        return Table.from_columns(schema, {"features": X, "label": y})

    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(traffic(512))

    server = ModelServer(model, version="v1", max_batch=64,
                         max_wait_ms=1.0, telemetry_port=0, drift=True)
    assert server.drift_monitor is not None, "drift=True armed no monitor"
    assert server._slo is not None, "no SLO monitor came up with drift"

    def get(path):
        try:
            with urllib.request.urlopen(server.telemetry.url(path),
                                        timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    def drive(n_batches, rows=32, **shift_kw):
        for _ in range(n_batches):
            server.predict(traffic(rows, **shift_kw), timeout=120)

    try:
        # -- leg 1: baseline traffic freezes the reference; all green ------
        drive(10)  # 320 rows > FMT_DRIFT_REF_ROWS
        mon = server.drift_monitor
        assert mon.reference_complete, "reference never froze"
        drive(4)  # live-window rows against the frozen reference
        res = server._slo.sample_once()
        verdict = res.get(slo.DRIFT_SLO)
        assert verdict and not verdict["burning"], verdict
        status, _ = get("/readyz")
        assert status == 200, status
        print(f"  baseline: reference frozen at "
              f"{mon.status()['reference']['rows']} rows, "
              f"drift burn {verdict['burn_rate']:.2f}x, /readyz 200")

        # -- leg 2: covariate shift on ONE column -> burn -> 503 -> dump ---
        shifted_col = 2
        drive(8, shift_col=shifted_col, shift=5.0)
        res = server._slo.sample_once()
        verdict = res.get(slo.DRIFT_SLO)
        assert verdict and verdict["burning"], verdict
        assert verdict["burn_rate"] > 1.0, verdict
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges.get("slo.burning.drift") == 1.0, gauges
        status, body = get("/readyz")
        assert status == 503, (status, body)
        payload = json.loads(body)
        reasons = {r["reason"] for r in payload["reasons"]}
        assert "drift" in reasons, payload
        status, body = get("/statusz")
        st = json.loads(body)
        worst = st["drift"]["columns"][0]
        assert worst["column"] == f"features[{shifted_col}]", worst
        dump_path = flight.last_dump_path()
        assert dump_path and "drift_breach" in os.path.basename(dump_path), (
            dump_path)
        lines = [json.loads(ln) for ln in open(dump_path)]
        header = lines[0]
        assert header["reason"] == "drift_breach", header
        assert header["worst_column"] == f"features[{shifted_col}]", header
        col_events = [e for e in lines[1:]
                      if e.get("kind") == "drift.column_breach"
                      and e.get("column") == f"features[{shifted_col}]"]
        assert col_events, "black box has no event for the shifted column"
        ev = col_events[0]
        assert ev["live_p50"] > ev["ref_p50"] + 2.0, ev  # the 5-sigma shift
        print(f"  breach: shifted features[{shifted_col}] burned at "
              f"{verdict['burn_rate']:.1f}x -> /readyz 503 {sorted(reasons)}"
              f", black box {os.path.basename(dump_path)} names it "
              f"(ref p50 {ev['ref_p50']:.2f} -> live p50 "
              f"{ev['live_p50']:.2f})")

        # -- leg 3: redeploy resets the reference -> recovery --------------
        server.deploy(model, "v2")
        assert not mon.reference_complete, (
            "redeploy did not reset the drift reference")
        drive(10, shift_col=shifted_col, shift=5.0)  # new-normal reference
        assert mon.reference_complete
        drive(4, shift_col=shifted_col, shift=5.0)   # live, same population
        res = server._slo.sample_once()
        verdict = res.get(slo.DRIFT_SLO)
        assert verdict and not verdict["burning"], verdict
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges.get("slo.burning.drift") == 0.0, gauges
        status, _ = get("/readyz")
        assert status == 200, status
        print(f"  recovery: redeploy v2 reset the reference; shifted "
              f"population is the new baseline (burn "
              f"{verdict['burn_rate']:.2f}x), /readyz 200")
    finally:
        server.shutdown()

    # the serving report carries the drift section the CLI renders
    out = subprocess.run(
        [sys.executable, "-m", "flink_ml_tpu.obs", "drift"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    assert "features[" in out.stdout, out.stdout
    print("  cli: `obs drift` renders the per-column comparison")
    for var in ("FMT_FLIGHT_MIN_S", "FMT_DRIFT_REF_ROWS",
                "FMT_DRIFT_MIN_ROWS"):
        os.environ.pop(var, None)
    print("drift chaos smoke OK")
    return 0


def online_main() -> int:
    """The continuous-learning chaos matrix (``--online``, ISSUE 14):
    the guarded train->validate->deploy loop under live traffic, a
    poisoned label burst, and a post-swap drift breach."""
    import time

    os.environ["FMT_OBS_REPORTS"] = tempfile.mkdtemp(
        prefix="chaos_online_reports_"
    )
    os.environ["FMT_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="chaos_online_flight_"
    )
    os.environ["FMT_FLIGHT_MIN_S"] = "0"  # every dump lands (test mode)
    os.environ["FMT_DRIFT_REF_ROWS"] = "256"
    os.environ["FMT_DRIFT_MIN_ROWS"] = "64"
    os.environ["FMT_SLO_WINDOW_S"] = "0.5"
    from flink_ml_tpu import obs
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.online import OnlineLogisticRegression
    from flink_ml_tpu.obs import flight
    from flink_ml_tpu.serving import (
        ContinuousLearningController,
        ModelServer,
    )
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.sources import QueueUnboundedSource
    from flink_ml_tpu.table.table import Table

    obs.reset()
    flight.reset()
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    dim = 4
    rng = np.random.RandomState(37)
    true_w = rng.randn(dim).astype(np.float64)

    def batch(n, shift_col=None, shift=0.0, poison_labels=False):
        X = rng.randn(n, dim).astype(np.float32)
        if shift_col is not None:
            X[:, shift_col] += shift
        y = (X.astype(np.float64) @ true_w > 0).astype(np.float64)
        if poison_labels:
            # finite in f64 (so the window's degenerate-row mask cannot
            # save us — this is adversarial data, not a null row) but an
            # overflow in the f32 training pipeline: the SGD goes
            # non-finite within one window and only the GATE stands
            # between the poisoned params and traffic
            y = y * 1e39 + 1e39
        return X, y

    def table_of(X, y):
        return Table.from_columns(schema, {"features": X, "label": y})

    Xi, yi = batch(256)
    init_model = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(2).fit(table_of(Xi, yi))
    )
    Xh, yh = batch(400)
    holdout = table_of(Xh, yh)
    Xp, yp = batch(32)
    probe = table_of(Xp, yp)

    server = ModelServer(init_model, version="v1", max_batch=64,
                         max_wait_ms=1.0, drift=True,
                         warmup=holdout.slice_rows(0, 8))
    source = QueueUnboundedSource(schema)

    def feed_labels(**kw):
        """One 100-row training chunk onto the label stream (~5 windows
        at 50ms spacing under the 1000ms window)."""
        X, y = batch(100, **kw)
        source.feed({"features": X, "label": y})
    estimator = (
        OnlineLogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_window_ms(1000)
    )
    controller = ContinuousLearningController(
        estimator, source, holdout, server=server,
        candidate_dir=tempfile.mkdtemp(prefix="chaos_online_cands_"),
        candidate_every=5, probation_s=120.0,
    )
    failures = []

    def serve(n_batches=4, rows=32, **kw):
        """Concurrent live traffic; every caller-visible failure is
        fatal to the leg."""
        futs = []
        for _ in range(n_batches):
            X, y = batch(rows, **kw)
            futs.append(server.submit(table_of(X, y)))
        out = []
        for f in futs:
            try:
                out.append(f.result(timeout=120))
            except Exception as exc:  # noqa: BLE001 - counted, asserted 0
                failures.append(exc)
        return out

    def wait_for(cond, what, timeout=90.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            serve(1)
            time.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    try:
        controller.start()

        # -- leg 1: the loop demo — >= 2 validated swaps, zero downtime ----
        # 5 chunks x 100 rows x 50ms = windows 1..24 fired: candidates
        # cut at windows 5/10/15/20 — waiting for all 4 quiesces the
        # loop at a KNOWN boundary (windows 21-24 pending), so leg 2's
        # first candidate after the baseline deterministically holds the
        # poisoned window
        for _ in range(5):
            feed_labels()
            serve(2)
        wait_for(lambda: controller.stats().get("lifecycle.swaps", 0) >= 4
                 and controller.windows >= 24,
                 ">= 4 validated candidate swaps")
        stats = controller.stats()
        assert stats["lifecycle.swaps"] >= 2  # the acceptance bar
        assert server.active_version.startswith("cl-"), (
            server.active_version)
        assert not failures, failures
        print(f"  loop: {stats['lifecycle.swaps']} validated candidates "
              f"swapped under live traffic (active "
              f"{server.active_version}), 0 failed requests")

        # -- leg 2: poisoned label burst -> swap blocked, old model exact --
        def quiesce():
            """Wait until the trainer drained everything fed so far (the
            queue is empty and the window count stops moving)."""
            deadline = time.monotonic() + 60
            last, stable = -1, 0
            while stable < 5 and time.monotonic() < deadline:
                w = controller.windows
                stable = stable + 1 if w == last else 0
                last = w
                time.sleep(0.05)

        def blocked_count():
            c = controller.stats()
            return (c.get("lifecycle.blocked.numeric_health", 0)
                    + c.get("lifecycle.blocked.score_quarantine", 0))

        quiesce()
        swaps_before = controller.stats().get("lifecycle.swaps", 0)
        for _ in range(2):
            feed_labels(poison_labels=True)
        wait_for(lambda: blocked_count() >= 1,
                 "the gate to block the poisoned candidate")
        stats = controller.stats()
        # a window straddling the clean/poison boundary may cut ONE more
        # all-clean candidate (stream pipelining, gate-validated); every
        # candidate holding a poisoned window must have been blocked
        assert stats.get("lifecycle.swaps", 0) - swaps_before <= 1, stats
        dump = flight.last_dump_path()
        assert dump and "lifecycle_blocked" in os.path.basename(dump), dump
        header = json.loads(open(dump).readline())
        assert header["reason"] == "lifecycle_blocked", header
        # the burst continues: serving must stay BIT-IDENTICAL on the
        # incumbent from here on while further poisoned candidates block
        incumbent = server.active_version
        probe_a = np.asarray(
            server.predict(probe, timeout=120).table.col("p"))
        swaps_at_probe = controller.stats().get("lifecycle.swaps", 0)
        feed_labels(poison_labels=True)
        wait_for(lambda: blocked_count() >= 2,
                 "the gate to block the continued burst")
        stats = controller.stats()
        assert stats.get("lifecycle.swaps", 0) == swaps_at_probe, (
            "a poisoned candidate reached traffic", stats)
        assert server.active_version == incumbent
        probe_b = np.asarray(
            server.predict(probe, timeout=120).table.col("p"))
        np.testing.assert_array_equal(probe_b, probe_a)
        assert not failures, failures
        reason = next(k for k in sorted(stats)
                      if k.startswith("lifecycle.blocked."))
        print(f"  poison: burst blocked at the gate "
              f"({blocked_count()}x {reason.split('.')[-1]}, black box "
              f"{os.path.basename(dump)}), incumbent {incumbent} served "
              "bit-identically, 0 failures")

        # the self-healing half: the trainer reset to the last good
        # candidate, so clean labels must produce a validating swap again
        assert stats.get("lifecycle.trainer_resets", 0) >= 1, stats
        for _ in range(3):
            feed_labels()
            serve(2)
        wait_for(lambda: controller.stats().get("lifecycle.swaps", 0)
                 > swaps_at_probe, "a post-burst candidate to swap")
        print(f"  recovery: trainer reset "
              f"({stats.get('lifecycle.trainer_resets')}x) and a clean "
              f"candidate swapped (active {server.active_version})")

        # -- leg 3: post-swap drift burn -> automatic rollback -------------
        swapped_to = server.active_version
        prev_version = server.previous_version
        assert prev_version is not None
        monitor = server.drift_monitor
        # freeze the new version's reference on clean traffic first
        wait_for(lambda: monitor.reference_complete,
                 "the drift reference to freeze")
        for _ in range(10):
            serve(2, shift_col=2, shift=5.0)  # the 5-sigma live shift
        wait_for(lambda: server.active_version == prev_version,
                 "the probation window to roll the swap back")
        c = obs.registry().snapshot()["counters"]
        assert c.get("lifecycle.rollbacks", 0) >= 1, c
        assert c.get("serving.rollbacks", 0) >= 1, c
        dump = flight.last_dump_path()
        assert dump and ("lifecycle_rollback" in os.path.basename(dump)
                         or "drift_breach" in os.path.basename(dump)), dump
        assert controller.incumbent_version == prev_version
        assert not failures, failures
        print(f"  probation: drift burn on the live stream rolled "
              f"{swapped_to} back to {prev_version} automatically "
              f"(lifecycle.rollbacks={c.get('lifecycle.rollbacks'):g}), "
              "0 failed requests")
    finally:
        source.close()
        try:
            controller.join(120)
        finally:
            controller.stop()
            server.shutdown()
    for var in ("FMT_FLIGHT_MIN_S", "FMT_DRIFT_REF_ROWS",
                "FMT_DRIFT_MIN_ROWS", "FMT_SLO_WINDOW_S"):
        os.environ.pop(var, None)
    assert not failures, failures
    print("online chaos smoke OK")
    return 0


def multichip_main() -> int:
    """The SPMD multi-chip serving chaos matrix (``--multichip``,
    ISSUE 15) — the fused mesh path on the forced 8-device mesh."""
    import time
    import warnings

    reports_dir = tempfile.mkdtemp(prefix="chaos_multichip_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ["FMT_SERVE_BREAKER_THRESHOLD"] = "2"
    os.environ["FMT_RETRY_ATTEMPTS"] = "2"
    os.environ["FMT_RETRY_BASE_S"] = "0.001"
    from flink_ml_tpu import fault, obs, serve
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.common import fused as fused_mod
    from flink_ml_tpu.fault import pressure
    from flink_ml_tpu.lib import LogisticRegression, StandardScaler
    from flink_ml_tpu.lib.encoding import OneHotEncoder, StringIndexer
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.RandomState(15)
    n_rows, req_rows = 2048, 64
    X = rng.randn(n_rows, 8).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    dense = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    cats = [f"v{rng.randint(9)}" for _ in range(n_rows)]
    cat = Table.from_columns(
        Schema.of(("c1", "string"), ("label", "double")),
        {"c1": cats,
         "label": (np.asarray(cats) == "v0").astype(np.float64)},
    )
    dense_model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(dense)
    csr_model = Pipeline([
        StringIndexer().set_selected_cols(["c1"]).set_output_cols(["i1"]),
        OneHotEncoder().set_selected_cols(["i1"]).set_output_col("f"),
        LogisticRegression().set_vector_col("f").set_label_col("label")
        .set_prediction_col("p").set_learning_rate(0.5).set_max_iter(2),
    ]).fit(cat)

    # -- leg 1: every fused dispatch rides shard_map (bypass detector) -------
    obs.reset()
    fused_mod.reset_mesh_stats()
    (dense_ref,) = dense_model.transform(dense)
    (csr_ref,) = csr_model.transform(cat)
    refp = np.asarray(dense_ref.col("p"))
    csr_refp = np.asarray(csr_ref.col("p"))
    c = obs.registry().snapshot()["counters"]
    assert c.get("pipeline.fused_dispatches", 0) >= 2, c
    assert (c.get("fused.shard_map_dispatches", 0)
            == c.get("pipeline.fused_dispatches")), c
    assert not c.get("pipeline.plan_fallback_batches"), c
    status = fused_mod.mesh_status()
    assert status["devices"] == 8, status
    assert sum(status["device_rows"].values()) == 2 * n_rows, status
    print(f"  sharded path: dense + segment-CSR plans, "
          f"{c.get('fused.shard_map_dispatches'):g}/"
          f"{c.get('pipeline.fused_dispatches'):g} dispatches through "
          "shard_map (CSR bypass gone), 8-device row shares accounted")

    # -- leg 2: OOM ceiling under serving load -> per-device AIMD recovery ---
    ceiling = 256
    pressure.reset_states()
    obs.reset()
    os.environ["FMT_PRESSURE_PROBE_S"] = "0"  # probe on every admit
    fault.configure(f"fault.oom>{ceiling}")
    failures = []
    try:
        with ModelServer(dense_model, max_batch=512,
                         max_wait_ms=1) as server:
            futs = [
                server.submit(
                    dense.slice_rows(i * req_rows, (i + 1) * req_rows))
                for i in range(n_rows // req_rows)
            ]
            for i, fut in enumerate(futs):
                try:
                    got = np.asarray(fut.result(120).table.col("p"))
                    np.testing.assert_array_equal(
                        got, refp[i * req_rows:(i + 1) * req_rows],
                        err_msg=f"request {i} diverged under pressure",
                    )
                except BaseException as exc:  # noqa: BLE001 - the assertion
                    failures.append(exc)
            assert not failures, (
                f"{len(failures)} of {len(futs)} requests failed under "
                f"the injected ceiling: {failures[0]!r}"
            )
            c = obs.registry().snapshot()["counters"]
            assert c.get("pressure.ooms", 0) >= 1, c
            assert c.get("pressure.bisections", 0) >= 1, c
            # the learned caps are PER-DEVICE: the plan's global limit
            # (cap x 8) sits within the ceiling instead of the whole
            # mesh collapsing toward a 1-device floor
            plan_caps = {k: st.cap for k, st in pressure._STATES.items()
                         if k.startswith("FusedPlan[")
                         and st.cap is not None}
            assert plan_caps, sorted(pressure._STATES)
            assert all(cap * 8 <= ceiling and cap >= 1
                       for cap in plan_caps.values()), plan_caps
            print(f"  ceiling: {len(futs)} x {req_rows}-row requests "
                  "served, zero failures, bit-identical; per-device caps "
                  f"{sorted(plan_caps.values())} (x8 <= {ceiling})")

            # the CSR sharded layout re-extracts its bisection sub-ranges
            (csr_pressured,) = csr_model.transform(cat)
            np.testing.assert_array_equal(
                np.asarray(csr_pressured.col("p")), csr_refp,
                err_msg="pressured segment-CSR predictions diverged",
            )
            print("  ceiling: sharded segment-CSR transform bisected "
                  "bit-identically")

            # -- ceiling lifts -> AIMD probes every cap back up ---------
            fault.configure(None)
            deadline = time.monotonic() + 60
            surfaces = [name for name in pressure._STATES
                        if name.startswith("FusedPlan[")]

            def caps():
                return [pressure.state(s).cap for s in surfaces]

            while any(cap is not None for cap in caps()):
                assert time.monotonic() < deadline, (
                    f"AIMD never recovered: caps={caps()}"
                )
                server.predict(dense.slice_rows(0, 512), timeout=120)
                csr_model.transform(cat)
        c = obs.registry().snapshot()["counters"]
        assert c.get("pressure.resizes", 0) >= 1, c
        before = c.get("pressure.bisections", 0)
        (out,) = dense_model.transform(dense)
        np.testing.assert_array_equal(np.asarray(out.col("p")), refp)
        after = obs.registry().snapshot()["counters"].get(
            "pressure.bisections", 0)
        assert after == before, (before, after)
        print(f"  AIMD: caps cleared "
              f"(resizes={c.get('pressure.resizes'):g}), full-batch "
              "mesh dispatch restored unsplit")
    finally:
        fault.configure(None)
        os.environ.pop("FMT_PRESSURE_PROBE_S", None)

    # -- leg 3: breaker trips on the mesh path -> staged fallback parity -----
    serve.reset_breakers()
    obs.reset()
    fault.configure("serve.dispatch@1+", seed=0)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            dense_model.transform(dense)        # breaker absorbs failures
            (fb_out,) = dense_model.transform(dense)  # now fully open
    finally:
        fault.configure(None)
    np.testing.assert_array_equal(
        np.asarray(fb_out.col("p")), refp,
        err_msg="mesh-path staged fallback predictions diverge",
    )
    c = obs.registry().snapshot()["counters"]
    plan_keys = [k for k in c if k.startswith("serve.fallbacks.FusedPlan[")]
    assert plan_keys, c
    plan_name = plan_keys[0][len("serve.fallbacks."):]
    assert serve.breaker(plan_name).state == 1.0, f"{plan_name}: not open"
    assert c.get("pipeline.plan_fallback_batches", 0) >= 1, c
    serve.reset_breakers()
    print(f"  breaker: sharded plan tripped open ({plan_name}), staged "
          "fallback parity exact "
          f"(fallback_batches={c.get('pipeline.plan_fallback_batches'):g})")
    print("multichip chaos smoke OK")
    return 0


def coldstart_main() -> int:
    """The cold-start resilience chaos matrix (``--coldstart``, ISSUE 18).

    1. **cold seed** — a path-deploy with a warmup sample must walk the
       bucket ladder, serialize every compiled executable into the
       model-adjacent warm-artifact store, and seal its manifest;
    2. **warm replay** — a fresh model load in the same store must serve
       its first request entirely off warm hits (zero fresh compile-ledger
       keys for the warmed rung) with predictions EXACTLY equal;
    3. **corrupt artifact** — a bit-flipped warm entry must degrade with
       the reason-coded ``warmstart.degraded.corrupt`` counter + a flight
       event, recompile, self-heal the entry, and serve bit-identical
       results (never a wrong answer, never a crash) — with the transform
       RunReport flagged by ``warmstart_degraded_runs`` (the
       ``obs --check`` WARMSTART-DEGRADED line);
    4. **kill -9 under load** — one replica of a 3-replica fleet is
       SIGKILLed mid-traffic; the router must respawn it with ZERO
       caller-visible failures and stamp the respawn ``warm`` (the child
       inherits the sealed manifest and replays instead of recompiling).
    """
    import glob
    import threading
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_coldstart_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ.pop("FMT_WARM_DIR", None)  # store lands beside the model
    os.environ["FMT_WARMSTART"] = "1"
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline, PipelineModel
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import flight
    from flink_ml_tpu.obs.report import load_reports, warmstart_degraded_runs
    from flink_ml_tpu.serving import ReplicaRouter, VersionManager, warmstart

    table = dense_table()
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)
    v1_dir = os.path.join(tempfile.mkdtemp(prefix="chaos_coldstart_"), "v1")
    model.save(v1_dir)
    (solo_out,) = model.transform(table)
    solo_full = np.asarray(solo_out.col("p"))
    solo = solo_full[:128]

    # -- leg 1: cold seed — ladder walked, store populated, manifest sealed --
    obs.reset()
    flight.reset()
    vm = VersionManager()
    vm.deploy(v1_dir, "v1", warmup=table.slice_rows(0, 8))
    c = obs.registry().snapshot()["counters"]
    assert c.get("warmstart.saves", 0) >= 1, c
    assert c.get("serving.warm_ladder_rungs", 0) >= 1, c
    inherited = warmstart.inherited_manifest_entries(v1_dir)
    assert inherited >= 1, "deploy did not seal a warm-artifact manifest"
    print(f"  cold seed: {c.get('warmstart.saves'):g} executables "
          f"serialized across {c.get('serving.warm_ladder_rungs'):g} "
          f"ladder rungs, manifest sealed ({inherited} entries)")

    # -- leg 2: warm replay — fresh load serves off hits, results exact ------
    obs.reset()
    (out,) = PipelineModel.load(v1_dir).transform(table.slice_rows(0, 128))
    c = obs.registry().snapshot()["counters"]
    assert c.get("warmstart.hits", 0) >= 1, c
    assert c.get("warmstart.compile_skips", 0) >= 1, c
    assert c.get("warmstart.degraded", 0) == 0, c
    np.testing.assert_array_equal(np.asarray(out.col("p")), solo)
    print(f"  warm replay: first request off {c.get('warmstart.hits'):g} "
          "warm hit(s), zero fresh compiles, predictions exact")

    # -- leg 3: corrupt artifact -> reason-coded degrade, self-heal, exact ---
    store = warmstart.active()
    assert store is not None
    entries = glob.glob(os.path.join(store.root, "*", "*.aot"))
    assert entries, store.root
    for path in entries:  # every rung: the replayed one must be among them
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(blob))
    obs.reset()
    flight.reset()
    (out,) = PipelineModel.load(v1_dir).transform(table.slice_rows(0, 128))
    c = obs.registry().snapshot()["counters"]
    assert c.get("warmstart.degraded.corrupt", 0) >= 1, c
    assert c.get("warmstart.degraded", 0) >= 1, c
    assert c.get("warmstart.saves", 0) >= 1, c  # the entry self-healed
    kinds = {e.get("kind") for e in flight.events()}
    assert "warmstart.degraded" in kinds, kinds
    np.testing.assert_array_equal(np.asarray(out.col("p")), solo)
    flagged = warmstart_degraded_runs(load_reports(reports_dir))
    assert flagged, "no transform RunReport flagged the degraded load"
    print(f"  corrupt artifact: degraded.corrupt={c.get('warmstart.degraded.corrupt'):g} "
          f"(flight event recorded, RunReport flagged), recompiled + "
          f"re-serialized, predictions exact")

    # -- leg 4: kill -9 under load -> warm respawn, zero failed requests -----
    obs.reset()
    n_replicas = 3
    router = ReplicaRouter(v1_dir, version="v1", replicas=n_replicas,
                           poll_ms=30)
    assert router.ready_count() == n_replicas, router.replicas
    failures, results = [], []
    stop = threading.Event()

    def load_loop():
        i = 0
        while not stop.is_set():
            lo = (i * 4) % (N - 4)
            try:
                res = router.predict(table.slice_rows(lo, lo + 4),
                                     timeout=120)
                results.append((lo, res))
            except BaseException as exc:  # noqa: BLE001 - the assertion
                failures.append(exc)
            i += 1
            time.sleep(0.002)

    loader = threading.Thread(target=load_loop, daemon=True)
    loader.start()
    while len(results) < 10:
        time.sleep(0.005)
    victim = router.replicas[0]["pid"]
    t_kill = time.monotonic()
    os.kill(victim, signal.SIGKILL)
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        stats = router.stats()
        if (stats.get("router.respawns", 0) >= 1
                and router.ready_count() >= n_replicas):
            break
        time.sleep(0.05)
    recovery_s = time.monotonic() - t_kill
    stop.set()
    loader.join(60)
    stats = router.stats()
    try:
        assert stats.get("router.respawns", 0) >= 1, stats
        assert stats.get("router.respawns_warm", 0) >= 1, (
            "the respawned replica booted cold — no sealed manifest "
            f"inherited: {stats}")
        assert router.ready_count() == n_replicas, router.replicas
        assert not failures, (
            f"{len(failures)} requests failed across the kill: "
            f"{failures[0]!r}")
        for lo, res in results:
            np.testing.assert_array_equal(
                np.asarray(res.table.col("p")), solo_full[lo:lo + 4],
                err_msg=f"rows {lo}..{lo + 4} diverge from solo")
        print(f"  kill -9 pid {victim}: {len(results)} requests served, "
              f"zero failures, warm respawn in {recovery_s:.2f}s "
              f"(respawns_warm={stats.get('router.respawns_warm'):g}, "
              f"manifest entries inherited: "
              f"{warmstart.inherited_manifest_entries(v1_dir)})")
    finally:
        router.shutdown()
    print("coldstart chaos smoke OK")
    return 0


def multitenant_main() -> int:
    """The multi-tenant serving chaos matrix (``--multitenant``, ISSUE 20).

    200 tenants — symlinked artifact dirs over TWO distinct fitted
    models, interleaved, so any cross-tenant routing mistake serves
    visibly wrong predictions — under Zipf-skewed traffic:

    1. **eviction churn in-process** — one ModelServer with a residency
       cap of 8 models over the 200 tenants: the Zipf tail forces
       constant evict/fault-in cycles, and every response must match
       that tenant's underlying model bit-for-bit (an evicted model that
       comes back wrong, or a mux that gathers another tenant's params,
       fails here);
    2. **kill -9 under multi-tenant load** — a 3-replica router fleet
       (each replica auto-registers ``<model>/tenants/``) serves the
       same Zipf stream while one replica is SIGKILLed mid-traffic:
       zero caller-visible failures, zero cross-tenant leakage across
       the respawn.
    """
    import shutil
    import threading
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_multitenant_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ["FMT_TENANT_MAX_RESIDENT"] = "8"  # churn: 200 tenants, 8 slots
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.serving import ModelServer, ReplicaRouter

    N_TENANTS, REQ_ROWS = 200, 4
    table = dense_table()

    def fit_variant(flip: bool):
        _X, _y = make_xy()
        if flip:
            _y = 1.0 - _y  # opposite decision surface: leakage flips preds
        from flink_ml_tpu.table.schema import DataTypes, Schema
        from flink_ml_tpu.table.table import Table

        t = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),
                      ("label", "double")),
            {"features": _X.astype(np.float32), "label": _y},
        )
        return Pipeline([
            StandardScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("p")
            .set_learning_rate(0.5).set_max_iter(3),
        ]).fit(t)

    work = tempfile.mkdtemp(prefix="chaos_multitenant_")
    try:
        model_a, model_b = fit_variant(False), fit_variant(True)
        v1_dir = os.path.join(work, "v1")
        model_a.save(v1_dir)
        a_dir = os.path.join(work, "model_a")
        b_dir = os.path.join(work, "model_b")
        model_a.save(a_dir)
        model_b.save(b_dir)
        # 200 tenants as symlinks into the two artifacts, interleaved —
        # the replica convention: <model>/tenants/<name>/ auto-registers
        tenants_dir = os.path.join(v1_dir, "tenants")
        os.makedirs(tenants_dir)
        names = [f"t{i:03d}" for i in range(N_TENANTS)]
        for i, name in enumerate(names):
            os.symlink(a_dir if i % 2 == 0 else b_dir,
                       os.path.join(tenants_dir, name))
        (out_a,) = model_a.transform(table)
        (out_b,) = model_b.transform(table)
        preds = {n: np.asarray((out_a if i % 2 == 0 else out_b).col("p"))
                 for i, n in enumerate(names)}
        assert not np.array_equal(preds["t000"], preds["t001"]), (
            "the two model variants agree everywhere — leakage would be "
            "invisible; the chaos leg needs distinguishable tenants")

        rng = np.random.RandomState(11)

        def zipf_stream(n):
            """(tenant, row_lo) pairs, Zipf-skewed over the 200 tenants."""
            out = []
            for v in rng.zipf(1.3, size=n):
                idx = int(v - 1) % N_TENANTS
                lo = int(rng.randint(0, N - REQ_ROWS))
                out.append((names[idx], lo))
            return out

        # -- leg 1: eviction churn in-process, parity on every response --
        obs.reset()
        server = ModelServer(path=v1_dir, version="v1", max_wait_ms=5)
        try:
            stream = zipf_stream(400)
            for burst_lo in range(0, len(stream), 40):
                burst = stream[burst_lo:burst_lo + 40]
                futs = [
                    (name, lo,
                     server.submit(table.slice_rows(lo, lo + REQ_ROWS),
                                   tenant=name))
                    for name, lo in burst
                ]
                for name, lo, f in futs:
                    res = f.result(120)
                    np.testing.assert_array_equal(
                        np.asarray(res.table.col("p")),
                        preds[name][lo:lo + REQ_ROWS],
                        err_msg=f"tenant {name} rows {lo}.. diverge — "
                                "cross-tenant leakage or a bad fault-in")
        finally:
            server.shutdown()
        c = obs.registry().snapshot()["counters"]
        distinct = len({n for n, _ in stream})
        assert c.get("serving.tenant.evictions", 0) >= 1, c
        assert c.get("serving.tenant.cold_loads", 0) > distinct, (
            "no refault churn: every tenant loaded at most once under an "
            f"8-slot cap over {distinct} distinct tenants: {c}")
        print(f"  eviction churn: 400 Zipf requests over {distinct} "
              f"distinct tenants, cap 8 — "
              f"{c.get('serving.tenant.cold_loads'):g} cold loads, "
              f"{c.get('serving.tenant.evictions'):g} evictions, "
              f"{c.get('serving.mux.dispatches', 0):g} mux dispatches, "
              "every response bit-exact")

        # -- leg 2: kill -9 one replica under multi-tenant load ----------
        obs.reset()
        n_replicas = 3
        router = ReplicaRouter(v1_dir, version="v1", replicas=n_replicas,
                               poll_ms=30)
        failures, results = [], []
        stop = threading.Event()

        def load_loop():
            i = 0
            stream = zipf_stream(10_000)
            while not stop.is_set() and i < len(stream):
                name, lo = stream[i]
                try:
                    res = router.predict(
                        table.slice_rows(lo, lo + REQ_ROWS),
                        tenant=name, timeout=120)
                    results.append((name, lo, res))
                except BaseException as exc:  # noqa: BLE001 - asserted
                    failures.append(exc)
                i += 1
                time.sleep(0.002)

        loader = threading.Thread(target=load_loop, daemon=True)
        loader.start()
        while len(results) < 20:
            time.sleep(0.005)
        victim = router.replicas[0]["pid"]
        t_kill = time.monotonic()
        os.kill(victim, signal.SIGKILL)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            stats = router.stats()
            if (stats.get("router.respawns", 0) >= 1
                    and router.ready_count() >= n_replicas):
                break
            time.sleep(0.05)
        recovery_s = time.monotonic() - t_kill
        while len(results) < 60:  # traffic ACROSS the respawn boundary
            time.sleep(0.01)
        stop.set()
        loader.join(60)
        stats = router.stats()
        try:
            assert stats.get("router.respawns", 0) >= 1, stats
            assert router.ready_count() == n_replicas, router.replicas
            assert not failures, (
                f"{len(failures)} requests failed across the kill: "
                f"{failures[0]!r}")
            for name, lo, res in results:
                np.testing.assert_array_equal(
                    np.asarray(res.table.col("p")),
                    preds[name][lo:lo + REQ_ROWS],
                    err_msg=f"tenant {name} rows {lo}.. diverge across "
                            "the respawn — cross-tenant leakage")
            served_tenants = len({n for n, _, _ in results})
            print(f"  kill -9 pid {victim}: {len(results)} requests over "
                  f"{served_tenants} tenants served, zero failures, "
                  f"respawn in {recovery_s:.2f}s, zero leakage")
        finally:
            router.shutdown()
    finally:
        shutil.rmtree(work, ignore_errors=True)
        os.environ.pop("FMT_TENANT_MAX_RESIDENT", None)
    print("multitenant chaos smoke OK")
    return 0


def autoscale_main() -> int:
    """The elastic-fleet chaos matrix (``--autoscale``, ISSUE 19).

    1. **ramp up** — a sustained traffic ramp against a 1-replica fleet
       must grow it through the autoscaler (queue-growth/burn trigger,
       standard spawn path) with ZERO failed requests and the
       driver-computed p99 inside the declared bound;
    2. **ramp down** — when the ramp ends, sustained idle must shrink
       the fleet back to min through the drain contract — zero
       caller-visible failures, every removal drain-safe;
    3. **SIGTERM storm with warm spares** — with ``warm_spares=1`` the
       fleet carries one replica above target; SIGTERMing two replicas
       under load must lose zero requests while the router self-heals
       with warm replacements (``router.respawns_warm`` stamped — the
       sealed manifest inherited, not recompiled).
    """
    import threading
    import time

    reports_dir = tempfile.mkdtemp(prefix="chaos_autoscale_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    os.environ.pop("FMT_WARM_DIR", None)  # store lands beside the model
    os.environ["FMT_WARMSTART"] = "1"
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.serving import (
        FleetAutoscaler,
        ReplicaRouter,
        VersionManager,
        warmstart,
    )

    table = dense_table()
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("p")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(table)
    v1_dir = os.path.join(tempfile.mkdtemp(prefix="chaos_autoscale_"), "v1")
    model.save(v1_dir)
    (solo_out,) = model.transform(table)
    solo = np.asarray(solo_out.col("p"))

    # seal the warm-artifact manifest (ISSUE 18) so every autoscaler
    # spawn and every respawn inherits it — leg 3 asserts the stamp
    VersionManager().deploy(v1_dir, "v1", warmup=table.slice_rows(0, 8))
    assert warmstart.inherited_manifest_entries(v1_dir) >= 1

    p99_bound_ms = 30_000.0  # the declared driver-side latency SLO
    obs.reset()
    router = ReplicaRouter(v1_dir, version="v1", replicas=1, poll_ms=30)
    scaler = FleetAutoscaler(router, min_replicas=1, max_replicas=3,
                             window_s=1.0, idle_windows=3,
                             cooldown_s=2.0, tick_s=0.25).start()
    failures, latencies = [], []
    lat_lock = threading.Lock()
    stop = threading.Event()

    def client_loop(seed):
        i = seed
        while not stop.is_set():
            lo = (i * 4) % (N - 4)
            t0 = time.monotonic()
            try:
                res = router.predict(table.slice_rows(lo, lo + 4),
                                     timeout=120)
                np.testing.assert_array_equal(
                    np.asarray(res.table.col("p")), solo[lo:lo + 4])
            except BaseException as exc:  # noqa: BLE001 - the assertion
                failures.append(exc)
            with lat_lock:
                latencies.append((time.monotonic() - t0) * 1e3)
            i += 1
            time.sleep(0.001)

    try:
        # -- leg 1: traffic ramp -> the fleet grows from min -----------------
        clients = [threading.Thread(target=client_loop, args=(s,),
                                    daemon=True) for s in range(12)]
        for t in clients:
            t.start()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if (router.fleet_size() >= 2
                    and scaler.stats()["scale_ups"] >= 1):
                break
            time.sleep(0.05)
        assert router.fleet_size() >= 2, (
            f"the ramp never grew the fleet: {scaler.stats()}, "
            f"{router.fleet_health()}")
        grown_to = router.fleet_size()
        sstats = scaler.stats()
        assert sstats["scale_ups"] >= 1, sstats
        assert router.stats().get("router.replicas_added", 0) >= 1
        print(f"  ramp up: fleet 1 -> {grown_to} "
              f"(scale_ups={sstats['scale_ups']}, "
              f"requests so far={len(latencies)})")

        # -- leg 2: ramp ends -> sustained idle shrinks it back, drain-safe --
        stop.set()
        for t in clients:
            t.join(60)
        assert not failures, (
            f"{len(failures)} requests failed during the ramp: "
            f"{failures[0]!r}")
        with lat_lock:
            p99_ms = float(np.percentile(latencies, 99))
        assert p99_ms <= p99_bound_ms, (
            f"driver p99 {p99_ms:.0f} ms breached the declared "
            f"{p99_bound_ms:.0f} ms bound")
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            # the scaler's own tally too: the router tombstones the slot
            # BEFORE the (seconds-long) child stop, so size alone races
            # the decision bookkeeping
            if (router.fleet_size() == 1
                    and scaler.stats()["scale_downs"] >= 1):
                break
            time.sleep(0.1)
        assert router.fleet_size() == 1, (
            f"sustained idle never shrank the fleet: {scaler.stats()}, "
            f"{router.fleet_health()}")
        sstats = scaler.stats()
        assert sstats["scale_downs"] >= 1, sstats
        assert router.stats().get("router.replicas_removed", 0) >= 1
        print(f"  ramp down: fleet {grown_to} -> 1 on sustained idle "
              f"(scale_downs={sstats['scale_downs']}, "
              f"{len(latencies)} requests, zero failures, "
              f"p99 {p99_ms:.1f} ms <= {p99_bound_ms:.0f} ms)")
        scaler.stop()

        # -- leg 3: SIGTERM two replicas under load -> warm spares absorb ----
        scaler = FleetAutoscaler(router, min_replicas=2, max_replicas=4,
                                 warm_spares=1, window_s=1.0,
                                 idle_windows=8, cooldown_s=2.0,
                                 tick_s=0.25).start()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if router.fleet_size() >= 3 and router.ready_count() >= 3:
                break
            time.sleep(0.05)
        assert router.ready_count() >= 3, (
            f"warm spares never provisioned: {scaler.stats()}, "
            f"{router.fleet_health()}")
        print(f"  warm spares: fleet at {router.fleet_size()} "
              f"(target 2 + 1 spare)")
        failures.clear()
        stop.clear()
        respawns_before = router.stats().get("router.respawns", 0)
        clients = [threading.Thread(target=client_loop, args=(s,),
                                    daemon=True) for s in range(8)]
        for t in clients:
            t.start()
        time.sleep(0.5)  # traffic is flowing before the storm
        victims = [r["pid"] for r in router.replicas[:2]
                   if r.get("pid")]
        assert len(victims) == 2, router.replicas
        for pid in victims:
            os.kill(pid, signal.SIGTERM)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            stats = router.stats()
            if (stats.get("router.respawns", 0) >= respawns_before + 2
                    and router.ready_count() >= 3):
                break
            time.sleep(0.05)
        stop.set()
        for t in clients:
            t.join(60)
        stats = router.stats()
        assert stats.get("router.respawns", 0) >= respawns_before + 2, stats
        assert stats.get("router.respawns_warm", 0) >= 2, (
            "the storm's replacements booted cold — no sealed manifest "
            f"inherited: {stats}")
        assert router.ready_count() >= 3, router.replicas
        assert not failures, (
            f"{len(failures)} requests failed across the SIGTERM storm: "
            f"{failures[0]!r}")
        print(f"  SIGTERM storm: pids {victims} killed under load, "
              f"zero failures, self-healed to "
              f"{router.ready_count()} ready with warm replacements "
              f"(respawns_warm={stats.get('router.respawns_warm'):g})")
    finally:
        stop.set()
        scaler.stop()
        router.shutdown()
    print("autoscale chaos smoke OK")
    return 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker(sys.argv[2], sys.argv[3])
        return 0
    if "--serve" in sys.argv:
        return serve_main()
    if "--serving" in sys.argv:
        return serving_main()
    if "--router" in sys.argv:
        return router_main()
    if "--trace" in sys.argv:
        return trace_main()
    if "--fleet-trace" in sys.argv:
        return fleet_trace_main()
    if "--pressure" in sys.argv:
        return pressure_main()
    if "--telemetry" in sys.argv:
        return telemetry_main()
    if "--drift" in sys.argv:
        return drift_main()
    if "--online" in sys.argv:
        return online_main()
    if "--multichip" in sys.argv:
        return multichip_main()
    if "--coldstart" in sys.argv:
        return coldstart_main()
    if "--autoscale" in sys.argv:
        return autoscale_main()
    if "--multitenant" in sys.argv:
        return multitenant_main()

    reports_dir = tempfile.mkdtemp(prefix="chaos_reports_")
    os.environ["FMT_OBS_REPORTS"] = reports_dir
    from flink_ml_tpu import fault, obs
    from flink_ml_tpu.table import slab_pool

    X, y = make_xy()

    # -- leg 1: fused GLM under a cold-placement fault (retried) --------------
    base_model = fused_est().fit(dense_table())
    w0, b0 = params_of(base_model)
    slab_pool.reset_pool()
    obs.reset()
    fault.configure("place.h2d@1", seed=0)
    try:
        chaos_model = fused_est().fit(dense_table())
    finally:
        fault.configure(None)
    w1, b1 = params_of(chaos_model)
    np.testing.assert_array_equal(w1, w0)
    assert b1 == b0
    counters = obs.registry().snapshot()["counters"]
    assert counters.get("fault.retries", 0) >= 1, counters
    assert counters.get("fault.injected", 0) >= 1, counters
    s0 = auc(X.astype(np.float32) @ w0 + b0, y)
    s1 = auc(X.astype(np.float32) @ w1 + b1, y)
    assert s1 == s0
    print(f"  fused GLM: chaos params exact, AUC parity {s1:.4f}, "
          f"retries={counters.get('fault.retries'):g}")

    # -- leg 1b: fused GLM under a slab-pool lookup fault (degrades) ----------
    import warnings

    slab_pool.reset_pool()
    obs.reset()
    fault.configure("slab.lookup@1", seed=0)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            pool_chaos = fused_est().fit(dense_table())
    finally:
        fault.configure(None)
    w2, b2 = params_of(pool_chaos)
    np.testing.assert_array_equal(w2, w0)
    assert b2 == b0
    counters = obs.registry().snapshot()["counters"]
    assert counters.get("fault.fallbacks", 0) >= 1, counters
    print("  fused GLM: pool-lookup fault degraded to direct placement, "
          f"params exact, fallbacks={counters.get('fault.fallbacks'):g}")

    # -- leg 2: streamed out-of-core under spill corruption + placement fault
    obs.reset()
    base_stream = streamed_est().fit(chunked_table())
    sw0, sb0 = params_of(base_stream)
    obs.reset()
    fault.configure("spill.read@1,place.h2d@1", seed=0)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            chaos_stream = streamed_est().fit(chunked_table())
    finally:
        fault.configure(None)
    sw1, sb1 = params_of(chaos_stream)
    np.testing.assert_array_equal(sw1, sw0)
    assert sb1 == sb0
    counters = obs.registry().snapshot()["counters"]
    assert counters.get("fault.spill_rebuilds", 0) >= 1, counters
    assert counters.get("fault.retries", 0) >= 1, counters
    print("  streamed ooc: spill corruption rebuilt, params exact, "
          f"retries={counters.get('fault.retries'):g}")

    # -- leg 3: SIGTERM mid-run -> emergency checkpoint -> exact resume -------
    with tempfile.TemporaryDirectory(prefix="chaos_ckpt_") as tmp:
        sigterm_resume_leg("fused", tmp)
        sigterm_resume_leg("ooc", tmp)

    # -- leg 4: dead-peer watchdog --------------------------------------------
    import time

    from flink_ml_tpu.fault.watchdog import CollectiveTimeoutError
    from flink_ml_tpu.parallel import mesh

    real_count = jax.process_count
    jax.process_count = lambda: 2
    from jax.experimental import multihost_utils

    real_gather = multihost_utils.process_allgather
    multihost_utils.process_allgather = lambda *a, **k: time.sleep(120)
    os.environ["FMT_AGREE_TIMEOUT_S"] = "1.0"
    t0 = time.perf_counter()
    try:
        mesh.agree_max(7)
        raise AssertionError("agree_max with a dead peer did not raise")
    except CollectiveTimeoutError as exc:
        took = time.perf_counter() - t0
        assert took < 10.0 and "agree_max" in str(exc)
        print(f"  watchdog: dead-peer agree_max diagnosed in {took:.1f}s")
    finally:
        jax.process_count = real_count
        multihost_utils.process_allgather = real_gather
        os.environ.pop("FMT_AGREE_TIMEOUT_S", None)

    # -- RunReport accounting: the chaos fits are self-identifying ------------
    from flink_ml_tpu.obs.report import fault_assisted_runs, load_reports

    flagged = fault_assisted_runs(load_reports(reports_dir))
    assert flagged, "no fit RunReport carried fault counters"
    names = {json.dumps(sorted(f["fault_counters"])) for f in flagged}
    print(f"  RunReports: {len(flagged)} fault-assisted fit(s) flagged "
          f"({len(names)} distinct counter sets)")
    print("chaos smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
