"""Replica-router example — a saved pipeline behind a 3-replica
scale-out fleet, under concurrent traffic, rolling-deployed and
chaos-killed mid-stream.

One ``ModelServer`` process is a ceiling; this is the shape past it
(ISSUE 13): the :class:`~flink_ml_tpu.serving.ReplicaRouter` fans the
same ``submit() -> Future`` contract across N replica subprocesses,
each running the full single-process serving stack (micro-batching,
breakers, telemetry) discovered through the ephemeral-port handshake.
The script:

1. fits a 3-stage pipeline twice (v1/v2) and SAVES both (integrity
   commit records included);
2. spins up a ``ReplicaRouter`` over the saved v1 — three replica
   children, health-aware power-of-two-choices balancing — and fires
   concurrent small requests at it from a thread pool;
3. mid-traffic, rolling-deploys v2 with zero downtime: one replica at a
   time drains, swaps, and re-admits on ``/readyz`` 200 while the rest
   of the fleet serves;
4. ``kill -9``\\ s one replica mid-traffic: its in-flight requests retry
   on the survivors (zero caller-visible failures) and a replacement is
   respawned;
5. prints throughput, request-latency p50/p99, the zero-failure count,
   and the death/respawn/deploy accounting.

Run: JAX_PLATFORMS=cpu python examples/router_serving.py [--requests N]
     [--threads K] [--replicas R]

On a TPU host this example fails at replica boot, by design: the parent
fits the model, so it holds the chip, and each child is pinned to the
parent's platform instead of quietly serving from XLA:CPU.  A fleet that
shares a host's chips is ROADMAP S7.
"""

import argparse
import os
import signal
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.api.pipeline import Pipeline
from flink_ml_tpu.lib import LogisticRegression
from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
from flink_ml_tpu.serving import ReplicaRouter
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

N_ROWS, N_FEATURES = 4096, 12


def fit_pipeline(table, max_iter):
    return Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(max_iter),
    ]).fit(table)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--requests", type=int, default=120)
    parser.add_argument("--threads", type=int, default=8)
    parser.add_argument("--replicas", type=int, default=3)
    args = parser.parse_args()

    obs.enable()
    rng = np.random.RandomState(42)
    X = (2.0 * rng.randn(N_ROWS, N_FEATURES) + 1.0).astype(np.float32)
    w = rng.randn(N_FEATURES).astype(np.float32)
    y = ((X - 1.0) @ w > 0).astype(np.float64)
    table = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )

    # 1. fit + save both versions (atomic writes, CRC commit records)
    save_root = tempfile.mkdtemp(prefix="router_serving_")
    v1_dir = os.path.join(save_root, "v1")
    v2_dir = os.path.join(save_root, "v2")
    fit_pipeline(table, max_iter=3).save(v1_dir)
    fit_pipeline(table, max_iter=6).save(v2_dir)
    print(f"saved v1 and v2 pipelines under {save_root}")

    # 2. the fleet: N replica children behind the router
    router = ReplicaRouter(v1_dir, version="v1", replicas=args.replicas,
                           poll_ms=30)
    print(f"fleet up: {router.ready_count()}/{args.replicas} replicas "
          f"ready (pids {[r['pid'] for r in router.replicas]})")

    sizes = rng.choice([1, 2, 4, 8], size=args.requests)
    offsets = np.cumsum(np.concatenate([[0], sizes[:-1]]))
    outcomes, errors = [], []

    def call(i):
        lo = int(offsets[i]) % (N_ROWS - 8)
        res = router.predict(table.slice_rows(lo, lo + int(sizes[i])),
                             timeout=120)
        return res.version, res.num_rows

    def fire(indices, pool):
        for future in [pool.submit(call, i) for i in indices]:
            try:
                outcomes.append(future.result())
            except Exception as exc:  # noqa: BLE001 - counted, reported
                errors.append(exc)

    router.predict(table.slice_rows(0, 4), timeout=120)  # warm the fleet
    deploy_at = args.requests // 3
    kill_at = 2 * args.requests // 3
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=args.threads) as pool:
        fire(range(deploy_at), pool)
        # 3. zero-downtime rolling deploy, one replica at a time
        status = router.deploy(v2_dir, "v2")
        deployed = sum(1 for r in status["replicas"]
                       if r["outcome"] == "deployed")
        fire(range(deploy_at, kill_at), pool)
        # 4. chaos: kill one replica outright, keep the traffic coming
        victim = router.replicas[0]["pid"]
        os.kill(victim, signal.SIGKILL)
        fire(range(kill_at, args.requests), pool)
    wall = time.perf_counter() - t0

    # wait out the respawn so the fleet leaves whole
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        stats = router.stats()
        if (stats.get("router.respawns", 0) >= 1
                and router.ready_count() >= args.replicas):
            break
        time.sleep(0.1)
    stats = router.stats()
    versions = sorted({v for v, _n in outcomes})
    total_rows = sum(n for _v, n in outcomes)
    ready = router.ready_count()
    router.shutdown()

    # 5. the numbers an operator would watch
    print(f"served {len(outcomes)} requests ({total_rows} rows) in "
          f"{wall * 1e3:.1f} ms -> {len(outcomes) / wall:.0f} req/s, "
          f"{total_rows / wall:.0f} rows/s")
    print(f"request latency p50 {stats.get('latency_p50_ms', 0):.1f} ms, "
          f"p99 {stats.get('latency_p99_ms', 0):.1f} ms")
    print(f"rolling deploy: {deployed}/{args.replicas} replicas on v2; "
          f"versions served: {versions}; failed requests: {len(errors)}")
    if errors:
        print(f"first failure: {errors[0]!r}")
    print(f"killed replica pid {victim}; fleet back to {ready}/"
          f"{args.replicas} ready "
          f"(deaths: {stats.get('router.replica_deaths', 0):.0f}, "
          f"respawns: {stats.get('router.respawns', 0):.0f}, "
          f"retries: {stats.get('router.retries', 0):.0f})")


if __name__ == "__main__":
    main()
