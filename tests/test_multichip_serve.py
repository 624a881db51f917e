"""Multi-device serving parity (ISSUE 15, tier-1).

The SPMD serving contract: serving on an 8-device mesh is a DEPLOYMENT
detail — every shipped mapper family (dense LR, sparse segment-CSR LR,
the scalers, KMeans assign, the Knn chunked scan) must produce the same
answers fused, staged, and across mesh widths (discrete outputs
bit-identical, floats within accumulation tolerance), quarantine
side-tables must carry the same original-feed offsets, and a
pressure-bisection run must recover bit-identically on the mesh.

The checks run in SUBPROCESSES (``XLA_FLAGS=--xla_force_host_platform_
device_count={8,1}``) because the device count pins at backend init:
the parent fits + saves the models once (model files are the
cross-process contract — both workers load identical bytes) and each
worker transforms identical deterministic tables; this module compares
their emitted results.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tests.multichip_serve_worker import make_tables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multichip_serve_worker.py")


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """Fit + save the five family pipelines ONCE; workers load them."""
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import KMeans, Knn, LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler

    dense, sparse = make_tables()
    root = tmp_path_factory.mktemp("multichip_models")
    Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_prediction_detail_col("proba")
        .set_learning_rate(0.5).set_max_iter(4),
    ]).fit(dense).save(str(root / "dense_lr"))
    # MinMaxScaler(aux dense) + LR(sparse CSR) fuse into ONE dispatch
    # with a dense AND a segment-CSR input — the mixed sharded layout
    Pipeline([
        MinMaxScaler().set_selected_col("aux"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_prediction_detail_col("proba")
        .set_learning_rate(0.5).set_max_iter(4),
    ]).fit(sparse).save(str(root / "sparse_lr"))
    Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
    ]).fit(dense).save(str(root / "scalers"))
    Pipeline([
        StandardScaler().set_selected_col("features"),
        KMeans().set_vector_col("features").set_k(4)
        .set_prediction_col("cluster").set_max_iter(3),
    ]).fit(dense).save(str(root / "kmeans"))
    Pipeline([
        StandardScaler().set_selected_col("features"),
        Knn().set_vector_col("features").set_label_col("label")
        .set_k(3).set_prediction_col("pred"),
    ]).fit(dense).save(str(root / "knn"))
    return str(root)


def _run_worker(model_dir: str, n_devices: int) -> dict:
    env = dict(os.environ)
    env.pop("FMT_FAULT_INJECT", None)
    env.pop("FMT_SERVE_MESH", None)
    env["FMT_OBS"] = "0"
    env["JAX_ENABLE_X64"] = "1"
    # replace (not append): the parent suite already forces 8 devices,
    # and XLA takes the FIRST occurrence of a repeated flag
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    out = subprocess.run(
        [sys.executable, WORKER, model_dir], capture_output=True,
        text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert lines, out.stdout
    return json.loads(lines[0][len("RESULT "):])


@pytest.fixture(scope="module")
def results(model_dir):
    """One worker per mesh width; in-worker fused-vs-staged parity has
    already been asserted by the time RESULT prints."""
    return {
        8: _run_worker(model_dir, 8),
        1: _run_worker(model_dir, 1),
    }


class TestMultichipServeParity:
    def test_workers_saw_their_meshes(self, results):
        assert results[8]["devices"] == 8
        assert results[1]["devices"] == 1

    @pytest.mark.parametrize("family,discrete_cols,float_cols", [
        ("dense_lr", ["pred"], ["proba"]),
        ("sparse_lr", ["pred"], ["proba"]),
        ("scalers", [], ["features"]),
        ("kmeans", ["cluster"], []),
        ("knn", ["pred"], []),
    ])
    def test_family_parity_8dev_vs_1dev(self, results, family,
                                        discrete_cols, float_cols):
        rec8 = results[8]["families"][family]
        rec1 = results[1]["families"][family]
        for c in discrete_cols:
            assert rec8[c] == rec1[c], (
                f"{family}.{c}: 8-device discrete outputs diverge from "
                "1-device")
        for c in float_cols:
            np.testing.assert_allclose(
                np.asarray(rec8[c]), np.asarray(rec1[c]),
                rtol=1e-4, atol=3e-5,
                err_msg=f"{family}.{c}: 8-device floats diverge",
            )

    def test_sharded_path_ran_on_the_mesh_only(self, results):
        """The 8-device worker must have dispatched through shard_map
        (the CSR bypass is gone); the 1-device worker must not have."""
        assert results[8]["shard_map_dispatches"] > 0, results[8]
        assert (results[8]["shard_map_dispatches"]
                == results[8]["fused_dispatches"]), results[8]
        assert results[1]["shard_map_dispatches"] == 0, results[1]
        assert results[8]["fused_dispatches"] > 0
        assert results[8]["plan_fallbacks"] == 0, (
            "a fused plan silently fell back to the staged path on the "
            "8-device mesh")
        assert results[1]["plan_fallbacks"] == 0

    def test_quarantine_offsets_match_across_meshes(self, results):
        assert results[8]["quarantine_rows"] == [5, 130, 383]
        assert results[1]["quarantine_rows"] == [5, 130, 383]
        assert (results[8]["quarantine_survivor_pred"]
                == results[1]["quarantine_survivor_pred"])

    def test_pressure_bisection_on_the_mesh(self, results):
        """The injected HBM ceiling forces bisection on BOTH meshes
        (bit-identical recovery asserted in-worker); the 8-device cap is
        per-device-denominated, so it lands well below the 1-device
        surface's cap."""
        assert results[8]["bisections"] > 0
        assert results[1]["bisections"] > 0
        cap8, cap1 = results[8]["per_device_cap"], \
            results[1]["per_device_cap"]
        assert cap8 is not None and cap1 is not None
        # per-device denomination: both meshes converge to the SAME
        # global working size under the same row ceiling — the 8-device
        # mesh's cap is that size divided across its 8 shards, not a
        # collapse of the whole mesh to a 1-device budget
        assert cap8 * 8 == cap1, (cap8, cap1)
