"""Two-process jax.distributed smoke test (SURVEY.md §2.6 comm-backend row, DCN).

The reference scales multi-node through Flink's runtime (job/task managers over
TCP; flink-ml-lib/pom.xml:40-58 provided deps).  Here the control plane is
``jax.distributed`` and the data plane is an XLA collective: two OS processes,
each owning 4 virtual CPU devices, form one 8-device mesh and jointly reduce a
globally-sharded array.  Run in subprocesses because the parent test process
already holds an initialized single-process JAX backend.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
WORKER = HERE / "distributed_worker.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_mesh_psum(tmp_path):
    # per-process file shards for the data-plane fit (VERDICT r3 item 2)
    from tests._distributed_common import make_shard_rows, write_shard_csv

    shards = make_shard_rows(2)
    for pid, (Xs, ys) in enumerate(shards):
        write_shard_csv(str(tmp_path / f"shard{pid}.csv"), Xs, ys)

    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(pid), "2", str(port),
             str(tmp_path)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=str(HERE.parent),
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            # generous: the workers compile every fit variant from a cold
            # jit cache, and the suite may be sharing the host's one core
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            partials = []
            for q in procs:
                q.kill()
                try:
                    partial, _ = q.communicate(timeout=10)
                except Exception:
                    partial = "<unreadable>"
                partials.append(partial)
            raise AssertionError(
                "distributed workers timed out; partial outputs:\n"
                + "\n---\n".join(partials)
            )
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        # sum(0..7) reduced across the two-process mesh
        assert "RESULT 28.0" in out, f"worker {pid} output:\n{out}"

    # the cross-process training epoch must equal the same epoch on a
    # single-process 8-device mesh (this test process, via conftest)
    import numpy as np

    from tests._distributed_common import make_epoch_inputs, make_epoch_step
    from flink_ml_tpu.parallel.mesh import default_mesh, replicate, shard_batch

    combined, params0 = make_epoch_inputs()
    mesh = default_mesh()
    params = replicate(mesh, params0)
    batch = shard_batch(
        mesh, (combined[..., :-2], combined[..., -2], combined[..., -1])
    )
    epoch_step = make_epoch_step(mesh)
    (w, b), (loss, _delta) = epoch_step(params, batch)
    expected = [float(v) for v in np.asarray(w)] + [float(b), float(loss)]

    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("TRAIN ")]
        assert line, f"worker {pid} printed no TRAIN line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected, rtol=1e-6, atol=1e-9,
            err_msg=f"worker {pid} diverged from single-process epoch",
        )

    # -- per-process file-shard fits (the real data plane) --------------------
    # single-process reference: the SAME estimator fit over the interleaved
    # row order (global step s = each process's s-th G/P-row window)
    from tests._distributed_common import (
        fit_shard_table,
        interleaved_rows,
        shard_schema,
    )
    from flink_ml_tpu.table.table import Table

    Xi, yi = interleaved_rows(shards, 2)
    ref_table = Table.from_columns(
        shard_schema(),
        {**{f"f{i}": Xi[:, i] for i in range(Xi.shape[1])}, "label": yi},
    )
    w_ref, b_ref = fit_shard_table(ref_table)
    expected_fit = list(w_ref) + [b_ref]

    for tag in ("FITMEM", "FITOOC"):
        for pid, out in enumerate(outs):
            line = [ln for ln in out.splitlines() if ln.startswith(tag + " ")]
            assert line, f"worker {pid} printed no {tag} line:\n{out}"
            got = [float(v) for v in line[0].split()[1:]]
            np.testing.assert_allclose(
                got, expected_fit, rtol=1e-6, atol=1e-8,
                err_msg=(
                    f"worker {pid} {tag}: per-process file-shard fit diverged "
                    "from the single-process interleaved-order fit"
                ),
            )

    # -- sparse per-process fit (cross-process nnz_pad agreement) -------------
    # the shards' nnz densities are unequal by construction, so the workers'
    # local packs disagree on the padded width until agree_max reconciles
    # them; the result must equal the single-process interleaved-order fit
    from tests._distributed_common import (
        fit_sparse_shard_table,
        interleaved_sparse_rows,
        make_sparse_shard_rows,
        sparse_shard_schema,
    )

    sshards = make_sparse_shard_rows(2)
    svecs, sy = interleaved_sparse_rows(sshards, 2)
    sref = Table.from_columns(
        sparse_shard_schema(), {"features": svecs, "label": sy}
    )
    w_sref, b_sref = fit_sparse_shard_table(sref)
    expected_sparse = (
        [float(np.sum(w_sref)), float(np.sum(w_sref * w_sref))]
        + [float(v) for v in w_sref[:8]] + [b_sref]
    )
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITSPARSE ")]
        assert line, f"worker {pid} printed no FITSPARSE line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected_sparse, rtol=1e-5, atol=1e-7,
            err_msg=(
                f"worker {pid} FITSPARSE: per-process sparse fit diverged "
                "from the single-process interleaved-order fit"
            ),
        )

    # sparse out-of-core: equal shards, so the streamed fit bit-matches
    # the in-memory fit and shares its expected digest
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITSOOC ")]
        assert line, f"worker {pid} printed no FITSOOC line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected_sparse, rtol=1e-5, atol=1e-7,
            err_msg=(
                f"worker {pid} FITSOOC: per-process sparse out-of-core fit "
                "diverged from the single-process interleaved-order fit"
            ),
        )

    # unequal shards: no single-process reference is expressible (the
    # short shard's trailing no-op windows interleave mid-stream), but the
    # two processes must land on the identical global model — and on
    # anything at all (a block-count mismatch would deadlock, caught by
    # the subprocess timeout)
    lines = []
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITSOOCU ")]
        assert line, f"worker {pid} printed no FITSOOCU line:\n{out}"
        lines.append([float(v) for v in line[0].split()[1:]])
    assert all(np.isfinite(lines[0]))
    np.testing.assert_allclose(
        lines[1], lines[0], rtol=1e-12,
        err_msg="workers disagree on the unequal-shard out-of-core model",
    )

    # KMeans: the single-process reference runs over the shards
    # CONCATENATED in process order (contiguous device blocks — see
    # fit_kmeans_shard_table docstring), with the same seed, so the
    # allgathered init pool and the Lloyd row partition match exactly
    from tests._distributed_common import fit_kmeans_shard_table

    Xc = np.concatenate([s[0] for s in shards])
    yc = np.concatenate([s[1] for s in shards])
    km_ref_table = Table.from_columns(
        shard_schema(),
        {**{f"f{i}": Xc[:, i] for i in range(Xc.shape[1])}, "label": yc},
    )
    cents_ref, cost_ref = fit_kmeans_shard_table(km_ref_table)
    expected_km = (
        [float(np.sum(cents_ref)), float(np.sum(cents_ref * cents_ref)),
         cost_ref] + [float(v) for v in cents_ref[0]]
    )
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITKM ")]
        assert line, f"worker {pid} printed no FITKM line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected_km, rtol=1e-5, atol=1e-7,
            err_msg=(
                f"worker {pid} FITKM: per-process KMeans fit diverged "
                "from the single-process concatenated-order fit"
            ),
        )

    # transform runs per-process on the local mesh: worker p's predictions
    # over ITS shard must match the single-process transform of that shard
    from flink_ml_tpu.lib import Knn
    from tests._distributed_common import SHARD_FEATURES

    from flink_ml_tpu.lib.classification import LogisticRegressionModel
    from flink_ml_tpu.lib.glm import make_model_table

    for pid, out in enumerate(outs):
        Xs, ys = shards[pid]
        shard_table = Table.from_columns(
            shard_schema(),
            {**{f"f{i}": Xs[:, i] for i in range(Xs.shape[1])}, "label": ys},
        )
        # the worker's GLM model is the cross-process (global) fit — the
        # same coefficients as the FITMEM reference; its transform runs on
        # the process-local mesh over the worker's own shard
        glm_ref = (
            LogisticRegressionModel().set_feature_cols(SHARD_FEATURES)
            .set_prediction_col("pred")
        )
        glm_ref.set_model_data(make_model_table(w_ref, b_ref))
        (ref_scored,) = glm_ref.transform(shard_table)
        ref_preds = np.asarray(ref_scored.col("pred"))[:32]
        line = [ln for ln in out.splitlines() if ln.startswith("XFORM ")]
        assert line, f"worker {pid} printed no XFORM line:\n{out}"
        got = np.asarray([float(v) for v in line[0].split()[1:]])
        np.testing.assert_allclose(got, ref_preds, atol=0,
                                   err_msg=f"worker {pid} XFORM diverged")
        knn_ref = (
            Knn().set_feature_cols(SHARD_FEATURES).set_label_col("label")
            .set_prediction_col("knnp").set_k(3).set_shard_model_data(True)
            .fit(shard_table)
        )
        (kref,) = knn_ref.transform(shard_table)
        kref_preds = np.asarray(kref.col("knnp"))[:32]
        line = [ln for ln in out.splitlines() if ln.startswith("XFORMKNN ")]
        assert line, f"worker {pid} printed no XFORMKNN line:\n{out}"
        got = np.asarray([float(v) for v in line[0].split()[1:]])
        np.testing.assert_allclose(got, kref_preds, atol=0,
                                   err_msg=f"worker {pid} XFORMKNN diverged")

    # 2-D (data x model) mesh: the single-process references run on the
    # same-shaped mesh over this process's 8 local devices; the workers'
    # global mesh spans both processes, with model-axis params placed via
    # global_put from each process's full host copy
    from flink_ml_tpu.parallel.mesh import create_mesh
    from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    env = MLEnvironmentFactory.get_default()
    old_mesh = env.get_mesh()
    env.set_mesh(create_mesh({"data": 4, "model": 2}))
    try:
        w_d2, b_d2 = fit_shard_table(ref_table)
        expected_d2 = list(w_d2) + [b_d2]
        w_s2, b_s2 = fit_sparse_shard_table(sref)
        expected_s2 = (
            [float(np.sum(w_s2)), float(np.sum(w_s2 * w_s2))]
            + [float(v) for v in w_s2[:8]] + [b_s2]
        )
        w_so2, b_so2 = fit_sparse_shard_table(
            ChunkedTable(
                CollectionSource(list(zip(svecs, sy)), sparse_shard_schema()),
                chunk_rows=64,
            )
        )
        expected_so2 = (
            [float(np.sum(w_so2)), float(np.sum(w_so2 * w_so2))]
            + [float(v) for v in w_so2[:8]] + [b_so2]
        )
    finally:
        env.set_mesh(old_mesh)
    for tag, expected in (("FITD2D", expected_d2), ("FITS2D", expected_s2),
                          ("FITS2DOOC", expected_so2)):
        for pid, out in enumerate(outs):
            line = [ln for ln in out.splitlines() if ln.startswith(tag + " ")]
            assert line, f"worker {pid} printed no {tag} line:\n{out}"
            got = [float(v) for v in line[0].split()[1:]]
            np.testing.assert_allclose(
                got, expected, rtol=1e-5, atol=1e-7,
                err_msg=(
                    f"worker {pid} {tag}: cross-process 2-D fit diverged "
                    "from the single-process same-mesh fit"
                ),
            )

    # KMeans out-of-core: same init (under-cap reservoir = the dataset in
    # concatenated order on both sides), Lloyd accumulation differs only
    # in per-device grouping — looser float tolerance than the GLMs'
    # schedule-exact paths (see KMeans._fit_out_of_core docstring)
    km_rows = [tuple(Xc[i]) + (yc[i],) for i in range(len(yc))]
    cents_oref, cost_oref = fit_kmeans_shard_table(
        ChunkedTable(CollectionSource(km_rows, shard_schema()), chunk_rows=64)
    )
    expected_km_ooc = (
        [float(np.sum(cents_oref)), float(np.sum(cents_oref * cents_oref)),
         cost_oref] + [float(v) for v in cents_oref[0]]
    )
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITKMOOC ")]
        assert line, f"worker {pid} printed no FITKMOOC line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected_km_ooc, rtol=1e-4, atol=1e-6,
            err_msg=(
                f"worker {pid} FITKMOOC: per-process out-of-core KMeans "
                "diverged from the single-process concatenated-order fit"
            ),
        )


def test_two_process_kill_and_resume(tmp_path):
    """VERDICT r4 #4: kill one worker mid-out-of-core-fit, restart both,
    resume from the chunked checkpoint, and land on the model an
    uninterrupted run produces — the Flink checkpoint/restart story
    (`/root/reference/pom.xml:396-401`) on the jax.distributed data plane."""
    import numpy as np

    RESUME_WORKER = HERE / "distributed_resume_worker.py"
    ckpt_root = tmp_path / "ck"
    ckpt_root.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)

    def spawn(phase, port):
        return [
            subprocess.Popen(
                [sys.executable, str(RESUME_WORKER), str(pid), "2",
                 str(port), phase, str(ckpt_root)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=str(HERE.parent),
            )
            for pid in range(2)
        ]

    # phase 1: crash.  Worker 1 os._exit(17)s right after its second
    # snapshot commits; worker 0 is left owing collectives — give it a
    # moment to finish its own epoch-2 snapshot, then kill it (the
    # "machine failure" takes out both).
    procs = spawn("crash", _free_port())
    out1, _ = procs[1].communicate(timeout=420)
    assert procs[1].returncode == 17, (
        f"worker 1 should simulate a crash (exit 17):\n{out1}"
    )
    try:
        out0, _ = procs[0].communicate(timeout=30)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        out0, _ = procs[0].communicate(timeout=30)
    from flink_ml_tpu.iteration.checkpoint import latest_checkpoint

    for pid in range(2):
        assert latest_checkpoint(str(ckpt_root / f"p{pid}")) is not None, (
            f"no snapshot survived for worker {pid}:\n{out0}\n{out1}"
        )

    # phase 2: restart both; each fleet member agrees on the common resume
    # epoch and continues to completion
    procs = spawn("resume", _free_port())
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=420)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"resume worker {pid} failed:\n{out}"

    # uninterrupted single-process reference over the interleaved order
    from tests._distributed_common import (
        fit_sparse_shard_table,
        interleaved_sparse_rows,
        make_sparse_shard_rows,
        sparse_shard_schema,
    )
    from flink_ml_tpu.table.table import Table

    sshards = make_sparse_shard_rows(2)
    svecs, sy = interleaved_sparse_rows(sshards, 2)
    sref = Table.from_columns(
        sparse_shard_schema(), {"features": svecs, "label": sy}
    )
    w_ref, b_ref = fit_sparse_shard_table(sref, max_iter=6)
    expected = (
        [float(np.sum(w_ref)), float(np.sum(w_ref * w_ref))]
        + [float(v) for v in w_ref[:8]] + [b_ref]
    )
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines() if ln.startswith("FITRESUME ")]
        assert line, f"worker {pid} printed no FITRESUME line:\n{out}"
        got = [float(v) for v in line[0].split()[1:]]
        np.testing.assert_allclose(
            got, expected, rtol=1e-5, atol=1e-7,
            err_msg=(
                f"worker {pid}: resumed model diverged from the "
                "uninterrupted single-process reference"
            ),
        )
