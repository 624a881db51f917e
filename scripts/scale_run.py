"""Multi-GB out-of-core scale run (VERDICT r4 #6).

Generates a >=4 GB Criteo-shaped LibSVM file (cached), then runs the
out-of-core sparse LogisticRegression fit with spill on, on the LOCAL CPU
backend (transfer is a memcpy, RSS is meaningful).
Reports one JSON line: steady-epoch throughput (two-point method), first
epoch (parse+spill) wall, peak RSS, spill volume, and the engine's
live-block bound.  It is the point
between "fits in RAM" and "larger than any host" — the engine streams
blocks whose count per epoch scales with the file, while host residency
stays bounded by the prefetch/in-flight caps regardless of file size.

Usage: python scripts/scale_run.py [target_gb] [epochs]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

NNZ = 39
DIM = 1_000_000
BYTES_PER_ROW = 355  # measured average for the generator's format


def generate(path: str, n_rows: int) -> None:
    rng = np.random.RandomState(5)
    true_w = (rng.randn(DIM) * 0.3).astype(np.float32)
    tmp = path + ".tmp"
    chunk = 200_000
    t0 = time.perf_counter()
    with open(tmp, "w") as f:
        for lo in range(0, n_rows, chunk):
            m = min(chunk, n_rows - lo)
            hot = rng.randint(0, 50_000, size=(m, NNZ - 10))
            cold = rng.randint(50_000, DIM, size=(m, 10))
            idx = np.concatenate([hot, cold], axis=1)
            idx.sort(axis=1)
            labels = (
                np.add.reduceat(
                    true_w[idx.ravel()], np.arange(0, m * NNZ, NNZ)
                ) > 0
            ).astype(np.int64)
            lines = []
            for i in range(m):
                ii = np.unique(idx[i])
                lines.append(
                    f"{labels[i]} " + " ".join(f"{j}:1" for j in ii)
                )
            f.write("\n".join(lines) + "\n")
            if lo % 2_000_000 == 0:
                print(f"generated {lo + m}/{n_rows} rows "
                      f"({time.perf_counter() - t0:.0f}s)", file=sys.stderr)
    os.replace(tmp, path)


def main(target_gb: float = 4.2, epochs: int = 4) -> None:
    import resource
    import tempfile

    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib import out_of_core as oc
    from flink_ml_tpu.table.sources import ChunkedTable, LibSvmSource

    n_rows = int(target_gb * 1e9 / BYTES_PER_ROW)
    path = os.path.join(
        tempfile.gettempdir(), f"scale_{int(target_gb * 10)}g.svm"
    )
    if not os.path.exists(path):
        generate(path, n_rows)
    size_gb = os.path.getsize(path) / 1e9
    # row count from the file (generation rounds differ from the estimate)
    with open(path, "rb") as f:
        head = f.read(1 << 22)
    rows_est = int(size_gb * 1e9 / (len(head) / head.count(b"\n")))

    # observe the spill volume: BlockSpill directories are per-fit temp
    # dirs deleted on close — record their size just before deletion
    spill_stats = {"bytes": 0, "files": 0}
    orig_close = oc.BlockSpill.close

    def measuring_close(self):
        try:
            for name in os.listdir(self.directory):
                p = os.path.join(self.directory, name)
                if os.path.isfile(p):
                    spill_stats["bytes"] += os.path.getsize(p)
                    spill_stats["files"] += 1
        except OSError:
            pass
        orig_close(self)

    oc.BlockSpill.close = measuring_close

    chunk_rows = 65_536

    def fit(n_epochs):
        est = (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(DIM).set_learning_rate(0.5)
            .set_global_batch_size(8192).set_max_iter(n_epochs)
        )
        source = LibSvmSource(path, n_features=DIM, zero_based=True)
        t0 = time.perf_counter()
        est.fit(ChunkedTable(source, chunk_rows, spill=True))
        return time.perf_counter() - t0

    wall_2 = fit(2)
    spill_gb = spill_stats["bytes"] / 1e9
    spill_stats["bytes"] = 0
    wall_n = fit(epochs)
    steady_epoch_s = max((wall_n - wall_2) / (epochs - 2), 1e-9)
    first_epoch_s = wall_2 - steady_epoch_s  # parse + pack + spill write
    peak_rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6

    print(json.dumps({
        "metric": "out-of-core sparse LR steady epoch rows/sec (multi-GB)",
        "value": round(rows_est / steady_epoch_s, 1),
        "unit": "rows/sec",
        "file_gb": round(size_gb, 2),
        "rows": rows_est,
        "first_epoch_s": round(first_epoch_s, 1),
        "steady_epoch_s": round(steady_epoch_s, 1),
        "spill_gb": round(spill_gb, 2),
        "peak_rss_gb": round(peak_rss_gb, 2),
        "chunk_rows": chunk_rows,
        "live_block_bound": "prefetch(2) + max_inflight(4) blocks",
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    args = [float(a) for a in sys.argv[1:]]
    main(*([args[0]] if args else []),
         **({"epochs": int(args[1])} if len(args) > 1 else {}))
