"""Out-of-core streaming tax on the CPU backend.

Runs the identical in-memory vs out-of-core sparse fit on the LOCAL CPU
backend, where host->device "transfer" is a memcpy — the floor of what
streaming costs when the transfer is free.  It says nothing about a TPU
host; the chip number is ROADMAP S8's to take.  Run:

  python scripts/ooc_tax_cpu.py [rows] [epochs]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main(n_rows=100_000, epochs=3, dim=1_000_000, batch=8192,
         chunk_rows=16_384):
    if epochs < 3:
        raise SystemExit("epochs must be >= 3 (the two-point steady-epoch "
                         "algebra needs wall_N > wall_2)")
    from bench_all import bench_sparse_file
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.table.sources import ChunkedTable, LibSvmSource

    path = bench_sparse_file(n_rows, dim, 39)
    source = LibSvmSource(path, n_features=dim, zero_based=True)

    def est():
        return (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_learning_rate(0.5)
            .set_global_batch_size(batch).set_max_iter(epochs)
        )

    table = source.read()
    est().fit(table)  # warmup: compile + pack + place
    t0 = time.perf_counter()
    m_mem = est().fit(table)
    mem_wall = time.perf_counter() - t0

    # spill on: epoch 1 parses text + writes binary blocks; steady epochs
    # stream the spill.  Two-point algebra isolates the steady epoch.
    est().set_max_iter(1).fit(ChunkedTable(source, chunk_rows))  # warm compile
    t0 = time.perf_counter()
    est().set_max_iter(2).fit(ChunkedTable(source, chunk_rows, spill=True))
    wall_2 = time.perf_counter() - t0
    t0 = time.perf_counter()
    m_ooc = est().fit(ChunkedTable(source, chunk_rows, spill=True))
    wall_n = time.perf_counter() - t0
    steady_epoch = max((wall_n - wall_2) / (epochs - 2), 1e-9)
    mem_epoch = mem_wall / epochs

    np.testing.assert_allclose(
        m_ooc.coefficients(), m_mem.coefficients(), rtol=1e-6,
    )
    print(json.dumps({
        "backend": jax.default_backend(),
        "mem_epoch_s": round(mem_epoch, 3),
        "ooc_steady_epoch_s": round(steady_epoch, 3),
        "ooc_vs_in_memory": round(mem_epoch / steady_epoch, 3),
        "shape": f"{n_rows} rows, {dim} dim, batch={batch}, "
                 f"chunk={chunk_rows}, epochs={epochs}",
    }))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
