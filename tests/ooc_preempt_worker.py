"""Worker for the streamed out-of-core kill-and-resume test (ISSUE 3).

Run as: python ooc_preempt_worker.py <phase> <ckpt_dir>

Phase ``plain``: run a checkpointed streamed (out-of-core) dense fit to
completion and print the final parameters.  Phase ``crash``: the same fit,
but a real SIGTERM is delivered to the process MID-EPOCH (from a hook in
the chunk stream, so the timing is deterministic); the preemption guard
finishes the epoch, commits an emergency checkpoint, and exits cleanly
with code 0 — the worker never reaches the final print.  Phase ``resume``:
the same fit over the same checkpoint dir; the existing resume path
continues from the emergency snapshot to completion and prints the final
parameters, which the parent asserts are BIT-IDENTICAL to the ``plain``
run's (the distributed_resume_worker covers the resident path; this covers
the streamed engine the ROADMAP's Criteo-scale story depends on).
"""

import os
import sys

phase = sys.argv[1]
ckpt_dir = sys.argv[2]

os.environ.setdefault("FMT_COMPILE_CACHE", "off")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import signal  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource  # noqa: E402

ROWS, DIM, CHUNK_ROWS = 256, 5, 64
N_CHUNKS = ROWS // CHUNK_ROWS


class SigtermMidEpoch(ChunkedTable):
    """Deliver a real SIGTERM to this process while the ``kill_at``-th
    chunk of the stream is being consumed — deterministically mid-epoch."""

    def __init__(self, source, chunk_rows, kill_at):
        super().__init__(source, chunk_rows)
        self._served = 0
        self._kill_at = kill_at

    def chunks(self):
        for t in super().chunks():
            self._served += 1
            if self._served == self._kill_at:
                os.kill(os.getpid(), signal.SIGTERM)
            yield t


def make_table():
    from flink_ml_tpu.table.schema import Schema

    rng = np.random.RandomState(11)
    X = rng.randn(ROWS, DIM)
    y = (X @ rng.randn(DIM) > 0).astype(np.float64)
    rows = [tuple(X[i]) + (y[i],) for i in range(ROWS)]
    schema = Schema(
        [f"f{i}" for i in range(DIM)] + ["label"], ["double"] * (DIM + 1)
    )
    source = CollectionSource(rows, schema)
    if phase == "crash":
        # chunk N_CHUNKS+2 is consumed mid-epoch-2: the guard must finish
        # the epoch, snapshot, and exit before epoch 3 dispatches
        return SigtermMidEpoch(source, CHUNK_ROWS, kill_at=N_CHUNKS + 2)
    return ChunkedTable(source, CHUNK_ROWS)


def fit(table):
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression()
        .set_feature_cols([f"f{i}" for i in range(DIM)])
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(6)
        .set_global_batch_size(32)
        .set_checkpoint_dir(ckpt_dir).set_checkpoint_interval(1)
    )
    return est.fit(table)


model = fit(make_table())
w = model.coefficients()
b = model.intercept()
print(
    "PARAMS " + " ".join(f"{v:.17g}" for v in list(w) + [b]),
    flush=True,
)
