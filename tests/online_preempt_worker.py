"""Worker for the continuous-learning kill-and-resume test (ISSUE 14).

Run as: python online_preempt_worker.py <phase> <candidate_dir>

Phase ``plain``: drive a :class:`ContinuousLearningController` (publish-
only: no server, the trainer-box half of a split deployment) over a
deterministic columnar label stream to completion and print the final
model parameters.  Phase ``crash``: the same loop, but a real SIGTERM is
delivered MID-STREAM (from a hook between source chunks, so the timing
is deterministic); the streaming driver commits an emergency snapshot at
the next span boundary, the controller commits an emergency CANDIDATE
through the sidecar-commit scheme, and the process exits cleanly with
code 0 — the worker never reaches the final print.  Phase ``resume``:
the same loop over the same candidate dir; the stream checkpoint fast-
forwards to the committed cut and the finished run's parameters must be
BIT-IDENTICAL to the ``plain`` run's (asserted by the parent test).
"""

import os
import sys

phase = sys.argv[1]
candidate_dir = sys.argv[2]

os.environ.setdefault("FMT_COMPILE_CACHE", "off")
os.environ.pop("XLA_FLAGS", None)

import jax

jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from flink_ml_tpu.lib.online import OnlineLogisticRegression  # noqa: E402
from flink_ml_tpu.serving import ContinuousLearningController  # noqa: E402
from flink_ml_tpu.table.schema import DataTypes, Schema  # noqa: E402
from flink_ml_tpu.table.sources import UnboundedSource  # noqa: E402
from flink_ml_tpu.table.table import Table  # noqa: E402

SCHEMA = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
ROWS, DIM, CHUNK = 1000, 4, 100
TRUE_W = np.array([2.0, -1.5, 1.0, 0.5])


def _xy(n, seed):
    r = np.random.RandomState(seed)
    X = r.randn(n, DIM)
    y = ((X @ TRUE_W) > 0).astype(np.float64)
    return X.astype(np.float32), y


class ChunkSource(UnboundedSource):
    """Deterministic columnar stream; in the ``crash`` phase a real
    SIGTERM is delivered to this process between chunks 6 and 7 —
    mid-stream, after several windows have fired."""

    def __init__(self, kill_at_chunk=None):
        self._kill_at = kill_at_chunk
        self._x, self._y = _xy(ROWS, seed=11)
        self._ts = np.arange(ROWS, dtype=np.int64) * 50

    def stream_chunks(self, max_rows=None):
        def gen():
            for i, a in enumerate(range(0, ROWS, CHUNK)):
                if i == self._kill_at:
                    os.kill(os.getpid(), signal.SIGTERM)
                b = a + CHUNK
                yield self._ts[a:b], {"features": self._x[a:b],
                                      "label": self._y[a:b]}

        return gen()

    def stream(self):
        from flink_ml_tpu.table.sources import chunk_row_iter

        for ts, cols in self.stream_chunks():
            yield from chunk_row_iter(ts, cols, SCHEMA)

    def schema(self):
        return SCHEMA


Xh, yh = _xy(300, seed=12)
holdout = Table.from_columns(SCHEMA, {"features": Xh, "label": yh})
estimator = (
    OnlineLogisticRegression().set_vector_col("features")
    .set_label_col("label").set_prediction_col("pred")
    .set_learning_rate(0.5).set_window_ms(1000)
)
source = ChunkSource(kill_at_chunk=6 if phase == "crash" else None)
controller = ContinuousLearningController(
    estimator, source, holdout, candidate_dir=candidate_dir,
    candidate_every=5,
)
model = controller.run()  # a crash-phase SIGTERM exits here with code 0
controller.stop()
w = model.coefficients()
b = model.intercept()
print(
    "PARAMS " + " ".join(f"{v:.17g}" for v in list(w) + [b]),
    flush=True,
)
