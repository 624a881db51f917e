"""Online LogisticRegression — unbounded streaming mini-batch training
(ROADMAP.md, Reach: online LR).

The reference defines this topology but never implements it: the unbounded
iteration entry point returns null (Iterations.java:87-90) and the
IncrementalLearningSkeleton example (SURVEY.md §3.4) shows the intended shape —
training stream -> event-time tumbling window -> model update per window;
prediction stream connected to the freshest model.  Here that shape runs on
the :class:`flink_ml_tpu.iteration.unbounded.StreamingDriver`: each fired
window is one jitted SGD step on a padded row bucket (static shapes keep the
jit cache bounded), and the prediction path scores batches with exactly the
model that was current at each record's event time.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.api.core import Estimator
from flink_ml_tpu.iteration.unbounded import StreamingDriver, StreamingResult
from flink_ml_tpu.lib.classification import LogisticRegressionModel, _log_loss_grads
from flink_ml_tpu.lib.common import bucket_rows, make_sgd_update, resolve_features
from flink_ml_tpu.lib.glm import GlmTrainParams, make_model_table
from flink_ml_tpu.lib.params import HasAllowedLateness, HasWindowMs
from flink_ml_tpu.table.sources import UnboundedSource
from flink_ml_tpu.table.table import Table


def _f64_or_nan(v) -> float:
    """Coerce one streamed cell to float64; junk (None, 'n/a', anything
    non-numeric) becomes NaN so the degenerate-row mask drops it instead
    of the coercion crashing the loop."""
    try:
        return float(v)
    except (TypeError, ValueError):
        return np.nan


class _PeekedSource(UnboundedSource):
    """Re-yields a record peeked off a single-pass source, then the remainder
    of the SAME iterator — nothing is lost to the dim probe.  One-shot:
    ``stream()`` may only be consumed once (like the source it wraps).
    Deliberately leaves ``stream_chunks`` unsupported: the peek consumed
    from the per-record view, so only that view is coherent."""

    def __init__(self, first, rest, inner: UnboundedSource):
        self._first = first
        self._rest = rest
        self._inner = inner

    def stream(self):
        yield self._first
        yield from self._rest

    def schema(self):
        return self._inner.schema()


class _PeekedChunkSource(UnboundedSource):
    """Chunk-protocol analog of :class:`_PeekedSource`: re-yields the chunk
    peeked for the dim probe ahead of the same chunk iterator, preserving
    the columnar fast path through the streaming driver.  One-shot."""

    def __init__(self, first_chunk, rest, inner: UnboundedSource):
        self._first = first_chunk
        self._rest = rest
        self._inner = inner

    def stream_chunks(self, max_rows: Optional[int] = None):
        def all_chunks():
            yield self._first
            yield from self._rest

        if max_rows is None:
            return all_chunks()

        step = int(max_rows)

        def resliced():
            # honor the caller's chunk bound by re-slicing buffered chunks
            for ts, cols in all_chunks():
                for a in range(0, len(ts), step):
                    b = a + step
                    yield ts[a:b], {k: v[a:b] for k, v in cols.items()}

        return resliced()

    def stream(self):
        from flink_ml_tpu.table.sources import chunk_row_iter

        schema = self.schema()
        for ts, cols in self.stream_chunks():
            yield from chunk_row_iter(ts, cols, schema)

    def schema(self):
        return self._inner.schema()


class OnlineLogisticRegression(Estimator, GlmTrainParams, HasWindowMs, HasAllowedLateness):
    """Streaming binary LR: one SGD step per fired event-time window.

    ``fit`` consumes a *bounded* table by replaying it as a timestamped
    stream (useful for tests); ``fit_unbounded`` is the real entry point:
    it drives training and optional concurrent prediction sources and
    returns the final model plus the full :class:`StreamingResult`
    (per-record predictions, model history, windows fired).
    """

    def __init__(self):
        super().__init__()
        self._dim: Optional[int] = None

    # -- feature packing for a window ---------------------------------------

    def _window_xyw(
        self, table: Table
    ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Padded (X, y, w) for one fired window, or ``None`` for a window
        with no usable rows.

        A live label stream carries junk — null vectors, wrong-width
        vectors, NaN labels — and a window must never crash the loop or
        perturb the model over rows that cannot train.  Degenerate rows
        are ZEROED and weighted 0 (zeroing matters: a NaN feature times a
        0 weight is still NaN through the gradient), so the surviving
        rows' update is bit-identical to a window that never held the bad
        rows; a window with nothing usable returns ``None`` and the
        update skips it (counted, never an all-zero dispatch: with L2 on,
        a zero-weight dispatch would still decay the params toward an
        all-zero candidate).
        """
        n = table.num_rows()
        if n == 0:
            return None
        try:
            X, _ = resolve_features(table, self, dim=self._dim)
            X = np.asarray(X, dtype=np.float64)
            row_ok = np.ones(n, dtype=bool)
        except Exception:  # noqa: BLE001 - degenerate rows: rebuild row-wise
            if self.get_vector_col() is not None:
                dim = self._dim
                col = table.col(self.get_vector_col())
                X = np.zeros((n, dim), dtype=np.float64)
                row_ok = np.zeros(n, dtype=bool)
                for i, v in enumerate(col):
                    try:
                        arr = np.asarray(v.to_dense().values,
                                         dtype=np.float64)
                    except Exception:  # noqa: BLE001 - null / not a vector
                        continue
                    if arr.shape != (dim,):
                        continue
                    X[i] = arr
                    row_ok[i] = True
            else:
                # featureCols layout: junk cells coerce to NaN and the
                # finite-row mask below drops them
                X = np.column_stack([
                    [_f64_or_nan(v) for v in table.col(c)]
                    for c in self.get_feature_cols()
                ])
                row_ok = np.ones(n, dtype=bool)
        raw_y = table.col(self.get_label_col())
        if isinstance(raw_y, np.ndarray) and raw_y.dtype != object:
            y = np.asarray(raw_y, dtype=np.float64)
        else:
            y = np.array([_f64_or_nan(v) for v in raw_y], dtype=np.float64)
        mask = row_ok & np.isfinite(y) & np.all(np.isfinite(X), axis=1)
        kept = int(mask.sum())
        if kept < n:
            obs.counter_add("online.dropped_rows", n - kept)
        if kept == 0:
            return None
        X[~mask] = 0.0
        y = np.where(mask, y, 0.0)
        b = bucket_rows(n, 64)
        Xp = np.zeros((b, X.shape[1]), dtype=np.float32)
        yp = np.zeros((b,), dtype=np.float32)
        wp = np.zeros((b,), dtype=np.float32)
        Xp[:n], yp[:n], wp[:n] = X, y, mask.astype(np.float32)
        return Xp, yp, wp

    def _infer_dim(self, source: UnboundedSource) -> Tuple[int, UnboundedSource]:
        """Feature dim + the source to actually train from.

        When the dim comes from peeking the first record, the peeked record is
        buffered and re-yielded ahead of the same iterator — the UnboundedSource
        contract does not promise ``stream()`` is re-iterable, and a
        single-pass source (socket/queue-backed) must not lose its first
        training record to the probe.
        """
        if self.get_feature_cols() is not None:
            return len(self.get_feature_cols()), source
        chunks = (
            source.stream_chunks()
            if hasattr(source, "stream_chunks") else None
        )
        if chunks is not None:
            # probe from the chunk view so the driver's vectorized ingest
            # path stays available downstream
            it = iter(chunks)
            first = next(it, None)
            while first is not None and len(first[0]) == 0:
                first = next(it, None)
            if first is None:
                raise ValueError(
                    "empty training stream; cannot infer feature dim"
                )
            schema = source.schema()
            # canonical name: chunk columns are keyed by schema field names,
            # the param lookup is case-insensitive (TableUtil.findColIndex)
            name = schema.field_names[
                schema.find_col_index(self.get_vector_col())
            ]
            col = first[1][name]
            if isinstance(col, np.ndarray) and col.ndim == 2:
                dim = int(col.shape[1])
            else:
                v = col[0]
                dim = v.size() if v.size() >= 0 else v.to_dense().size()
            return dim, _PeekedChunkSource(first, it, source)
        it = iter(source.stream())
        try:
            first = next(it)
        except StopIteration:
            raise ValueError("empty training stream; cannot infer feature dim")
        i = source.schema().find_col_index(self.get_vector_col())
        v = first[1][i]
        dim = v.size() if v.size() >= 0 else v.to_dense().size()
        return dim, _PeekedSource(first, it, source)

    # -- streaming fit -------------------------------------------------------

    def fit_unbounded(
        self,
        training_source: UnboundedSource,
        prediction_source: Optional[UnboundedSource] = None,
        max_windows: Optional[int] = None,
        keep_model_history: bool = False,
        checkpoint=None,
        window_hook=None,
    ) -> Tuple[LogisticRegressionModel, StreamingResult]:
        self._dim, training_source = self._infer_dim(training_source)
        lr = self.get_learning_rate()
        reg = self.get_reg()
        grad_fn = _log_loss_grads(self.get_with_intercept())

        sgd_update = make_sgd_update(lr, reg)

        @jax.jit
        def sgd_step(params, x, y, w):
            grads, _, w_sum = grad_fn(params, x, y, w)
            return sgd_update(params, grads, jnp.maximum(w_sum, 1.0))

        @jax.jit
        def score(params, x):
            w, b = params
            return x @ w + b

        def update(state, window_table: Table, epoch: int):
            xyw = self._window_xyw(window_table)
            if xyw is None:
                # nothing trainable in the window: skip, count, keep
                # streaming — the returned state is the SAME object, which
                # window hooks use to tell a skip from a real step
                obs.counter_add("online.skipped_windows")
                new_state = state
            else:
                x, y, w = xyw
                new_state = sgd_step(
                    state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w)
                )
            if window_hook is not None:
                # the continuous-learning controller's tap (ISSUE 14): a
                # non-None return REPLACES the trainer state — how a
                # poisoned run is reset to the last good candidate
                replacement = window_hook(epoch, new_state)
                if replacement is not None:
                    new_state = replacement
            return new_state

        # host mirror of the freshest reachable params for the CPU fallback:
        # the live ``state`` is a device pytree, and pulling it during an
        # outage is itself a device call — the fallback must score from
        # memory the dead accelerator cannot take down.  Refreshed on every
        # fallback while the device still answers D2H; when even that fails,
        # the last-reachable model serves (stale-model degraded semantics).
        host_params = {
            "w": np.zeros((self._dim,), dtype=np.float32),
            "b": np.float32(0.0),
        }

        def predict(state, batch_table: Table):
            from flink_ml_tpu import serve

            X, _ = resolve_features(batch_table, self, dim=self._dim)
            n = X.shape[0]
            b = bucket_rows(n, 64)
            Xp = np.zeros((b, X.shape[1]), dtype=np.float32)
            Xp[:n] = X

            def cpu_scores():
                try:
                    host_params["w"], host_params["b"] = (
                        np.asarray(state[0], np.float32),
                        np.float32(np.asarray(state[1])),
                    )
                except Exception:  # noqa: BLE001 - D2H died with the device
                    pass
                return Xp[:n] @ host_params["w"] + host_params["b"]

            scores = serve.dispatch(
                "OnlineLogisticRegression.predict",
                device=lambda: np.asarray(score(state, jnp.asarray(Xp)))[:n],
                fallback=cpu_scores,
            )
            return (scores > 0).astype(np.float64)

        params0 = (
            jnp.zeros((self._dim,), dtype=jnp.float32),
            jnp.zeros((), dtype=jnp.float32),
        )
        driver = StreamingDriver(
            window_ms=self.get_window_ms(),
            keep_model_history=keep_model_history,
            allowed_lateness_ms=self.get_allowed_lateness_ms(),
        )
        # an explicit CheckpointConfig wins over the param-derived one
        if checkpoint is None and self.get_checkpoint_dir() is not None:
            from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

            checkpoint = CheckpointConfig(
                directory=self.get_checkpoint_dir(),
                every_n_epochs=self.get_checkpoint_interval(),
            )
        result = driver.run(
            params0,
            training_source,
            update,
            prediction_source=prediction_source,
            predict=predict if prediction_source is not None else None,
            max_windows=max_windows,
            checkpoint=checkpoint,
        )
        w, b = (np.asarray(a) for a in result.final_state)
        model = LogisticRegressionModel()
        model.get_params().merge(self.get_params())
        model.set_model_data(make_model_table(w, float(b)))
        model.windows_fired_ = result.windows_fired
        model.train_metrics_ = result.metrics
        obs.fit_report(
            type(self).__name__,
            step_metrics=result.metrics,
            extra={"windows_fired": result.windows_fired},
        )
        return model, result

    # -- bounded convenience (replay a table as a stream) --------------------

    def fit(self, *inputs: Table) -> LogisticRegressionModel:
        from flink_ml_tpu.table.sources import GeneratorSource

        (table,) = inputs
        rows = table.to_rows()
        # spread rows uniformly so each window holds ~globalBatchSize rows
        per_window = self.get_global_batch_size() or 32
        interval = max(1, self.get_window_ms() // per_window)
        source = GeneratorSource.linear_timestamps(rows, interval, table.schema)
        model, _ = self.fit_unbounded(source)
        return model
