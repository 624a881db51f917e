"""LinearRegression — squared-loss GLM (ROADMAP.md, Reach: YearPredictionMSD linreg).

The productized form of the reference's only trainer
(examples-batch/.../LinearRegression.java): the per-record gradient step
(SubUpdate:215-231), sum-reduce (UpdateAccumulator:235-246) and average
(Update:249-256) become one jitted epoch with in-step psum; the broadcast of
new parameters (withBroadcastSet:114) is the replicated params placement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.lib.glm import GlmEstimatorBase, GlmModelBase, LinearScoreMapper
from flink_ml_tpu.table.schema import DataTypes, Schema


class LinearRegressionModel(GlmModelBase):
    """Predicts x·w + b into ``predictionCol``.

    Serving robustness (quarantine of bad feature rows, the dispatch
    circuit breaker, and the NumPy CPU fallback) rides the shared
    :class:`~flink_ml_tpu.lib.glm.LinearScoreMapper` machinery."""

    def _make_mapper(self, data_schema: Schema):
        model = self

        class _Mapper(LinearScoreMapper):
            def output_cols(self):
                return [model.get_prediction_col()], [DataTypes.DOUBLE]

            def map_batch(self, batch):
                # explicit f64 cast: the declared output type is DOUBLE and
                # the device/fallback paths hand back f32 scores
                scores = np.asarray(self._scores(batch), dtype=np.float64)
                return {model.get_prediction_col(): scores}

            def _fused_finalize(self, fetched, n):
                return {model.get_prediction_col(): np.asarray(
                    fetched["scores"], dtype=np.float64
                )}

        return _Mapper(self, data_schema)


from functools import lru_cache


@lru_cache(maxsize=None)
def _squared_loss_grads(with_intercept: bool):
    keep_b = 1.0 if with_intercept else 0.0

    def grad_fn(params, x, y, w):
        wts, b = params
        with jax.named_scope("fmt.train.scores"):
            pred = x @ wts + b
        with jax.named_scope("fmt.train.grad"):
            err = (pred - y) * w
            # d/dw of 0.5*sum(w*(pred-y)^2)
            g_w = x.T @ err
            g_b = jnp.sum(err) * keep_b
        with jax.named_scope("fmt.train.scores"):
            loss_sum = 0.5 * jnp.sum(err * (pred - y))
        return (g_w, g_b), loss_sum, jnp.sum(w)

    #: what the fused dense fit reads to give this gradient to the one-pass
    #: kernel (lib/common.py:_onepass_rows); a grad fn without them keeps
    #: the XLA step
    grad_fn.glm_kind = "squared"
    grad_fn.with_intercept = with_intercept
    return grad_fn


class LinearRegression(GlmEstimatorBase):
    """Estimator: squared loss, minibatch SGD over the data-parallel mesh."""

    LOSS_KIND = "squared"

    def _grad_fn(self):
        return _squared_loss_grads(self.get_with_intercept())

    def _make_model(self) -> LinearRegressionModel:
        return LinearRegressionModel()
