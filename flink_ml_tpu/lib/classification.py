"""LogisticRegression — binary log-loss GLM (the first configuration
ROADMAP.md's Reach lists, and the north star's: a click log fitted on a chip).

Labels are {0, 1}. Training is the same data-parallel SGD harness as
LinearRegression with the logistic gradient; prediction emits the argmax
label into ``predictionCol`` and, optionally, the positive-class probability
into ``predictionDetailCol``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu.lib.glm import GlmEstimatorBase, GlmModelBase, LinearScoreMapper
from flink_ml_tpu.table.schema import DataTypes, Schema


def _stable_sigmoid(scores: np.ndarray) -> np.ndarray:
    """Overflow-free sigmoid: ``np.exp(-scores)`` overflows (with a runtime
    warning and an inf that rounds through to 0.0) once a score passes
    ~-745 in f64 / ~-88 in f32 — scores a wide model on unnormalized
    serving traffic produces routinely.  Exponentiate only the negative
    half-line instead."""
    scores = np.asarray(scores, dtype=np.float64)
    out = np.empty_like(scores)
    pos = scores >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-scores[pos]))
    e = np.exp(scores[~pos])
    out[~pos] = e / (1.0 + e)
    return out


class LogisticRegressionModel(GlmModelBase):
    """Predicts the {0,1} label; optional probability detail column."""

    def _make_mapper(self, data_schema: Schema):
        model = self
        detail = model.get_prediction_detail_col()

        class _Mapper(LinearScoreMapper):
            def output_cols(self):
                names = [model.get_prediction_col()]
                types = [DataTypes.DOUBLE]
                if detail is not None:
                    names.append(detail)
                    types.append(DataTypes.DOUBLE)
                return names, types

            def map_batch(self, batch):
                scores = self._scores(batch)
                return self._score_cols(scores)

            def _score_cols(self, scores):
                out = {model.get_prediction_col(): (scores > 0).astype(np.float64)}
                if detail is not None:
                    out[detail] = _stable_sigmoid(scores)
                return out

            def _fused_finalize(self, fetched, n):
                # fused-plan host tail: identical to the map_batch tail —
                # (scores > 0) is bit-stable under the f32->f64 fetch cast,
                # so fused discrete predictions match the staged path
                return self._score_cols(fetched["scores"])

        return _Mapper(self, data_schema)

    def predict_proba(self, table) -> np.ndarray:
        """Positive-class probabilities for a feature table (convenience)."""
        mapper = self._make_mapper(table.schema)
        mapper.load_model(*self.get_model_data())
        scores = mapper._scores(table)
        return _stable_sigmoid(scores)


from functools import lru_cache


@lru_cache(maxsize=None)
def _log_loss_grads(with_intercept: bool):
    keep_b = 1.0 if with_intercept else 0.0

    def grad_fn(params, x, y, w):
        wts, b = params
        # scopes are names on the operations; their order stays as it was,
        # so the program (and its cache key) is the one it was
        with jax.named_scope("fmt.train.scores"):
            logits = x @ wts + b
        with jax.named_scope("fmt.train.grad"):
            p = jax.nn.sigmoid(logits)
            err = (p - y) * w
            g_w = x.T @ err
            g_b = jnp.sum(err) * keep_b
        with jax.named_scope("fmt.train.scores"):
            # numerically-stable weighted log-loss sum
            loss = jnp.sum(
                w * (jnp.logaddexp(0.0, logits) - y * logits)
            )
        return (g_w, g_b), loss, jnp.sum(w)

    #: what the fused dense fit reads to give this gradient to the one-pass
    #: kernel (lib/common.py:_onepass_rows); a grad fn without them keeps
    #: the XLA step
    grad_fn.glm_kind = "logistic"
    grad_fn.with_intercept = with_intercept
    return grad_fn


class LogisticRegression(GlmEstimatorBase):
    """Estimator: binary log loss, minibatch SGD over the data-parallel mesh."""

    LOSS_KIND = "logistic"

    def _grad_fn(self):
        return _log_loss_grads(self.get_with_intercept())

    def _make_model(self) -> LogisticRegressionModel:
        return LogisticRegressionModel()
