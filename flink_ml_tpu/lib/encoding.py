"""Categorical encoding stages + evaluation — the Criteo-shaped pipeline
head (categorical columns -> indices -> one sparse feature vector) and the
quality metric the benchmarks assert.

The reference snapshot ships no concrete transformers (SURVEY.md §0.3);
these follow its stage conventions exactly: selectedCols vocabulary
(HasSelectedCol.java:33-47 pattern), OutputColsHelper merge rules
(OutputColsHelper.java:32-52), model-as-table persistence
(Model.java:102-122).

TPU-first shapes:

* ``StringIndexer.transform`` is one vectorized ``searchsorted`` over the
  stringified column per output — no per-record dictionary lookups.
* ``OneHotEncoder`` emits ONE combined sparse vector column for all its
  input columns (offset-stacked slots) backed by :class:`CsrRows` — three
  contiguous arrays, zero per-row Python objects — which is exactly the
  column form the sparse trainer's vectorized packer consumes, so
  indexer -> encoder -> sparse LogisticRegression runs columnar
  end-to-end.  (A per-column one-hot + dense assembly would materialize
  the full vocabulary width per row — unusable at hashed-feature scale.)
* ``BinaryClassificationEvaluator`` is an AlgoOperator (not a Model):
  one rank-based AUC over the scored table, tie-aware.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from flink_ml_tpu.api.core import AlgoOperator, Estimator
from flink_ml_tpu.common.mapper import ModelMapper
from flink_ml_tpu.lib.model_base import TableModelBase
from flink_ml_tpu.params import param_info
from flink_ml_tpu.params.params import ParamInfo, WithParams
from flink_ml_tpu.params.shared import (
    HasOutputCol,
    HasReservedCols,
    HasSelectedCols,
)
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.table.output_cols import OutputColsHelper
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

INDEXER_MODEL_SCHEMA = Schema.of(
    ("colName", DataTypes.STRING),
    ("value", DataTypes.STRING),
    ("index", DataTypes.DOUBLE),
)

ENCODER_MODEL_SCHEMA = Schema.of(
    ("colName", DataTypes.STRING), ("size", DataTypes.DOUBLE)
)


class HasStringOrderType(WithParams):
    STRING_ORDER_TYPE: ParamInfo = param_info(
        "stringOrderType",
        "Vocabulary order: frequencyDesc | frequencyAsc | alphabetAsc | "
        "alphabetDesc (ties always break lexicographically ascending).",
        default="frequencyDesc",
        value_type=str,
        validator=lambda v: v in (
            "frequencyDesc", "frequencyAsc", "alphabetAsc", "alphabetDesc"
        ),
    )

    def get_string_order_type(self) -> str:
        return self.get(self.STRING_ORDER_TYPE)

    def set_string_order_type(self, value: str):
        return self.set(self.STRING_ORDER_TYPE, value)


class HasHandleInvalid(WithParams):
    HANDLE_INVALID: ParamInfo = param_info(
        "handleInvalid",
        "What to do with values unseen at fit time: 'error' raises, "
        "'keep' maps them to one extra slot past the vocabulary.",
        default="error",
        value_type=str,
        validator=lambda v: v in ("error", "keep"),
    )

    def get_handle_invalid(self) -> str:
        return self.get(self.HANDLE_INVALID)

    def set_handle_invalid(self, value: str):
        return self.set(self.HANDLE_INVALID, value)


class HasOutputColsDefaultAsNull(WithParams):
    OUTPUT_COLS: ParamInfo = param_info(
        "outputCols",
        "Names of the output columns; null overwrites selectedCols in "
        "place.",
        default=None,
        value_type=list,
        optional=True,
    )

    def get_output_cols(self) -> Optional[list]:
        return self.get(self.OUTPUT_COLS)

    def set_output_cols(self, value: list):
        return self.set(self.OUTPUT_COLS, list(value))


class StringIndexerParams(
    HasSelectedCols,
    HasOutputColsDefaultAsNull,
    HasReservedCols,
    HasStringOrderType,
    HasHandleInvalid,
):
    """Shared vocabulary for the indexer estimator and model."""

    def resolved_output_cols(self) -> list:
        out = self.get_output_cols()
        if out is None:
            return list(self.get_selected_cols())
        if len(out) != len(self.get_selected_cols()):
            raise ValueError(
                f"outputCols arity {len(out)} != selectedCols arity "
                f"{len(self.get_selected_cols())}"
            )
        return list(out)


def _stringify(column) -> np.ndarray:
    """A column's values by their string form — the indexing key.  Numeric
    categories index by str(value) (documented; '1.0' and '1' differ)."""
    return np.asarray([str(v) for v in column], dtype=object).astype(str)


def _vocab_order(values: np.ndarray, counts: np.ndarray, order: str):
    if order == "frequencyDesc":
        return np.lexsort((values, -counts))
    if order == "frequencyAsc":
        return np.lexsort((values, counts))
    if order == "alphabetAsc":
        return np.argsort(values)
    return np.argsort(values)[::-1]  # alphabetDesc


class StringIndexerModelMapper(ModelMapper):
    def __init__(self, model: "StringIndexerModel", data_schema: Schema):
        self._model_stage = model
        super().__init__(
            [INDEXER_MODEL_SCHEMA], data_schema, model.get_params()
        )

    def reserved_cols(self) -> Optional[list]:
        return self._model_stage.get_reserved_cols()

    def output_cols(self) -> Tuple[list, list]:
        outs = self._model_stage.resolved_output_cols()
        return outs, [DataTypes.DOUBLE] * len(outs)

    def load_model(self, *model_tables: Table) -> None:
        (t,) = model_tables
        col_names = _stringify(t.col("colName"))
        values = _stringify(t.col("value"))
        indices = np.asarray(t.col("index"), dtype=np.float64)
        # per column: vocab sorted by value, with its index vector — the
        # searchsorted lookup form (one vectorized lookup per transform)
        self._lookup = {}
        for c in np.unique(col_names):
            mask = col_names == c
            vals = values[mask]
            order = np.argsort(vals)
            self._lookup[str(c)] = (vals[order], indices[mask][order])

    def fused_kernel(self):
        # pure host lookup (vectorized searchsorted — there is no device
        # dispatch to fuse away): joins a fused run as a pre-kernel so an
        # indexer -> encoder -> model chain still compiles to one dispatch
        from flink_ml_tpu.common.fused import FusedKernel

        return FusedKernel(host=True)

    def map_batch(self, batch: Table):
        model = self._model_stage
        invalid = model.get_handle_invalid()
        result = {}
        for c, out in zip(model.get_selected_cols(),
                          model.resolved_output_cols()):
            entry = self._lookup.get(c)
            if entry is None:
                raise ValueError(
                    f"column {c!r} has no fitted vocabulary in the model "
                    "data (the model was fit without this column, or its "
                    "model rows were filtered out)"
                )
            sorted_vals, idx = entry
            vals = _stringify(batch.col(c))
            pos = np.searchsorted(sorted_vals, vals)
            pos_safe = np.clip(pos, 0, len(sorted_vals) - 1)
            hit = (
                (pos < len(sorted_vals))
                & (sorted_vals[pos_safe] == vals)
            ) if len(sorted_vals) else np.zeros(len(vals), dtype=bool)
            if invalid == "error" and not np.all(hit):
                missing = vals[~hit][:5]
                raise ValueError(
                    f"column {c!r} holds values unseen at fit time "
                    f"(e.g. {list(missing)}); set handleInvalid='keep' to "
                    "map them to the extra slot"
                )
            out_idx = np.where(hit, idx[pos_safe], float(len(sorted_vals)))
            result[out] = out_idx.astype(np.float64)
        return result


class StringIndexerModel(TableModelBase, StringIndexerParams):
    """Maps each selected column's values to double vocabulary indices."""

    REQUIRED_MODEL_COL = "colName"

    def _make_mapper(self, data_schema: Schema) -> StringIndexerModelMapper:
        return StringIndexerModelMapper(self, data_schema)

    def vocab_sizes(self) -> dict:
        """Per-column vocabulary size (excludes the handleInvalid='keep'
        extra slot)."""
        (t,) = self.get_model_data()
        col_names = _stringify(t.col("colName"))
        out = {}
        for c in np.unique(col_names):
            out[str(c)] = int(np.sum(col_names == c))
        return out


class StringIndexer(Estimator, StringIndexerParams):
    """Estimator: one vectorized unique+count pass per selected column.

    Vocabulary order follows ``stringOrderType`` (default frequencyDesc —
    index 0 is the most frequent value); ties always break lexicographically ascending, so the
    fit is deterministic.
    """

    def fit(self, *inputs) -> StringIndexerModel:
        (table,) = inputs
        order = self.get_string_order_type()
        cols = list(self.get_selected_cols())
        rows = []
        if getattr(table, "is_chunked", False):
            # out-of-core fit: one streaming pass, per-column value counts
            # merged across chunks — the ordering is a pure function of the
            # total counts, so the result matches the in-memory fit exactly
            tallies: list = [{} for _ in cols]
            for t in table.chunks():
                for tally, c in zip(tallies, cols):
                    uniq, counts = np.unique(
                        _stringify(t.col(c)), return_counts=True
                    )
                    for v, n in zip(uniq, counts):
                        tally[str(v)] = tally.get(str(v), 0) + int(n)
            for tally, c in zip(tallies, cols):
                uniq = np.asarray(sorted(tally), dtype=str)
                counts = np.asarray([tally[v] for v in uniq])
                for i, j in enumerate(_vocab_order(uniq, counts, order)):
                    rows.append((c, str(uniq[j]), float(i)))
        else:
            for c in cols:
                vals = _stringify(table.col(c))
                uniq, counts = np.unique(vals, return_counts=True)
                for i, j in enumerate(_vocab_order(uniq, counts, order)):
                    rows.append((c, str(uniq[j]), float(i)))
        model = StringIndexerModel()
        model.get_params().merge(self.get_params())
        model.set_model_data(Table.from_rows(rows, INDEXER_MODEL_SCHEMA))
        return model


class OneHotEncoderParams(
    HasSelectedCols,
    HasOutputCol,
    HasReservedCols,
    HasHandleInvalid,
):
    """Shared vocabulary for the encoder estimator and model."""


class OneHotEncoderModelMapper(ModelMapper):
    def __init__(self, model: "OneHotEncoderModel", data_schema: Schema):
        self._model_stage = model
        super().__init__(
            [ENCODER_MODEL_SCHEMA], data_schema, model.get_params()
        )

    def reserved_cols(self) -> Optional[list]:
        return self._model_stage.get_reserved_cols()

    def output_cols(self) -> Tuple[list, list]:
        return (
            [self._model_stage.get_output_col()],
            [DataTypes.SPARSE_VECTOR],
        )

    def load_model(self, *model_tables: Table) -> None:
        (t,) = model_tables
        names = [str(v) for v in t.col("colName")]
        sizes = {
            n: int(s) for n, s in zip(names, t.col("size"))
        }
        keep = self._model_stage.get_handle_invalid() == "keep"
        cols = list(self._model_stage.get_selected_cols())
        # slot budget per column (+1 invalid bucket under 'keep'), offsets
        # in selectedCols order
        self._sizes = np.asarray(
            [sizes[c] + (1 if keep else 0) for c in cols], dtype=np.int64
        )
        self._offsets = np.concatenate(
            [[0], np.cumsum(self._sizes)[:-1]]
        )
        self._dim = int(self._sizes.sum())

    def fused_kernel(self):
        # host pre-kernel: the offset-stacked CSR build is integer numpy
        # with no device call of its own (see StringIndexerModelMapper)
        from flink_ml_tpu.common.fused import FusedKernel

        return FusedKernel(host=True)

    def map_batch(self, batch: Table):
        model = self._model_stage
        cols = list(model.get_selected_cols())
        keep = model.get_handle_invalid() == "keep"
        n = batch.num_rows()
        k = len(cols)
        idx = np.empty((n, k), dtype=np.int64)
        for j, c in enumerate(cols):
            v = np.asarray(batch.col(c), dtype=np.float64)
            vi = v.astype(np.int64)
            size = self._sizes[j] - (1 if keep else 0)
            bad = (vi < 0) | (vi >= size) | (vi != v)
            if np.any(bad):
                if not keep:
                    raise ValueError(
                        f"column {c!r} holds indices outside [0, {size}) "
                        f"(e.g. {v[bad][:5].tolist()}); set "
                        "handleInvalid='keep' to bucket them"
                    )
                vi = np.where(bad, size, vi)
            idx[:, j] = vi + self._offsets[j]
        # offsets ascend in column order, so each row's indices are already
        # sorted — the CsrRows contract — and the whole batch is three
        # contiguous arrays (zero per-row objects)
        csr = CsrRows(
            self._dim,
            np.arange(0, (n + 1) * k, k, dtype=np.int64),
            idx.reshape(-1),
            np.ones(n * k, dtype=np.float64),
        )
        return {model.get_output_col(): csr}


class OneHotEncoderModel(TableModelBase, OneHotEncoderParams):
    """Encodes the selected index columns into ONE offset-stacked sparse
    vector column (CsrRows-backed)."""

    REQUIRED_MODEL_COL = "colName"

    def _make_mapper(self, data_schema: Schema) -> OneHotEncoderModelMapper:
        return OneHotEncoderModelMapper(self, data_schema)

    def total_size(self) -> int:
        """The output vector width (includes 'keep' buckets when set) —
        what a downstream estimator's numFeatures should be."""
        (t,) = self.get_model_data()
        keep = self.get_handle_invalid() == "keep"
        return int(sum(
            int(s) + (1 if keep else 0) for s in t.col("size")
        ))


class OneHotEncoder(Estimator, OneHotEncoderParams):
    """Estimator: per-column slot count = max observed index + 1."""

    @staticmethod
    def _check_indices(c: str, v: np.ndarray) -> None:
        if len(v) and (np.any(v < 0) or np.any(v != v.astype(np.int64))):
            raise ValueError(
                f"column {c!r} must hold non-negative integer indices "
                "(use StringIndexer upstream)"
            )

    def fit(self, *inputs) -> OneHotEncoderModel:
        (table,) = inputs
        cols = list(self.get_selected_cols())
        if getattr(table, "is_chunked", False):
            # out-of-core fit: slot count = running max over the stream
            maxes = np.full(len(cols), -1.0)
            for t in table.chunks():
                for j, c in enumerate(cols):
                    v = np.asarray(t.col(c), dtype=np.float64)
                    self._check_indices(c, v)
                    if len(v):
                        maxes[j] = max(maxes[j], float(v.max()))
            rows = [
                (c, float(int(m) + 1 if m >= 0 else 1))
                for c, m in zip(cols, maxes)
            ]
        else:
            rows = []
            for c in cols:
                v = np.asarray(table.col(c), dtype=np.float64)
                self._check_indices(c, v)
                size = int(v.max()) + 1 if len(v) else 1
                rows.append((c, float(size)))
        model = OneHotEncoderModel()
        model.get_params().merge(self.get_params())
        model.set_model_data(Table.from_rows(rows, ENCODER_MODEL_SCHEMA))
        return model


class HasRawPredictionCol(WithParams):
    RAW_PREDICTION_COL: ParamInfo = param_info(
        "rawPredictionCol",
        "Column holding the positive-class score (higher = more positive).",
        default="rawPrediction",
        value_type=str,
    )

    def get_raw_prediction_col(self) -> str:
        return self.get(self.RAW_PREDICTION_COL)

    def set_raw_prediction_col(self, value: str):
        return self.set(self.RAW_PREDICTION_COL, value)


class HasLabelColEval(WithParams):
    LABEL_COL: ParamInfo = param_info(
        "labelCol", "The binary label column (0/1).",
        default="label", value_type=str,
    )

    def get_label_col(self) -> str:
        return self.get(self.LABEL_COL)

    def set_label_col(self, value: str):
        return self.set(self.LABEL_COL, value)


EVAL_SCHEMA = Schema.of(
    ("areaUnderROC", DataTypes.DOUBLE), ("count", DataTypes.DOUBLE)
)


def binary_auc(labels: np.ndarray, scores: np.ndarray) -> float:
    """Tie-aware rank AUC: P(score+ > score-) + 0.5 P(tie) — the same
    statistic the AUC parity tests compare."""
    labels = np.asarray(labels, dtype=np.float64)
    scores = np.asarray(scores, dtype=np.float64)
    pos = labels > 0.5
    n_pos = int(pos.sum())
    n_neg = len(labels) - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("AUC needs both classes present")
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores), dtype=np.float64)
    sorted_scores = scores[order]
    # average ranks over ties, fully vectorized: group equal scores, then
    # each group's average rank is (first_rank + last_rank) / 2
    new_group = np.r_[True, sorted_scores[1:] != sorted_scores[:-1]]
    group_id = np.cumsum(new_group) - 1
    counts = np.bincount(group_id)
    ends = np.cumsum(counts).astype(np.float64)  # 1-based rank of group end
    avg_rank = ends - (counts - 1) / 2.0
    ranks[order] = avg_rank[group_id]
    return float(
        (ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)
    )


class BinaryClassificationEvaluator(
    AlgoOperator, HasLabelColEval, HasRawPredictionCol
):
    """AlgoOperator: scored table in, one metrics row out (areaUnderROC).

    An AlgoOperator rather than a Model — it has no model data, matching
    the reference's api-level AlgoOperator contract
    (AlgoOperator.java:153-161: multi-in/multi-out transform)."""

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        labels = np.asarray(table.col(self.get_label_col()), dtype=np.float64)
        scores = np.asarray(
            table.col(self.get_raw_prediction_col()), dtype=np.float64
        )
        auc = binary_auc(labels, scores)
        return (
            Table.from_rows([(auc, float(len(labels)))], EVAL_SCHEMA),
        )


# keep OutputColsHelper imported name referenced for mapper machinery users
_ = OutputColsHelper
