"""Algorithm hyper-parameter vocabulary.

Extends the shared column mixins (params/shared.py, cf.
flink-ml-lib/.../params/shared/) with the training hyper-parameters the
estimators need.  Same mixin pattern as the reference
(HasSelectedCol.java:33-47): one ParamInfo class attribute + typed accessors
per interface, composable by inheritance.
"""

from __future__ import annotations

from flink_ml_tpu.params.params import ParamInfo, WithParams, param_info


class HasLabelCol(WithParams):
    LABEL_COL: ParamInfo = param_info(
        "labelCol", "Name of the label column.", default="label", value_type=str,
    )

    def get_label_col(self) -> str:
        return self.get(self.LABEL_COL)

    def set_label_col(self, value: str):
        return self.set(self.LABEL_COL, value)


class HasVectorColDefaultAsNull(WithParams):
    VECTOR_COL: ParamInfo = param_info(
        "vectorCol", "Name of a vector column holding the features.",
        default=None, value_type=str,
    )

    def get_vector_col(self):
        return self.get(self.VECTOR_COL)

    def set_vector_col(self, value: str):
        return self.set(self.VECTOR_COL, value)


class HasFeatureColsDefaultAsNull(WithParams):
    FEATURE_COLS: ParamInfo = param_info(
        "featureCols", "Names of numeric feature columns.",
        default=None, value_type=list,
    )

    def get_feature_cols(self):
        return self.get(self.FEATURE_COLS)

    def set_feature_cols(self, value):
        return self.set(self.FEATURE_COLS, list(value) if value is not None else None)


class HasMaxIter(WithParams):
    MAX_ITER: ParamInfo = param_info(
        "maxIter", "Maximum number of training epochs.",
        default=100, value_type=int,
        validator=lambda v: v > 0,
    )

    def get_max_iter(self) -> int:
        return self.get(self.MAX_ITER)

    def set_max_iter(self, value: int):
        return self.set(self.MAX_ITER, value)


class HasLearningRate(WithParams):
    LEARNING_RATE: ParamInfo = param_info(
        "learningRate", "SGD learning rate.",
        default=0.1, value_type=float,
        validator=lambda v: v > 0,
    )

    def get_learning_rate(self) -> float:
        return self.get(self.LEARNING_RATE)

    def set_learning_rate(self, value: float):
        return self.set(self.LEARNING_RATE, value)


class HasGlobalBatchSize(WithParams):
    GLOBAL_BATCH_SIZE: ParamInfo = param_info(
        "globalBatchSize",
        "Rows per SGD mini-batch across the whole mesh; 0 means full batch.",
        default=0, value_type=int,
        validator=lambda v: v >= 0,
    )

    def get_global_batch_size(self) -> int:
        return self.get(self.GLOBAL_BATCH_SIZE)

    def set_global_batch_size(self, value: int):
        return self.set(self.GLOBAL_BATCH_SIZE, value)


class HasTol(WithParams):
    TOL: ParamInfo = param_info(
        "tol",
        "Convergence tolerance on the parameter-update norm; 0 disables "
        "early stopping.",
        default=0.0, value_type=float,
        validator=lambda v: v >= 0,
    )

    def get_tol(self) -> float:
        return self.get(self.TOL)

    def set_tol(self, value: float):
        return self.set(self.TOL, value)


class HasReg(WithParams):
    REG: ParamInfo = param_info(
        "reg", "L2 regularization strength.", default=0.0, value_type=float,
        validator=lambda v: v >= 0,
    )

    def get_reg(self) -> float:
        return self.get(self.REG)

    def set_reg(self, value: float):
        return self.set(self.REG, value)


class HasWithIntercept(WithParams):
    WITH_INTERCEPT: ParamInfo = param_info(
        "withIntercept", "Whether to fit an intercept term.",
        default=True, value_type=bool,
    )

    def get_with_intercept(self) -> bool:
        return self.get(self.WITH_INTERCEPT)

    def set_with_intercept(self, value: bool):
        return self.set(self.WITH_INTERCEPT, value)


class HasSeed(WithParams):
    SEED: ParamInfo = param_info(
        "seed", "Random seed for reproducible runs.", default=0, value_type=int,
    )

    def get_seed(self) -> int:
        return self.get(self.SEED)

    def set_seed(self, value: int):
        return self.set(self.SEED, value)


class HasCheckpoint(WithParams):
    CHECKPOINT_DIR: ParamInfo = param_info(
        "checkpointDir",
        "Directory for periodic training snapshots; None disables "
        "checkpointing. An existing snapshot there resumes training.",
        default=None, value_type=str,
    )
    CHECKPOINT_INTERVAL: ParamInfo = param_info(
        "checkpointInterval", "Snapshot every N completed epochs.",
        default=1, value_type=int,
        validator=lambda v: v > 0,
    )

    def get_checkpoint_dir(self):
        return self.get(self.CHECKPOINT_DIR)

    def set_checkpoint_dir(self, value: str):
        return self.set(self.CHECKPOINT_DIR, value)

    def get_checkpoint_interval(self) -> int:
        return self.get(self.CHECKPOINT_INTERVAL)

    def set_checkpoint_interval(self, value: int):
        return self.set(self.CHECKPOINT_INTERVAL, value)


class HasNumFeatures(WithParams):
    NUM_FEATURES: ParamInfo = param_info(
        "numFeatures",
        "Feature-space dimension for sparse vectors; None infers from data.",
        default=None, value_type=int,
    )

    def get_num_features(self):
        return self.get(self.NUM_FEATURES)

    def set_num_features(self, value: int):
        return self.set(self.NUM_FEATURES, value)


class HasWindowMs(WithParams):
    WINDOW_MS: ParamInfo = param_info(
        "windowMs", "Event-time tumbling window size in milliseconds.",
        default=5000, value_type=int,
        validator=lambda v: v > 0,
    )

    def get_window_ms(self) -> int:
        return self.get(self.WINDOW_MS)

    def set_window_ms(self, value: int):
        return self.set(self.WINDOW_MS, value)


class HasBf16Distances(WithParams):
    BF16_DISTANCES: ParamInfo = param_info(
        "bf16Distances",
        "Compute the distance-matrix cross term (x . c^T) in bf16 with f32 "
        "accumulation — ~2x MXU throughput on the matmul-bound Knn "
        "transform. Opt-in: distances lose ~8 bits of mantissa, so exact "
        "tie-breaking and bit-parity with the f32 path are not guaranteed "
        "(neighbor SETS can differ when distances are closer than the bf16 "
        "rounding of the cross term). The norm terms stay f32.",
        default=False, value_type=bool,
    )

    def get_bf16_distances(self) -> bool:
        return self.get(self.BF16_DISTANCES)

    def set_bf16_distances(self, value: bool):
        return self.set(self.BF16_DISTANCES, value)


class HasShardModelData(WithParams):
    SHARD_MODEL_DATA: ParamInfo = param_info(
        "shardModelData",
        "Shard the model data over the mesh's data axis instead of "
        "replicating it, for models (e.g. a Knn reference set) too large "
        "for one device's memory.",
        default=False, value_type=bool,
    )

    def get_shard_model_data(self) -> bool:
        return self.get(self.SHARD_MODEL_DATA)

    def set_shard_model_data(self, value: bool):
        return self.set(self.SHARD_MODEL_DATA, value)


class HasAllowedLateness(WithParams):
    ALLOWED_LATENESS_MS: ParamInfo = param_info(
        "allowedLatenessMs",
        "Bounded event-time out-of-orderness: the watermark trails the max "
        "event time seen by this much, so records up to this late still land "
        "in their window (later ones go to the late-data side output).",
        default=0, value_type=int,
        validator=lambda v: v >= 0,
    )

    def get_allowed_lateness_ms(self) -> int:
        return self.get(self.ALLOWED_LATENESS_MS)

    def set_allowed_lateness_ms(self, value: int):
        return self.set(self.ALLOWED_LATENESS_MS, value)


class HasK(WithParams):
    K: ParamInfo = param_info(
        "k", "Number of clusters / neighbors.", default=2, value_type=int,
        validator=lambda v: v > 0,
    )

    def get_k(self) -> int:
        return self.get(self.K)

    def set_k(self, value: int):
        return self.set(self.K, value)
