"""flink_ml_tpu — a TPU-native ML-pipeline framework.

A brand-new framework with the capabilities of Apache Flink ML (pre-2.0 snapshot,
see SURVEY.md): Estimator/Transformer/Model pipelines with a typed JSON-persistable
parameter system, a columnar table data plane, bounded/unbounded iterative training
with epoch semantics, and batched mapper inference — designed TPU-first on
JAX/XLA/pjit/shard_map rather than ported from the reference's per-record JVM design.

Layer map (bottom-up, cf. SURVEY.md §7.1):
  ops/        math kernel (replaces flink-ml-lib linalg + netlib BLAS/LAPACK)
  table/      columnar data plane (replaces Flink Table + conversion utils)
  parallel/   device mesh + collectives (replaces the Flink runtime's comm role)
  iteration/  bounded/unbounded iteration runtime (implements FLIP-176 semantics
              that the reference's Iterations.java:89,112 left as `return null`)
  api/        Stage/Estimator/Transformer/Model/Pipeline (flink-ml-api parity)
  params/     Params/ParamInfo/WithParams (flink-ml-api misc/param parity)
  mapper/     batched inference machinery (flink-ml-lib common/mapper parity)
  models/     LogisticRegression, LinearRegression, KMeans, Knn, OnlineLR, ...
  utils/      environment registry, metrics, persistence helpers
"""

__version__ = "0.1.0"

from flink_ml_tpu.utils.compile_cache import enable_compilation_cache

# Warm-process startup parity with the reference's JVM: persist XLA
# executables across processes so only the first process ever pays the
# fused-program compile.  Resolved here, before any backend exists, so no
# compile can precede it (JAX_COMPILATION_CACHE_DIR places the cache;
# FMT_COMPILE_CACHE=off opts out).
enable_compilation_cache()

from flink_ml_tpu.params import (  # noqa: F401
    ParamInfo,
    ParamValidator,
    Params,
    WithParams,
    param_info,
)
