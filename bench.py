"""Headline benchmark: LogisticRegression.fit samples/sec/chip, plus the
repeated-fit (warm-path) sweep.

Thin wrapper over :func:`bench_all.bench_logreg` and
:func:`bench_all.bench_warm_fit` (the full matrix lives in ``bench_all.py``
— all five BASELINE.json configs plus the Criteo-shaped sparse path).
Prints one JSON line per workload:
  {"metric", "value", "unit", "vs_baseline", ...}

``vs_baseline`` is against the honest vectorized-numpy minibatch SGD on the
host CPU (identical update rule); the reference-shaped per-record loop is
also measured and reported as ``vs_per_record``.  AUC parity against the
vectorized baseline is computed on held-out rows (``auc_parity``).
Throughput is read from the training driver's own StepMetrics.

The repeated-fit sweep (ISSUE 2) fits ONE table three times (learning rate
varied on the third) and reports cold vs warm call latency plus slab-pool
hit counts — ``warm_over_cold`` is the ratio BASELINE.json gates.
"""

import os

from bench_all import bench_logreg, bench_warm_fit


def _refuse_unasked_cpu():
    """This is the chip entry: a run that finds no TPU fails instead of
    quietly measuring XLA:CPU (every record carries ``platform`` /
    ``device_kind`` / ``device_count`` either way).  A CPU run must be
    asked for by name: ``JAX_PLATFORMS=cpu python bench.py``."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: JAX found platform {platform!r}, not a TPU; refusing "
            "to record CPU numbers as chip numbers (set JAX_PLATFORMS=cpu "
            "to run the CPU check on purpose)")


def main():
    _refuse_unasked_cpu()
    from flink_ml_tpu import obs

    obs.enable()
    obs.reset()
    bench_logreg()
    # fresh registry scope so the warm-fit RunReport's metrics snapshot
    # describes the repeated-fit sweep alone
    obs.reset()
    bench_warm_fit()


if __name__ == "__main__":
    main()
