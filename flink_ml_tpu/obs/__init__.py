"""Unified run telemetry (ISSUE 1): metrics registry, phase timers, reports.

The reference has no in-library observability at all — Flink's web UI was
the only hook (see ``utils/metrics.py``).  This package is the repo's one
measurement layer:

  * :mod:`flink_ml_tpu.obs.registry` — a process-wide registry of counters,
    gauges, and timing histograms, plus the program's ONE span API:
    ``obs.span("train.dispatch")`` times a piece of host code into the
    registry, writes it as ``fmt.train.dispatch`` on the profiler's clock
    (``jax.profiler.TraceAnnotation``, imported there and nowhere else)
    and into the thread's request trace; ``phase("pack_csr")`` is a span
    whose name nests.  While it is on it also listens to
    ``jax.monitoring`` (imported there and nowhere else): every program
    traced, lowered, compiled or read from the persistent cache is timed
    under ``compile.*`` and ``compile.under/<the open span>`` and leaves
    a ``compile`` event in the flight recorder.  **Off by default** and
    near-zero-cost when off: every hook degrades to one module-level
    boolean check and no listener stands.  Enable with ``obs.enable()``
    or ``FMT_OBS=1``.
  * :mod:`flink_ml_tpu.obs.report` — structured JSONL :class:`RunReport`
    records (git SHA, device topology, registry snapshot, StepMetrics
    summary) written by every ``fit`` / ``transform`` / serving run
    while obs is on, and the ``python -m flink_ml_tpu.obs`` CLI that
    summarises them (degraded and fault-assisted runs, drift, timing
    tails).

``StepMetrics`` (per-step wall/loss/throughput) remains the per-run
primitive; this package is where its output — and everything else worth
keeping — gets aggregated and persisted per run instead of dying in stdout.

ISSUE 8 added the per-request layer on top of the aggregates:

  * :mod:`flink_ml_tpu.obs.trace` — Dapper-style span tracing with
    explicit cross-thread context handoff (``FMT_TRACE`` /
    ``FMT_TRACE_SAMPLE``, off by default, one-bool hooks), a JSONL span
    sink, and the ``python -m flink_ml_tpu.obs trace`` waterfall CLI.
  * :mod:`flink_ml_tpu.obs.flight` — an always-on bounded ring of
    structured events (swaps, sheds, breaker transitions, fault
    retries/rollbacks, plan fallbacks) dumped as a redacted JSONL black
    box on breaker-open, deploy failure, guard rollback, or crash.

ISSUE 11 added the DATA plane next to the system plane:

  * :mod:`flink_ml_tpu.obs.sketch` — mergeable fixed-memory streaming
    distribution sketches (DDSketch-style quantiles + count/mean/var/
    null/NaN accumulators per column).
  * :mod:`flink_ml_tpu.obs.drift` — the ``DriftMonitor``: a reference
    distribution snapshotted at deploy (persisted next to the model),
    a rolling live window tapped at the quarantine boundary / fused
    plan entry / serving demux, PSI+KS per column, the third (``drift``)
    SLO, and the ``python -m flink_ml_tpu.obs drift`` comparison CLI
    (``FMT_DRIFT``, off by default).

ISSUE 10 added the LIVE plane on top of the post-hoc layers:

  * :mod:`flink_ml_tpu.obs.telemetry` — an embedded HTTP endpoint
    (``FMT_TELEMETRY_PORT``, off by default) exposing ``/metrics``
    (OpenMetrics rendering of the registry), ``/healthz`` / ``/readyz``
    (liveness vs. reason-coded readiness: open breakers, pressure caps,
    deploys in progress, queue saturation, burning SLOs), and
    ``/statusz`` (one JSON snapshot).
  * :mod:`flink_ml_tpu.obs.slo` — the in-process SLO burn-rate monitor
    (serving p99 latency + shed/error ratio on a rolling window)
    feeding the ``slo.burning.*`` gauges, flight-recorder breach dumps,
    and ``/readyz``.
"""

from flink_ml_tpu.obs import drift, flight, sketch, slo, telemetry, trace  # noqa: F401
from flink_ml_tpu.obs.registry import (
    MetricsRegistry,
    counter_add,
    disable,
    enable,
    enabled,
    gauge_set,
    observe,
    phase,
    phased,
    profiler_annotation,
    record_hbm_gauges,
    registry,
    reset,
    span,
)
from flink_ml_tpu.obs.report import (
    RunReport,
    fit_report,
    git_sha,
    load_reports,
    reports_dir,
    write_run_report,
)

__all__ = [
    "MetricsRegistry",
    "RunReport",
    "counter_add",
    "disable",
    "drift",
    "enable",
    "enabled",
    "fit_report",
    "flight",
    "gauge_set",
    "git_sha",
    "load_reports",
    "observe",
    "phase",
    "phased",
    "profiler_annotation",
    "record_hbm_gauges",
    "registry",
    "reports_dir",
    "reset",
    "sketch",
    "slo",
    "span",
    "telemetry",
    "trace",
    "write_run_report",
]
