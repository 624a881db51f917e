"""Serving robustness layer: quarantine, model integrity, circuit breaking.

PR 3 made every *training* path survive faults; this package is the same
discipline for the inference stack the north star says must "serve heavy
traffic from millions of users".  Three legs, wired through
``common/mapper.py`` and every concrete ModelMapper:

* :mod:`~flink_ml_tpu.serve.quarantine` — input validation + per-row
  quarantine at the MapperAdapter boundary: bad rows (NaN/Inf, wrong
  vector dimension, nulls, wrong types) are masked out of the jitted
  computation and emitted to a reason-coded side-table while the good
  rows still serve;
* :mod:`~flink_ml_tpu.serve.integrity` — atomic tmp+rename model writes
  with length+CRC32 sidecar commit records (the spill-block scheme),
  verified by every loader; corruption raises
  :class:`~flink_ml_tpu.serve.errors.ModelIntegrityError` instead of
  serving silently-wrong params;
* :mod:`~flink_ml_tpu.serve.breaker` — deadline + jittered-retry dispatch
  behind a per-mapper circuit breaker that degrades to an exact-parity
  NumPy CPU fallback when the device path keeps failing.

Everything lands in the obs registry (``serve.*`` counters, the
``serve.breaker_state`` gauges, the ``serve.deadline_ms`` histogram) and
in per-transform RunReports; ``python -m flink_ml_tpu.obs --check``
prints ``SERVE-DEGRADED`` for transforms that only completed via
fallback.  Chaos entry point: ``python scripts/chaos_smoke.py --serve``
(CI job ``chaos-smoke``).

Knobs (README.md, "Serving robustness"): ``FMT_SERVE_QUARANTINE``,
``FMT_SERVE_QUARANTINE_CAP``, ``FMT_SERVE_DEADLINE_MS``,
``FMT_SERVE_BREAKER_THRESHOLD``, ``FMT_SERVE_BREAKER_COOLDOWN_S``.
"""

from flink_ml_tpu.serve import quarantine  # noqa: F401
from flink_ml_tpu.serve.breaker import (  # noqa: F401
    CircuitBreaker,
    breaker,
    dispatch,
    open_breaker_names,
    reset_breakers,
    serve_counter_delta,
    serve_counter_snapshot,
)
from flink_ml_tpu.serve.errors import (  # noqa: F401
    MapperOutputMisalignedError,
    ModelIntegrityError,
)
from flink_ml_tpu.serve.integrity import (  # noqa: F401
    AtomicFile,
    atomic_json_dump,
    verify_commit_record,
    write_commit_record,
)

__all__ = [
    "AtomicFile",
    "CircuitBreaker",
    "MapperOutputMisalignedError",
    "ModelIntegrityError",
    "atomic_json_dump",
    "breaker",
    "dispatch",
    "open_breaker_names",
    "quarantine",
    "reset_breakers",
    "serve_counter_delta",
    "serve_counter_snapshot",
    "verify_commit_record",
    "write_commit_record",
]
