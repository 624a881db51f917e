"""Online serving runtime: dynamic micro-batching model server.

The request-level layer over the inference stack (PR 7): a
:class:`~flink_ml_tpu.serving.server.ModelServer` hosts loaded
``PipelineModel``s and turns streams of single-row/small-batch requests
into full fused dispatches —

* **micro-batching** — ``submit`` returns a future; a dispatcher thread
  coalesces queued requests into one ``transform`` per batch (flush on
  ``FMT_SERVING_MAX_BATCH`` rows or ``FMT_SERVING_MAX_WAIT_MS``), padded
  to the shared batch-shape ladder so the compile cache is reused across
  request sizes, then demultiplexes outputs — and quarantine side-tables
  — back to callers with request-local row offsets;
* **admission control** — bounded queue, per-request deadlines,
  shed-oldest-past-deadline-first, reason-coded
  :class:`~flink_ml_tpu.serving.errors.ServerOverloadedError` rejection,
  breaker-open shedding: overload degrades predictably instead of
  queueing unboundedly;
* **hot swap** — ``deploy(path, version)`` loads + integrity-verifies +
  pre-warms off the hot path, then swaps atomically between batches;
  in-flight requests finish on the old version and a corrupt deploy
  leaves the old version serving.

Horizontal scale-out (ISSUE 13): :class:`~flink_ml_tpu.serving.router.
ReplicaRouter` fans the same ``submit() -> Future`` contract across N
``ModelServer`` replica subprocesses — health-aware power-of-two-choices
balancing off each replica's ``/readyz`` + ``/metrics``, reason-code
retry classification (:func:`~flink_ml_tpu.serving.errors.shed_policy`),
drain-aware zero-downtime rolling deploys, and crash supervision with
respawn (:mod:`flink_ml_tpu.serving.replica` owns the subprocess
lifecycle and wire protocol).

Continuous learning (ISSUE 14): :class:`~flink_ml_tpu.serving.lifecycle.
ContinuousLearningController` closes the reference's second topology —
an online fitter consumes a label stream beside the live server,
periodically cuts a candidate, pushes it through a hard validation gate
(numeric health, holdout no-regression, score quarantine/PSI sanity),
auto-deploys passing candidates through the swap contract, and watches a
post-swap probation window that rolls back automatically on an SLO or
drift burn (``ModelServer.rollback`` / ``VersionManager.rollback``).

Elastic fleet (ISSUE 19): :class:`~flink_ml_tpu.serving.autoscaler.
FleetAutoscaler` closes the observe→decide→act loop over the router —
SLO-burn/queue-growth/shed scale-up before the p99 burns, sustained-idle
drain-safe scale-down through the rolling-deploy drain contract,
hysteresis + cooldown flap protection, and a preemption-aware
warm-spares mode (``FMT_SCALE_WARM_SPARES``) so SIGTERM storms never
drop serving capacity below target.

Entry points: ``python scripts/chaos_smoke.py --serving`` / ``--router`` /
``--autoscale`` (shed / hot-swap / corrupt-deploy / replica-kill /
elastic-ramp legs), ``examples/online_serving.py``,
``examples/router_serving.py``.
"""

from flink_ml_tpu.serving.admission import ServingConfig  # noqa: F401
from flink_ml_tpu.serving.autoscaler import (  # noqa: F401
    FleetAutoscaler,
    ScalerConfig,
)
from flink_ml_tpu.serving.batcher import (  # noqa: F401
    ServeRequest,
    ServeResult,
)
from flink_ml_tpu.serving.errors import (  # noqa: F401
    ServerClosedError,
    ServerOverloadedError,
    shed_policy,
)
from flink_ml_tpu.serving.lifecycle import (  # noqa: F401
    ContinuousLearningController,
)
from flink_ml_tpu.serving.replica import (  # noqa: F401
    ReplicaClient,
    ReplicaProcess,
    ReplicaRemoteError,
    ReplicaUnreachableError,
)
from flink_ml_tpu.serving.router import (  # noqa: F401
    ReplicaRouter,
    RollingDeployError,
    RouterConfig,
)
from flink_ml_tpu.serving.server import ModelServer  # noqa: F401
from flink_ml_tpu.serving.versioning import (  # noqa: F401
    ModelVersion,
    VersionManager,
)

__all__ = [
    "ContinuousLearningController",
    "FleetAutoscaler",
    "ModelServer",
    "ModelVersion",
    "ReplicaClient",
    "ReplicaProcess",
    "ReplicaRemoteError",
    "ReplicaRouter",
    "ReplicaUnreachableError",
    "RollingDeployError",
    "RouterConfig",
    "ScalerConfig",
    "ServeRequest",
    "ServeResult",
    "ServerClosedError",
    "ServerOverloadedError",
    "ServingConfig",
    "VersionManager",
    "shed_policy",
]
