"""Retry with jittered exponential backoff for transient-failure surfaces.

The reference gets retries for free from Flink's task-restart strategy;
here the transient surfaces are explicit — spill I/O, checkpoint writes,
cold H2D placement — and each wraps its failable body in
:func:`with_retry`.  Every retry and giveup lands in the obs registry
(``fault.retries`` / ``fault.giveups``), so a fit RunReport's per-fit
delta shows when a run only passed by retrying (the ``obs --check``
flag).

What counts as transient: OS-level I/O errors, the chaos layer's
:class:`~flink_ml_tpu.fault.injection.InjectedFault`, and runtime errors
whose message carries a transient gRPC/XLA status (``RESOURCE_EXHAUSTED``,
``UNAVAILABLE``, ``DEADLINE_EXCEEDED``, ``DATA_LOSS``, ``ABORTED``) — the
classes a device/host blip produces.  Anything else (shape errors, value
errors, real bugs) re-raises immediately: retrying a deterministic failure
just triples its latency.

One carve-out (ISSUE 9): a ``RESOURCE_EXHAUSTED`` whose message matches
an *allocator* OOM (:func:`~flink_ml_tpu.fault.pressure.is_oom` — "out
of memory", bytes-requested patterns, the ``fault.oom`` injection) is
deterministic, not transient: the identical batch fails identically, so
it routes to the pressure layer's batch bisection instead of a same-size
retry.  Genuine transient exhaustion (quota, RPC backpressure) carries
no allocator vocabulary and stays retryable.
"""

from __future__ import annotations

import errno
import random
import time
from dataclasses import dataclass
from typing import Callable, Optional

from flink_ml_tpu import obs
from flink_ml_tpu.fault.injection import InjectedFault
from flink_ml_tpu.utils import knobs

__all__ = [
    "RetryPolicy",
    "default_policy",
    "is_transient",
    "with_retry",
]


#: runtime-error message fragments that mark a failure as transient (the
#: gRPC/XLA status vocabulary device and cross-host blips surface as)
_TRANSIENT_STATUSES = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "DATA_LOSS",
    "ABORTED",
)


#: OSError subclasses/errnos a retry can never fix — retrying them only
#: triples the latency of the true error and pollutes the fault counters
_DETERMINISTIC_OS_ERRORS = (
    FileNotFoundError, PermissionError, NotADirectoryError,
    IsADirectoryError, FileExistsError,
)
_DETERMINISTIC_ERRNOS = frozenset(
    e for e in (
        errno.ENOSPC, errno.EROFS, errno.ENAMETOOLONG,
        getattr(errno, "EDQUOT", None),
    )
    if e is not None
)


def is_transient(exc: BaseException) -> bool:
    """Would retrying this failure plausibly succeed?"""
    from flink_ml_tpu.fault.pressure import is_compile_failure, is_oom

    if is_oom(exc) or is_compile_failure(exc):
        # allocator exhaustion is DETERMINISTIC: the same batch fails
        # identically, so a same-size retry only triples the latency —
        # recovery belongs to fault.pressure's bisection, not here.  A
        # kernel that overflows VMEM carries RESOURCE_EXHAUSTED too, and
        # is a compile failure nobody may absorb.
        return False
    if isinstance(exc, InjectedFault):
        return True
    if isinstance(exc, OSError):
        # I/O blips (EIO, EAGAIN, ETIMEDOUT, network errnos) are transient;
        # missing paths, permissions, full/read-only filesystems are not
        if isinstance(exc, _DETERMINISTIC_OS_ERRORS):
            return False
        return exc.errno not in _DETERMINISTIC_ERRNOS
    if isinstance(exc, RuntimeError):
        msg = str(exc)
        return any(s in msg for s in _TRANSIENT_STATUSES)
    return False


@dataclass(frozen=True)
class RetryPolicy:
    """attempts = total tries (1 = no retry); delays grow ``base * factor^k``
    capped at ``max_delay_s``, each multiplied by a uniform jitter in
    ``[1-jitter, 1+jitter]`` so a fleet of workers retrying the same shared
    resource (a filesystem, a coordinator) doesn't stampede in lockstep."""

    attempts: int = 3
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    factor: float = 2.0
    jitter: float = 0.5

    def delay(self, attempt: int) -> float:
        """Backoff before retry number ``attempt`` (1-based)."""
        d = min(self.base_delay_s * (self.factor ** (attempt - 1)),
                self.max_delay_s)
        return d * (1.0 + self.jitter * (2.0 * random.random() - 1.0))


def default_policy() -> RetryPolicy:
    """The process default, env-tunable: ``FMT_RETRY_ATTEMPTS`` /
    ``FMT_RETRY_BASE_S`` (README.md, "Fault tolerance")."""
    return RetryPolicy(
        attempts=knobs.knob_int("FMT_RETRY_ATTEMPTS"),
        base_delay_s=knobs.knob_float("FMT_RETRY_BASE_S"),
    )


def with_retry(fn: Callable, name: str,
               policy: Optional[RetryPolicy] = None):
    """Run ``fn()``; on a transient failure, back off and retry.

    ``name`` labels the surface in telemetry (``fault.retries.<name>``)
    and in the giveup's exception chain.  Non-transient failures and the
    final transient failure re-raise unchanged — callers see the true
    error, with the retry history visible in the counters."""
    if policy is None:
        policy = default_policy()
    attempt = 1
    while True:
        try:
            return fn()
        except BaseException as exc:  # noqa: BLE001 - filtered just below
            if not is_transient(exc) or attempt >= policy.attempts:
                if is_transient(exc):
                    obs.counter_add("fault.giveups")
                    obs.counter_add(f"fault.giveups.{name}")
                    obs.flight.record("fault.giveup", surface=name,
                                      attempts=attempt,
                                      error=type(exc).__name__)
                raise
            obs.counter_add("fault.retries")
            obs.counter_add(f"fault.retries.{name}")
            obs.flight.record("fault.retry", surface=name, attempt=attempt,
                              error=type(exc).__name__, detail=str(exc))
            time.sleep(policy.delay(attempt))
            attempt += 1
