"""Central declaration table for every ``FMT_*`` environment knob.

Eleven PRs grew ~50 ``FMT_*`` environment variables, each parsed ad hoc
at its point of use — and the documentation drifted (round 14's notes
documented 45 of the 50 the code actually read).  This module is the
single source of truth the static analyzer (``flink_ml_tpu.analysis``,
rule family KNOB*) enforces:

* every knob is **declared** here exactly once — name, default, type,
  one doc line;
* every runtime read goes through the typed getters below (this module
  owns the only ``os.environ`` read of an ``FMT_*`` name in the
  package);
* the analyzer cross-references the declarations against README.md,
  so an undocumented knob — or a documented-but-deleted
  one — is a CI failure, not a silent drift.

Parsing semantics (shared by every knob so no two call sites can
disagree):

* ``bool`` — an **unset or empty** variable takes the declared default.
  Default-off knobs turn on only for ``1/true/yes/on``; default-on
  knobs turn off only for ``0/false/no/off`` (so a typo'd value keeps
  the safe default behavior of its knob, matching the historical
  per-site parsers).
* ``int`` / ``float`` — unset, empty, or unparsable values take the
  declared default (a malformed knob must degrade to the default, never
  crash a serving process at import time).
* ``str`` — :func:`raw` returns the variable verbatim (``None`` when
  unset); :func:`knob_str` substitutes the declared default.

Pure stdlib on purpose: the analyzer parses this file's AST without
importing JAX, and importing it at runtime adds nothing to the package's
import graph.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

__all__ = [
    "Knob",
    "DECLARATIONS",
    "declared",
    "get",
    "raw",
    "knob_bool",
    "knob_int",
    "knob_float",
    "knob_str",
]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``default`` is kept in its string (environment) form so ``raw`` and
    the typed getters agree about what an unset variable means.
    """

    name: str
    default: str
    type: str  # "bool" | "int" | "float" | "str"
    doc: str


# NOTE for checker authors: the analyzer reads this tuple *statically*
# (literal Knob(...) calls); keep every field a plain literal.
DECLARATIONS: Tuple[Knob, ...] = (
    # -- observability ----------------------------------------------------
    Knob("FMT_OBS", "0", "bool",
         "Enable the in-process metrics registry (counters/gauges/timers)."),
    Knob("FMT_OBS_REPORTS", "", "str",
         "Directory for RunReport JSONL output (default: <repo>/reports)."),
    Knob("FMT_GIT_SHA", "", "str",
         "Override the git SHA stamped into RunReports (CI detached heads)."),
    Knob("FMT_TRACE", "0", "bool",
         "Enable Dapper-style request tracing (span records per request)."),
    Knob("FMT_TRACE_SAMPLE", "1.0", "float",
         "Head-sampling probability for request traces (0..1)."),
    Knob("FMT_TRACE_DIR", "", "str",
         "Span sink directory (default: traces/ under the reports dir)."),
    Knob("FMT_TRACE_TAIL", "", "str",
         "Tail-sampling modes (slow|shed|error, comma-combinable): keep "
         "only traces whose boundary span is anomalous."),
    Knob("FMT_TRACE_SLOW_MS", "250", "float",
         "Boundary-span duration that counts as slow for FMT_TRACE_TAIL."),
    Knob("FMT_TRACE_MAX_MB", "64", "float",
         "Rotate a process's trace sink past this size (0 disables)."),
    Knob("FMT_FLIGHT_EVENTS", "512", "int",
         "Flight-recorder ring capacity (events kept for black-box dumps)."),
    Knob("FMT_FLIGHT_MIN_S", "30", "float",
         "Minimum seconds between flight-recorder dumps per reason."),
    Knob("FMT_FLIGHT_DIR", "", "str",
         "Flight-recorder dump directory (default: flight/ under reports)."),
    Knob("FMT_TELEMETRY_PORT", "", "str",
         "Telemetry HTTP port: unset=off, 0=ephemeral, N=fixed port."),
    Knob("FMT_TELEMETRY_HOST", "127.0.0.1", "str",
         "Bind host for the telemetry HTTP endpoint (loopback by default)."),
    Knob("FMT_TELEMETRY_PORT_FILE", "", "str",
         "File that atomically receives host:port when the endpoint binds."),
    Knob("FMT_READY_PRESSURE_FLOOR", "8", "int",
         "/readyz degrades when a pressure cap pins below this row count."),
    Knob("FMT_READY_QUEUE_FRAC", "0.95", "float",
         "/readyz degrades when the serving queue exceeds this cap fraction."),
    Knob("FMT_SLO_WINDOW_S", "30", "float",
         "SLO monitor sampling window in seconds."),
    Knob("FMT_SLO_P99_MS", "0", "float",
         "Serving p99 latency SLO in milliseconds (0 disables the SLO)."),
    Knob("FMT_SLO_ERR_RATIO", "0", "float",
         "Shed+error ratio SLO threshold (0 disables the SLO)."),
    Knob("FMT_SLO_MIN_EVENTS", "10", "int",
         "Minimum events per window before an SLO burn rate is judged."),
    Knob("FMT_DRIFT", "0", "bool",
         "Enable data-drift monitoring (reference vs live sketches)."),
    Knob("FMT_DRIFT_REF_ROWS", "512", "int",
         "Rows folded into the deploy-time drift reference distribution."),
    Knob("FMT_DRIFT_PSI", "0.2", "float",
         "Per-column PSI threshold that flips the drift SLO to burning."),
    Knob("FMT_DRIFT_WINDOW_S", "60", "float",
         "Rolling live drift window rotation period in seconds."),
    Knob("FMT_DRIFT_WINDOW_ROWS", "8192", "int",
         "Per-window sketch row cap (rate denominators stay exact)."),
    Knob("FMT_DRIFT_MIN_ROWS", "64", "int",
         "Minimum live rows in a window before drift is judged."),
    Knob("FMT_DRIFT_MAX_COLS", "16", "int",
         "Cap on per-dimension fan-out of dense vector columns."),
    # -- fault tolerance --------------------------------------------------
    Knob("FMT_FAULT_INJECT", "", "str",
         "Deterministic fault-injection spec, e.g. 'slab_pool.place@2'."),
    Knob("FMT_FAULT_SEED", "0", "int",
         "Seed for probabilistic fault-injection rules."),
    Knob("FMT_GUARD", "1", "bool",
         "Numeric-health guard around training snapshots (rollback on NaN)."),
    Knob("FMT_GUARD_MAX_RETRIES", "2", "int",
         "Guard rollback retries before giving up a fit."),
    Knob("FMT_GUARD_LR_BACKOFF", "0.5", "float",
         "Learning-rate multiplier applied on each guard rollback."),
    Knob("FMT_RETRY_ATTEMPTS", "3", "int",
         "Transient-failure retry attempts (spill I/O, checkpoint, H2D)."),
    Knob("FMT_RETRY_BASE_S", "0.05", "float",
         "Base delay for jittered-exponential retry backoff, in seconds."),
    Knob("FMT_AGREE_TIMEOUT_S", "0", "float",
         "Dead-peer watchdog timeout for agree collectives (0 disables)."),
    Knob("FMT_PRESSURE", "1", "bool",
         "Allocator-OOM recovery (eviction, batch bisection, AIMD caps)."),
    Knob("FMT_PRESSURE_PROBE_S", "30", "float",
         "Seconds between AIMD up-probes of a pressure-lowered batch cap."),
    # -- serving robustness ----------------------------------------------
    Knob("FMT_SERVE_QUARANTINE", "1", "bool",
         "Input quarantine at the mapper boundary (bad rows side-tabled)."),
    Knob("FMT_SERVE_QUARANTINE_CAP", "10000", "int",
         "Max quarantined rows stored per side-table (counters stay exact)."),
    Knob("FMT_SERVE_BREAKER_THRESHOLD", "3", "int",
         "Consecutive dispatch failures that open a circuit breaker."),
    Knob("FMT_SERVE_BREAKER_COOLDOWN_S", "30", "float",
         "Seconds an open breaker waits before a half-open probe."),
    Knob("FMT_SERVE_DEADLINE_MS", "0", "float",
         "Per-dispatch deadline in ms; overruns count toward the breaker."),
    # -- serving runtime --------------------------------------------------
    Knob("FMT_SERVING_MAX_BATCH", "512", "int",
         "Rows per coalesced ModelServer dispatch (flush trigger 1)."),
    Knob("FMT_SERVING_MAX_WAIT_MS", "2.0", "float",
         "Oldest-request age that forces a dispatch flush (trigger 2)."),
    Knob("FMT_SERVING_QUEUE_CAP", "4096", "int",
         "Max queued rows before admission sheds (queue_full)."),
    Knob("FMT_SERVING_QUEUE_CAP_MB", "0", "float",
         "Max estimated queued megabytes before a memory_pressure shed."),
    Knob("FMT_SERVING_DEADLINE_MS", "0", "float",
         "Default per-request serving deadline in ms (0 = none)."),
    Knob("FMT_SERVING_SHED_ON_BREAKER", "1", "bool",
         "Refuse requests at the door while a circuit breaker is open."),
    # -- multi-tenant serving ---------------------------------------------
    Knob("FMT_TENANT_MAX_RESIDENT", "64", "int",
         "Max tenant models resident per server before LRU fault-out."),
    Knob("FMT_TENANT_QUOTA_ROWS", "0", "int",
         "Per-tenant queued-row quota before a tenant_quota shed (0=off)."),
    Knob("FMT_TENANT_MUX", "1", "bool",
         "Coalesce same-family tenants into one multiplexed fused dispatch."),
    # -- replica router ---------------------------------------------------
    Knob("FMT_ROUTER_REPLICAS", "2", "int",
         "Replica processes a ReplicaRouter spawns by default."),
    Knob("FMT_ROUTER_POLL_MS", "50", "float",
         "Router health-poll interval (readyz + metrics scrape) in ms."),
    Knob("FMT_ROUTER_QUEUE_CAP", "4096", "int",
         "Max queued rows at the router door before admission sheds."),
    Knob("FMT_ROUTER_DISPATCH_THREADS", "8", "int",
         "Concurrent router->replica dispatches (the forwarding pool)."),
    Knob("FMT_ROUTER_RETRIES", "2", "int",
         "Cross-replica retries per request before the caller sees the error."),
    Knob("FMT_ROUTER_SPAWN_TIMEOUT_S", "120", "float",
         "Seconds a replica subprocess gets to bind its endpoints at boot."),
    Knob("FMT_ROUTER_DRAIN_TIMEOUT_S", "30", "float",
         "Seconds a rolling deploy waits for one replica's in-flight work."),
    Knob("FMT_ROUTER_SCRAPE_STRIKES", "3", "int",
         "Consecutive failed scrapes before a live replica leaves rotation."),
    Knob("FMT_ROUTER_CRASHLOOP_MAX", "3", "int",
         "Replica deaths inside the crash-loop window that quarantine a slot."),
    Knob("FMT_ROUTER_CRASHLOOP_WINDOW_S", "30", "float",
         "Sliding window over one slot's deaths for crash-loop detection."),
    # -- fleet autoscaler -------------------------------------------------
    Knob("FMT_SCALE_MIN", "1", "int",
         "Lower fleet bound the autoscaler never shrinks below."),
    Knob("FMT_SCALE_MAX", "8", "int",
         "Upper fleet bound the autoscaler never grows past."),
    Knob("FMT_SCALE_UP_BURN", "1.0", "float",
         "Replica SLO burn rate at or above which the fleet scales up."),
    Knob("FMT_SCALE_DOWN_BURN", "0.5", "float",
         "Burn rate every replica must sit below before a scale-down."),
    Knob("FMT_SCALE_WINDOW_S", "30", "float",
         "Observation window for queue-growth and shed-rate up triggers."),
    Knob("FMT_SCALE_IDLE_WINDOWS", "3", "int",
         "Consecutive idle observation windows before one scale-down step."),
    Knob("FMT_SCALE_COOLDOWN_S", "60", "float",
         "Post-action cooldown before the autoscaler acts again."),
    Knob("FMT_SCALE_WARM_SPARES", "0", "int",
         "Warm spare replicas kept above target (preemption-aware mode)."),
    # -- continuous learning ----------------------------------------------
    Knob("FMT_LIFECYCLE_EVERY_WINDOWS", "8", "int",
         "Effective training windows between candidate checkpoints."),
    Knob("FMT_LIFECYCLE_REGRESSION_TOL", "0.02", "float",
         "Holdout-AUC regression a candidate may show vs the incumbent."),
    Knob("FMT_LIFECYCLE_SCORE_PSI", "0.25", "float",
         "Candidate-vs-incumbent holdout score PSI above which a swap blocks."),
    Knob("FMT_LIFECYCLE_PROBATION_S", "60", "float",
         "Post-swap probation window watching live SLO/drift burn."),
    Knob("FMT_LIFECYCLE_HISTORY", "3", "int",
         "Model versions the VersionManager retains for rollback."),
    Knob("FMT_LIFECYCLE_DIR", "", "str",
         "Default candidate-checkpoint directory for the lifecycle loop."),
    # -- device data plane ------------------------------------------------
    Knob("FMT_FUSE_TRANSFORM", "1", "bool",
         "Fuse kernel-capable pipeline stages into one dispatch per batch."),
    Knob("FMT_SERVE_MESH", "1", "bool",
         "SPMD fused serving over the mesh data axis (0 = one device)."),
    Knob("FMT_SERVE_CSR_PAD", "512", "int",
         "Per-shard nnz pad multiple for mesh-sharded segment-CSR serving."),
    Knob("FMT_FUSE_DONATE", "1", "bool",
         "Donate placed batch buffers to the fused serving dispatch."),
    Knob("FMT_SLAB_POOL", "1", "bool",
         "Cross-fit device slab pool for placed training batches."),
    Knob("FMT_SLAB_POOL_BUDGET_MB", "4096", "int",
         "Device-memory budget for the slab pool (LRU beyond it)."),
    Knob("FMT_SLAB_CHUNK_MB", "0", "int",
         "Chunk size for double-buffered cold placement (0 = one shot)."),
    Knob("FMT_SERVE_PALLAS", "0", "bool",
         "Pallas-fused serving kernel: scan+scale+score in one HBM pass."),
    Knob("FMT_SERVE_PALLAS_TILE", "512", "int",
         "Row-tile size for the Pallas serving kernel grid."),
    Knob("FMT_SERVE_PRECISION", "f32", "str",
         "Serving numeric precision: f32 (default), bf16, or int8."),
    # -- cold-start resilience --------------------------------------------
    Knob("FMT_COMPILE_CACHE", "", "str",
         "'off' disables the persistent XLA compile cache; the directory "
         "is JAX_COMPILATION_CACHE_DIR (default <repo>/.jax_cache)."),
    Knob("FMT_WARMSTART", "1", "bool",
         "Warm-artifact layer: persist AOT-serialized fused executables "
         "next to the model and load them before compiling."),
    Knob("FMT_WARM_DIR", "", "str",
         "Explicit warm-artifact store directory (default: warm_aot/ "
         "beside the deployed model artifact)."),
    Knob("FMT_WARM_LADDER_MAX", "4", "int",
         "Bucket-ladder rungs deploy() pre-warms off the hot path when a "
         "warm-artifact store is active (0 = live-sample shape only)."),
    Knob("FMT_WARM_CACHE_MB", "512", "int",
         "On-disk budget for the warm-artifact store; GC evicts stale "
         "fingerprints first, then oldest entries."),
)

_BY_NAME: Dict[str, Knob] = {k.name: k for k in DECLARATIONS}

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def declared() -> Dict[str, Knob]:
    """Name -> :class:`Knob` view of every declaration."""
    return dict(_BY_NAME)


def get(name: str) -> Knob:
    """The declaration for ``name`` (KeyError names the missing knob)."""
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(
            f"undeclared knob {name!r}: every FMT_* environment variable "
            f"must be declared in flink_ml_tpu/utils/knobs.py"
        ) from None


def raw(name: str) -> Optional[str]:
    """The environment value of a declared knob, verbatim (None=unset).

    The one ``os.environ`` read of an ``FMT_*`` name in the package —
    everything else routes through here so the KNOB001 rule can hold.
    """
    get(name)  # undeclared names must fail loudly, not read silently
    return os.environ.get(name)


def knob_str(name: str) -> str:
    """String knob: the raw value, or the declared default when unset."""
    value = raw(name)
    return value if value is not None else get(name).default


def knob_bool(name: str) -> bool:
    """Bool knob with default-biased parsing (see module docstring)."""
    knob = get(name)
    value = (os.environ.get(name) or "").strip()
    if value == "":
        value = knob.default
    default_on = knob.default.lower() in _TRUTHY
    if default_on:
        return value.lower() not in _FALSY
    return value.lower() in _TRUTHY


def knob_int(name: str) -> int:
    """Int knob; unset/empty/unparsable values take the declared default.
    Float-form values (``8192.0``, ``1e4``) truncate, matching the
    historical ``int(_env_float(...))`` parsing at the serving sites."""
    knob = get(name)
    value = os.environ.get(name, "").strip()
    try:
        return int(float(value)) if value else int(float(knob.default))
    except ValueError:
        return int(float(knob.default))


def knob_float(name: str) -> float:
    """Float knob; unset/empty/unparsable values take the declared default."""
    knob = get(name)
    value = os.environ.get(name, "").strip()
    try:
        return float(value) if value else float(knob.default)
    except ValueError:
        return float(knob.default)
