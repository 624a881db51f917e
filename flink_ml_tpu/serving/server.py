"""ModelServer — the dynamic micro-batching request runtime.

Every inference path below this layer is table-at-a-time: PR 6 made one
fused dispatch per batch nearly optimal, but a production feed is not a
batch — it is thousands of concurrent single-row requests, each of which
would pay its own dispatch through ``transform``.  This server is the
layer that FILLS those fused batches from small requests (the Clipper-
style adaptive-batching frontend, specialized to our fused plans,
circuit breakers, and integrity-checked model files):

* ``submit(table)`` returns a ``concurrent.futures.Future`` immediately;
  requests land in a bounded queue and a dispatcher thread coalesces them
  into ONE ``PipelineModel.transform`` call — flushed when
  ``FMT_SERVING_MAX_BATCH`` rows are queued or the oldest request has
  waited ``FMT_SERVING_MAX_WAIT_MS``, whichever first.  The transform
  pads to the shared batch-shape ladder
  (``utils/compile_cache.bucket_batch_rows``), so mixed request sizes
  reuse a handful of compiled programs instead of compiling per size;
* outputs — and quarantine side-tables — demultiplex back to each caller
  with request-local row offsets (``batcher.demux``): a caller's result
  is bit-identical to a solo ``transform`` of its rows;
* admission control sheds instead of melting: queue at its row cap ->
  expired requests shed first, then ``queue_full`` rejection; a request
  past its deadline is shed, never served late; an OPEN circuit breaker
  sheds at the door (``breaker_open``) rather than queueing onto a dead
  device (``serve.open_breaker_names``);
* ``deploy(path, version)`` hot-swaps the model with zero downtime
  (``versioning.VersionManager``): integrity-verified load, pre-warm off
  the hot path, atomic pointer swap — in-flight batches finish on the old
  version, and a corrupt deploy leaves the old version serving;
* ``shutdown(drain=True)`` serves everything already queued, then joins
  the dispatcher; ``drain=False`` fails queued futures with a
  ``shutdown`` shed code.

Telemetry: ``serving.requests`` / ``request_rows`` / ``batches`` /
``served_rows`` / ``shed`` (+ per-reason) / ``failed_requests`` /
``swaps`` / ``deploy_failures`` counters, ``serving.queue_depth`` and
``serving.batch_occupancy`` gauges, and the ``serving.request_latency_ms``
histogram (p50/p99 via the registry's timing quantiles) — all landing in
a ``serving`` RunReport at shutdown.

Tracing (ISSUE 8, ``FMT_TRACE``): every submit mints a per-request
``trace_id`` (head-sampled via ``FMT_TRACE_SAMPLE``); the dispatcher
hands the context across its thread explicitly, so one request renders
as one ``submit -> queue_wait -> coalesce -> transform -> demux``
waterfall (``python -m flink_ml_tpu.obs trace``), sheds stamp the
``trace_id`` into ``ServerOverloadedError`` and the flight-recorder
ring, and quarantined rows carry it in their side-table.

Memory pressure (ISSUE 9, round 12): admission also enforces a
bytes-denominated budget — ``FMT_SERVING_QUEUE_CAP_MB`` (estimated from
each request's schema row width) sheds with the ``memory_pressure``
reason before the queue's memory footprint can grow past what the
device budget could ever serve — and the dispatcher recovers from
allocator OOM by splitting the coalesced batch at request boundaries
(bit-identical per-caller results), with the ``serving.batch`` pressure
state capping subsequent coalescing until the AIMD probe restores full
batches.

Live telemetry (ISSUE 10, ``FMT_TELEMETRY_PORT`` / the
``telemetry_port`` argument): the server brings up an embedded
OpenMetrics endpoint (``/metrics`` / ``/healthz`` / ``/readyz`` /
``/statusz``) and the SLO burn-rate monitor with its lifecycle —
``/readyz`` degrades reason-coded on open breakers, pressure caps,
deploys in progress, a saturating queue, and burning SLOs
(:mod:`flink_ml_tpu.obs.telemetry` / :mod:`flink_ml_tpu.obs.slo`).

Data drift (ISSUE 11, ``FMT_DRIFT`` / the ``drift`` argument): the
server arms a :class:`~flink_ml_tpu.obs.drift.DriftMonitor` whose
reference distribution snapshots at deploy (persisted next to a
path-deployed model, reset by redeploys), taps input features at the
quarantine boundary and output scores at demux, and feeds the third
(``drift``) SLO — ``slo.burning.drift``, a reason-coded ``drift``
``/readyz`` entry, per-column ``/statusz``, and ``drift_breach``
black boxes.

Knobs (README.md, "Online serving" and after): ``FMT_SERVING_MAX_BATCH``,
``FMT_SERVING_MAX_WAIT_MS``, ``FMT_SERVING_QUEUE_CAP``,
``FMT_SERVING_QUEUE_CAP_MB``, ``FMT_SERVING_DEADLINE_MS``,
``FMT_SERVING_SHED_ON_BREAKER``, ``FMT_TELEMETRY_PORT``,
``FMT_SLO_WINDOW_S``, ``FMT_SLO_P99_MS``, ``FMT_SLO_ERR_RATIO``,
``FMT_DRIFT``, ``FMT_DRIFT_REF_ROWS``, ``FMT_DRIFT_PSI``,
``FMT_DRIFT_WINDOW_S``.
"""

from __future__ import annotations

import os
import threading
import time
from collections import Counter, deque
from concurrent.futures import Future
from typing import Deque, List, Optional

from flink_ml_tpu import obs
from flink_ml_tpu.fault import pressure
from flink_ml_tpu.serving.admission import (
    ServingConfig,
    now_s,
    overloaded,
    shed,
)
from flink_ml_tpu.serving.batcher import (
    ServeRequest,
    ServeResult,
    coalesce,
    demux,
)
from flink_ml_tpu.serving.errors import (
    SHED_BREAKER_OPEN,
    SHED_DEADLINE,
    SHED_MEMORY_PRESSURE,
    SHED_QUEUE_FULL,
    SHED_SHUTDOWN,
    SHED_TENANT_QUOTA,
    ServerClosedError,
)
from flink_ml_tpu.serving.tenants import (
    DEFAULT_TENANT,
    TenantRegistry,
    validate_tenant_key,
)
from flink_ml_tpu.serving.versioning import VersionManager
from flink_ml_tpu.table.table import Table

__all__ = ["ModelServer"]

#: rows retained from the newest coalesced batch as the default warmup
#: sample for the next deploy (enough to exercise the plan, cheap to hold)
_WARMUP_SAMPLE_ROWS = 8

#: the dispatcher's memory-pressure surface (ISSUE 9): an allocator OOM
#: from a coalesced transform splits the batch at a request boundary and
#: caps subsequent coalescing here until the AIMD probe recovers
_SERVING_SURFACE = "serving.batch"


def _breaker_scope_names(model) -> frozenset:
    """The breaker names this model's transforms can dispatch through:
    its stages' serving telemetry keys (mapper ``serve_name`` defaults to
    the model stage's class name).  Scopes the shed-on-breaker admission
    check so an unrelated pipeline's open breaker — another server in the
    same process, a batch job's mapper — never sheds THIS server's
    traffic.  A custom mapper overriding ``serve_name`` beyond its class
    name falls outside the scope and simply never sheds at admission
    (fail-open: the transform path's own breaker/fallback still applies).
    """
    stages = getattr(model, "stages", None)
    if stages is None:
        stages = [model]
    return frozenset(type(s).__name__ for s in stages)


def _breaker_in_scope(name: str, scope: frozenset) -> bool:
    """Does an open breaker belong to one of this server's dispatch
    surfaces?  Per-mapper breakers match by name; per-plan breakers
    (``FusedPlan[A+B+...]``) match when every fused member is one of the
    server's stages."""
    if name in scope:
        return True
    if name.startswith("FusedPlan[") and name.endswith("]"):
        members = name[len("FusedPlan["):-1].split("+")
        return all(m in scope for m in members)
    return False


def _transform_one(model, table: Table) -> Table:
    """One model's 1-in/1-out serving transform (the ``ModelVersion.
    transform`` tuple-unwrap, for tenant models that carry no version
    wrapper)."""
    out = model.transform(table)
    (result,) = out if isinstance(out, tuple) else (out,)
    return result


def _warmstart_status() -> dict:
    """The /statusz warmstart section: the active warm-artifact store (or
    None when the layer is inert) and its sealed-manifest coverage."""
    from flink_ml_tpu.serving import warmstart

    store = warmstart.active()
    if store is None:
        return {"store": None}
    return {
        "store": store.root,
        "fingerprint": store.fingerprint,
        "manifest_entries": len(store.manifest().get("entries", {})),
    }


class ModelServer:
    """Request-level model server over a deployed pipeline.

    ``ModelServer(model)`` (or ``ModelServer(path=...)``) deploys version
    ``v1`` and starts the dispatcher; use as a context manager or call
    :meth:`shutdown` explicitly.  ``start=False`` builds the server
    paused — submissions queue (admission rules apply) until
    :meth:`start`, which tests and pre-loading setups use.
    """

    def __init__(self, model=None, *, path: Optional[str] = None,
                 version: str = "v1", warmup: Optional[Table] = None,
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 queue_cap: Optional[int] = None,
                 queue_cap_mb: Optional[float] = None,
                 deadline_ms: Optional[float] = None,
                 shed_on_breaker: Optional[bool] = None,
                 telemetry_port: Optional[int] = None,
                 drift: Optional[bool] = None,
                 tenants: Optional[str] = None,
                 start: bool = True):
        if (model is None) == (path is None):
            raise ValueError("pass exactly one of model / path")
        self.config = ServingConfig.from_env(
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            queue_cap=queue_cap, queue_cap_mb=queue_cap_mb,
            deadline_ms=deadline_ms,
            shed_on_breaker=shed_on_breaker,
        )
        # mesh-aware coalescing (ISSUE 15): the transform below shards
        # every fused dispatch over the mesh's data axis, so a full flush
        # should feed EVERY device — the knob-default coalescing target
        # scales to mesh_size x FMT_SERVING_MAX_BATCH.  An explicit
        # max_batch argument is the caller's number and stays verbatim.
        self._mesh_devices = self._serving_mesh_width()
        if max_batch is None and self._mesh_devices > 1:
            import dataclasses

            self.config = dataclasses.replace(
                self.config,
                max_batch=self.config.max_batch * self._mesh_devices,
            )
        # a coalesced dispatch must stay a SINGLE internal transform batch:
        # past the environment batch size the fused path switches to its
        # prefetch-producer thread, which the dispatcher's thread-local
        # quarantine capture cannot see — demux would lose side-tables.
        # Clamp rather than fail: the operator asked for bigger batches
        # than the pipeline will form anyway.
        limit = self._single_batch_rows()
        if limit and self.config.max_batch > limit:
            import dataclasses
            import warnings

            warnings.warn(
                f"FMT_SERVING_MAX_BATCH={self.config.max_batch} exceeds "
                f"the environment batch size ({limit}); clamping — a "
                "coalesced dispatch must stay one internal transform "
                "batch for quarantine demux to see its side-tables",
                stacklevel=2,
            )
            self.config = dataclasses.replace(self.config, max_batch=limit)
        self._versions = VersionManager()
        deployed = self._versions.deploy(
            model if model is not None else path, version, warmup=warmup
        )
        self._breaker_scope = _breaker_scope_names(deployed.model)
        self._warmup_sample: Optional[Table] = warmup
        self._cond = threading.Condition()
        self._queue: Deque[ServeRequest] = deque()
        self._queued_rows = 0
        self._queued_bytes = 0
        self._stopping = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # per-server accounting: stats()/the shutdown report must describe
        # THIS server's traffic — the process-global serving.* counters
        # and latency histogram aggregate across every server (and test)
        # in the process, so each server tallies its own events alongside
        self._counts: Counter = Counter()
        self._counts_lock = threading.Lock()
        self._latencies: Deque[float] = deque(maxlen=512)
        # multi-tenant serving (ISSUE 20): the tenant-keyed model
        # registry (LRU-resident over the slab pool) plus per-tenant
        # queued-row accounting for the FMT_TENANT_QUOTA_ROWS admission
        # quota (guarded by self._cond like every other queue stat).  A
        # path deploy auto-registers every subdirectory of
        # <path>/tenants/ (or the explicit ``tenants`` directory) — the
        # replica convention: lay models out next to the default one.
        self._tenants = TenantRegistry(tally=self._tally)
        self._tenant_queued: Counter = Counter()
        tenant_dir = tenants if tenants is not None else (
            os.path.join(path, "tenants") if path is not None else None
        )
        if tenant_dir is not None and os.path.isdir(tenant_dir):
            for name in sorted(os.listdir(tenant_dir)):
                p = os.path.join(tenant_dir, name)
                if os.path.isdir(p):
                    self._tenants.register(name, p)
        # open-breaker admission memo (the scan locks every breaker in
        # the process): revalidated on any breaker state TRANSITION (the
        # generation counter — an opening breaker sheds immediately) or
        # after ~50 ms (a cooldown EXPIRING fires no transition)
        self._breaker_memo = (float("-inf"), -1, [])
        # data-plane drift monitor (ISSUE 11, FMT_DRIFT / the drift
        # argument): reference snapshotted at deploy — reloaded from the
        # model dir's persisted baseline when one exists — live window
        # tapped per coalesced batch; feeds the third SLO below
        self._drift = None
        self._drift_status_key: Optional[str] = None
        from flink_ml_tpu.obs import drift as _drift_mod

        drift_on = _drift_mod.enabled() if drift is None else bool(drift)
        if drift_on:
            self._drift = self._make_drift_monitor(deployed)
        # live telemetry plane (ISSUE 10): the endpoint + SLO monitor
        # come up with the server — even a paused (start=False) server
        # is scrapeable, and its saturated queue shows in /readyz
        self._telemetry = None
        self._slo = None
        self._status_key: Optional[str] = None
        self._mesh_status_key: Optional[str] = None
        from flink_ml_tpu.obs import telemetry as _telemetry_mod

        port = (telemetry_port if telemetry_port is not None
                else _telemetry_mod.env_port())
        if port is not None:
            self._start_telemetry(port)
        elif self._drift is not None:
            # no endpoint, but drift is armed: the SLO monitor still
            # samples so slo.burning.drift flips and /readyz (from some
            # other process surface) can consume it
            from flink_ml_tpu.obs import slo as slo_mod

            self._slo = slo_mod.SLOMonitor(drift=self._drift).start()
        if start:
            self.start()

    def _tally(self, name: str, n: float = 1) -> None:
        """Per-server tally only — the matching global counter is bumped
        where the event happens (obs.counter_add here, or the admission
        shed helpers), so neither side double-counts.  Own lock: submit
        threads and the dispatcher tally concurrently, and a lost
        increment would fail the exact-count assertions reports rely on."""
        with self._counts_lock:
            self._counts[name] += n

    def _shed(self, request: ServeRequest, reason: str,
              detail: str = "") -> None:
        """Shed one queued request: per-server tally + the counted,
        reason-coded future rejection (admission.shed).  Never call while
        holding ``self._cond``."""
        self._tally("serving.shed")
        self._tally(f"serving.shed.{reason}")
        shed(request, reason, detail)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ModelServer":
        with self._cond:
            if self._closed:
                raise ServerClosedError("server already shut down")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="fmt-serving-dispatcher",
                    daemon=True,
                )
                self._thread.start()
        return self

    @property
    def running(self) -> bool:
        with self._cond:  # reentrant: _cond wraps an RLock
            thread = self._thread
        return thread is not None and thread.is_alive()

    def __enter__(self) -> "ModelServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.shutdown()
        return False

    def shutdown(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the server.  ``drain=True`` serves every queued request
        first (their futures resolve normally); ``drain=False`` sheds the
        queue with the ``shutdown`` reason code.  Idempotent."""
        dropped: List[ServeRequest] = []
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stopping = True
            if not drain:
                dropped = list(self._queue)
                self._queue.clear()
                self._queued_rows = 0
                self._queued_bytes = 0
                self._tenant_queued.clear()
            thread = self._thread  # join OUTSIDE the lock, on a stable ref
            self._cond.notify_all()
        for r in dropped:  # complete futures outside the lock
            self._shed(r, SHED_SHUTDOWN, "server shut down without draining")
        self._tenants.close()  # detach the pool eviction listener
        if thread is not None:
            thread.join(timeout=timeout)
        elif drain:
            # never started: drain inline on the calling thread so queued
            # futures still resolve (submit-before-start is supported)
            while True:
                batch = self._next_batch()
                if batch is None:
                    break
                self._serve_batch(batch)
        self._stop_telemetry()
        self._write_report()

    # -- data-plane drift (ISSUE 11) -----------------------------------------

    @property
    def drift_monitor(self):
        """This server's :class:`~flink_ml_tpu.obs.drift.DriftMonitor`
        (None when drift is off)."""
        return self._drift

    def _make_drift_monitor(self, deployed):
        """The deploy-time reference contract: a path deploy whose model
        dir holds a persisted ``drift_reference.json`` restarts with its
        committed baseline; anything else starts snapshotting a fresh
        one from the pre-warm sample + the first ``FMT_DRIFT_REF_ROWS``
        live rows (persisted back to the model dir once frozen, so the
        NEXT restart keeps it).  A corrupt persisted baseline warns and
        re-learns — drift is advisory telemetry, and refusing to serve
        over it would invert the severity."""
        import warnings

        from flink_ml_tpu.obs import drift as drift_mod

        source = deployed.source_path
        monitor = drift_mod.DriftMonitor(name="serving",
                                         persist_path=source)
        if source:
            try:
                monitor.load_reference(source)
            except Exception as exc:  # noqa: BLE001 - advisory, see above
                warnings.warn(
                    f"persisted drift reference under {source!r} is "
                    f"unusable ({type(exc).__name__}: {exc}); re-learning "
                    "a baseline from live traffic",
                    RuntimeWarning, stacklevel=3,
                )
                obs.flight.record("drift.reference_corrupt",
                                  source=source,
                                  error=type(exc).__name__)
        if not monitor.reference_complete and self._warmup_sample is not None:
            monitor.bootstrap(self._warmup_sample)
        return monitor

    def _reset_drift_for(self, deployed, warmup: Optional[Table]) -> None:
        """Redeploy semantics: the new version serves a (possibly
        intentionally different) population, so the baseline resets —
        unless the NEW model dir already carries its own persisted
        reference, which is the restart/rollback case and wins."""
        import warnings

        monitor = self._drift
        if monitor is None:
            return
        source = deployed.source_path
        if source:
            try:
                if monitor.load_reference(source):
                    return
            except Exception as exc:  # noqa: BLE001
                warnings.warn(
                    f"persisted drift reference under {source!r} is "
                    f"unusable ({type(exc).__name__}); re-learning",
                    RuntimeWarning, stacklevel=3,
                )
        monitor.reset_reference(persist_path=source, warmup=warmup)

    # -- live telemetry plane (ISSUE 10) -------------------------------------

    @property
    def telemetry(self):
        """This server's :class:`~flink_ml_tpu.obs.telemetry.
        TelemetryServer` (None when telemetry is off or failed to bind)."""
        return self._telemetry

    @property
    def telemetry_address(self) -> Optional[str]:
        """The BOUND ``host:port`` of this server's telemetry endpoint
        (None when telemetry is off or failed to bind) — with an
        ephemeral ``telemetry_port=0`` this is where the listener
        actually landed, the address ``FMT_TELEMETRY_PORT_FILE``
        publishes for out-of-process discovery (ISSUE 13)."""
        t = self._telemetry
        if t is None or t.port is None:
            return None
        return f"{t.host}:{t.port}"

    def _start_telemetry(self, port: int) -> None:
        """Bring up the /metrics endpoint + SLO monitor and plug this
        server's readiness/status into them.  A bind failure warns and
        leaves the server serving — telemetry must never take down the
        traffic it observes."""
        import warnings

        from flink_ml_tpu.obs import slo as slo_mod
        from flink_ml_tpu.obs import telemetry as telemetry_mod

        try:
            self._telemetry = telemetry_mod.TelemetryServer(
                port=port).start()
        except OSError as exc:
            warnings.warn(
                f"telemetry endpoint failed to bind port {port}: {exc}; "
                "serving continues without /metrics",
                RuntimeWarning, stacklevel=3,
            )
            self._telemetry = None
            return
        telemetry_mod.register_readiness(self._readiness_reasons)
        self._status_key = telemetry_mod.register_status(
            "server", self._telemetry_status)
        if self._drift is not None:
            # /statusz gains the per-column drift section
            self._drift_status_key = telemetry_mod.register_status(
                "drift", self._drift.status)
        if self._mesh_devices > 1:
            # /statusz gains the per-device row-share breakdown of the
            # SPMD fused dispatches this server's transforms run
            from flink_ml_tpu.common import fused as fused_mod

            self._mesh_status_key = telemetry_mod.register_status(
                "mesh", fused_mod.mesh_status)
        self._slo = slo_mod.SLOMonitor(drift=self._drift).start()

    def _stop_telemetry(self) -> None:
        if self._slo is not None:
            self._slo.stop()
            self._slo = None
        if self._telemetry is not None:
            from flink_ml_tpu.obs import telemetry as telemetry_mod

            telemetry_mod.unregister_readiness(self._readiness_reasons)
            if self._status_key is not None:
                telemetry_mod.unregister_status(self._status_key)
                self._status_key = None
            if self._drift_status_key is not None:
                telemetry_mod.unregister_status(self._drift_status_key)
                self._drift_status_key = None
            if self._mesh_status_key is not None:
                telemetry_mod.unregister_status(self._mesh_status_key)
                self._mesh_status_key = None
            self._telemetry.stop()
            self._telemetry = None
        if self._drift is not None:
            self._drift.close()

    def _readiness_reasons(self) -> List[dict]:
        """This server's /readyz feed: a deploy mid-flight and a
        saturating queue both mean "stop routing here" BEFORE admission
        starts shedding.  Plain int reads — no lock: readiness is a
        heuristic probe, and a stale-by-one row count cannot matter."""
        from flink_ml_tpu.obs import telemetry as telemetry_mod

        reasons: List[dict] = []
        if self._versions.deploy_in_progress:
            reasons.append({
                "reason": "deploy_in_progress",
                "detail": f"deploying over {self.active_version!r}",
            })
        cap = self.config.queue_cap
        saturated_at = max(1, int(cap * telemetry_mod.
                                  queue_saturation_frac()))
        if self._queued_rows >= saturated_at:
            reasons.append({
                "reason": "queue_saturated",
                "detail": (f"{self._queued_rows} of {cap} queue-cap rows "
                           f"queued (saturation at {saturated_at})"),
            })
        return reasons

    def _telemetry_status(self) -> dict:
        """This server's /statusz contribution."""
        from flink_ml_tpu.common.fused import (
            serve_pallas_enabled, serve_precision,
        )

        with self._cond:
            queued_rows = self._queued_rows
        return {
            "active_version": self.active_version,
            "versions": self.versions,
            "running": self.running,
            "deploy_in_progress": self._versions.deploy_in_progress,
            "queued_rows": queued_rows,
            "queue_cap": self.config.queue_cap,
            "max_batch": self.config.max_batch,
            # the data plane's numeric contract (ISSUE 17): the router
            # surfaces each replica's serving precision and whether the
            # Pallas hot path is requested — an operator diffing replica
            # scores needs to see a precision split before anything else
            "precision": serve_precision(),
            "pallas": serve_pallas_enabled(),
            # cold-start resilience (ISSUE 18): which warm-artifact store
            # this replica serves from, and how much of the ladder its
            # manifest says is already warm — the router's rollup makes a
            # cold respawn visible before its first slow request would
            "warmstart": _warmstart_status(),
            # multi-tenant plane (ISSUE 20): registered/resident tenant
            # counts, the residency cap and quota, and the top-N-by-
            # traffic tenant table (requests/rows/sheds/cold-loads/
            # evictions per tenant)
            "tenants": self._tenants.status(),
            "stats": self.stats(),
        }

    # -- the request path ----------------------------------------------------

    def submit(self, table: Table,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one request; returns a Future resolving to a
        :class:`~flink_ml_tpu.serving.batcher.ServeResult`.

        ``tenant`` routes the rows to a registered tenant model (ISSUE
        20); None — the wire-compatible default — serves the deployed
        version exactly as before.  A malformed or unregistered tenant
        key raises ``ValueError`` at the door (a caller bug, never a
        shed); a tenant past its ``FMT_TENANT_QUOTA_ROWS`` queued-row
        quota sheds reason-coded ``tenant_quota``.

        Raises :class:`ServerClosedError` when the server is shut down and
        :class:`ServerOverloadedError` (reason-coded) when the request is
        shed at admission: the queue is at ``queue_cap`` rows even after
        shedding expired entries, or a circuit breaker is open and
        ``shed_on_breaker`` is on.
        """
        n = table.num_rows()
        if n == 0:
            raise ValueError("empty request: submit at least one row")
        if tenant is None:
            tenant = DEFAULT_TENANT
        else:
            validate_tenant_key(tenant)
            if tenant != DEFAULT_TENANT and not self._tenants.known(tenant):
                raise ValueError(
                    f"unknown tenant {tenant!r}: register_tenant() it "
                    "before submitting its traffic"
                )
        limit = self._single_batch_rows()
        if limit and n > limit:
            raise ValueError(
                f"request of {n} rows exceeds the environment batch size "
                f"({limit}); a request that large is a table, not a "
                "request — call transform directly"
            )
        # the request's trace root (None when tracing is off / sampled
        # out): minted HERE so even a synchronous admission shed carries
        # a trace_id, and every downstream hop parents under one context
        t_submit = time.perf_counter()
        req_trace = obs.trace.start_request(
            "serving.request", {"rows": n, "tenant": tenant}
        )
        trace_id = req_trace.trace_id if req_trace is not None else None
        # breaker admission reads no queue state: check it OUTSIDE the
        # condition lock so every submit doesn't serialize a scan of all
        # breakers against the dispatcher's wakeups.  Only breakers on
        # THIS server's dispatch surfaces count — another pipeline's dead
        # device must not shed a healthy server's traffic.
        if self.config.shed_on_breaker:
            open_names = self._open_scoped_breakers()
            if open_names:
                self._tally("serving.shed")
                self._tally(f"serving.shed.{SHED_BREAKER_OPEN}")
                self._tenants.note_shed(tenant)
                if req_trace is not None:
                    req_trace.end(status="shed", attrs={
                        "shed_reason": SHED_BREAKER_OPEN,
                        "breaker": open_names[0],
                    })
                raise overloaded(
                    SHED_BREAKER_OPEN,
                    f"circuit breaker open for {open_names[0]!r} — "
                    "refusing to queue onto a degraded dispatch path",
                    trace_id=trace_id,
                )
        now = now_s()
        request = ServeRequest(
            table=table, future=Future(), enqueued_at=now,
            deadline_at=self.config.deadline_at(now, deadline_ms),
            trace=req_trace, tenant=tenant,
        )
        quota = self._tenants.quota_rows()
        cap_bytes = self.config.queue_cap_bytes
        expired: List[ServeRequest] = []
        rejected = None
        try:
            with self._cond:
                if self._closed or self._stopping:
                    if req_trace is not None:
                        req_trace.end(status="error",
                                      attrs={"error": "ServerClosedError"})
                    raise ServerClosedError("server is shut down")
                if self._queued_rows + n > self.config.queue_cap or (
                    cap_bytes
                    and self._queued_bytes + request.n_bytes > cap_bytes
                ):
                    # make room by shedding what can no longer be served
                    # in time — oldest first (FIFO order IS age order)
                    expired = self._collect_expired_locked(now)
                if self._queued_rows + n > self.config.queue_cap:
                    rejected = (SHED_QUEUE_FULL, (
                        f"{self._queued_rows} rows queued against a cap "
                        f"of {self.config.queue_cap} (request adds {n})"
                    ))
                elif (cap_bytes
                      and self._queued_bytes + request.n_bytes > cap_bytes):
                    # the rows fit but the BYTES don't: the queue's
                    # estimated memory footprint would exceed the HBM
                    # admission budget (FMT_SERVING_QUEUE_CAP_MB)
                    rejected = (SHED_MEMORY_PRESSURE, (
                        f"{self._queued_bytes} estimated bytes queued "
                        f"against a cap of {cap_bytes} (request adds "
                        f"{request.n_bytes})"
                    ))
                elif quota and self._tenant_queued[tenant] + n > quota:
                    # per-tenant fair-share door (ISSUE 20): ONE hot
                    # tenant's backlog sheds against its own quota, not
                    # against its batch-mates' shared queue cap
                    rejected = (SHED_TENANT_QUOTA, (
                        f"tenant {tenant!r} has "
                        f"{self._tenant_queued[tenant]} rows queued "
                        f"against a quota of {quota} (request adds {n})"
                    ))
                else:
                    self._queue.append(request)
                    self._queued_rows += n
                    self._tenant_queued[tenant] += n
                    obs.gauge_set("serving.queue_depth", self._queued_rows)
                    if cap_bytes:
                        self._queued_bytes += request.n_bytes
                        obs.gauge_set("serving.queue_bytes",
                                      self._queued_bytes)
                    self._cond.notify()
        finally:
            # futures complete OUTSIDE the lock: done-callbacks may touch
            # the server (shed-retry submits) and must not re-enter
            for r in expired:
                self._shed(r, SHED_DEADLINE, "deadline passed while queued")
        if rejected is not None:
            reason, detail = rejected
            self._tally("serving.shed")
            self._tally(f"serving.shed.{reason}")
            self._tenants.note_shed(tenant)
            if req_trace is not None:
                req_trace.end(status="shed",
                              attrs={"shed_reason": reason,
                                     "tenant": tenant})
            raise overloaded(reason, detail, trace_id=trace_id)
        if req_trace is not None:
            # the admission + enqueue window, on the caller thread
            obs.trace.record_span(
                (req_trace.ctx,), "submit",
                time.perf_counter() - t_submit, {"rows": n},
            )
        self._tally("serving.requests")
        self._tally("serving.request_rows", n)
        obs.counter_add("serving.requests")
        obs.counter_add("serving.request_rows", n)
        self._tenants.note_request(tenant, n)
        return request.future

    def predict(self, table: Table, deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None,
                tenant: Optional[str] = None) -> ServeResult:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(
            table, deadline_ms=deadline_ms, tenant=tenant
        ).result(timeout)

    def register_tenant(self, tenant: str, source,
                        version: str = "v1") -> None:
        """Bind ``tenant`` to a saved-model directory (or an in-memory
        model).  Registration is metadata-only — the model faults in on
        the tenant's first request (LRU-resident over the slab pool,
        evicted under pressure, re-faulted in milliseconds off the
        warm-artifact store).  See :mod:`flink_ml_tpu.serving.tenants`."""
        self._tenants.register(tenant, source, version=version)

    @property
    def tenants(self) -> List[str]:
        """Registered tenant keys (the default tenant not included)."""
        return [t for t in self._tenants.tenants() if t != DEFAULT_TENANT]

    def _open_scoped_breakers(self) -> List[str]:
        """Open breakers on THIS server's dispatch surfaces, memoized:
        the registry scan locks every breaker in the process, so the
        admission hot path reuses the last answer until a breaker state
        TRANSITION bumps the generation counter (a breaker opening sheds
        the very next submit) or ~50 ms pass (a cooldown expiring fires
        no transition, so traffic resumes within the window)."""
        from flink_ml_tpu.serve import open_breaker_names
        from flink_ml_tpu.serve.breaker import state_generation

        now = now_s()
        gen = state_generation()
        stamp, memo_gen, names = self._breaker_memo
        if gen == memo_gen and now - stamp < 0.05:
            return names
        names = [
            b for b in open_breaker_names()
            if _breaker_in_scope(b, self._breaker_scope)
        ]
        self._breaker_memo = (now, gen, names)
        return names

    # -- hot swap ------------------------------------------------------------

    def deploy(self, model_or_path, version: str,
               warmup: Optional[Table] = None):
        """Hot-swap to a new model version with zero downtime.

        Runs on the CALLING thread: load + integrity verification + plan
        pre-warm happen while the dispatcher keeps serving the old
        version; only the final pointer swap is shared state.  ``warmup``
        defaults to a sample retained from live traffic (the last batch's
        head) so mid-traffic deploys warm the exact request schema.
        Raises on a failed deploy (corrupt artifact, broken transform) —
        the old version never stops serving.
        """
        if warmup is None:
            warmup = self._warmup_sample
        try:
            deployed = self._versions.deploy(model_or_path, version,
                                             warmup=warmup)
        except BaseException:
            self._tally("serving.deploy_failures")
            raise
        self._tally("serving.swaps")
        self._breaker_scope = _breaker_scope_names(deployed.model)
        # drift reference reset (ISSUE 11): the new version's population
        # is the new normal — unless its model dir carries a persisted
        # baseline (restart/rollback), which is reloaded instead
        self._reset_drift_for(deployed, warmup)
        return deployed

    def rollback(self, warmup: Optional[Table] = None):
        """Redeploy the previous retained version through the same
        integrity-verified swap path as :meth:`deploy` (ISSUE 14) — the
        continuous-learning controller's answer to a post-swap SLO/drift
        breach, and an operator's big red button.  The drift baseline
        follows the rollback: the restored version's model dir usually
        carries its persisted reference, which wins over re-learning."""
        if warmup is None:
            warmup = self._warmup_sample
        deployed = self._versions.rollback(warmup=warmup)
        self._tally("serving.rollbacks")
        self._breaker_scope = _breaker_scope_names(deployed.model)
        self._reset_drift_for(deployed, warmup)
        return deployed

    @property
    def active_version(self) -> Optional[str]:
        return self._versions.active_version

    @property
    def active_model(self):
        """The model object currently serving (the active version's)."""
        return self._versions.active().model

    @property
    def previous_version(self) -> Optional[str]:
        """Label a :meth:`rollback` would reactivate (None when no
        previous version is retained)."""
        return self._versions.previous_version

    @property
    def versions(self) -> List[str]:
        return self._versions.history

    @property
    def slo_monitor(self):
        """This server's :class:`~flink_ml_tpu.obs.slo.SLOMonitor` (None
        when neither telemetry nor drift armed one) — the burn-rate
        signal the continuous-learning probation window watches."""
        return self._slo

    # -- dispatcher ----------------------------------------------------------

    def _loop(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            self._serve_batch(batch)

    def _next_batch(self) -> Optional[List[ServeRequest]]:
        """Block until a flush condition holds, then take one batch.

        Flush when: queued rows >= ``max_batch``; OR the oldest request
        has waited ``max_wait_ms``; OR the server is draining.  Expired
        requests shed here too — a request that died waiting must not
        consume device time.  Their futures complete OUTSIDE the lock
        (the ``try``'s ``finally`` runs after the ``with`` releases it):
        a caller's done-callback may touch the server and must not
        re-enter under the lock mid-queue-iteration."""
        cfg = self.config
        while True:
            expired: List[ServeRequest] = []
            cancelled: List = []  # RequestTraces of drops, ended unlocked
            try:
                with self._cond:
                    while True:
                        now = now_s()
                        expired.extend(self._collect_expired_locked(now))
                        if self._queue:
                            flush_at = (
                                self._queue[0].enqueued_at + cfg.max_wait_s
                            )
                            if (
                                self._queued_rows >= cfg.max_batch
                                or now >= flush_at
                                or self._stopping
                            ):
                                return self._take_locked(cancelled)
                            if expired:
                                break  # shed first, then come back
                            self._cond.wait(timeout=flush_at - now)
                        elif self._stopping:
                            return None
                        else:
                            if expired:
                                break
                            self._cond.wait()
            finally:
                # cancellation is a terminal outcome too: a sampled
                # cancelled request's root span must still land (outside
                # the lock — ending a root flushes the span sink)
                for tr in cancelled:
                    tr.end(status="cancelled")
                for r in expired:
                    self._shed(r, SHED_DEADLINE,
                               "deadline passed while waiting in queue")

    def _take_locked(self, cancelled: Optional[List] = None,
                     ) -> List[ServeRequest]:
        """Pop whole requests up to ``max_batch`` rows (an oversized
        request serves alone; a schema change cuts the batch so coalesce
        never mixes schemas).  Each taken request transitions its future
        to RUNNING — a request whose caller cancelled it while queued is
        dropped here (its trace appended to ``cancelled`` for the CALLER
        to end once the lock is released), and a RUNNING future can no
        longer be cancelled, so result delivery cannot race a
        cancellation."""
        taken: List[ServeRequest] = []
        rows = 0
        bytes_out = 0
        dropped = 0
        schema = None
        # under memory pressure the coalescing target shrinks to the last
        # working batch size (and AIMD-probes back toward max_batch) —
        # one OOM must not re-split every subsequent coalesced dispatch.
        # The cap is per-device-denominated (ISSUE 15): an OOM on an
        # 8-device mesh shrinks the per-device share, not the whole
        # mesh's batch to a 1-device floor.  The width is read LIVE (not
        # the construction-time cache) so a mid-flight FMT_SERVE_MESH
        # flip keeps the pressure accounting on the actual dispatch width
        max_rows = pressure.state(_SERVING_SURFACE).admit(
            self.config.max_batch, n_dev=self._serving_mesh_width()
        )
        track_bytes = bool(self.config.queue_cap_bytes)
        while self._queue:
            r = self._queue[0]
            if taken and (
                rows + r.n_rows > max_rows
                or r.table.schema != schema
                or not self._tenant_compat(taken[0].tenant, r.tenant)
            ):
                break
            self._queue.popleft()
            self._tenant_queued[r.tenant] = max(
                self._tenant_queued[r.tenant] - r.n_rows, 0
            )
            if track_bytes:
                bytes_out += r.n_bytes
            if not r.future.set_running_or_notify_cancel():
                dropped += r.n_rows  # cancelled while queued
                if r.trace is not None and cancelled is not None:
                    cancelled.append(r.trace)
                continue
            schema = r.table.schema
            taken.append(r)
            rows += r.n_rows
        self._queued_rows -= rows + dropped
        obs.gauge_set("serving.queue_depth", self._queued_rows)
        if track_bytes:
            self._queued_bytes = max(self._queued_bytes - bytes_out, 0)
            obs.gauge_set("serving.queue_bytes", self._queued_bytes)
        if dropped:
            self._tally("serving.cancelled_rows", dropped)
            obs.counter_add("serving.cancelled_rows", dropped)
        return taken

    def _tenant_compat(self, a: str, b: str) -> bool:
        """May requests of tenants ``a`` and ``b`` share one coalesced
        batch?  Same tenant always; different tenants only when the mux
        is on and BOTH tenants' models are known same-family (their
        structural plan tokens, recorded at each tenant's first serve,
        compare equal) — so the first-ever request of a tenant serves
        solo once and coalesces ever after."""
        if a == b:
            return True
        from flink_ml_tpu.serving.mux import mux_enabled

        if not mux_enabled():
            return False
        ta = self._tenants.family_token(a)
        return ta is not None and ta == self._tenants.family_token(b)

    def _resolve_tenant(self, tenant: str, version):
        """One tenant's (model, version label) for a dispatch: the
        default tenant is the snapshotted active version; a registered
        tenant faults in through the registry (slab-pool resident)."""
        if tenant == DEFAULT_TENANT:
            return version.model, version.version
        return self._tenants.resolve(tenant)

    def _note_tenant_family(self, tenant: str, model, schema) -> None:
        """Record (once per tenant) the family token under which this
        tenant's model is mux-eligible — the compat check
        :meth:`_take_locked` runs at every batch cut.  A model whose
        chain cannot mux records nothing: its tenant simply keeps
        serving solo batches."""
        if self._tenants.family_token(tenant) is not None:
            return
        from flink_ml_tpu.serving import mux as mux_mod

        run = mux_mod.mux_run_for(
            model, schema, self._single_batch_rows() or None
        )
        if run is not None:
            self._tenants.note_family(tenant, mux_mod.family_token(run))

    def _collect_expired_locked(self, now: float) -> List[ServeRequest]:
        """Remove every expired request from the queue and return them
        for the CALLER to shed once the lock is released (completing a
        future under the lock would run caller callbacks re-entrantly)."""
        if not any(r.expired(now) for r in self._queue):
            return []
        expired: List[ServeRequest] = []
        kept: Deque[ServeRequest] = deque()
        track_bytes = bool(self.config.queue_cap_bytes)
        for r in self._queue:
            if r.expired(now):
                self._queued_rows -= r.n_rows
                self._tenant_queued[r.tenant] = max(
                    self._tenant_queued[r.tenant] - r.n_rows, 0
                )
                if track_bytes:
                    self._queued_bytes = max(
                        self._queued_bytes - r.n_bytes, 0
                    )
                expired.append(r)
            else:
                kept.append(r)
        self._queue = kept
        obs.gauge_set("serving.queue_depth", self._queued_rows)
        if track_bytes:
            obs.gauge_set("serving.queue_bytes", self._queued_bytes)
        return expired

    def _serve_batch(self, requests: List[ServeRequest]) -> None:
        """One coalesced dispatch, with memory-pressure recovery (ISSUE
        9): an allocator OOM from the transform splits the batch at a
        REQUEST boundary and serves each half on its own dispatch.
        Request-local demux offsets never depended on batchmates, so
        every caller's result — outputs and quarantine side-tables —
        stays bit-identical to the unsplit (and the solo) path.  The
        ``serving.batch`` pressure state caps subsequent coalescing at
        the working size, and the AIMD probe restores full batches once
        pressure clears."""
        if not requests:
            return
        from flink_ml_tpu.obs import drift as drift_mod

        try:
            # the drift tap scope (ISSUE 11): deep taps (quarantine
            # boundary, fused plan entry) inside this batch's transform
            # feed THIS server's monitor; exit rolls it (reference
            # freeze/persist + window rotation).  None = no-op context.
            with drift_mod.active(self._drift):
                self._serve_batch_once(requests)
        except BaseException as exc:  # noqa: BLE001 - OOM-only, see below
            # _serve_batch_once resolves every other failure into the
            # futures itself; only a splittable OOM escapes it
            if not (pressure.enabled() and pressure.is_oom(exc)
                    and len(requests) > 1):
                raise
            n_rows = sum(r.n_rows for r in requests)
            pressure.note_oom(_SERVING_SURFACE, n_rows, exc,
                              n_dev=self._serving_mesh_width())
            obs.counter_add("pressure.bisections")
            obs.counter_add(f"pressure.bisections.{_SERVING_SURFACE}")
            obs.counter_add("serving.pressure_splits")
            self._tally("serving.pressure_splits")
            obs.flight.record("serving.pressure_split", rows=n_rows,
                              requests=len(requests))
            mid = len(requests) // 2
            self._serve_batch(requests[:mid])
            self._serve_batch(requests[mid:])

    def _serve_batch_once(self, requests: List[ServeRequest]) -> None:
        """One coalesced dispatch: snapshot the active version, transform
        under quarantine capture, demux, resolve futures.

        Trace handoff: the dispatcher installs EVERY sampled request's
        context at once (``trace.use``), so the batch-scope spans —
        coalesce, the transform (and the fused plan's place/dispatch/sync
        spans under it), demux — fan out to each participating trace with
        shared timestamps: every caller's waterfall is complete on its
        own, and a racing sibling's spans can never cross over."""
        from flink_ml_tpu.obs import trace
        from flink_ml_tpu.serve.quarantine import QUARANTINE_REASON_COL

        if not requests:
            return  # every taken request was cancelled while queued
        if any(r.tenant != requests[0].tenant for r in requests):
            # multi-tenant batch (ISSUE 20): per-tenant-contiguous span
            # order — the mux stacks params per contiguous tenant span
            # and finalize runs per tenant slice.  The sort is stable,
            # so FIFO order holds WITHIN each tenant, and demux/futures
            # walk this same reordered list end to end.
            requests = sorted(requests, key=lambda r: r.tenant)
        version = self._versions.active()  # in-flight pins the old version
        traced = [r.trace for r in requests if r.trace is not None]
        now0 = now_s()
        for r in requests:
            # once per request: a memory-pressure split re-enters here
            # for each half, and a duplicate queue_wait would double-
            # count the wait in the request's waterfall
            if r.trace is not None and not getattr(
                    r, "_queue_wait_recorded", False):
                r._queue_wait_recorded = True
                trace.record_span((r.trace.ctx,), "queue_wait",
                                  now0 - r.enqueued_at)
        with trace.use(tuple(t.ctx for t in traced)):
            with trace.span("coalesce", {"requests": len(requests)}):
                table, spans = coalesce(requests)
            n_rows = table.num_rows()
            try:
                with obs.phase("serving.batch"):
                    results, scored = self._serve_spans(
                        requests, table, spans, version
                    )
                if self._drift is not None and scored is not None:
                    # the demux-side drift tap (ISSUE 11): produced
                    # score/prediction columns of the whole coalesced
                    # batch into the live (or still-filling reference)
                    # window, request input columns excluded.  Only
                    # default-tenant batches feed it: the reference
                    # belongs to the ACTIVE VERSION, and tenant outputs
                    # would drift it by construction
                    self._drift.observe_scores(
                        scored, exclude=frozenset(table.schema.field_names)
                    )
            except BaseException as exc:  # noqa: BLE001 - futures carry it
                if (pressure.enabled() and pressure.is_oom(exc)
                        and len(requests) > 1):
                    # allocator exhaustion on a splittable batch: let the
                    # caller split at a request boundary — the futures
                    # stay pending and every request still serves
                    raise
                self._tally("serving.failed_batches")
                self._tally("serving.failed_requests", len(requests))
                obs.counter_add("serving.failed_batches")
                obs.counter_add("serving.failed_requests", len(requests))
                for r in requests:
                    if r.trace is not None:  # before the future resolves
                        r.trace.end(status="error", attrs={
                            "error": type(exc).__name__,
                        })
                    if not r.future.done():
                        r.future.set_exception(exc)
                return
        now = now_s()
        for r, res in zip(requests, results):
            if r.trace is not None:
                # end the trace BEFORE resolving the future: once the
                # caller observes completion the whole trace must already
                # be recorded (a caller that disables tracing right after
                # result() must never race a trailing root-span write)
                attrs = {"version": res.version,
                         "quarantined": res.num_quarantined}
                if res.num_quarantined:
                    attrs["quarantine_reasons"] = ",".join(sorted({
                        str(x) for t in res.quarantine.values()
                        for x in t.col(QUARANTINE_REASON_COL)
                    }))
                r.trace.end(status="ok", attrs=attrs)
            r.future.set_result(res)
            latency_ms = (now - r.enqueued_at) * 1e3
            self._latencies.append(latency_ms)
            obs.observe("serving.request_latency_ms", latency_ms)
        self._tally("serving.batches")
        self._tally("serving.served_rows", n_rows)
        self._tally("serving.coalesced_requests", len(requests))
        obs.counter_add("serving.batches")
        obs.counter_add("serving.served_rows", n_rows)
        obs.counter_add("serving.coalesced_requests", len(requests))
        obs.gauge_set("serving.batch_occupancy",
                      min(n_rows / self.config.max_batch, 1.0))
        # retain a live-schema head as the default warmup for hot swaps
        self._warmup_sample = table.slice_rows(
            0, min(n_rows, _WARMUP_SAMPLE_ROWS)
        )

    def _serve_spans(self, requests: List[ServeRequest], table: Table,
                     spans, version):
        """Transform + demux for one taken batch, tenant-aware.

        Returns ``(results, scored)``: per-request results in span
        order, plus the combined output table when the whole batch was
        the default tenant (the drift monitor's feed; None otherwise).

        An all-default batch runs the historical single-model body
        verbatim.  A multi-tenant batch — only formed when every
        member's family token matched at the cut — serves as ONE
        multiplexed dispatch (:mod:`flink_ml_tpu.serving.mux`); mux
        ineligibility or failure falls back to per-tenant groups, each
        its own transform under a fresh quarantine capture, so every
        caller's outputs and side-tables stay bit-identical to solo
        serving either way."""
        from flink_ml_tpu.obs import trace
        from flink_ml_tpu.serve import quarantine

        trace_ids = [
            r.trace.trace_id if r.trace is not None else None
            for r in requests
        ]
        tenants = [r.tenant for r in requests]
        if all(t == DEFAULT_TENANT for t in tenants):
            with trace.span("transform", {
                "rows": table.num_rows(), "version": version.version,
            }):
                with quarantine.capture() as captured:
                    out = version.transform(table)
            with trace.span("demux"):
                results = demux(out, captured, spans, version.version,
                                trace_ids=trace_ids)
            self._note_tenant_family(DEFAULT_TENANT, version.model,
                                     table.schema)
            return results, out
        # contiguous per-tenant request groups (take order = span order)
        groups: List[tuple] = []  # (tenant, first request idx, last+1)
        for i, t in enumerate(tenants):
            if groups and groups[-1][0] == t:
                groups[-1] = (t, groups[-1][1], i + 1)
            else:
                groups.append((t, i, i + 1))
        if len(groups) > 1:
            results = self._serve_mux(requests, table, spans, groups,
                                      version, trace_ids)
            if results is not None:
                return results, None
        # per-tenant fallback: each group is exactly the single-tenant
        # body on its slice of the batch — own capture, own demux, so
        # offsets never need cross-group surgery
        from flink_ml_tpu.table import slab_pool

        results = []
        for tenant, i0, i1 in groups:
            lo, hi = spans[i0][0], spans[i1 - 1][1]
            g_table = (table if lo == 0 and hi == table.num_rows()
                       else table.slice_rows(lo, hi))
            g_spans = [(a - lo, b - lo) for a, b in spans[i0:i1]]
            model, label = self._resolve_tenant(tenant, version)
            with slab_pool.pool().pinned(model):
                with trace.span("transform", {
                    "rows": g_table.num_rows(), "version": label,
                    "tenant": tenant,
                }):
                    with quarantine.capture() as captured:
                        out = _transform_one(model, g_table)
                with trace.span("demux"):
                    results.extend(demux(
                        out, captured, g_spans, label,
                        trace_ids=trace_ids[i0:i1],
                    ))
            self._note_tenant_family(tenant, model, g_table.schema)
        return results, None

    def _serve_mux(self, requests, table: Table, spans, groups,
                   version, trace_ids):
        """One multiplexed dispatch for a multi-tenant batch, or None
        when a member's plan turns out mux-ineligible (the caller falls
        back to per-tenant groups).  Every tenant's model is pinned
        (slab-pool pin invariant) for the duration of the dispatch, so
        neither budget pressure nor the residency cap can fault a
        batch-mate out mid-flight.  A dispatch failure degrades to the
        fallback too — except an allocator OOM, which propagates so the
        request-boundary pressure split can halve the batch."""
        import contextlib

        from flink_ml_tpu.obs import trace
        from flink_ml_tpu.parallel.mesh import inference_mesh
        from flink_ml_tpu.serve import quarantine
        from flink_ml_tpu.serving import mux as mux_mod
        from flink_ml_tpu.table import slab_pool
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        if not mux_mod.mux_enabled():
            return None
        batch_size = self._single_batch_rows() or None
        mux_spans: List = []
        models: List = []
        labels = {}
        token = None
        for tenant, i0, i1 in groups:
            model, label = self._resolve_tenant(tenant, version)
            run = mux_mod.mux_run_for(model, table.schema, batch_size)
            if run is None:
                return None
            tok = mux_mod.family_token(run)
            if token is None:
                token = tok
            elif tok != token:
                return None
            lo, hi = spans[i0][0], spans[i1 - 1][1]
            mux_spans.append(mux_mod.MuxSpan(tenant, run, lo, hi))
            models.append(model)
            labels[tenant] = label
        try:
            with contextlib.ExitStack() as stack:
                pool = slab_pool.pool()
                for m in models:
                    stack.enter_context(pool.pinned(m))
                mesh = inference_mesh(
                    MLEnvironmentFactory.get_default().get_mesh()
                )
                with trace.span("transform", {
                    "rows": table.num_rows(), "mux_tenants": len(groups),
                }):
                    with quarantine.capture() as captured:
                        out = mux_mod.serve_mux(table, mux_spans, mesh)
                with trace.span("demux"):
                    results = demux(out, captured, spans, version.version,
                                    trace_ids=trace_ids)
        except BaseException as exc:  # noqa: BLE001 - OOM re-raised below
            if (pressure.enabled() and pressure.is_oom(exc)
                    and len(requests) > 1):
                raise
            obs.counter_add("serving.mux_fallbacks")
            self._tally("serving.mux_fallbacks")
            obs.flight.record("serving.mux_fallback",
                              error=type(exc).__name__,
                              tenants=len(groups))
            return None
        # each caller reads ITS tenant's version label on the result
        for r, res in zip(requests, results):
            res.version = labels.get(r.tenant, res.version)
        return results

    # -- accounting ----------------------------------------------------------

    @staticmethod
    def _serving_mesh_width() -> int:
        """The data-axis width the transforms below this server dispatch
        over — 1 when ``FMT_SERVE_MESH`` pins serving to one device."""
        from flink_ml_tpu.common.fused import serve_mesh_enabled
        from flink_ml_tpu.parallel.mesh import (
            data_parallel_size,
            inference_mesh,
        )
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        if not serve_mesh_enabled():
            return 1
        return data_parallel_size(
            inference_mesh(MLEnvironmentFactory.get_default().get_mesh())
        )

    @staticmethod
    def _single_batch_rows() -> int:
        """The environment's internal transform batch size — the row bound
        under which a coalesced dispatch is guaranteed to run as ONE batch
        on the dispatcher thread (0 = unbounded)."""
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        return int(
            MLEnvironmentFactory.get_default().default_batch_size or 0
        )

    def stats(self) -> dict:
        """THIS server's own tallies (requests, batches, shed per reason,
        swaps, ...) plus latency quantiles over its own requests — the
        shutdown report's payload, readable live.  Per-server by
        construction: the process-global ``serving.*`` counters and the
        ``serving.request_latency_ms`` histogram aggregate across every
        server in the process, so reports read the local ledger instead."""
        from flink_ml_tpu.obs.registry import sample_quantile

        delta = {k: v for k, v in sorted(self._counts.items()) if v}
        samples = sorted(self._latencies)
        if samples:
            delta["latency_p50_ms"] = round(
                sample_quantile(samples, 0.50), 3)
            delta["latency_p99_ms"] = round(
                sample_quantile(samples, 0.99), 3)
            delta["latency_mean_ms"] = round(
                sum(samples) / len(samples), 3)
        delta["active_version"] = self.active_version
        return delta

    def _write_report(self) -> None:
        if not obs.enabled():
            return
        from flink_ml_tpu.obs.report import serving_report

        extra = self.stats()
        if self._drift is not None:
            # the drift section `obs --check` flags and the
            # `obs drift` CLI renders
            section = self._drift.report_section()
            if section is not None:
                extra["drift"] = section
        serving_report("ModelServer", extra=extra)
