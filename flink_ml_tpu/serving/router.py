"""Replica router: the horizontal scale-out front-end over the telemetry
plane (ISSUE 13, ROADMAP item 2).

Every request so far terminated in ONE ``ModelServer`` process — a hard
ceiling no matter how fast the chip path gets.  The reference
architecture splits the framework from an execution substrate that
scales it out (PAPER.md layer map: Pipeline/Estimator API above, Flink's
distributed runtime below); :class:`ReplicaRouter` is that substrate's
first rung: the same ``submit() -> Future`` contract as ``ModelServer``,
fanned across N replica subprocesses, each running its own
``ModelServer`` (micro-batching, breakers, pressure recovery, telemetry —
the whole single-process stack) behind the wire layer in
:mod:`flink_ml_tpu.serving.replica`.

**Health-aware balancing.**  A background poll loop scrapes every
replica's ``/readyz`` + ``/metrics`` (PR 10 built exactly the probes an
orchestrator needs — now we are the orchestrator): a replica reporting
503 — ``breaker_open``, ``memory_pressure``, ``slo_burning``, ``drift``,
``deploy_in_progress``, ``queue_saturated`` — is routed around.  Among
ready replicas, dispatch picks by power-of-two-choices on observed load
(scraped queue depth + the router's own in-flight count): two random
candidates, the less-loaded one wins — near-optimal balance without a
global scan per request.

**Shed classification, not string matching.**  A replica's reason-coded
shed is classified by :func:`~flink_ml_tpu.serving.errors.shed_policy`:
``queue_full`` / ``memory_pressure`` / ``deadline_expired`` retry on
another replica (one replica's transient load), ``shutdown`` /
``breaker_open`` route away (eject the replica from rotation AND retry
elsewhere), anything unknown sheds to the caller unchanged.  Retries are
budgeted by ``FMT_ROUTER_RETRIES`` and counted in ``router.retries``.

**Rolling deploys.**  ``deploy(path, version)`` reuses the round-10 swap
contract per replica, one replica at a time: stop routing to it (drain),
wait for its in-flight requests, drive its ``/deploy`` (load -> verify ->
pre-warm -> atomic swap inside the replica), wait for ``/readyz`` 200,
re-admit — the rest of the fleet serves throughout, so a deploy sheds
nothing.  A failed deploy (corrupt artifact, broken warmup) leaves THAT
replica on its old version (the versioning.py contract is the rollback),
stops the roll, and raises :class:`RollingDeployError` carrying the
partial per-replica status (also readable at :attr:`deploy_status`).

**Supervision.**  A crashed or killed replica is detected two ways —
the poll loop's ``waitpid`` check and the dead socket its in-flight
dispatches hit — its requests retry on surviving replicas, and a
replacement is respawned on the router's current (path, version), with
bounded spawn retries before a slot is abandoned.

Telemetry: ``router.replicas_ready`` / ``router.queue_depth`` gauges;
``router.requests`` / ``router.retries`` / ``router.shed(.reason)`` /
``router.replica_deaths`` / ``router.respawns`` /
``router.rolling_deploys`` counters; a ``serving`` RunReport at
shutdown.  Chaos levers: injection points ``router.dispatch`` (before
each forward) and ``router.spawn`` (replica boot).

**Elastic membership (round 22).**  The fleet is no longer fixed at
boot: :meth:`add_replica` grows it through the standard spawn path (the
child inherits the sealed warmstart manifest, so its first request
stays warm) and :meth:`remove_replica` shrinks it drain-aware by
reusing the rolling-deploy drain contract via :meth:`_drain_replica` —
stop routing, wait out in-flight work, terminate, tombstone the slot so
every index stays stable.  Membership changes serialize with rolling
deploys.  :class:`~flink_ml_tpu.serving.autoscaler.FleetAutoscaler`
closes the observe→decide→act loop over :meth:`fleet_health`.  Two
supervision refinements ride along: a live replica only leaves rotation
after ``FMT_ROUTER_SCRAPE_STRIKES`` consecutive failed scrapes (with
jittered re-probes between strikes — one blackholed scrape must not
read like a dead socket; waitpid-confirmed death stays immediate), and
a slot whose replica dies ``FMT_ROUTER_CRASHLOOP_MAX`` times inside
``FMT_ROUTER_CRASHLOOP_WINDOW_S`` is quarantined with exponential
backoff (a ``router.crashloop`` flight dump names the slot and exit
status) instead of hot-loop respawning.

Knobs (README.md, "Replica router"): ``FMT_ROUTER_REPLICAS``,
``FMT_ROUTER_POLL_MS``, ``FMT_ROUTER_QUEUE_CAP``,
``FMT_ROUTER_DISPATCH_THREADS``, ``FMT_ROUTER_RETRIES``,
``FMT_ROUTER_SPAWN_TIMEOUT_S``, ``FMT_ROUTER_DRAIN_TIMEOUT_S``; the
round-22 table adds ``FMT_ROUTER_SCRAPE_STRIKES``,
``FMT_ROUTER_CRASHLOOP_MAX`` and ``FMT_ROUTER_CRASHLOOP_WINDOW_S``.
"""

from __future__ import annotations

import random
import threading
import time
from collections import Counter, deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple

from flink_ml_tpu import obs
from flink_ml_tpu.serving.admission import now_s
from flink_ml_tpu.serving.batcher import ServeResult
from flink_ml_tpu.serving.errors import (
    POLICY_FAIL,
    POLICY_ROUTE_AWAY,
    SHED_DEADLINE,
    SHED_NO_REPLICA,
    SHED_QUEUE_FULL,
    SHED_SHUTDOWN,
    ServerClosedError,
    ServerOverloadedError,
    shed_policy,
)
from flink_ml_tpu.serving.replica import (
    ReplicaClient,
    ReplicaProcess,
    ReplicaRemoteError,
    ReplicaUnreachableError,
)
from flink_ml_tpu.utils import knobs

__all__ = ["ReplicaRouter", "RollingDeployError", "RouterConfig"]

#: consecutive failed probe rounds before a process-less (injected)
#: replica backend is treated as dead; process-backed replicas are
#: declared dead by ``waitpid``, which needs no debounce
_PROBE_FAILURE_DEBOUNCE = 3

#: poll beats between /metrics queue-depth scrapes (readiness is checked
#: every beat; the full-registry exposition is the expensive half of a
#: probe and the router's own in-flight counts stay current in between)
_DEPTH_SCRAPE_EVERY = 4

#: spawn attempts per replacement before a slot is abandoned (the fleet
#: keeps serving on the survivors; abandoning beats a respawn hot-loop)
_MAX_SPAWN_ATTEMPTS = 3

#: per-forward wire timeout — generous: the replica's own admission
#: deadline is the real latency contract, this only bounds a wedged peer
_DISPATCH_TIMEOUT_S = 120.0

#: first crash-loop quarantine parks the slot this long, doubling per
#: consecutive episode up to the cap — long enough to break a hot loop,
#: short enough that a recovered dependency re-admits the slot soon
_CRASHLOOP_BACKOFF_S = 2.0
_CRASHLOOP_BACKOFF_CAP_S = 60.0


@dataclass(frozen=True)
class RouterConfig:
    """Resolved router knobs (environment defaults, overrides win)."""

    replicas: int = 2
    poll_ms: float = 50.0
    queue_cap: int = 4096
    dispatch_threads: int = 8
    retries: int = 2
    spawn_timeout_s: float = 120.0
    drain_timeout_s: float = 30.0
    scrape_strikes: int = 3
    crashloop_max: int = 3
    crashloop_window_s: float = 30.0

    @classmethod
    def from_env(cls, replicas: Optional[int] = None,
                 poll_ms: Optional[float] = None,
                 queue_cap: Optional[int] = None,
                 dispatch_threads: Optional[int] = None,
                 retries: Optional[int] = None,
                 spawn_timeout_s: Optional[float] = None,
                 drain_timeout_s: Optional[float] = None,
                 scrape_strikes: Optional[int] = None,
                 crashloop_max: Optional[int] = None,
                 crashloop_window_s: Optional[float] = None
                 ) -> "RouterConfig":
        cfg = cls(
            replicas=int(replicas if replicas is not None
                         else knobs.knob_int("FMT_ROUTER_REPLICAS")),
            poll_ms=float(poll_ms if poll_ms is not None
                          else knobs.knob_float("FMT_ROUTER_POLL_MS")),
            queue_cap=int(queue_cap if queue_cap is not None
                          else knobs.knob_int("FMT_ROUTER_QUEUE_CAP")),
            dispatch_threads=int(
                dispatch_threads if dispatch_threads is not None
                else knobs.knob_int("FMT_ROUTER_DISPATCH_THREADS")),
            retries=int(retries if retries is not None
                        else knobs.knob_int("FMT_ROUTER_RETRIES")),
            spawn_timeout_s=float(
                spawn_timeout_s if spawn_timeout_s is not None
                else knobs.knob_float("FMT_ROUTER_SPAWN_TIMEOUT_S")),
            drain_timeout_s=float(
                drain_timeout_s if drain_timeout_s is not None
                else knobs.knob_float("FMT_ROUTER_DRAIN_TIMEOUT_S")),
            scrape_strikes=max(int(
                scrape_strikes if scrape_strikes is not None
                else knobs.knob_int("FMT_ROUTER_SCRAPE_STRIKES")), 1),
            crashloop_max=int(
                crashloop_max if crashloop_max is not None
                else knobs.knob_int("FMT_ROUTER_CRASHLOOP_MAX")),
            crashloop_window_s=float(
                crashloop_window_s if crashloop_window_s is not None
                else knobs.knob_float("FMT_ROUTER_CRASHLOOP_WINDOW_S")),
        )
        if cfg.replicas < 1 or cfg.dispatch_threads < 1 or cfg.queue_cap < 1:
            raise ValueError(
                f"replicas, dispatch_threads and queue_cap must be >= 1 "
                f"(got {cfg.replicas}, {cfg.dispatch_threads}, "
                f"{cfg.queue_cap})"
            )
        return cfg


class RollingDeployError(RuntimeError):
    """A rolling deploy stopped mid-fleet.  ``status`` holds the partial
    per-replica outcome (which replicas swapped, which failed and rolled
    back, which were skipped) — the failing replica itself kept serving
    its OLD version, per the versioning.py contract."""

    def __init__(self, status: dict):
        failed = [r["replica"] for r in status.get("replicas", [])
                  if r.get("outcome") == "failed"]
        super().__init__(
            f"rolling deploy of {status.get('version')!r} stopped: "
            f"{', '.join(failed) or 'drain timeout'} — fleet left "
            f"partially on {status.get('previous')!r} (see .status)"
        )
        self.status = status


@dataclass
class _RouterRequest:
    table: object
    future: Future
    enqueued_at: float
    deadline_at: Optional[float]
    n_rows: int
    attempts: int = 0
    #: the routed request's ROOT trace (obs.trace.RequestTrace, None when
    #: tracing is off/sampled out) — every dispatch attempt parents under
    #: it, and its context ships to the replica over the wire
    trace: Optional[object] = None
    #: multi-tenant routing key (ISSUE 20); None = the default tenant,
    #: kept off the wire so pre-tenant replicas still parse the payload
    tenant: Optional[str] = None

    def expired(self, now: float) -> bool:
        return self.deadline_at is not None and now > self.deadline_at

    def remaining_ms(self, now: float) -> Optional[float]:
        if self.deadline_at is None:
            return None
        return max((self.deadline_at - now) * 1e3, 1.0)


class _Replica:
    """The router's view of one replica slot: wire client + health and
    load state, all transitions under the replica's own lock (probe
    thread, N dispatch threads, and the deploy thread all touch it)."""

    def __init__(self, name: str, client: ReplicaClient,
                 process: Optional[ReplicaProcess] = None,
                 version: str = "", scrape_strikes: int = 1):
        self.name = name
        self.client = client
        self.process = process
        self._lock = threading.Condition()
        self._ready = False
        self._reasons: List[str] = ["booting"]
        self._queue_depth = 0.0
        self._burn_rates: Dict[str, float] = {}
        self._in_flight = 0
        self._draining = False
        self._dead = False
        self._probe_failures = 0
        self._probe_inflight = False
        self._scrape_strikes = max(int(scrape_strikes), 1)
        self._version = version

    # -- health (poll loop) --------------------------------------------------

    def mark_probe(self, probe: dict) -> None:
        with self._lock:
            self._ready = bool(probe.get("ready"))
            self._reasons = list(probe.get("reasons", []))
            if "queue_depth" in probe:
                # readiness refreshes every beat; depth only on scrape
                # beats (absent key = keep the last observation)
                self._queue_depth = float(probe["queue_depth"])
            if "burn_rates" in probe:
                self._burn_rates = dict(probe["burn_rates"])
            self._probe_failures = 0

    def note_probe_failure(self) -> int:
        """One unreachable probe; returns the consecutive-failure count.
        Transient-vs-dead discrimination: a live replica only leaves
        rotation after ``scrape_strikes`` consecutive failures — one
        blackholed scrape must not read like a dead socket (a dead
        socket is waitpid's verdict, which needs no debounce)."""
        with self._lock:
            self._probe_failures += 1
            if self._probe_failures >= self._scrape_strikes:
                self._ready = False
                self._reasons = ["unreachable"]
            return self._probe_failures

    def try_begin_probe(self) -> bool:
        """Claim this replica's probe slot (False = a probe is still in
        flight — a wedged peer's 2 s timeout must stall only its OWN
        refresh, never the fleet's)."""
        with self._lock:
            if self._probe_inflight:
                return False
            self._probe_inflight = True
            return True

    def end_probe(self) -> None:
        with self._lock:
            self._probe_inflight = False

    def mark_unready(self, reason: str) -> None:
        """A dispatch-path verdict (a route-away shed): stop routing here
        until the next probe says otherwise."""
        with self._lock:
            self._ready = False
            self._reasons = [reason]

    def mark_dead(self, why: str) -> None:
        with self._lock:
            self._dead = True
            self._ready = False
            self._reasons = [why]
            self._lock.notify_all()  # a drain waiter must not outwait a corpse

    def is_dead(self) -> bool:
        with self._lock:
            return self._dead

    # -- routing (dispatch threads) ------------------------------------------

    def routable(self) -> bool:
        with self._lock:
            return self._ready and not self._draining and not self._dead

    def load(self) -> float:
        """The power-of-two-choices comparand: the replica's scraped
        queue depth plus the router's own not-yet-acknowledged forwards
        (the scrape lags; in-flight is current)."""
        with self._lock:
            return self._queue_depth + float(self._in_flight)

    def begin_dispatch(self) -> None:
        with self._lock:
            self._in_flight += 1

    def end_dispatch(self) -> None:
        with self._lock:
            self._in_flight = max(self._in_flight - 1, 0)
            if self._in_flight == 0:
                self._lock.notify_all()

    # -- rolling deploy (deploy thread) --------------------------------------

    def set_draining(self, draining: bool) -> None:
        with self._lock:
            self._draining = bool(draining)

    def wait_drained(self, timeout_s: float) -> bool:
        """Block until no router-originated request is in flight on this
        replica (or it dies); False on timeout."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            while self._in_flight > 0 and not self._dead:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._lock.wait(timeout=remaining)
            return True

    def set_version(self, version: str) -> None:
        with self._lock:
            self._version = version

    # -- introspection -------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "name": self.name,
                "ready": self._ready,
                "reasons": list(self._reasons),
                "queue_depth": self._queue_depth,
                "burn_rates": dict(self._burn_rates),
                "in_flight": self._in_flight,
                "draining": self._draining,
                "dead": self._dead,
                "version": self._version,
            }
        if self.process is not None:
            snap["pid"] = self.process.pid
            snap["serve_address"] = self.process.serve_address
            snap["telemetry_address"] = self.process.telemetry_address
        return snap


class ReplicaRouter:
    """Scale-out front-end over N ``ModelServer`` replica processes.

    ``ReplicaRouter(path, replicas=3)`` spawns three replicas serving the
    saved pipeline at ``path`` and starts balancing; use as a context
    manager or call :meth:`shutdown`.  ``submit``/``predict`` mirror
    ``ModelServer`` — a caller's :class:`ServeResult` is bit-identical to
    a solo in-process transform of its rows.

    ``replica_factory`` (tests, embeddings) replaces subprocess spawning:
    a callable ``(slot_name, path, version) -> (client, process_or_None)``
    returning anything speaking the :class:`ReplicaClient` protocol.
    """

    def __init__(self, path: str, *, version: str = "v1",
                 replicas: Optional[int] = None,
                 queue_cap: Optional[int] = None,
                 poll_ms: Optional[float] = None,
                 dispatch_threads: Optional[int] = None,
                 retries: Optional[int] = None,
                 spawn_timeout_s: Optional[float] = None,
                 drain_timeout_s: Optional[float] = None,
                 scrape_strikes: Optional[int] = None,
                 crashloop_max: Optional[int] = None,
                 crashloop_window_s: Optional[float] = None,
                 replica_env: Optional[Dict[str, str]] = None,
                 replica_factory=None,
                 start: bool = True):
        self.config = RouterConfig.from_env(
            replicas=replicas, poll_ms=poll_ms, queue_cap=queue_cap,
            dispatch_threads=dispatch_threads, retries=retries,
            spawn_timeout_s=spawn_timeout_s,
            drain_timeout_s=drain_timeout_s,
            scrape_strikes=scrape_strikes,
            crashloop_max=crashloop_max,
            crashloop_window_s=crashloop_window_s,
        )
        self._replica_env = dict(replica_env or {})
        self._factory = replica_factory or self._spawn_backend
        self._cond = threading.Condition()
        self._queue: Deque[_RouterRequest] = deque()
        self._queued_rows = 0
        self._stopping = False
        self._closed = False
        self._rep_lock = threading.Lock()
        self._slots: List[Optional[_Replica]] = []
        self._generation = 0
        self._respawning: set = set()
        #: per-slot recent death stamps + quarantine episodes (crash-loop
        #: detection, round 22) — both under ``_rep_lock``
        self._death_times: Dict[int, Deque[float]] = {}
        self._quarantine: Dict[int, dict] = {}
        self._source_path = str(path)
        self._source_version = str(version)
        self._deploy_status: Optional[dict] = None
        self._deploy_lock = threading.Lock()
        self._counts: Counter = Counter()
        self._counts_lock = threading.Lock()
        self._latencies: Deque[float] = deque(maxlen=512)
        self._threads: List[threading.Thread] = []
        self._poll_stop = threading.Event()
        self._boot_replicas()
        if start:
            self.start()

    # -- lifecycle -----------------------------------------------------------

    def _spawn_backend(self, name: str, path: str, version: str
                       ) -> Tuple[ReplicaClient, Optional[ReplicaProcess]]:
        process = ReplicaProcess.spawn(
            path, version, extra_env=self._replica_env,
            boot_timeout_s=self.config.spawn_timeout_s,
        )
        return (ReplicaClient(process.serve_address,
                              process.telemetry_address), process)

    def _boot_replicas(self) -> None:
        """Spawn the initial fleet in parallel (replica boot is seconds
        of jax import + model load each; serial boot would multiply it).
        Any boot failure stops the already-started children and raises —
        a router that opens must open whole."""
        results: List[Optional[_Replica]] = [None] * self.config.replicas
        errors: List[BaseException] = []

        def boot(i: int) -> None:
            try:
                results[i] = self._make_replica(i)
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=boot, args=(i,), daemon=True)
                   for i in range(self.config.replicas)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            for replica in results:
                if replica is not None:
                    self._stop_backend(replica)
            raise errors[0]
        with self._rep_lock:
            self._slots = results
        obs.gauge_set("router.replicas", float(self.config.replicas))

    def _make_replica(self, index: int) -> _Replica:
        with self._rep_lock:
            self._generation += 1
            generation = self._generation
            path, version = self._source_path, self._source_version
        name = f"replica-{index}-g{generation}"
        client, process = self._factory(name, path, version)
        replica = _Replica(name, client, process, version=version,
                           scrape_strikes=self.config.scrape_strikes)
        # first health sample inline: a fresh replica is routable the
        # moment it answers, not one poll interval later
        try:
            replica.mark_probe(client.probe())
        except ReplicaUnreachableError:
            replica.note_probe_failure()
        if obs.trace.enabled() and process is not None:
            # one NTP-style clock sample per (re)spawn: enough for the
            # fleet stitcher to land this child's spans on our timeline
            try:
                obs.trace.note_clock_offset(**client.clock_probe())
            except (ReplicaUnreachableError, AttributeError, TypeError):
                pass
        return replica

    def start(self) -> "ReplicaRouter":
        with self._cond:
            if self._closed:
                raise ServerClosedError("router already shut down")
            if self._threads:
                return self
        threads = [
            threading.Thread(target=self._dispatch_loop,
                             name=f"fmt-router-dispatch-{i}", daemon=True)
            for i in range(self.config.dispatch_threads)
        ]
        threads.append(threading.Thread(
            target=self._poll_loop, name="fmt-router-poll", daemon=True))
        for t in threads:
            t.start()
        self._threads = threads
        return self

    def __enter__(self) -> "ReplicaRouter":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.shutdown()
        return False

    def shutdown(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop routing.  ``drain=True`` serves the queue first;
        ``drain=False`` sheds it with the ``shutdown`` reason.  Replicas
        get SIGTERM (they drain their own queues and exit 0).
        Idempotent."""
        dropped: List[_RouterRequest] = []
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stopping = True
            if not drain:
                dropped = list(self._queue)
                self._queue.clear()
                self._queued_rows = 0
            self._cond.notify_all()
        for request in dropped:
            self._fail(request, self._shed_error(
                SHED_SHUTDOWN, "router shut down without draining"))
        self._poll_stop.set()
        started = bool(self._threads)
        for t in self._threads:
            t.join(timeout=timeout)
        self._threads = []
        if not started and drain:
            # never started: drain inline so queued futures still resolve
            while True:
                request = self._next_request(block=False)
                if request is None:
                    break
                self._route(request)
        # wait out in-flight respawns (they abort on the stopping flag,
        # stopping their own replacement) so the fresh snapshot below
        # covers every child that could have been installed
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            with self._rep_lock:
                respawning = bool(self._respawning)
            if not respawning:
                break
            time.sleep(0.05)
        stoppers = [threading.Thread(target=self._stop_backend, args=(r,),
                                     daemon=True)
                    for r in self._replicas_snapshot() if r is not None]
        for t in stoppers:
            t.start()
        for t in stoppers:
            t.join(timeout=30.0)
        obs.gauge_set("router.replicas_ready", 0.0)
        self._write_report()

    @staticmethod
    def _stop_backend(replica: _Replica) -> None:
        if replica.process is not None:
            replica.process.stop()

    # -- the request path ----------------------------------------------------

    def submit(self, table, deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one request for the fleet; returns a Future resolving
        to a :class:`ServeResult`.  Sheds reason-coded at the door when
        the router queue is at ``FMT_ROUTER_QUEUE_CAP`` rows.

        ``tenant`` (ISSUE 20) names the registered model that serves the
        rows; None routes to each replica's default deployed model, and
        the key is validated/resolved at the REPLICA door (the router
        holds no model state)."""
        n = table.num_rows()
        if n == 0:
            raise ValueError("empty request: submit at least one row")
        now = now_s()
        deadline_at = (now + float(deadline_ms) / 1e3
                       if deadline_ms and deadline_ms > 0 else None)
        trace_attrs = {"rows": n}
        if tenant is not None:
            trace_attrs["tenant"] = tenant
        req_trace = obs.trace.start_request("router.request", trace_attrs)
        t_submit = time.perf_counter()
        request = _RouterRequest(table=table, future=Future(),
                                 enqueued_at=now, deadline_at=deadline_at,
                                 n_rows=n, trace=req_trace, tenant=tenant)
        rejected = None
        with self._cond:
            if self._closed or self._stopping:
                if req_trace is not None:
                    req_trace.end(status="shed",
                                  attrs={"shed_reason": SHED_SHUTDOWN})
                raise ServerClosedError("router is shut down")
            if self._queued_rows + n > self.config.queue_cap:
                rejected = (
                    f"{self._queued_rows} rows queued against a cap of "
                    f"{self.config.queue_cap} (request adds {n})"
                )
            else:
                self._queue.append(request)
                self._queued_rows += n
                obs.gauge_set("router.queue_depth", self._queued_rows)
                self._cond.notify()
        if rejected is not None:
            if req_trace is not None:
                req_trace.end(status="shed",
                              attrs={"shed_reason": SHED_QUEUE_FULL})
            raise self._shed_error(
                SHED_QUEUE_FULL, rejected,
                trace_id=req_trace.trace_id if req_trace else None)
        if req_trace is not None:
            obs.trace.record_span((req_trace.ctx,), "submit",
                                  time.perf_counter() - t_submit,
                                  {"rows": n})
        self._tally("router.requests")
        self._tally("router.request_rows", n)
        obs.counter_add("router.requests")
        obs.counter_add("router.request_rows", n)
        return request.future

    def predict(self, table, deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None,
                tenant: Optional[str] = None) -> ServeResult:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(table, deadline_ms=deadline_ms,
                           tenant=tenant).result(timeout)

    # -- dispatch ------------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            request = self._next_request()
            if request is None:
                return
            try:
                self._route(request)
            except BaseException as exc:  # noqa: BLE001 - lane must survive
                # _route resolves every expected failure into the future
                # itself; anything that still escapes must not kill the
                # dispatch lane (a dead lane strands queued futures)
                self._fail(request, exc)

    def _next_request(self, block: bool = True
                      ) -> Optional[_RouterRequest]:
        """Pop one request (FIFO), shedding expired entries on the way.
        Returns None when the router is stopping and the queue is empty.
        Sheds complete OUTSIDE the lock (done-callbacks may re-enter)."""
        while True:
            expired: Optional[_RouterRequest] = None
            with self._cond:
                while not self._queue:
                    if self._stopping or not block:
                        return None
                    self._cond.wait()
                request = self._queue.popleft()
                self._queued_rows -= request.n_rows
                obs.gauge_set("router.queue_depth", self._queued_rows)
                if request.expired(now_s()):
                    expired = request
            if expired is not None:
                self._fail(expired, self._shed_error(
                    SHED_DEADLINE, "deadline passed in the router queue"))
                continue
            if not request.future.set_running_or_notify_cancel():
                continue  # caller cancelled while queued
            return request

    def _route(self, request: _RouterRequest) -> None:
        """Forward one request, retrying across replicas per the shed
        classification, until it serves, its budget runs out, or no
        replica can take it."""
        from flink_ml_tpu.fault.injection import InjectedFault, maybe_fail

        req_trace = request.trace
        if req_trace is not None:
            obs.trace.record_span(
                (req_trace.ctx,), "queue_wait",
                max(now_s() - request.enqueued_at, 0.0))
        # install the request's context on THIS dispatch lane: each
        # attempt below records a router.dispatch span under the root —
        # retries render as SIBLINGS, and the winning attempt's span is
        # the parent the replica's adopted subtree nests under
        with obs.trace.use((req_trace.ctx,) if req_trace is not None
                           else ()):
            excluded: set = set()
            last_exc: Optional[BaseException] = None
            while True:
                now = now_s()
                if request.expired(now):
                    self._fail(request, self._shed_error(
                        SHED_DEADLINE, "deadline passed while routing"))
                    return
                replica = self._pick(excluded)
                if replica is None and excluded:
                    # every routable replica already failed this request
                    # once; budget permitting, give the fleet a second
                    # pass (their transient load — a full queue — may
                    # have drained)
                    excluded.clear()
                    replica = self._pick(excluded)
                if replica is None:
                    replica = self._wait_routable(request)
                    if replica is None:
                        self._fail(request, last_exc or self._shed_error(
                            SHED_NO_REPLICA,
                            "no ready replica (all dead, draining, or "
                            "reason-coded unready)"))
                        return
                try:
                    with obs.trace.span("router.dispatch", {
                        "replica": replica.name,
                        "attempt": request.attempts + 1,
                        "rows": request.n_rows,
                    }):
                        maybe_fail("router.dispatch")
                        replica.begin_dispatch()
                        try:
                            ctx = obs.trace.current()
                            result = replica.client.submit(
                                request.table,
                                # remaining time re-read NOW:
                                # _wait_routable may have blocked for
                                # seconds since the iteration's deadline
                                # check, and a stale clock would hand the
                                # replica budget the caller no longer has
                                deadline_ms=request.remaining_ms(now_s()),
                                timeout_s=_DISPATCH_TIMEOUT_S,
                                # kwarg only when keyed: default-tenant
                                # traffic must reach clients (and fakes)
                                # that predate the tenant parameter
                                **({"tenant": request.tenant}
                                   if request.tenant is not None else {}),
                                **({"trace_ctx": (ctx[0].trace_id,
                                                  ctx[0].span_id)}
                                   if ctx else {}),
                            )
                        finally:
                            replica.end_dispatch()
                except ServerOverloadedError as exc:
                    policy = shed_policy(exc.reason)
                    if policy == POLICY_ROUTE_AWAY:
                        # the replica said "I am degraded", not "I am
                        # busy": out of rotation until a probe clears it
                        replica.mark_unready(exc.reason)
                    if policy == POLICY_FAIL or not self._budget(request):
                        self._tally(f"router.shed.{exc.reason}")
                        self._tally("router.shed")
                        obs.counter_add("router.shed")
                        obs.counter_add(f"router.shed.{exc.reason}")
                        self._fail(request, exc)
                        return
                    excluded.add(replica.name)
                    last_exc = exc
                    self._note_retry(replica.name, exc.reason)
                    continue
                except (ReplicaUnreachableError, InjectedFault) as exc:
                    if isinstance(exc, ReplicaUnreachableError):
                        self._note_unreachable(replica)
                    if not self._budget(request):
                        self._fail(request, exc)
                        return
                    excluded.add(replica.name)
                    last_exc = exc
                    self._note_retry(replica.name, type(exc).__name__)
                    continue
                except ReplicaRemoteError as exc:
                    # a real failure inside the replica's transform is
                    # deterministic for this request — no cross-replica
                    # retry
                    self._tally("router.failed_requests")
                    obs.counter_add("router.failed_requests")
                    self._fail(request, exc)
                    return
                except BaseException as exc:  # noqa: BLE001 - futures carry it
                    self._fail(request, exc)
                    return
                latency_ms = (now_s() - request.enqueued_at) * 1e3
                with self._counts_lock:
                    # under the tally lock: stats() sorts this deque from
                    # other threads, and a concurrent append would raise
                    # "deque mutated during iteration"
                    self._latencies.append(latency_ms)
                obs.observe("router.request_latency_ms", latency_ms)
                self._tally("router.served_requests")
                self._tally("router.served_rows", result.num_rows)
                obs.counter_add("router.served_requests")
                if req_trace is not None:
                    # end the root BEFORE resolving the future (the
                    # server-side discipline) and backfill the trace id
                    # onto the result so callers can correlate without
                    # tailing span files
                    req_trace.end(status="ok", attrs={
                        "replica": replica.name, "version": result.version,
                    })
                    if getattr(result, "trace_id", None) is None:
                        result.trace_id = req_trace.trace_id
                if not request.future.cancelled():
                    request.future.set_result(result)
                return

    def _budget(self, request: _RouterRequest) -> bool:
        """Consume one retry; False when the request is out of budget
        (``FMT_ROUTER_RETRIES`` cross-replica retries per request)."""
        request.attempts += 1
        return request.attempts <= self.config.retries

    def _note_retry(self, replica_name: str, why: str) -> None:
        self._tally("router.retries")
        obs.counter_add("router.retries")
        obs.flight.record("router.retry", replica=replica_name, why=why)

    def _fail(self, request: _RouterRequest,
              exc: BaseException) -> None:
        req_trace = getattr(request, "trace", None)
        if req_trace is not None:
            if isinstance(exc, ServerOverloadedError):
                req_trace.end(status="shed", attrs={
                    "shed_reason": getattr(exc, "reason", "")})
            else:
                req_trace.end(status="error",
                              attrs={"error": type(exc).__name__})
        if not request.future.done():
            request.future.set_exception(exc)

    def _pick(self, excluded: set) -> Optional[_Replica]:
        """Power-of-two-choices among routable replicas: two random
        candidates, the lower observed load wins — near-optimal balance
        with O(1) work and no global scan under a lock.

        Liveness is re-checked HERE, not just on the poll loop: a
        replica's last probe may be stale (on a starved box the scrape
        loop can fall seconds behind), but ``waitpid`` is a microsecond
        syscall — a killed replica must never be picked on stale health,
        and noticing its corpse here starts the respawn immediately."""
        candidates = []
        for replica in self._replicas_snapshot():
            if replica is None or replica.is_dead():
                continue
            # liveness outranks health: a corpse must enter the respawn
            # path even when a stale probe already marked it unready
            if (replica.process is not None
                    and replica.process.poll_dead() is not None):
                self._kick_death(replica)
                continue
            if replica.routable() and replica.name not in excluded:
                candidates.append(replica)
        if not candidates:
            return None
        if len(candidates) == 1:
            return candidates[0]
        a, b = random.sample(candidates, 2)
        return a if a.load() <= b.load() else b

    def _kick_death(self, replica: _Replica) -> None:
        """Route a corpse discovered outside the poll loop into the
        death/respawn path (idempotent under the claim guard)."""
        index = self._index_of(replica)
        if index is not None:
            self._on_replica_death(
                replica=replica, index=index,
                why=f"exit {replica.process.poll_dead()}")

    def _wait_routable(self, request: _RouterRequest,
                       timeout_s: float = 5.0) -> Optional[_Replica]:
        """Brief grace for a transiently empty rotation (a respawn or a
        breaker cooldown mid-flight), bounded by the request deadline."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if request.expired(now_s()):
                return None
            self._sweep_liveness()
            replica = self._pick(set())
            if replica is not None:
                return replica
            time.sleep(0.01)
        return None

    def _shed_error(self, reason: str, detail: str,
                    trace_id: Optional[str] = None) -> ServerOverloadedError:
        self._tally("router.shed")
        self._tally(f"router.shed.{reason}")
        obs.counter_add("router.shed")
        obs.counter_add(f"router.shed.{reason}")
        obs.flight.record("router.shed", reason=reason, detail=detail)
        return ServerOverloadedError(reason, detail, trace_id=trace_id)

    # -- supervision (poll loop) ---------------------------------------------

    def _poll_loop(self) -> None:
        interval = max(self.config.poll_ms, 1.0) / 1e3
        beat = 0
        while not self._poll_stop.wait(timeout=interval):
            beat += 1
            # liveness first, health second: the waitpid sweep costs
            # microseconds and must never queue behind HTTP probes (on a
            # starved box one slow /metrics scrape is seconds)
            self._sweep_liveness()
            # readiness every beat; the queue-depth /metrics scrape —
            # rendering the child's whole registry, the expensive half —
            # on a slower cadence (the in-flight counter keeps the
            # balancer current between scrapes)
            depth = beat % _DEPTH_SCRAPE_EVERY == 0
            for index, replica in enumerate(self._replicas_snapshot()):
                if replica is None or replica.is_dead():
                    continue
                if not replica.try_begin_probe():
                    continue  # its previous probe is still in flight
                # one short-lived thread per probe: a wedged replica's
                # probe timeout stalls only itself — the survivors'
                # health keeps refreshing at the polled cadence
                threading.Thread(
                    target=self._probe_replica,
                    args=(index, replica, depth),
                    name=f"fmt-router-probe-{index}", daemon=True,
                ).start()
            ready = sum(1 for r in self._replicas_snapshot()
                        if r is not None and r.routable())
            obs.gauge_set("router.replicas_ready", float(ready))

    def _probe_replica(self, index: int, replica: _Replica,
                       depth: bool) -> None:
        try:
            while True:
                try:
                    replica.mark_probe(replica.client.probe(depth=depth))
                    return
                except Exception:  # noqa: BLE001 - the probe must not escape
                    # ANY probe failure (unreachable, torn response, a
                    # future probe bug) reads as a strike, never as a
                    # dead probe thread — a silent supervisor is the one
                    # failure mode a supervisor must not have
                    failures = replica.note_probe_failure()
                    if (replica.process is None
                            and failures >= _PROBE_FAILURE_DEBOUNCE):
                        self._on_replica_death(index, replica,
                                               "probe unreachable")
                        return
                    if (failures >= self.config.scrape_strikes
                            or replica.is_dead()):
                        return  # struck out: out of rotation until a
                        # probe succeeds again
                    # below the strike count the replica KEPT its slot
                    # in rotation — re-probe after a short jittered
                    # delay instead of spending a full poll interval
                    # per strike (a blackholed scrape should cost
                    # milliseconds of uncertainty, not seconds)
                    delay = min(max(self.config.poll_ms, 1.0) / 1e3, 0.25)
                    if self._poll_stop.wait(
                            timeout=delay * random.uniform(0.5, 1.5)):
                        return
        finally:
            replica.end_probe()

    def _sweep_liveness(self) -> None:
        """``waitpid`` every process-backed replica; corpses go straight
        to the death/respawn path.  Called from the poll loop and from
        request paths that would otherwise wait on stale health."""
        for index, replica in enumerate(self._replicas_snapshot()):
            if (replica is not None and not replica.is_dead()
                    and replica.process is not None
                    and replica.process.poll_dead() is not None):
                self._on_replica_death(
                    index, replica,
                    f"exit {replica.process.poll_dead()}")

    def _note_unreachable(self, replica: _Replica) -> None:
        """A dispatch hit a dead socket: the fastest death signal there
        is.  Mark and let the poll loop confirm + respawn."""
        self._tally("router.dispatch_unreachable")
        obs.counter_add("router.dispatch_unreachable")
        replica.mark_unready("unreachable")
        if replica.process is not None and not replica.process.alive():
            index = self._index_of(replica)
            if index is not None:
                self._on_replica_death(index, replica, "dead pipe")

    def _index_of(self, replica: _Replica) -> Optional[int]:
        with self._rep_lock:
            for i, r in enumerate(self._slots):
                if r is replica:
                    return i
        return None

    def _on_replica_death(self, index: int, replica: _Replica,
                          why: str) -> None:
        """A replica is gone: eject it, count it, respawn a replacement
        on a supervisor thread (boot takes seconds — the poll loop must
        keep probing the survivors meanwhile)."""
        with self._cond:
            stopping = self._stopping
        if stopping:
            # a corpse noticed DURING shutdown is the shutdown's own
            # SIGTERM, not a death: no counter, no flight event, no
            # respawn — a clean stop must not read as a crash
            replica.mark_dead(why)
            return
        with self._rep_lock:
            if index in self._respawning or self._slots[index] is not replica:
                return  # another thread already claimed this death
            self._respawning.add(index)
            self._death_times.setdefault(
                index, deque(maxlen=32)).append(time.monotonic())
        replica.mark_dead(why)
        exit_status = (replica.process.poll_dead()
                       if replica.process is not None else None)
        self._tally("router.replica_deaths")
        obs.counter_add("router.replica_deaths")
        obs.flight.record("router.replica_death", replica=replica.name,
                          why=why)
        if replica.process is not None:
            replica.process.stop(grace_s=0.1)  # reap the zombie
        threading.Thread(target=self._respawn, args=(index, exit_status),
                         name=f"fmt-router-respawn-{index}",
                         daemon=True).start()

    def _crashloop_backoff(self, index: int,
                           exit_status) -> Optional[float]:
        """Crash-loop gate for one slot's respawn: ``None`` = spawn
        immediately; a float = the slot just entered quarantine — the
        respawn must sit out that many seconds first.  A slot whose
        replica died ``FMT_ROUTER_CRASHLOOP_MAX`` times inside the
        window is looping on something a hot respawn cannot fix (bad
        artifact, dead dependency, OOM killer) — parking it with
        exponential backoff keeps the survivors' poll loop and the
        spawn path from burning on a doomed slot.  Quarantines are
        observable: ``router.crashloops`` counter, quarantine state in
        :meth:`stats`, and a ``router.crashloop`` flight dump naming
        the slot and exit status."""
        window = self.config.crashloop_window_s
        limit = self.config.crashloop_max
        now = time.monotonic()
        with self._rep_lock:
            deaths = self._death_times.setdefault(index, deque(maxlen=32))
            while deaths and now - deaths[0] > window:
                deaths.popleft()
            if limit < 1 or len(deaths) < limit:
                # below the threshold (or detection disabled): a prior
                # quarantine episode ended in a replica that outlived
                # the window, so the slot's slate is clean again
                self._quarantine.pop(index, None)
                return None
            episodes = self._quarantine.get(index, {}).get("episodes", 0) + 1
            backoff = min(_CRASHLOOP_BACKOFF_S * (2 ** (episodes - 1)),
                          _CRASHLOOP_BACKOFF_CAP_S)
            self._quarantine[index] = {
                "episodes": episodes,
                "backoff_s": backoff,
                "until": now + backoff,
            }
            deaths_in_window = len(deaths)
        self._tally("router.crashloops")
        obs.counter_add("router.crashloops")
        obs.flight.record("router.crashloop", slot=index,
                          exit_status=exit_status,
                          deaths_in_window=deaths_in_window,
                          backoff_s=backoff)
        obs.flight.dump("router_crashloop", extra={
            "slot": index, "exit_status": exit_status,
            "deaths_in_window": deaths_in_window, "backoff_s": backoff,
        })
        return backoff

    def _respawn(self, index: int, exit_status=None) -> None:
        import warnings

        try:
            backoff = self._crashloop_backoff(index, exit_status)
            if backoff is not None and self._poll_stop.wait(timeout=backoff):
                return  # shutdown interrupted the quarantine sleep
            for attempt in range(1, _MAX_SPAWN_ATTEMPTS + 1):
                try:
                    replacement = self._make_replica(index)
                except BaseException as exc:  # noqa: BLE001 - bounded retry
                    self._tally("router.spawn_failures")
                    obs.counter_add("router.spawn_failures")
                    if attempt == _MAX_SPAWN_ATTEMPTS:
                        warnings.warn(
                            f"replica slot {index} abandoned after "
                            f"{attempt} spawn failures "
                            f"({type(exc).__name__}: {exc}); the fleet "
                            "continues on the survivors",
                            RuntimeWarning, stacklevel=2,
                        )
                        obs.flight.record("router.slot_abandoned",
                                          slot=index,
                                          error=type(exc).__name__)
                        return
                    time.sleep(0.5 * attempt)
                    continue
                with self._cond:
                    stopping = self._stopping
                if stopping:
                    # the router shut down while this replacement was
                    # booting: installing it would orphan a live child
                    # nobody supervises — stop it instead
                    self._stop_backend(replacement)
                    return
                with self._rep_lock:
                    self._slots[index] = replacement
                self._tally("router.respawns")
                obs.counter_add("router.respawns")
                # cold-start resilience (ISSUE 18): stamp how much of the
                # warm-artifact ladder the replacement inherits — a 0 here
                # on a fleet that should be warm is the first thing an
                # operator chasing a post-crash latency spike needs to see
                from flink_ml_tpu.serving import warmstart

                with self._rep_lock:
                    source_path = self._source_path
                warm = warmstart.inherited_manifest_entries(source_path)
                if warm:
                    self._tally("router.respawns_warm")
                    obs.counter_add("router.respawns_warm")
                obs.flight.record("router.respawn", slot=index,
                                  replica=replacement.name,
                                  warm_entries=warm)
                return
        finally:
            with self._rep_lock:
                self._respawning.discard(index)

    # -- elastic membership (round 22) ---------------------------------------

    def _drain_replica(self, replica: _Replica) -> bool:
        """The drain contract a rolling deploy and a scale-down share:
        stop routing to the replica, then wait out its router-originated
        in-flight work, bounded by ``FMT_ROUTER_DRAIN_TIMEOUT_S``.
        False on timeout — the replica is LEFT DRAINING; the caller
        either re-admits it (``set_draining(False)``) or terminates it."""
        replica.set_draining(True)
        return replica.wait_drained(self.config.drain_timeout_s)

    def add_replica(self) -> Optional[str]:
        """Grow the fleet by one replica through the standard spawn path
        (the child inherits the sealed warmstart manifest, so its first
        request stays warm).  Returns the new replica's name, or None
        when membership can't change right now (router stopping, or a
        rolling deploy holds the fleet — a roll iterates a fleet
        snapshot and must not race a slot appearing mid-roll).  Raises
        on spawn failure; the fleet is unchanged either way (the
        reserved slot stays a tombstone every iterator already skips)."""
        if not self._deploy_lock.acquire(blocking=False):
            return None
        try:
            with self._cond:
                if self._closed or self._stopping:
                    return None
            with self._rep_lock:
                index = len(self._slots)
                self._slots.append(None)     # reserve the slot index...
                self._respawning.add(index)  # ...and claim it (shutdown
                # waits out every claimed slot before its final sweep)
            try:
                replica = self._make_replica(index)
            except BaseException:
                with self._rep_lock:
                    self._respawning.discard(index)
                self._tally("router.spawn_failures")
                obs.counter_add("router.spawn_failures")
                raise
            with self._cond:
                stopping = self._stopping
            with self._rep_lock:
                self._respawning.discard(index)
                if not stopping:
                    self._slots[index] = replica
            if stopping:
                # shut down while the child booted: installing it would
                # orphan a live process nobody supervises
                self._stop_backend(replica)
                return None
            self._tally("router.replicas_added")
            obs.counter_add("router.replicas_added")
            obs.gauge_set("router.replicas", float(self.fleet_size()))
            obs.flight.record("router.replica_added", slot=index,
                              replica=replica.name)
            return replica.name
        finally:
            self._deploy_lock.release()

    def remove_replica(self) -> Optional[str]:
        """Shrink the fleet by one replica, drain-aware: the least
        loaded routable replica stops taking new traffic, its in-flight
        requests finish (the same :meth:`_drain_replica` contract a
        rolling deploy uses — zero caller-visible failures), then it is
        terminated (SIGTERM: the replica drains its own queue and exits
        0) and its slot tombstoned so every index stays stable.
        Returns the removed replica's name; None when nothing is
        removable — a lone routable replica is never removed, a busy
        replica whose drain timed out is re-admitted, and a rolling
        deploy holds the fleet."""
        if not self._deploy_lock.acquire(blocking=False):
            return None
        try:
            with self._cond:
                if self._closed or self._stopping:
                    return None
            candidates = [r for r in self._replicas_snapshot()
                          if r is not None and r.routable()]
            if len(candidates) <= 1:
                return None
            victim = min(candidates, key=lambda r: r.load())
            if not self._drain_replica(victim):
                victim.set_draining(False)  # busy is not removable
                self._tally("router.remove_drain_timeouts")
                obs.counter_add("router.remove_drain_timeouts")
                return None
            index = self._index_of(victim)
            with self._rep_lock:
                removable = (index is not None
                             and index not in self._respawning
                             and self._slots[index] is victim)
                if removable:
                    self._slots[index] = None
            if not removable:
                # the victim died mid-drain and its death was claimed,
                # or the fleet changed under us: re-admit and report
                # nothing removed (the supervisor owns the slot now)
                victim.set_draining(False)
                return None
            victim.mark_dead("removed")
            self._stop_backend(victim)
            self._tally("router.replicas_removed")
            obs.counter_add("router.replicas_removed")
            obs.gauge_set("router.replicas", float(self.fleet_size()))
            obs.flight.record("router.replica_removed", slot=index,
                              replica=victim.name)
            return victim.name
        finally:
            self._deploy_lock.release()

    def fleet_size(self) -> int:
        """Occupied slots (live, booting, or awaiting respawn) — the
        membership count scale decisions measure against; tombstoned
        (removed/abandoned) slots don't count."""
        with self._rep_lock:
            return sum(1 for r in self._slots if r is not None)

    def quarantined_count(self) -> int:
        """Slots currently parked by the crash-loop quarantine — the
        autoscaler reads these as capacity loss and compensates."""
        now = time.monotonic()
        with self._rep_lock:
            return sum(1 for q in self._quarantine.values()
                       if q.get("until", 0.0) > now)

    def fleet_health(self) -> dict:
        """One autoscaler observation off state the router already
        maintains — the poll loop's probes and the door tallies, no
        extra scrape.  ``burn_seen`` distinguishes "no SLO burning"
        from "no burn data at all" (a replica with no judged SLO window
        reports an empty ``burn_rates``), and ``probe_suspect`` counts
        replicas whose unreadiness is a failed/unreachable probe rather
        than a reason-coded verdict — fail-closed inputs a scale-down
        decision must treat as vetoes, never as idleness."""
        self._sweep_liveness()
        snaps = [r.snapshot() for r in self._replicas_snapshot()
                 if r is not None]
        with self._cond:
            queued = self._queued_rows
        with self._counts_lock:
            requests = float(self._counts.get("router.requests", 0))
            shed = float(self._counts.get("router.shed", 0))
        ready = live = probe_suspect = 0
        max_burn, burn_seen = 0.0, False
        for snap in snaps:
            if not snap["dead"]:
                live += 1
            if snap["ready"] and not snap["draining"] and not snap["dead"]:
                ready += 1
            rates = snap.get("burn_rates") or {}
            if rates:
                burn_seen = True
                max_burn = max(max_burn, max(rates.values()))
            if not snap["ready"] and any(
                    r in ("unreachable", "probe_error")
                    for r in snap["reasons"]):
                probe_suspect += 1
        return {
            "size": len(snaps),
            "live": live,
            "ready": ready,
            "quarantined": self.quarantined_count(),
            "queued_rows": int(queued),
            "requests": requests,
            "shed": shed,
            "max_burn_rate": max_burn,
            "burn_seen": burn_seen,
            "probe_suspect": probe_suspect,
        }

    # -- rolling deploy ------------------------------------------------------

    def deploy(self, path: str, version: str) -> dict:
        """Zero-downtime rolling deploy: one replica at a time — drain,
        swap (the replica-side versioning.py contract), await readiness,
        re-admit — while the rest of the fleet serves.  Returns the
        per-replica status dict; raises :class:`RollingDeployError` on
        the first *deploy* failure (that replica kept its old version —
        the swap contract IS the rollback — and the rest of the fleet
        stays on the known-good version; the partial status is preserved
        at :attr:`deploy_status`).  A replica that turns out to be DEAD
        when the roll reaches it is not a deploy failure: it enters the
        respawn path (which boots the roll's target version) and the
        roll continues over the survivors."""
        with self._deploy_lock:
            self._tally("router.rolling_deploys")
            obs.counter_add("router.rolling_deploys")
            with self._rep_lock:
                previous_path = self._source_path
                previous = self._source_version
                # respawns mid-roll must boot the roll's TARGET: a slot
                # that dies while the fleet converges on `version` would
                # otherwise come back on the old one and stay there.
                # Reverted below if the roll fails.  Updated BEFORE the
                # liveness sweep — the sweep itself can start a respawn,
                # which must already see the target.
                self._source_path = str(path)
                self._source_version = str(version)
            self._sweep_liveness()  # roll over the LIVE fleet, not corpses
            status: dict = {"version": str(version), "previous": previous,
                            "ok": False, "replicas": []}
            obs.flight.record("router.rolling_deploy", version=str(version),
                              previous=previous)
            try:
                for replica in self._replicas_snapshot():
                    if replica is None or replica.is_dead():
                        status["replicas"].append({
                            "replica": getattr(replica, "name",
                                               "<empty slot>"),
                            "outcome": "skipped_dead",
                        })
                        continue
                    entry = {"replica": replica.name}
                    try:
                        if not self._drain_replica(replica):
                            entry["outcome"] = "drain_timeout"
                            status["replicas"].append(entry)
                            raise RollingDeployError(status)
                        try:
                            active = replica.client.deploy(
                                str(path), str(version))
                            if not self._await_ready(replica):
                                raise ReplicaUnreachableError(
                                    f"{replica.name} died awaiting "
                                    "post-deploy readiness")
                        except ReplicaUnreachableError as exc:
                            # the replica is GONE, not refusing the
                            # artifact: hand it to the supervisor (the
                            # respawn boots the target version) and keep
                            # rolling the survivors
                            entry["outcome"] = "died"
                            entry["detail"] = str(exc)
                            status["replicas"].append(entry)
                            self._sweep_liveness()
                            continue
                        except BaseException as exc:
                            # a real deploy refusal (corrupt artifact,
                            # broken warmup): this replica already
                            # rolled back to its old version — stop the
                            # roll so the fleet stays known-good.  A
                            # wire-wrapped refusal names the REPLICA-side
                            # exception (ModelIntegrityError), not the
                            # envelope.
                            entry["outcome"] = "failed"
                            entry["error"] = (
                                exc.remote_type
                                if isinstance(exc, ReplicaRemoteError)
                                else type(exc).__name__)
                            entry["detail"] = str(exc)
                            status["replicas"].append(entry)
                            raise RollingDeployError(status) from exc
                    finally:
                        replica.set_draining(False)
                    replica.set_version(active)
                    entry["outcome"] = "deployed"
                    entry["active_version"] = active
                    status["replicas"].append(entry)
            except RollingDeployError:
                with self._rep_lock:
                    self._source_path = previous_path
                    self._source_version = previous
                self._finish_deploy(status, ok=False)
                raise
            self._finish_deploy(status, ok=True)
            return status

    def _finish_deploy(self, status: dict, ok: bool) -> None:
        status["ok"] = ok
        with self._rep_lock:
            self._deploy_status = status
        obs.flight.record("router.rolling_deploy_done",
                          version=status["version"], ok=ok)
        if not ok:
            self._tally("router.deploy_failures")
            obs.counter_add("router.deploy_failures")
            obs.flight.dump("router_partial_deploy")

    def _await_ready(self, replica: _Replica,
                     timeout_s: float = 60.0) -> bool:
        """Post-swap re-admission gate: the replica must answer
        ``/readyz`` 200 (its warmup compiled, its deploy flag cleared)
        before it takes fresh traffic again.  Returns False when the
        replica DIED while waiting (the caller hands it to the
        supervisor); raises on a live replica that stays unready."""
        deadline = time.monotonic() + timeout_s
        last: dict = {}
        while time.monotonic() < deadline:
            if (replica.process is not None
                    and replica.process.poll_dead() is not None):
                return False
            try:
                last = replica.client.probe()
            except ReplicaUnreachableError:
                last = {"ready": False, "reasons": ["unreachable"]}
            if last.get("ready"):
                replica.mark_probe(last)
                return True
            time.sleep(0.02)
        raise RuntimeError(
            f"{replica.name} never returned to ready after deploy "
            f"(last reasons: {last.get('reasons')})"
        )

    # -- introspection -------------------------------------------------------

    @property
    def active_version(self) -> str:
        with self._rep_lock:
            return self._source_version

    @property
    def deploy_status(self) -> Optional[dict]:
        """The last rolling deploy's per-replica outcome (partial on
        failure) — what an operator reads after a RollingDeployError."""
        with self._rep_lock:
            return self._deploy_status

    def _replicas_snapshot(self) -> List[Optional[_Replica]]:
        with self._rep_lock:
            return list(self._slots)

    @property
    def replicas(self) -> List[dict]:
        """Point-in-time fleet view: per-replica readiness, reasons,
        load, pid/addresses — the /statusz analog."""
        return [r.snapshot() for r in self._replicas_snapshot()
                if r is not None]

    def ready_count(self) -> int:
        self._sweep_liveness()  # stale health must not count a corpse
        return sum(1 for r in self._replicas_snapshot()
                   if r is not None and r.routable())

    def _tally(self, name: str, n: float = 1) -> None:
        with self._counts_lock:
            self._counts[name] += n

    def stats(self) -> dict:
        """THIS router's tallies plus request-latency quantiles — the
        shutdown report's payload, readable live (per-router by
        construction, like ``ModelServer.stats``)."""
        from flink_ml_tpu.obs.registry import sample_quantile

        with self._counts_lock:
            delta = {k: v for k, v in sorted(self._counts.items()) if v}
            samples = sorted(self._latencies)
        if samples:
            delta["latency_p50_ms"] = round(
                sample_quantile(samples, 0.50), 3)
            delta["latency_p99_ms"] = round(
                sample_quantile(samples, 0.99), 3)
        delta["active_version"] = self.active_version
        delta["replicas_ready"] = self.ready_count()
        delta["replicas"] = self.replicas
        quarantined = self.quarantined_count()
        if quarantined:
            now = time.monotonic()
            with self._rep_lock:
                delta["quarantined_slots"] = {
                    str(i): {"episodes": q["episodes"],
                             "backoff_s": q["backoff_s"],
                             "remaining_s": round(q["until"] - now, 3)}
                    for i, q in self._quarantine.items()
                    if q.get("until", 0.0) > now
                }
        return delta

    def _write_report(self) -> None:
        if not obs.enabled():
            return
        from flink_ml_tpu.obs.report import serving_report

        serving_report("ReplicaRouter", extra=self.stats())
