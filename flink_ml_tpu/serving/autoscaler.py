"""SLO-guarded fleet autoscaler: the elastic control loop over
:class:`~flink_ml_tpu.serving.router.ReplicaRouter` (ISSUE 19, ROADMAP
item 4 — the last control-plane gap).

Every signal an autoscaler needs already exists — ``slo.burn_rate.*``
gauges off the replicas' own monitors, scraped queue depth and
reason-coded ``/readyz`` off the router's poll loop, and a warmstart
store that makes spawning a replica cheap — yet fleet size was a static
``FMT_ROUTER_REPLICAS`` fixed at boot.  :class:`FleetAutoscaler` closes
the observe→decide→act cycle:

**Observe.**  One :meth:`ReplicaRouter.fleet_health` sample per tick —
state the router already maintains (ready/live/slot counts, crash-loop
quarantine, door queue depth, cumulative request/shed tallies) plus the
fleet-max ``slo.burn_rate.*`` the replicas expose through the STRICT
OpenMetrics scrape path their probes already ride.  No new scrape loop.

**Decide.**  Scale up *before* the p99 SLO burns: any replica's burn
rate at ``FMT_SCALE_UP_BURN``, sustained queue growth over
``FMT_SCALE_WINDOW_S``, or sheds inside the window each add one replica
to the target.  Scale down only on *sustained idle* — every sample
across ``FMT_SCALE_IDLE_WINDOWS`` windows must show an empty queue and
zero sheds, and the decision is fail-closed: a replica whose
unreadiness is a broken probe, a quarantined slot, or live traffic with
no judged burn data (the thin-SLO-window case — ``burning()`` under
``FMT_SLO_MIN_EVENTS`` arrivals says nothing, not "all clear") each
VETO the shrink.  Hysteresis is structural: the up threshold
(``FMT_SCALE_UP_BURN``) and down threshold (``FMT_SCALE_DOWN_BURN``)
are separate knobs, a post-action cooldown (``FMT_SCALE_COOLDOWN_S``)
rate-limits actions, and the idle horizon is several windows long — a
square wave at the threshold produces at most one scale event per
period (tested as such).

**Act.**  Growth goes through :meth:`ReplicaRouter.add_replica` (the
standard spawn path — the child inherits the sealed warmstart manifest,
so its first request stays warm); shrink through
:meth:`ReplicaRouter.remove_replica` (the rolling-deploy drain
contract: stop routing → wait in-flight → terminate — zero
caller-visible failures).  ``FMT_SCALE_WARM_SPARES`` keeps N spares
*above* target so a SIGTERM storm never drops serving capacity below
target while the router respawns; quarantined slots read as capacity
loss and are compensated the same way.

Every decision is observable: ``autoscaler.scale_ups`` /
``autoscaler.scale_downs`` / ``autoscaler.blocked.<reason>`` counters,
``autoscaler.target`` / ``autoscaler.actual`` gauges, flight events
carrying the triggering signal snapshot, an ``autoscaler`` section on
``/statusz``, and a decision span on the fleet trace timeline per
scale action.

Knobs (README.md, "Elastic fleet"): ``FMT_SCALE_MIN``,
``FMT_SCALE_MAX``, ``FMT_SCALE_UP_BURN``, ``FMT_SCALE_DOWN_BURN``,
``FMT_SCALE_WINDOW_S``, ``FMT_SCALE_IDLE_WINDOWS``,
``FMT_SCALE_COOLDOWN_S``, ``FMT_SCALE_WARM_SPARES``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Optional

from flink_ml_tpu import obs
from flink_ml_tpu.utils import knobs

__all__ = ["FleetAutoscaler", "ScalerConfig"]


@dataclass(frozen=True)
class ScalerConfig:
    """Resolved autoscaler knobs (environment defaults, overrides win)."""

    min_replicas: int = 1
    max_replicas: int = 8
    up_burn: float = 1.0
    down_burn: float = 0.5
    window_s: float = 30.0
    idle_windows: int = 3
    cooldown_s: float = 60.0
    warm_spares: int = 0

    @classmethod
    def from_env(cls, min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 up_burn: Optional[float] = None,
                 down_burn: Optional[float] = None,
                 window_s: Optional[float] = None,
                 idle_windows: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 warm_spares: Optional[int] = None) -> "ScalerConfig":
        cfg = cls(
            min_replicas=int(min_replicas if min_replicas is not None
                             else knobs.knob_int("FMT_SCALE_MIN")),
            max_replicas=int(max_replicas if max_replicas is not None
                             else knobs.knob_int("FMT_SCALE_MAX")),
            up_burn=float(up_burn if up_burn is not None
                          else knobs.knob_float("FMT_SCALE_UP_BURN")),
            down_burn=float(down_burn if down_burn is not None
                            else knobs.knob_float("FMT_SCALE_DOWN_BURN")),
            window_s=float(window_s if window_s is not None
                           else knobs.knob_float("FMT_SCALE_WINDOW_S")),
            idle_windows=int(idle_windows if idle_windows is not None
                             else knobs.knob_int("FMT_SCALE_IDLE_WINDOWS")),
            cooldown_s=float(cooldown_s if cooldown_s is not None
                             else knobs.knob_float("FMT_SCALE_COOLDOWN_S")),
            warm_spares=int(warm_spares if warm_spares is not None
                            else knobs.knob_int("FMT_SCALE_WARM_SPARES")),
        )
        if cfg.min_replicas < 1 or cfg.max_replicas < cfg.min_replicas:
            raise ValueError(
                f"fleet bounds must satisfy 1 <= min <= max "
                f"(got {cfg.min_replicas}..{cfg.max_replicas})"
            )
        if cfg.window_s <= 0 or cfg.idle_windows < 1:
            raise ValueError(
                f"window_s must be > 0 and idle_windows >= 1 "
                f"(got {cfg.window_s}, {cfg.idle_windows})"
            )
        if cfg.warm_spares < 0:
            raise ValueError(f"warm_spares must be >= 0 "
                             f"(got {cfg.warm_spares})")
        return cfg


class FleetAutoscaler:
    """Elastic control loop over one :class:`ReplicaRouter`.

    ``FleetAutoscaler(router).start()`` samples the fleet every tick and
    converges occupied slots on ``target + warm_spares``, where
    ``target`` moves one step per decision inside
    ``[FMT_SCALE_MIN, FMT_SCALE_MAX]``.  Use as a context manager or
    call :meth:`stop`.  Tests drive :meth:`step` directly with an
    injected ``now_fn`` — every decision is a pure function of the
    sample history and the clock, so hysteresis is provable without
    sleeping.
    """

    def __init__(self, router, *,
                 min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 up_burn: Optional[float] = None,
                 down_burn: Optional[float] = None,
                 window_s: Optional[float] = None,
                 idle_windows: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 warm_spares: Optional[int] = None,
                 tick_s: Optional[float] = None,
                 now_fn=time.monotonic):
        self._router = router
        self._cfg = ScalerConfig.from_env(
            min_replicas=min_replicas, max_replicas=max_replicas,
            up_burn=up_burn, down_burn=down_burn, window_s=window_s,
            idle_windows=idle_windows, cooldown_s=cooldown_s,
            warm_spares=warm_spares,
        )
        self._now = now_fn
        #: sample cadence: several observations per window (a trend
        #: needs points), bounded away from a busy-loop
        self._tick_s = float(tick_s if tick_s is not None
                             else max(min(self._cfg.window_s / 4.0, 2.0),
                                      0.05))
        self._mu = threading.Lock()
        self._samples: Deque[dict] = deque()
        cfg = self._cfg
        initial = getattr(router, "fleet_size", lambda: cfg.min_replicas)()
        self._target = min(max(int(initial) - cfg.warm_spares,
                               cfg.min_replicas), cfg.max_replicas)
        self._last_action_t: Optional[float] = None
        self._ups = 0
        self._downs = 0
        self._events: Deque[dict] = deque(maxlen=64)
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._status_key = None

    @property
    def config(self) -> ScalerConfig:
        return self._cfg

    @property
    def target(self) -> int:
        """Desired serving capacity (spares ride on top of this)."""
        with self._mu:
            return self._target

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "FleetAutoscaler":
        with self._mu:
            if self._thread is not None:
                return self
            self._stop_evt.clear()
            thread = threading.Thread(target=self._loop,
                                      name="fmt-autoscaler", daemon=True)
            self._thread = thread
        if self._status_key is None:
            from flink_ml_tpu.obs import telemetry
            self._status_key = telemetry.register_status(
                "autoscaler", self._status_section)
        thread.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        with self._mu:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=30.0)
        if self._status_key is not None:
            from flink_ml_tpu.obs import telemetry
            telemetry.unregister_status(self._status_key)
            self._status_key = None

    def __enter__(self) -> "FleetAutoscaler":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop()
        return False

    def _loop(self) -> None:
        while not self._stop_evt.wait(timeout=self._tick_s):
            try:
                self.step()
            except Exception:  # noqa: BLE001 - the control loop must survive
                # a failed observation or a racing shutdown is a skipped
                # beat, never a dead supervisor
                obs.counter_add("autoscaler.errors")

    # -- observe → decide → act ----------------------------------------------

    def step(self) -> dict:
        """One control cycle; returns the decision record (also the
        flight-event payload when the cycle acted or was blocked)."""
        now = self._now()
        health = self._router.fleet_health()
        with self._mu:
            sample = self._observe(now, health)
            actual = int(health["size"]) - int(health["quarantined"])
            decision = {"t": now, "action": "hold", "reason": "",
                        "target": self._target, "actual": actual,
                        "signal": sample}
            up_reason = self._up_signal(now)
            down_ok, down_block = self._down_signal(now)
            # target moves one step per decision, cooldown-gated like
            # the act itself: a brief burst must not ratchet the target
            # to max and keep the fleet growing after traffic subsides
            if up_reason and self._target >= self._cfg.max_replicas:
                self._note_blocked_locked(decision, "at_max", up_reason)
            elif up_reason and self._in_cooldown_locked(now):
                self._note_blocked_locked(decision, "cooldown", up_reason)
            elif up_reason:
                self._target += 1
                decision["reason"] = up_reason
            elif down_ok and self._target > self._cfg.min_replicas:
                if self._in_cooldown_locked(now):
                    self._note_blocked_locked(decision, "cooldown",
                                              "scale_down")
                else:
                    self._target -= 1
                    decision["reason"] = "sustained_idle"
            elif down_block is not None:
                # idleness was plausible but a fail-closed input vetoed
                # the shrink: a broken probe, a quarantined slot, or
                # traffic with no judged burn window must never read as
                # "safe to remove capacity"
                self._note_blocked_locked(decision, down_block, "scale_down")
            desired = self._target + self._cfg.warm_spares
            decision["target"] = self._target
        if actual < desired:
            self._try_scale(decision, "up", now,
                            decision["reason"] or "capacity_loss")
        elif actual > desired and down_ok:
            self._try_scale(decision, "down", now,
                            decision["reason"] or "sustained_idle")
        obs.gauge_set("autoscaler.target", float(decision["target"]))
        obs.gauge_set("autoscaler.actual", float(actual))
        if decision["action"] != "hold" or decision.get("blocked"):
            with self._mu:
                self._events.append(decision)
        return decision

    def _observe(self, now: float, health: dict) -> dict:
        sample = {
            "t": now,
            "queued": int(health.get("queued_rows", 0)),
            "ready": int(health.get("ready", 0)),
            "size": int(health.get("size", 0)),
            "quarantined": int(health.get("quarantined", 0)),
            "requests": float(health.get("requests", 0.0)),
            "shed": float(health.get("shed", 0.0)),
            "burn": float(health.get("max_burn_rate", 0.0)),
            "burn_seen": bool(health.get("burn_seen", False)),
            "probe_suspect": int(health.get("probe_suspect", 0)),
        }
        self._samples.append(sample)
        # retain one window beyond the idle horizon so coverage checks
        # ("do my samples actually span the window?") stay answerable
        horizon = self._cfg.window_s * (self._cfg.idle_windows + 1)
        while (len(self._samples) > 2
               and self._samples[0]["t"] < now - horizon):
            self._samples.popleft()
        return sample

    def _up_signal(self, now: float) -> Optional[str]:
        """The scale-up triggers, checked most-urgent first.  Burn rate
        acts on the LATEST sample (an SLO already burning pays for every
        tick of delay); queue growth and sheds must sustain across
        ``window_s`` (one bursty sample must not flap the fleet)."""
        cfg = self._cfg
        latest = self._samples[-1]
        if latest["burn_seen"] and latest["burn"] >= cfg.up_burn:
            return "slo_burn"
        if self._samples[0]["t"] > now - cfg.window_s:
            return None  # history doesn't span the window yet
        window = [s for s in self._samples if s["t"] >= now - cfg.window_s]
        if not window:
            return None
        if (all(s["queued"] > 0 for s in window)
                and window[-1]["queued"] >= window[0]["queued"]):
            return "queue_growth"
        if window[-1]["shed"] > window[0]["shed"]:
            return "shed"
        return None

    def _down_signal(self, now: float):
        """``(ok, block_reason)``: ``ok`` means sustained idle held for
        the full horizon with every fail-closed veto clear.  A non-None
        ``block_reason`` means idleness was otherwise plausible but a
        veto stopped the shrink — that's a counted, observable decision;
        plain traffic is neither (an active fleet isn't "blocked from
        scaling down", it's just busy)."""
        cfg = self._cfg
        horizon = cfg.window_s * cfg.idle_windows
        if self._samples[0]["t"] > now - horizon:
            return False, None  # not enough history: patience, not a veto
        win = [s for s in self._samples if s["t"] >= now - horizon]
        if not win:
            return False, None
        if any(s["queued"] > 0 for s in win):
            return False, None
        if win[-1]["shed"] > win[0]["shed"]:
            return False, None
        if any(s["quarantined"] > 0 for s in win):
            return False, "quarantine"
        if any(s["probe_suspect"] > 0 for s in win):
            return False, "probe_error"
        if win[-1]["requests"] > win[0]["requests"]:
            # requests flowed this horizon (empty queue = fleet keeping
            # up): shrinking needs positive evidence the SLO sits well
            # below the DOWN threshold — and a thin SLO window that
            # judged nothing provides none
            if not all(s["burn_seen"] for s in win):
                return False, "no_burn_signal"
            if max(s["burn"] for s in win) >= cfg.down_burn:
                return False, None  # hysteresis: burn not low enough
        return True, None

    def _in_cooldown_locked(self, now: float) -> bool:
        return (self._last_action_t is not None
                and now - self._last_action_t < self._cfg.cooldown_s)

    def _note_blocked_locked(self, decision: dict, reason: str,
                             wanted: str) -> None:
        decision.setdefault("blocked", []).append(reason)
        obs.counter_add(f"autoscaler.blocked.{reason}")
        obs.flight.record("autoscaler.blocked", reason=reason,
                          wanted=wanted, target=self._target,
                          signal=decision["signal"])

    def _try_scale(self, decision: dict, direction: str, now: float,
                   reason: str) -> None:
        """One act attempt toward ``target + spares`` — cooldown-gated,
        traced as a decision span on the fleet timeline, and recorded
        with the triggering signal snapshot whichever way it goes."""
        with self._mu:
            if self._in_cooldown_locked(now):
                self._note_blocked_locked(decision, "cooldown", reason)
                return
        req = obs.trace.start_request("autoscaler.scale", {
            "direction": direction, "reason": reason,
            "target": decision["target"],
        })
        name = None
        try:
            if direction == "up":
                name = self._router.add_replica()
            else:
                name = self._router.remove_replica()
        except BaseException:
            with self._mu:
                self._note_blocked_locked(decision, "spawn_failed",
                                          reason)
            if req is not None:
                req.end(status="error", attrs={"reason": reason})
            return
        if req is not None:
            req.end(status="ok" if name else "blocked",
                    attrs={"replica": name or ""})
        with self._mu:
            if name is None:
                # the router declined (deploy in progress, lone replica,
                # drain timeout): counted, retried after the next tick
                self._note_blocked_locked(decision, "router_busy", reason)
                return
            decision["action"] = direction
            decision["reason"] = reason
            decision["replica"] = name
            self._last_action_t = now
            if direction == "up":
                self._ups += 1
            else:
                self._downs += 1
        counter = ("autoscaler.scale_ups" if direction == "up"
                   else "autoscaler.scale_downs")
        obs.counter_add(counter)
        obs.flight.record("autoscaler.scale", direction=direction,
                          reason=reason, replica=name,
                          target=decision["target"],
                          signal=decision["signal"])

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            return {
                "target": self._target,
                "scale_ups": self._ups,
                "scale_downs": self._downs,
                "last_action_t": self._last_action_t,
            }

    def _status_section(self) -> dict:
        """The ``/statusz`` ``autoscaler`` section: configuration,
        position, and the recent decision tail — what an operator needs
        to answer "why is the fleet this size?" without log archaeology."""
        cfg = self._cfg
        now = self._now()
        with self._mu:
            return {
                "target": self._target,
                "bounds": [cfg.min_replicas, cfg.max_replicas],
                "warm_spares": cfg.warm_spares,
                "up_burn": cfg.up_burn,
                "down_burn": cfg.down_burn,
                "window_s": cfg.window_s,
                "idle_windows": cfg.idle_windows,
                "cooldown_s": cfg.cooldown_s,
                "in_cooldown": self._in_cooldown_locked(now),
                "scale_ups": self._ups,
                "scale_downs": self._downs,
                "recent": [dict(e, signal=None) for e in
                           list(self._events)[-8:]],
            }
