"""Guarded continuous learning: online train -> validated candidate ->
auto-deploy, with poisoned-data rollback (ISSUE 14).

The reference designs exactly two topologies and this closes the second:
the unbounded connected train/predict stream (the
``IncrementalLearningSkeleton`` shape, PAPER.md §0.4) wired into the
serving runtime as a self-healing model lifecycle.  A
:class:`ContinuousLearningController` runs an online fitter
(:meth:`~flink_ml_tpu.lib.online.OnlineLogisticRegression.fit_unbounded`
over :mod:`flink_ml_tpu.iteration.unbounded`) on a label stream beside a
live :class:`~flink_ml_tpu.serving.server.ModelServer`, and every
``FMT_LIFECYCLE_EVERY_WINDOWS`` effective training windows it cuts a
**candidate** and pushes it through a hard validation gate before the
candidate is allowed anywhere near traffic:

1. **numeric health** — :func:`~flink_ml_tpu.fault.guard.check_health`
   on the candidate's parameters (a poisoned label burst that drove the
   online SGD to NaN/Inf dies HERE, reason-coded ``numeric_health``);
2. **score quarantine** — the candidate's holdout scores must be finite
   (``score_quarantine``: finite params can still overflow a dot
   product);
3. **holdout no-regression** — the candidate's holdout AUC may trail the
   incumbent's by at most ``FMT_LIFECYCLE_REGRESSION_TOL``
   (``holdout_regression``);
4. **score-drift sanity** — PSI between the candidate's and the
   incumbent's STANDARDIZED holdout score distributions must stay under
   ``FMT_LIFECYCLE_SCORE_PSI`` (``score_drift``: a candidate whose AUC
   survived but whose score distribution changed shape — a sign flip, a
   collapse to a point mass, a bimodal split — scores a different
   function than the ranking metric can see; near-constant candidate
   scores are degenerate and block outright, which is also what keeps an
   all-zero candidate away from traffic).

A **passing** candidate is committed to disk through the sidecar-commit
scheme (``Stage.save`` integrity sidecars + a ``lifecycle.json``
descriptor written last-as-commit) and auto-deploys through the round-10
swap contract (:meth:`ModelServer.deploy`: integrity-verified load ->
pre-warm off the hot path -> atomic swap; the server's drift reference
resets so the new version's population is the new normal).  A
**failing** candidate is reason-coded (``lifecycle.blocked.<reason>``),
flight-recorded with a black-box dump, and the old model keeps serving;
when the failure says the TRAINER state itself is poisoned
(``numeric_health`` / ``score_quarantine``), the controller resets the
online fitter to the last validated candidate's parameters
(``lifecycle.trainer_resets``) so one poisoned burst cannot wedge the
loop forever.

After every swap a **probation window** (``FMT_LIFECYCLE_PROBATION_S``)
watches the live burn-rate signals (``slo.burning.*`` — serving p99,
shed/error ratio, drift PSI) through the server's
:class:`~flink_ml_tpu.obs.slo.SLOMonitor`; a breach rolls the server
back to the previous version through the SAME integrity-verified swap
path (:meth:`ModelServer.rollback`), restores the incumbent baseline,
and counts ``lifecycle.rollbacks``.

Preemption (the satellite contract): the streaming driver polls SIGTERM
at record/span boundaries and commits an emergency stream snapshot; the
controller then commits an **emergency candidate** before the clean
exit, and a restarted loop resumes from the committed state
bit-identically (subprocess-tested).

Counters: ``lifecycle.candidates`` / ``lifecycle.swaps`` /
``lifecycle.blocked`` (+ ``.{reason}``) / ``lifecycle.rollbacks`` /
``lifecycle.trainer_resets`` / ``lifecycle.emergency_candidates``.
Knobs (README.md, "Continuous learning"): ``FMT_LIFECYCLE_EVERY_WINDOWS``,
``FMT_LIFECYCLE_REGRESSION_TOL``, ``FMT_LIFECYCLE_SCORE_PSI``,
``FMT_LIFECYCLE_PROBATION_S``, ``FMT_LIFECYCLE_HISTORY``,
``FMT_LIFECYCLE_DIR``.

Entry points: ``scripts/chaos_smoke.py --online`` (poisoned burst /
drift-burn rollback / multi-swap loop legs),
``tests/test_lifecycle.py``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.table.table import Table

__all__ = [
    "BLOCK_DEPLOY_FAILED",
    "BLOCK_HOLDOUT_REGRESSION",
    "BLOCK_NUMERIC_HEALTH",
    "BLOCK_SCORE_DRIFT",
    "BLOCK_SCORE_QUARANTINE",
    "ContinuousLearningController",
    "latest_candidate",
]

#: gate reason codes (the ``lifecycle.blocked.<reason>`` vocabulary)
BLOCK_NUMERIC_HEALTH = "numeric_health"
BLOCK_SCORE_QUARANTINE = "score_quarantine"
BLOCK_HOLDOUT_REGRESSION = "holdout_regression"
BLOCK_SCORE_DRIFT = "score_drift"
BLOCK_DEPLOY_FAILED = "deploy_failed"

#: gate failures that mean the TRAINER state itself is poisoned — the
#: controller resets the online fitter to the last good candidate
_POISON_REASONS = frozenset({BLOCK_NUMERIC_HEALTH, BLOCK_SCORE_QUARANTINE})

#: the candidate commit descriptor, written last-as-commit: a candidate
#: directory without one is an aborted save, never a resume point
_CANDIDATE_FILE = "lifecycle.json"
_CANDIDATE_PREFIX = "candidate-"

#: probation poll cadence — cheap (one dict read off the SLO monitor)
_PROBE_INTERVAL_S = 0.25

#: candidate-outcome records kept in the controller's history window —
#: the loop runs forever, so even bookkeeping must stay bounded (the
#: counters keep the true totals)
_HISTORY_RECORDS = 256


def _auc(y: np.ndarray, scores: np.ndarray) -> float:
    """Rank-based AUC (Mann-Whitney) — the holdout no-regression metric."""
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    pos = y == 1
    n1 = int(pos.sum())
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        return 0.5
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def _score_psi(reference: np.ndarray, live: np.ndarray) -> Optional[float]:
    """Shape-PSI between two holdout score vectors via the obs quantile
    sketches (the same statistic the data-plane drift monitor judges).

    Both vectors are STANDARDIZED first: continued online training
    legitimately grows score magnitude window over window, so raw-score
    PSI would block every healthy candidate — what the sanity gate hunts
    is a SHAPE change (sign flip, collapse to a point mass, bimodal
    split) that says the candidate scores a different function, not a
    sharper one.  Returns None for a degenerate (near-constant) live
    distribution — the caller blocks those outright, which is also what
    keeps an all-zero candidate away from traffic."""
    from flink_ml_tpu.obs.sketch import QuantileSketch, psi

    live_std = float(np.std(live))
    if live_std < 1e-12:
        return None
    ref_std = float(np.std(reference)) or 1.0
    ref = QuantileSketch()
    ref.update((reference - np.mean(reference)) / ref_std)
    cur = QuantileSketch()
    cur.update((live - np.mean(live)) / live_std)
    return psi(ref, cur)


def latest_candidate(candidate_dir: str) -> Optional[Tuple[str, dict]]:
    """``(path, descriptor)`` of the newest COMMITTED candidate under
    ``candidate_dir``, or None.  Commit = a parseable ``lifecycle.json``
    (written last); aborted saves are invisible, exactly like the spill
    blocks and checkpoints this scheme is borrowed from."""
    if not os.path.isdir(candidate_dir):
        return None
    best = None
    for name in sorted(os.listdir(candidate_dir)):
        if not name.startswith(_CANDIDATE_PREFIX):
            continue
        descriptor = os.path.join(candidate_dir, name, _CANDIDATE_FILE)
        try:
            with open(descriptor) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            continue  # uncommitted / torn candidate: not a resume point
        best = (os.path.join(candidate_dir, name), meta)
    return best


class ContinuousLearningController:
    """Online training -> validated candidate -> auto-deploy, guarded.

    ``estimator`` is an
    :class:`~flink_ml_tpu.lib.online.OnlineLogisticRegression` (feature/
    label cols configured); ``training_source`` its label stream;
    ``holdout`` a labeled validation table the gate judges every
    candidate on.  ``server`` is the live :class:`ModelServer` passing
    candidates deploy onto — ``None`` runs the loop in publish-only mode
    (candidates validate and commit to disk, nothing deploys), the
    trainer-box half of a split deployment.

    ``run()`` drives the loop on the calling thread (the preemption-
    scope entry point — use this from a process's main thread);
    ``start()`` runs it on a background thread beside the caller.  The
    probation watcher runs on its own daemon thread either way.
    """

    def __init__(self, estimator, training_source, holdout: Table, *,
                 server=None, candidate_dir: Optional[str] = None,
                 candidate_every: Optional[int] = None,
                 regression_tol: Optional[float] = None,
                 score_psi: Optional[float] = None,
                 probation_s: Optional[float] = None,
                 max_windows: Optional[int] = None):
        from flink_ml_tpu.lib.common import resolve_features
        from flink_ml_tpu.utils import knobs

        self.estimator = estimator
        self._training_source = training_source
        self._server = server
        self._max_windows = max_windows
        self.candidate_every = int(
            candidate_every if candidate_every is not None
            else knobs.knob_int("FMT_LIFECYCLE_EVERY_WINDOWS")
        )
        if self.candidate_every < 1:
            raise ValueError("candidate_every must be >= 1")
        self.regression_tol = float(
            regression_tol if regression_tol is not None
            else knobs.knob_float("FMT_LIFECYCLE_REGRESSION_TOL")
        )
        self.score_psi = float(
            score_psi if score_psi is not None
            else knobs.knob_float("FMT_LIFECYCLE_SCORE_PSI")
        )
        self.probation_s = float(
            probation_s if probation_s is not None
            else knobs.knob_float("FMT_LIFECYCLE_PROBATION_S")
        )
        if candidate_dir is None:
            candidate_dir = knobs.knob_str("FMT_LIFECYCLE_DIR")
        if not candidate_dir:
            import tempfile

            candidate_dir = tempfile.mkdtemp(prefix="fmt_lifecycle_")
        self.candidate_dir = candidate_dir
        os.makedirs(self.candidate_dir, exist_ok=True)
        #: the streaming driver's snapshot directory — its cadence is
        #: pinned to the candidate cadence so a committed candidate and
        #: the stream snapshot describe the same window boundary
        self.stream_dir = os.path.join(self.candidate_dir, "stream")

        Xh, _ = resolve_features(holdout, estimator)
        self._holdout_x = np.asarray(Xh, dtype=np.float64)
        self._holdout_y = np.asarray(
            holdout.col(estimator.get_label_col()), dtype=np.float64
        )
        if not np.all(np.isfinite(self._holdout_x)) or not np.all(
                np.isfinite(self._holdout_y)):
            raise ValueError(
                "holdout table carries non-finite features/labels — the "
                "gate's yardstick must itself be clean"
            )

        # mutable shared state: the trainer thread and the probation
        # watcher both touch it, so every access goes through _lock
        self._lock = threading.Lock()
        # serializes the trainer's candidate deploy against the prober's
        # rollback: interleaving them would leave the serving pointer,
        # the retained-version ordering, and the incumbent bookkeeping
        # telling three different stories
        self._deploy_mutex = threading.Lock()
        self._state = None          # latest device pytree from the hook
        self._windows = 0           # windows fired (incl. skipped)
        self._effective_since = 0   # effective windows since last candidate
        self._seq = 0               # candidate sequence number
        self._incumbent: Optional[dict] = None   # {version,path,w,b,auc,scores}
        self._prev_incumbent: Optional[dict] = None
        from collections import deque

        self._probation_until = 0.0
        self._counts: Dict[str, int] = {}
        self._history: "deque[dict]" = deque(maxlen=_HISTORY_RECORDS)
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._trainer: Optional[threading.Thread] = None
        self._prober: Optional[threading.Thread] = None

        self._bootstrap_incumbent()

    # -- bootstrap / resume ---------------------------------------------------

    def _bootstrap_incumbent(self) -> None:
        """The gate's baseline: the server's live model when it is
        score-capable, else the newest committed candidate on disk (the
        restart path), else None — the first candidate then deploys
        gated by health/finiteness alone, and BECOMES the baseline."""
        latest = latest_candidate(self.candidate_dir)
        if latest is not None:
            path, meta = latest
            with self._lock:
                self._seq = int(meta.get("seq", 0))
        record = None
        if self._server is not None:
            record = self._eval_model(
                self._server.active_model,
                version=self._server.active_version, path=None)
        if record is None and latest is not None:
            path, meta = latest
            try:
                from flink_ml_tpu.api.core import load_stage

                record = self._eval_model(
                    load_stage(path), version=meta.get("version"),
                    path=path)
            except Exception:  # noqa: BLE001 - a rotted candidate is not
                record = None  # a baseline; the loop re-learns one
        with self._lock:
            self._incumbent = record

    def _eval_model(self, model, version, path) -> Optional[dict]:
        """Holdout evaluation of a score-capable (GLM-family) model, or
        None for stages with no linear scores to compare against."""
        try:
            w = np.asarray(model.coefficients(), dtype=np.float64)
            b = float(model.intercept())
        except Exception:  # noqa: BLE001 - not a GLM-family stage
            return None
        if w.shape != (self._holdout_x.shape[1],):
            return None
        scores = self._holdout_x @ w + b
        if not np.all(np.isfinite(scores)):
            return None
        return {
            "version": version, "path": path, "w": w, "b": b,
            "auc": _auc(self._holdout_y, scores), "scores": scores,
        }

    # -- lifecycle ------------------------------------------------------------

    def run(self):
        """Drive the training loop to stream end on the CALLING thread
        (blocking).  From a main thread this is the preemption-scope
        entry: a SIGTERM commits the driver's emergency stream snapshot
        AND an emergency candidate, then exits cleanly via
        :class:`~flink_ml_tpu.fault.guard.Preempted`.  Returns the final
        fitted model."""
        from flink_ml_tpu.fault.guard import Preempted
        from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

        self._start_prober()
        checkpoint = CheckpointConfig(
            directory=self.stream_dir,
            every_n_epochs=self.candidate_every,
            min_interval_s=0.0,
        )
        try:
            model, _ = self.estimator.fit_unbounded(
                self._training_source,
                max_windows=self._max_windows,
                checkpoint=checkpoint,
                window_hook=self._on_window,
            )
        except Preempted:
            self._emergency_candidate()
            raise
        # stream end: the final state is the last candidate opportunity
        with self._lock:
            state = self._state
            due = self._effective_since > 0
        if due and state is not None:
            self._candidate(state)
        return model

    def start(self) -> "ContinuousLearningController":
        """Run the loop on a background thread beside the caller (the
        in-process serving topology).  A SIGTERM still reaches worker-
        thread boundary polls when the process's main thread holds a
        preemption scope; the emergency-candidate epilogue runs either
        way."""
        from flink_ml_tpu.fault.guard import Preempted

        def body():
            try:
                self.run()
            except Preempted:
                pass  # clean preemption exit recorded by the epilogue
            except BaseException as exc:  # noqa: BLE001 - surfaced via .error
                with self._lock:
                    self._error = exc

        self._trainer = threading.Thread(
            target=body, name="fmt-lifecycle-trainer", daemon=True,
        )
        self._trainer.start()
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for a :meth:`start`-ed loop to reach stream end; re-raise
        the trainer's failure if it died."""
        if self._trainer is not None:
            self._trainer.join(timeout=timeout)
        err = self.error
        if err is not None:
            raise err

    def stop(self, timeout: float = 5.0) -> None:
        """Stop the probation watcher (the trainer stops when its source
        ends — close/drain the source to stop it early).  Idempotent."""
        self._stop.set()
        prober, self._prober = self._prober, None
        if prober is not None:
            prober.join(timeout=timeout)

    @property
    def error(self) -> Optional[BaseException]:
        with self._lock:
            return self._error

    @property
    def windows(self) -> int:
        """Windows the trainer has fired (skipped ones included)."""
        with self._lock:
            return self._windows

    @property
    def incumbent_version(self) -> Optional[str]:
        with self._lock:
            return (self._incumbent or {}).get("version")

    def stats(self) -> dict:
        """Counts + candidate history, the controller's report payload."""
        with self._lock:
            return {
                **dict(sorted(self._counts.items())),
                "windows": self._windows,
                "incumbent": (self._incumbent or {}).get("version"),
                "history": [dict(h) for h in self._history],
            }

    def _count_locked(self, name: str, n: int = 1) -> None:
        self._counts[name] = self._counts.get(name, 0) + n

    # -- the window hook ------------------------------------------------------

    def _on_window(self, epoch: int, state):
        """Called by the online fitter after EVERY fired window (on the
        trainer thread).  Tracks effective windows (a skipped window
        returns the identical state object), cuts a candidate every
        ``candidate_every`` effective windows, and returns a replacement
        state when the gate says the trainer itself is poisoned."""
        with self._lock:
            skipped = state is self._state and self._windows > 0
            self._state = state
            self._windows = epoch + 1
            if not skipped:
                self._effective_since += 1
            due = self._effective_since >= self.candidate_every
        if not due:
            return None
        return self._candidate(state)

    # -- candidate pipeline ---------------------------------------------------

    def _candidate(self, state):
        """Cut one candidate from the live trainer state: fetch, gate,
        commit, deploy.  Returns a replacement trainer state (the
        poisoned-trainer reset) or None."""
        w = np.asarray(state[0], dtype=np.float64)
        b = float(np.asarray(state[1]))
        with self._lock:
            self._effective_since = 0
            self._seq += 1
            seq = self._seq
        version = f"cl-{seq}"
        obs.counter_add("lifecycle.candidates")
        with self._lock:
            self._count_locked("lifecycle.candidates")
        verdict = self._gate(w, b)
        if verdict["reason"] is not None:
            return self._blocked(version, verdict)
        path = self._commit_candidate(seq, version, w, b, verdict["auc"])
        if self._server is not None:
            try:
                with self._deploy_mutex:
                    self._server.deploy(path, version)
            except BaseException as exc:  # noqa: BLE001 - old model serves
                return self._blocked(version, {
                    "reason": BLOCK_DEPLOY_FAILED,
                    "detail": f"{type(exc).__name__}: {exc}",
                    "auc": verdict["auc"], "scores": None,
                })
            obs.counter_add("lifecycle.swaps")
            obs.flight.record("lifecycle.swap", version=version,
                              auc=round(verdict["auc"], 6), path=path)
        else:
            obs.counter_add("lifecycle.published")
        with self._lock:
            self._count_locked("lifecycle.swaps"
                               if self._server is not None
                               else "lifecycle.published")
            self._prev_incumbent = self._incumbent
            self._incumbent = {
                "version": version, "path": path, "w": w, "b": b,
                "auc": verdict["auc"], "scores": verdict["scores"],
            }
            self._history.append({
                "version": version, "outcome": "swapped"
                if self._server is not None else "published",
                "auc": round(verdict["auc"], 6), "windows": self._windows,
            })
            # probation arms only when there is a live server whose SLOs
            # can breach — and a previous version to roll back onto
            if self._server is not None:
                self._probation_until = time.monotonic() + self.probation_s
        return None

    def _blocked(self, version: str, verdict: dict):
        """Reason-code, count, flight-record a blocked candidate; the old
        model keeps serving.  Returns the trainer-reset state when the
        reason marks the trainer itself as poisoned."""
        reason, detail = verdict["reason"], verdict["detail"]
        obs.counter_add("lifecycle.blocked")
        obs.counter_add(f"lifecycle.blocked.{reason}")
        obs.flight.record("lifecycle.candidate_blocked", version=version,
                          reason=reason, detail=detail)
        obs.flight.dump("lifecycle_blocked")
        with self._lock:
            self._count_locked("lifecycle.blocked")
            self._count_locked(f"lifecycle.blocked.{reason}")
            self._history.append({
                "version": version, "outcome": "blocked", "reason": reason,
                "detail": detail, "windows": self._windows,
            })
            incumbent = self._incumbent
        if reason not in _POISON_REASONS:
            return None
        # the trainer state itself is poisoned: continuing to fold clean
        # windows into NaN params can never recover — reset the online
        # fitter to the last validated candidate (or a cold start)
        import jax.numpy as jnp

        dim = self._holdout_x.shape[1]
        if incumbent is not None:
            w0, b0 = incumbent["w"], incumbent["b"]
            target = incumbent["version"]
        else:
            w0, b0 = np.zeros((dim,)), 0.0
            target = "initial"
        obs.counter_add("lifecycle.trainer_resets")
        obs.flight.record("lifecycle.trainer_reset", to=target,
                          reason=reason)
        with self._lock:
            self._count_locked("lifecycle.trainer_resets")
        return (
            jnp.asarray(np.asarray(w0, dtype=np.float32)),
            jnp.asarray(np.float32(b0)),
        )

    def _gate(self, w: np.ndarray, b: float) -> dict:
        """The hard validation gate.  Returns ``{reason, detail, auc,
        scores}`` — ``reason`` None means the candidate may deploy."""
        from flink_ml_tpu.fault.guard import NumericHealthError, check_health

        out = {"reason": None, "detail": "", "auc": 0.0, "scores": None}
        try:
            check_health(leaves=(w, np.float64(b)),
                         where="lifecycle.candidate")
        except NumericHealthError as exc:
            out.update(reason=BLOCK_NUMERIC_HEALTH, detail=str(exc))
            return out
        if not (np.all(np.isfinite(w)) and np.isfinite(b)):
            # FMT_GUARD=0 turns check_health into a no-op, but a swap
            # gate has no business deploying NaN params regardless
            out.update(reason=BLOCK_NUMERIC_HEALTH,
                       detail="non-finite candidate parameters")
            return out
        scores = self._holdout_x @ w + b
        if not np.all(np.isfinite(scores)):
            bad = int(np.size(scores) - np.isfinite(scores).sum())
            out.update(reason=BLOCK_SCORE_QUARANTINE,
                       detail=f"{bad} non-finite holdout scores")
            return out
        out["scores"] = scores
        out["auc"] = _auc(self._holdout_y, scores)
        with self._lock:
            incumbent = self._incumbent
        if incumbent is not None:
            floor = incumbent["auc"] - self.regression_tol
            if out["auc"] < floor:
                out.update(
                    reason=BLOCK_HOLDOUT_REGRESSION,
                    detail=(f"holdout AUC {out['auc']:.4f} under the "
                            f"incumbent's {incumbent['auc']:.4f} - "
                            f"{self.regression_tol:g} tolerance"),
                )
                return out
            psi_value = _score_psi(incumbent["scores"], scores)
            if psi_value is None:
                out.update(
                    reason=BLOCK_SCORE_DRIFT,
                    detail="degenerate candidate scores (near-constant "
                           "holdout score distribution)",
                )
                return out
            if psi_value > self.score_psi:
                out.update(
                    reason=BLOCK_SCORE_DRIFT,
                    detail=(f"candidate-vs-incumbent standardized holdout "
                            f"score PSI {psi_value:.4f} > "
                            f"{self.score_psi:g}"),
                )
                return out
        return out

    def _commit_candidate(self, seq: int, version: str, w: np.ndarray,
                          b: float, auc: float,
                          emergency: bool = False) -> str:
        """Persist one candidate through the sidecar-commit scheme: the
        model saves first (its own integrity sidecars), the
        ``lifecycle.json`` descriptor lands last as the commit record."""
        from flink_ml_tpu.lib.classification import LogisticRegressionModel
        from flink_ml_tpu.lib.glm import make_model_table
        from flink_ml_tpu.serve.integrity import atomic_json_dump

        model = LogisticRegressionModel()
        model.get_params().merge(self.estimator.get_params())
        model.set_model_data(make_model_table(w, float(b)))
        path = os.path.join(self.candidate_dir,
                            f"{_CANDIDATE_PREFIX}{seq:06d}")
        model.save(path)
        with self._lock:
            windows = self._windows
        atomic_json_dump({
            "seq": seq, "version": version, "windows": windows,
            "auc": round(float(auc), 6), "emergency": bool(emergency),
        }, os.path.join(path, _CANDIDATE_FILE))
        return path

    def _emergency_candidate(self) -> None:
        """The preemption epilogue: commit the current trainer state as a
        candidate (no gate, no deploy — it is a checkpoint, not a swap)
        unless that state is non-finite, which would poison the restart's
        incumbent bootstrap."""
        with self._lock:
            state = self._state
            self._seq += 1
            seq = self._seq
        if state is None:
            return
        w = np.asarray(state[0], dtype=np.float64)
        b = float(np.asarray(state[1]))
        if not (np.all(np.isfinite(w)) and np.isfinite(b)):
            return
        scores = self._holdout_x @ w + b
        auc = _auc(self._holdout_y, scores) if np.all(
            np.isfinite(scores)) else 0.5
        self._commit_candidate(seq, f"cl-{seq}", w, b, auc,
                               emergency=True)
        obs.counter_add("lifecycle.emergency_candidates")
        obs.flight.record("lifecycle.emergency_candidate", seq=seq)

    # -- probation ------------------------------------------------------------

    def _start_prober(self) -> None:
        if self._prober is not None and self._prober.is_alive():
            return
        self._stop.clear()
        self._prober = threading.Thread(
            target=self._probe_loop, name="fmt-lifecycle-probation",
            daemon=True,
        )
        self._prober.start()

    def _probe_loop(self) -> None:
        while not self._stop.wait(_PROBE_INTERVAL_S):
            try:
                self._probe_once()
            except Exception:  # noqa: BLE001 - the watcher must outlive
                pass           # one bad sample; rollback failure is logged

    def _burning_now(self) -> Dict[str, float]:
        """The live burn signal: every SLO the server's monitor says is
        burning right now (empty when no monitor is armed)."""
        if self._server is None:
            return {}
        monitor = self._server.slo_monitor
        if monitor is None:
            return {}
        return dict(monitor.burning())

    def _probe_once(self) -> None:
        with self._lock:
            armed = (self._probation_until > 0.0
                     and time.monotonic() < self._probation_until)
        if not armed:
            return
        burning = self._burning_now()
        if not burning:
            return
        with self._lock:
            # disarm BEFORE rolling back: one breach, one rollback — the
            # prober must not machine-gun the version history while the
            # burn gauge takes a window to clear
            if not (self._probation_until > 0.0
                    and time.monotonic() < self._probation_until):
                return
            self._probation_until = 0.0
        self._rollback(burning)

    def _rollback(self, burning: Dict[str, float]) -> None:
        slos = ",".join(sorted(burning))
        try:
            with self._deploy_mutex:
                deployed = self._server.rollback()
        except Exception as exc:  # noqa: BLE001 - nothing to roll back to /
            # a rotted artifact: the breach stands, loudly, and the
            # current version keeps serving
            obs.flight.record("lifecycle.rollback_failed", slos=slos,
                              error=type(exc).__name__, detail=str(exc))
            return
        obs.counter_add("lifecycle.rollbacks")
        obs.flight.record("lifecycle.rollback", version=deployed.version,
                          slos=slos,
                          burn=round(max(burning.values()), 4))
        obs.flight.dump("lifecycle_rollback")
        with self._lock:
            self._count_locked("lifecycle.rollbacks")
            rolled_from = (self._incumbent or {}).get("version")
            # the incumbent baseline follows the serving pointer: the
            # next candidate must beat the RESTORED version, and the
            # poisoned-trainer reset targets it too
            if self._prev_incumbent is not None:
                self._incumbent = self._prev_incumbent
                self._prev_incumbent = None
            self._history.append({
                "version": rolled_from, "outcome": "rolled_back",
                "slos": slos, "restored": deployed.version,
            })
