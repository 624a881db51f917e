"""Online data-drift detection for the serving stack (ISSUE 11).

The system plane (traces, flight recorder, /metrics, SLO burn rates) says
whether the process is healthy; nothing says whether the DATA is.  A
model served on a shifted input distribution returns confident garbage
while every probe stays green.  This module is the data-plane half:

* a :class:`DriftMonitor` snapshots a **reference distribution** at
  ``deploy()`` — the pre-warm sample plus the first ``FMT_DRIFT_REF_ROWS``
  live rows, per feature column AND per score/prediction column, held as
  fixed-memory :mod:`~flink_ml_tpu.obs.sketch` sketches — and persists it
  next to the model via the sidecar-commit scheme
  (``drift_reference.json`` + ``.commit.json``), so a process restart
  reloads its baseline instead of re-learning one from possibly-shifted
  traffic;
* a **rolling live window** (two rotating sketches, merged for judgment,
  rotated every ``FMT_DRIFT_WINDOW_S``) accumulates the same columns from
  live traffic, tapped at the quarantine/apply boundary (input features,
  with per-reason quarantine rates riding the reason-coded side-table
  machinery), at the fused-plan entry, and at the ``ModelServer``
  demux (output scores);
* **PSI and KS statistics** per column compare live against reference;
  the worst column's ``PSI / FMT_DRIFT_PSI`` is the ``drift`` SLO's burn
  rate (:mod:`flink_ml_tpu.obs.slo`), feeding ``slo.burning.drift``,
  a reason-coded ``drift`` entry in ``/readyz``, a per-column section in
  ``/statusz``, OpenMetrics histogram families in ``/metrics``, and a
  ``drift_breach`` flight-recorder black box naming the offending
  columns with reference-vs-live quantiles.

Off by default (``FMT_DRIFT``), with the obs discipline: every tap in a
hot path reduces to ONE module-level boolean check until a monitor
exists in the process.  Taps ride the thread-ambient scope the serving
dispatcher (or a top-level transform) installs, so a stage deep inside a
fused plan feeds the right server's monitor without threading a handle
through every layer; the scope's owner rule (first validating mapper
wins) keeps a multi-stage pipeline from sketching the same rows once per
stage.

``python -m flink_ml_tpu.obs drift`` renders the per-column
reference-vs-live comparison table from the latest serving/transform
RunReport; ``obs --check`` prints one ``DRIFT`` line per report whose
worst column crosses the threshold.

Knobs (README.md, "Data drift observability"): ``FMT_DRIFT``,
``FMT_DRIFT_REF_ROWS``, ``FMT_DRIFT_PSI``, ``FMT_DRIFT_WINDOW_S``,
``FMT_DRIFT_MIN_ROWS``, ``FMT_DRIFT_MAX_COLS``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from flink_ml_tpu.obs import flight
from flink_ml_tpu.obs.registry import counter_add, gauge_set
from flink_ml_tpu.obs.sketch import ColumnSketch, ks, psi, update_matrix
from flink_ml_tpu.utils import knobs

__all__ = [
    "DriftMonitor",
    "REFERENCE_FILE",
    "active",
    "default_monitor",
    "drift_main",
    "enabled",
    "max_cols",
    "min_rows",
    "observe_input",
    "observe_quarantine",
    "psi_threshold",
    "ref_rows",
    "report_section",
    "reset",
    "transform_scope",
    "window_s",
]

#: the persisted reference's filename, written next to the model artifact
REFERENCE_FILE = "drift_reference.json"


def enabled() -> bool:
    """Is data-drift monitoring armed?  ``FMT_DRIFT`` (default off)."""
    return knobs.knob_bool("FMT_DRIFT")


def ref_rows() -> int:
    """``FMT_DRIFT_REF_ROWS`` (default 512): live rows (on top of the
    pre-warm sample) folded into the reference before it freezes."""
    return knobs.knob_int("FMT_DRIFT_REF_ROWS")


def psi_threshold() -> float:
    """``FMT_DRIFT_PSI`` (default 0.2 — the classic "population has
    shifted" PSI bound): the worst column's PSI at which the ``drift``
    SLO burn rate reads 1.0.  0 disables the SLO (sketching and the
    status/report sections still run)."""
    return knobs.knob_float("FMT_DRIFT_PSI")


def window_s() -> float:
    """``FMT_DRIFT_WINDOW_S`` (default 60): live-window rotation period.
    Judgment always reads the current PLUS previous window, so a breach
    is visible for at least one full window and a recovered stream stops
    being judged against stale rows after at most two."""
    return knobs.knob_float("FMT_DRIFT_WINDOW_S")


def min_rows() -> int:
    """``FMT_DRIFT_MIN_ROWS`` (default 64): live windows with fewer rows
    are not judged (entering a breach; a burning SLO is re-judged on any
    window — the SLO monitor's asymmetry rule)."""
    return knobs.knob_int("FMT_DRIFT_MIN_ROWS")


def max_cols() -> int:
    """``FMT_DRIFT_MAX_COLS`` (default 16): per-table cap on sketched
    columns — a vector column contributes its first N dimensions.  The
    hot-path cost is one vectorized pass over the sketched columns per
    batch, so the cap is the knob that bounds its width."""
    return knobs.knob_int("FMT_DRIFT_MAX_COLS")


def window_rows() -> int:
    """``FMT_DRIFT_WINDOW_ROWS`` (default 8192): per-window cap on LIVE
    rows sketched.  A drift judgment is a statistical comparison — a few
    thousand rows pin PSI/KS down to well under any actionable
    threshold, and sketching every row of a saturated server buys no
    signal for real hot-path cost.  Once a window's sample is full,
    further batches cost one counter bump until rotation; quarantine
    reason RATES stay exact (seen-row denominators keep counting)."""
    return knobs.knob_int("FMT_DRIFT_WINDOW_ROWS")


# -- column extraction --------------------------------------------------------


def _spec_columns(batch, spec: dict, cap: int):
    """Feature columns from a mapper's ``serve_validation_spec`` —
    ``(matrix_groups, single_cols)`` where a matrix group is
    ``(names, (n, k) array)`` folded through the vectorized
    :func:`~flink_ml_tpu.obs.sketch.update_matrix` path.  A dense vector
    column fans out per dimension (capped); a sparse column contributes
    its nnz-per-row profile (densifying a million-wide row to sketch it
    would cost more than the model's own matmul); numeric feature
    columns stack into one matrix group."""
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.table.schema import DataTypes

    mats: List[tuple] = []
    cols: Dict[str, np.ndarray] = {}
    vc = spec.get("vector_col")
    fcs = spec.get("feature_cols")
    dim = spec.get("dim")
    if vc is not None and batch.schema.contains(vc):
        typ = batch.schema.type_of(vc)
        col = batch.col(vc)
        if isinstance(col, CsrRows):
            cols[f"{vc}.nnz"] = col.nnz_per_row()
        elif typ == DataTypes.SPARSE_VECTOR or (
            dim is not None and int(dim) > 1024
        ):
            # sparse (or absurdly wide) geometry: profile the sparsity
            cols[f"{vc}.nnz"] = np.asarray([
                v.indices.size if hasattr(v, "indices")
                else (len(v) if v is not None else 0)
                for v in col
            ], dtype=np.float64)
        elif DataTypes.is_vector(typ):
            X = batch.features_dense(vc, dim=dim)
            w = min(X.shape[1], cap)
            mats.append(([f"{vc}[{i}]" for i in range(w)], X[:, :w]))
        else:
            cols[vc] = col
    elif fcs:
        sel = [c for c in list(fcs)[:cap] if batch.schema.contains(c)]
        if sel:
            mats.append((list(sel), batch.numeric_matrix(sel)))
    return mats, cols


def _table_columns(table, cap: int,
                   exclude: frozenset = frozenset()) -> Dict[str, np.ndarray]:
    """Every sketchable column of a table (the generic walk): numeric
    columns as themselves, dense vector columns per dimension, sparse
    columns as their nnz profile.  ``exclude`` drops input-schema names —
    the score tap's "produced columns only" rule."""
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.table.schema import DataTypes

    cols: Dict[str, np.ndarray] = {}
    for name in table.schema.field_names:
        if name in exclude or len(cols) >= cap:
            continue
        typ = table.schema.type_of(name)
        if DataTypes.is_numeric(typ):
            cols[name] = table.col(name)
        elif typ == DataTypes.SPARSE_VECTOR:
            col = table.col(name)
            if isinstance(col, CsrRows):
                cols[f"{name}.nnz"] = col.nnz_per_row()
        elif DataTypes.is_vector(typ):
            col = table.col(name)
            if isinstance(col, np.ndarray) and col.ndim == 2:
                for i in range(min(col.shape[1], cap - len(cols))):
                    cols[f"{name}[{i}]"] = col[:, i]
    return cols


# -- the monitor --------------------------------------------------------------


class DriftMonitor:
    """Reference-vs-live distribution tracking for one serving surface.

    Rows observed before the reference is complete fold INTO the
    reference (it is still being snapshotted); after ``freeze`` they
    land in the rolling live window.  All mutation happens under one
    lock — the dispatcher thread, readiness probes, scrapes, and the
    SLO sampler race freely."""

    def __init__(self, name: str = "serving",
                 threshold: Optional[float] = None,
                 ref_target: Optional[int] = None,
                 window: Optional[float] = None,
                 min_window_rows: Optional[int] = None,
                 cap_cols: Optional[int] = None,
                 persist_path: Optional[str] = None):
        global _ARMED
        self.name = str(name)
        self.threshold = (psi_threshold() if threshold is None
                          else float(threshold))
        self.ref_target = (ref_rows() if ref_target is None
                           else int(ref_target))
        self.window_s = window_s() if window is None else float(window)
        self.min_rows = (min_rows() if min_window_rows is None
                         else int(min_window_rows))
        self.cap_cols = max_cols() if cap_cols is None else int(cap_cols)
        self.window_rows = window_rows()
        self._lock = threading.Lock()
        self._ref: Dict[str, ColumnSketch] = {}
        self._ref_reasons: Dict[str, int] = {}
        self._ref_in_rows = 0
        self._ref_score_rows = 0
        self._ref_complete = False
        self._loaded_from: Optional[str] = None
        self._persist_path = persist_path
        self._persisted = False
        self._cur: Dict[str, ColumnSketch] = {}
        self._prev: Dict[str, ColumnSketch] = {}
        self._cur_reasons: Dict[str, int] = {}
        self._prev_reasons: Dict[str, int] = {}
        self._cur_rows = 0       # live rows SKETCHED this window
        self._prev_rows = 0
        self._cur_seen = 0       # live rows seen (incl. past the cap)
        self._prev_seen = 0
        self._rotated_at = time.monotonic()
        self._ref_announced = False
        self._hist_key: Optional[str] = None
        from flink_ml_tpu.obs import telemetry

        self._hist_key = telemetry.register_histograms(
            f"drift.{self.name}", self.histograms
        )
        _ARMED = True

    def close(self) -> None:
        """Unplug from the telemetry plane (server shutdown)."""
        if self._hist_key is not None:
            from flink_ml_tpu.obs import telemetry

            telemetry.unregister_histograms(self._hist_key)
            self._hist_key = None

    # -- ingest ---------------------------------------------------------------

    @property
    def reference_complete(self) -> bool:
        with self._lock:
            return self._ref_complete

    def _target_locked(self) -> Dict[str, ColumnSketch]:
        return self._ref if not self._ref_complete else self._cur

    def _window_full_locked(self, n: int) -> bool:
        """Past-the-cap check for one live batch (under the lock): a
        full window's further rows are counted (rates stay exact) but
        not sketched — the steady-state hot-path cost is this check."""
        if not self._ref_complete:
            return False
        if self._cur_rows < self.window_rows:
            return False
        self._cur_seen += n
        return True

    def _observe_locked(self, mats, cols: Dict[str, np.ndarray]) -> None:
        target = self._target_locked()
        updated = 0
        for names, X in mats:
            sketches = []
            for name in names:
                cs = target.get(name)
                if cs is None:
                    cs = target[name] = ColumnSketch()
                sketches.append(cs)
            update_matrix(sketches, X)
            updated += len(names)
        for name, values in cols.items():
            cs = target.get(name)
            if cs is None:
                cs = target[name] = ColumnSketch()
            cs.update(values)
            updated += 1
        counter_add("drift.sketch_updates", updated)

    def observe_input(self, batch, spec: dict) -> None:
        """Fold one validated batch's feature columns in (the
        quarantine/apply-boundary and fused-plan-entry tap)."""
        n = batch.num_rows()
        if n == 0:
            return
        with self._lock:
            if self._window_full_locked(n):
                counter_add("drift.rows_skipped", n)
                return
        mats, cols = _spec_columns(batch, spec, self.cap_cols)
        if not mats and not cols:
            return
        with self._lock:
            self._observe_locked(mats, cols)
            if self._ref_complete:
                self._cur_rows += n
                self._cur_seen += n
            else:
                self._ref_in_rows += n
        counter_add("drift.rows", n)

    def observe_scores(self, table, exclude: frozenset) -> None:
        """Fold one served batch's produced (score/prediction) columns
        in — the ``ModelServer`` demux tap."""
        n = table.num_rows()
        if n == 0:
            return
        with self._lock:
            if self._window_full_locked(0):  # seen-rows counted by the input tap
                counter_add("drift.rows_skipped", n)
                return
        cols = _table_columns(table, self.cap_cols, exclude=exclude)
        if not cols:
            return
        with self._lock:
            self._observe_locked((), cols)
            if not self._ref_complete:
                self._ref_score_rows += n
        counter_add("drift.rows", n)

    def observe_reasons(self, counts: Dict[str, int]) -> None:
        """Per-reason quarantine tallies for the active window — the
        reason-coded side-table machinery's feed (rates are judged
        against the rows the same window observed)."""
        with self._lock:
            target = (self._ref_reasons if not self._ref_complete
                      else self._cur_reasons)
            for reason, c in counts.items():
                target[reason] = target.get(reason, 0) + int(c)

    def bootstrap(self, table) -> None:
        """Seed the reference from the pre-warm sample: every sketchable
        column, generically named — live feature taps that share a
        column name keep folding into the same sketch."""
        n = table.num_rows()
        if n == 0:
            return
        cols = _table_columns(table, self.cap_cols)
        if not cols:
            return
        with self._lock:
            if self._ref_complete:
                return
            self._observe_locked((), cols)
            self._ref_in_rows += n

    def roll(self) -> None:
        """End-of-batch housekeeping (the scope exit): freeze the
        reference once its row target is met (then persist it), and
        rotate the live window on ``window_s`` expiry."""
        persist_to = None
        announce = False
        with self._lock:
            if not self._ref_complete and max(
                self._ref_in_rows, self._ref_score_rows
            ) >= self.ref_target:
                self._ref_complete = True
                gauge_set("drift.reference_rows",
                          max(self._ref_in_rows, self._ref_score_rows))
                gauge_set("drift.reference_columns", len(self._ref))
                if self._persist_path and not self._persisted:
                    # claim the persist while still holding the lock: two
                    # dispatcher threads rolling past the freeze together
                    # must not both write the reference sidecar
                    self._persisted = True
                    persist_to = self._persist_path
                if not self._ref_announced:
                    # the freezing thread also claims the announce, so a
                    # racing roll() cannot record reference_complete with
                    # a persisted flag whose save is still in flight
                    self._ref_announced = True
                    announce = True
            now = time.monotonic()
            if self._ref_complete and now - self._rotated_at >= self.window_s:
                self._prev, self._cur = self._cur, {}
                self._prev_reasons, self._cur_reasons = self._cur_reasons, {}
                self._prev_rows, self._cur_rows = self._cur_rows, 0
                self._prev_seen, self._cur_seen = self._cur_seen, 0
                self._rotated_at = now
        if persist_to:
            try:
                self.save(persist_to)
            except OSError:  # telemetry must never fail serving
                counter_add("drift.persist_failures")
                with self._lock:
                    self._persisted = False
        with self._lock:
            if not announce and self._ref_complete and not self._ref_announced:
                # reference completed by load() rather than a live freeze:
                # no persist can be in flight, so _persisted is final
                self._ref_announced = True
                announce = True
            rows = max(self._ref_in_rows, self._ref_score_rows)
            columns = len(self._ref)
            persisted = self._persisted
        if announce:
            flight.record("drift.reference_complete", monitor=self.name,
                          rows=rows, columns=columns, persisted=persisted)

    # -- scoring --------------------------------------------------------------

    def _live_merged(self):
        """Current + previous live windows, merged into fresh copies
        (merge mutates; judgment must not corrupt the windows)."""
        with self._lock:
            cur = {k: v.to_dict() for k, v in self._cur.items()}
            prev = {k: v.to_dict() for k, v in self._prev.items()}
            rows = self._cur_rows + self._prev_rows
        merged = {k: ColumnSketch.from_dict(d) for k, d in cur.items()}
        for k, d in prev.items():
            cs = ColumnSketch.from_dict(d)
            if k in merged:
                merged[k].merge(cs)
            else:
                merged[k] = cs
        return merged, rows

    def column_scores(self) -> List[dict]:
        """Per-column drift statistics, worst first: every column the
        reference AND the live window both hold, with PSI, KS, and the
        reference-vs-live quantile summaries the breach dump carries."""
        with self._lock:
            if not self._ref_complete:
                return []
        live, _rows = self._live_merged()
        with self._lock:
            ref = dict(self._ref)
        out = []
        for name, ref_cs in sorted(ref.items()):
            live_cs = live.get(name)
            if live_cs is None or live_cs.rows == 0:
                continue
            # PSI's small-sample noise floor is ~(bins-1) * (1/n_ref +
            # 1/n_live): judging a 100-row window at the classic 10 bins
            # would read ~0.2 PSI on UNSHIFTED traffic — a false breach
            # at the default threshold.  Scale the bins to what the live
            # sample can support instead.
            bins = int(np.clip(live_cs.n // 32, 4, 10))
            out.append({
                "column": name,
                "psi": round(psi(ref_cs.sketch, live_cs.sketch,
                                 bins=bins), 4),
                "ks": round(ks(ref_cs.sketch, live_cs.sketch), 4),
                "ref": ref_cs.summary(),
                "live": live_cs.summary(),
            })
        out.sort(key=lambda c: -c["psi"])
        return out

    def reason_rates(self) -> dict:
        """Quarantine per-reason rates, reference window vs live window.
        Live denominators count every row SEEN (including rows past the
        sketch cap) — a rate judged against a truncated denominator
        would inflate under load exactly when it matters."""
        with self._lock:
            ref_rows_n = max(self._ref_in_rows, 1)
            live_rows_n = max(self._cur_seen + self._prev_seen, 1)
            ref = {r: round(c / ref_rows_n, 6)
                   for r, c in sorted(self._ref_reasons.items())}
            live_counts = dict(self._prev_reasons)
            for r, c in self._cur_reasons.items():
                live_counts[r] = live_counts.get(r, 0) + c
            live = {r: round(c / live_rows_n, 6)
                    for r, c in sorted(live_counts.items())}
        return {"reference": ref, "live": live}

    def armed(self) -> bool:
        """Does this monitor feed the ``drift`` SLO?  (threshold > 0)"""
        return self.threshold > 0

    def judge(self, allow_small: bool = False) -> Optional[dict]:
        """One SLO-window verdict: ``None`` when not judgeable (reference
        still filling, or the live window is below ``min_rows`` and
        ``allow_small`` is False — the SLO monitor passes True while the
        SLO is already burning), else the burn-rate math plus the
        offending columns."""
        if self.threshold <= 0:
            return None
        with self._lock:
            if not self._ref_complete:
                return None
            live_rows = self._cur_rows + self._prev_rows
        if live_rows < self.min_rows and not allow_small:
            return None
        scores = self.column_scores()
        if not scores and not allow_small:
            return None
        worst = scores[0] if scores else None
        max_psi = worst["psi"] if worst else 0.0
        gauge_set("drift.live_rows", live_rows)
        return {
            "burn": max_psi / self.threshold,
            "max_psi": max_psi,
            "worst_column": worst["column"] if worst else None,
            "threshold": self.threshold,
            "live_rows": live_rows,
            "columns": scores,
            "breaching": [c for c in scores if c["psi"] > self.threshold],
        }

    # -- surfaces -------------------------------------------------------------

    def status(self) -> dict:
        """The ``/statusz`` drift section: reference state plus the
        per-column comparison."""
        with self._lock:
            ref_state = {
                "complete": self._ref_complete,
                "rows": max(self._ref_in_rows, self._ref_score_rows),
                "target_rows": self.ref_target,
                "columns": len(self._ref),
                "loaded_from": self._loaded_from,
                "persisted": self._persisted,
            }
            live_rows = self._cur_rows + self._prev_rows
        return {
            "monitor": self.name,
            "threshold": self.threshold,
            "window_s": self.window_s,
            "reference": ref_state,
            "live_rows": live_rows,
            "columns": self.column_scores(),
            "quarantine_rates": self.reason_rates(),
        }

    def report_section(self) -> Optional[dict]:
        """The compact record a transform/serving RunReport carries (and
        the ``obs drift`` CLI renders).  None while nothing is
        comparable yet."""
        with self._lock:
            live_rows = self._cur_rows + self._prev_rows
            complete = self._ref_complete
        if not complete:
            return {"monitor": self.name, "reference_complete": False,
                    "live_rows": live_rows}
        scores = self.column_scores()
        return {
            "monitor": self.name,
            "reference_complete": True,
            "threshold": self.threshold,
            "live_rows": live_rows,
            "columns": scores,
            "quarantine_rates": self.reason_rates(),
        }

    def histograms(self) -> Dict[str, tuple]:
        """The ``/metrics`` export: each reference and live column as an
        OpenMetrics histogram family ``(bounds, cumulative, sum, count)``
        (compacted — the exposition must stay bounded no matter how many
        internal bins a sketch holds).  Computed UNDER the monitor lock:
        the dispatcher mutates these sketches (``_collapse`` pops bucket
        keys mid-walk), and a scrape must read a consistent snapshot,
        not crash into a racing writer."""
        out: Dict[str, tuple] = {}
        with self._lock:
            for kind, cols in (("ref", self._ref), ("live", self._cur)):
                for name, cs in cols.items():
                    bounds, cum = cs.sketch.histogram(20)
                    out[f"drift.{kind}.{name}"] = (
                        bounds, cum, cs.sketch.total, cs.n,
                    )
        return out

    # -- reference lifecycle --------------------------------------------------

    def reset_reference(self, persist_path: Optional[str] = None,
                        warmup=None) -> None:
        """Drop the baseline and start snapshotting a fresh one — the
        redeploy semantics: a new model version serves a (possibly
        intentionally different) population, so yesterday's reference
        would alarm on the new normal forever."""
        with self._lock:
            self._ref = {}
            self._ref_reasons = {}
            self._ref_in_rows = 0
            self._ref_score_rows = 0
            self._ref_complete = False
            self._cur, self._prev = {}, {}
            self._cur_reasons, self._prev_reasons = {}, {}
            self._cur_rows = self._prev_rows = 0
            self._cur_seen = self._prev_seen = 0
            self._rotated_at = time.monotonic()
            self._persist_path = persist_path
            self._persisted = False
            self._loaded_from = None
            self._ref_announced = False
        counter_add("drift.reference_resets")
        flight.record("drift.reference_reset", monitor=self.name,
                      persist_path=persist_path)
        gauge_set("drift.reference_columns", 0)
        if warmup is not None:
            self.bootstrap(warmup)

    def load_reference(self, model_dir: str) -> bool:
        """Adopt the persisted baseline from ``model_dir`` (restart /
        same-artifact redeploy).  Returns False when none exists; raises
        :class:`~flink_ml_tpu.serve.errors.ModelIntegrityError` on a
        corrupt one (the caller decides whether that blocks)."""
        path = os.path.join(model_dir, REFERENCE_FILE)
        if not os.path.exists(path):
            return False
        from flink_ml_tpu.serve.errors import ModelIntegrityError
        from flink_ml_tpu.serve.integrity import verify_commit_record

        verify_commit_record(path)
        try:
            with open(path) as f:
                data = json.load(f)
            ref = {name: ColumnSketch.from_dict(d)
                   for name, d in data["columns"].items()}
        except (ValueError, KeyError, TypeError) as exc:
            raise ModelIntegrityError(
                f"drift reference {path!r} is unparseable ({exc}); "
                "delete it to re-learn a baseline from live traffic"
            ) from exc
        with self._lock:
            self._ref = ref
            self._ref_reasons = {
                str(k): int(v)
                for k, v in (data.get("reasons") or {}).items()
            }
            self._ref_in_rows = int(data.get("rows", 0))
            self._ref_score_rows = int(data.get("rows", 0))
            self._ref_complete = True
            self._loaded_from = path
            self._persist_path = model_dir
            self._persisted = True
            self._cur, self._prev = {}, {}
            self._cur_reasons, self._prev_reasons = {}, {}
            self._cur_rows = self._prev_rows = 0
            self._cur_seen = self._prev_seen = 0
            self._rotated_at = time.monotonic()
        gauge_set("drift.reference_columns", len(ref))
        counter_add("drift.reference_loads")
        return True

    def save(self, model_dir: str) -> str:
        """Persist the reference next to the model (atomic write + the
        length/CRC32 commit sidecar — the model-integrity scheme)."""
        from flink_ml_tpu.serve.integrity import AtomicFile

        with self._lock:
            payload = {
                "monitor": self.name,
                "created_at": time.time(),
                "rows": max(self._ref_in_rows, self._ref_score_rows),
                "reasons": dict(self._ref_reasons),
                "columns": {name: cs.to_dict()
                            for name, cs in self._ref.items()},
            }
        path = os.path.join(model_dir, REFERENCE_FILE)
        with AtomicFile(path) as f:
            f.write(json.dumps(payload, sort_keys=True))
        counter_add("drift.reference_persists")
        return path


# -- thread-ambient tap scope -------------------------------------------------

#: flipped True (forever) by the first DriftMonitor in the process: the
#: one-bool disabled path every hot-path tap checks first
_ARMED = False

_SCOPE = threading.local()

_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[DriftMonitor] = None


class _Scope:
    __slots__ = ("monitor", "owner")

    def __init__(self, monitor: DriftMonitor):
        self.monitor = monitor
        self.owner: Optional[str] = None

    def observe_scores(self, table, exclude: frozenset = frozenset()) -> None:
        self.monitor.observe_scores(table, exclude)


def default_monitor() -> Optional[DriftMonitor]:
    """The process-wide monitor standalone transforms feed when
    ``FMT_DRIFT`` is on and no server scope is active (lazy; None while
    drift is off)."""
    global _DEFAULT
    if not enabled():
        return None
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = DriftMonitor(name="transform")
        return _DEFAULT


def reset() -> None:
    """Drop the default monitor (tests; per-run scoping)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        mon, _DEFAULT = _DEFAULT, None
    if mon is not None:
        mon.close()


@contextlib.contextmanager
def active(monitor: Optional[DriftMonitor]):
    """Install ``monitor`` as this thread's tap target for one serving
    batch (the dispatcher wraps each coalesced dispatch).  ``None`` is a
    no-op context so callers need no branch.  Exit rolls the monitor —
    reference freeze/persist and window rotation happen once per batch,
    after its scores landed."""
    if monitor is None:
        yield None
        return
    prev = getattr(_SCOPE, "scope", None)
    scope = _Scope(monitor)
    _SCOPE.scope = scope
    try:
        yield scope
    finally:
        _SCOPE.scope = prev
        monitor.roll()


@contextlib.contextmanager
def transform_scope():
    """The standalone-transform tap scope: a no-op when a scope is
    already active (a served batch, a nested pipeline stage) or drift is
    off; otherwise installs the process default monitor for the duration
    of one top-level transform.  Yields the scope (None when inactive) —
    the caller feeds the produced table to ``scope.observe_scores``
    BEFORE the block exits so the roll sees the whole transform."""
    if getattr(_SCOPE, "scope", None) is not None or not enabled():
        yield None
        return
    monitor = default_monitor()
    if monitor is None:
        yield None
        return
    scope = _Scope(monitor)
    _SCOPE.scope = scope
    try:
        yield scope
    finally:
        _SCOPE.scope = None
        monitor.roll()


def observe_input(mapper, batch) -> None:
    """The quarantine/apply-boundary tap: fold a validated batch's
    feature columns into the scoped monitor.  First validating mapper
    wins (the owner rule) — a multi-stage pipeline must not sketch the
    same rows once per stage, and a multi-batch apply keeps feeding
    through its owning mapper."""
    if not _ARMED:
        return
    scope = getattr(_SCOPE, "scope", None)
    if scope is None:
        return
    name = mapper.serve_name()
    if scope.owner is None:
        scope.owner = name
    elif scope.owner != name:
        return
    spec = mapper.serve_validation_spec()
    if spec is None:
        return
    scope.monitor.observe_input(batch, spec)


def observe_quarantine(reasons) -> None:
    """The reason-coded side-table feed: per-reason quarantine tallies
    for the scoped monitor's active window."""
    if not _ARMED:
        return
    scope = getattr(_SCOPE, "scope", None)
    if scope is None:
        return
    counts: Dict[str, int] = {}
    for r in reasons:
        r = str(r)
        counts[r] = counts.get(r, 0) + 1
    if counts:
        scope.monitor.observe_reasons(counts)


def report_section() -> Optional[dict]:
    """The drift section a transform RunReport carries: the default
    monitor's compact record (None when drift is off/idle)."""
    if not _ARMED:
        return None
    with _DEFAULT_LOCK:
        mon = _DEFAULT
    if mon is None:
        return None
    return mon.report_section()


# -- the CLI ------------------------------------------------------------------


def _render_columns(section: dict) -> List[str]:
    cols = section.get("columns") or []
    threshold = section.get("threshold", 0.0)
    lines = []
    if not cols:
        lines.append("  (no comparable columns yet)")
        return lines
    head = (f"  {'column':<20} {'psi':>8} {'ks':>8} "
            f"{'ref p50':>12} {'live p50':>12} "
            f"{'ref p95':>12} {'live p95':>12}  verdict")
    lines.append(head)
    for c in cols:
        verdict = ("BREACH" if threshold and c["psi"] > threshold
                   else "ok")
        lines.append(
            f"  {c['column']:<20} {c['psi']:>8.4f} {c['ks']:>8.4f} "
            f"{c['ref']['p50']:>12.5g} {c['live']['p50']:>12.5g} "
            f"{c['ref']['p95']:>12.5g} {c['live']['p95']:>12.5g}  {verdict}"
        )
    return lines


def drift_main(argv=None) -> int:
    """``python -m flink_ml_tpu.obs drift [--reports DIR] [--ref DIR]``:
    render the per-column reference-vs-live comparison from the latest
    serving/transform RunReport carrying a drift section, or (with
    ``--ref``) the persisted reference next to a saved model."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m flink_ml_tpu.obs drift",
        description="Render the per-column drift comparison table.",
    )
    parser.add_argument("--reports", default=None,
                        help="reports directory (default: repo reports/)")
    parser.add_argument("--ref", default=None, metavar="MODEL_DIR",
                        help="render the persisted reference next to a "
                             "saved model instead of a report")
    parser.add_argument("--json", action="store_true",
                        help="emit the raw drift section as JSON")
    args = parser.parse_args(argv)

    if args.ref:
        mon = DriftMonitor(name="cli", persist_path=None)
        try:
            if not mon.load_reference(args.ref):
                print(f"no {REFERENCE_FILE} under {args.ref!r}")
                return 1
            with mon._lock:
                ref = dict(mon._ref)
            payload = {
                "loaded_from": mon._loaded_from,
                "rows": mon._ref_in_rows,
                "columns": {n: cs.summary() for n, cs in sorted(ref.items())},
            }
            if args.json:
                print(json.dumps(payload, sort_keys=True, indent=1))
                return 0
            print(f"drift reference {mon._loaded_from} "
                  f"({mon._ref_in_rows} rows):")
            for n, s in sorted(payload["columns"].items()):
                print(f"  {n:<20} n={s['n']:<8} mean={s['mean']:<12g} "
                      f"p05={s['p05']:<12g} p50={s['p50']:<12g} "
                      f"p95={s['p95']:<12g} nulls={s['nulls']} "
                      f"nans={s['nans']}")
            return 0
        finally:
            mon.close()

    from flink_ml_tpu.obs.report import load_reports

    reports = load_reports(args.reports)
    latest = None
    for r in reports:
        if r.get("kind") in ("serving", "transform") and (
            (r.get("extra") or {}).get("drift")
        ):
            latest = r
    if latest is None:
        print("no serving/transform RunReport with a drift section — "
              "serve with FMT_DRIFT=1 and FMT_OBS=1 first")
        return 1
    section = latest["extra"]["drift"]
    if args.json:
        print(json.dumps({"name": latest.get("name"),
                          "kind": latest.get("kind"),
                          "ts": latest.get("ts"),
                          "drift": section}, sort_keys=True, indent=1))
        return 0
    print(f"drift: {latest.get('kind')} {latest.get('name')} "
          f"[{latest.get('git_sha', '')}]")
    if not section.get("reference_complete"):
        print(f"  reference still filling "
              f"({section.get('live_rows', 0)} live rows so far)")
        return 0
    print(f"  threshold PSI {section.get('threshold')}, "
          f"{section.get('live_rows')} live rows vs reference")
    for line in _render_columns(section):
        print(line)
    rates = section.get("quarantine_rates") or {}
    if rates.get("reference") or rates.get("live"):
        print(f"  quarantine rates: ref={rates.get('reference')} "
              f"live={rates.get('live')}")
    return 0
