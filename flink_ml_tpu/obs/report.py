"""Structured JSONL run reports + the CLI that summarises them.

Every ``fit``, ``transform`` and serving run with obs enabled appends one
:class:`RunReport` line to ``<reports dir>/runs.jsonl``: git SHA, device
topology, the metrics-registry snapshot, the driver's StepMetrics summary,
and free-form extras.

The CLI::

    python -m flink_ml_tpu.obs [--check] [--json] [--last N] [--reports DIR]

(``python -m flink_ml_tpu.obs.report`` also works, at the cost of a runpy
re-execution warning — the package __init__ already imports this module).

prints what the reports say went wrong or deserves a look: the
FAULT-ASSISTED / SERVE-DEGRADED / PALLAS-DEGRADED / WARMSTART-DEGRADED
flags, DRIFT rows, the COMPILED lines (every fit that compiled a program
or read one from the persistent cache inside itself: seconds by stage,
hits and misses, the span it happened under), fmtlint's ANALYSIS line and
the TIMING tail quantiles.  It is no benchmark and compares no number:
speed is measured on the chip (``BENCHMARK.json``, ``PERF.md``).  ``--check`` exits
non-zero when there are no reports to read.  ``--json`` swaps the human
text for one machine-readable object; ``python -m flink_ml_tpu.obs
trace`` renders a request waterfall from the span sink
(:mod:`flink_ml_tpu.obs.trace`).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

# bind the functions, not the submodule: the package __init__ re-exports
# a *function* named ``registry`` that shadows the submodule attribute, so
# both ``from flink_ml_tpu.obs import registry`` and ``import
# flink_ml_tpu.obs.registry as x`` resolve to the wrong object once the
# package is initialized
from flink_ml_tpu.obs.registry import enabled as _obs_enabled
from flink_ml_tpu.obs.registry import registry as _obs_registry
from flink_ml_tpu.obs.registry import reset_generation as _obs_reset_gen
from flink_ml_tpu.obs.registry import sample_quantile
from flink_ml_tpu.utils import knobs

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_GIT_SHA: Optional[str] = None


def git_sha() -> str:
    """The repo HEAD SHA (cached; ``unknown`` outside a git checkout)."""
    global _GIT_SHA
    if _GIT_SHA is None:
        sha = knobs.raw("FMT_GIT_SHA")
        if not sha:
            try:
                sha = subprocess.run(
                    ["git", "rev-parse", "HEAD"],
                    cwd=_REPO_ROOT, capture_output=True, text=True, timeout=10,
                ).stdout.strip() or "unknown"
            except Exception:  # noqa: BLE001 - telemetry must never break fit
                sha = "unknown"
        _GIT_SHA = sha
    return _GIT_SHA


def device_topology() -> dict:
    """Backend / device-count / process-count / device-kind of this run."""
    try:
        import jax

        devices = jax.devices()
        return {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
            "device_kind": devices[0].device_kind if devices else None,
        }
    except Exception:  # noqa: BLE001 - report even when jax is unhappy
        return {"backend": "unknown", "device_count": 0,
                "process_count": 0, "device_kind": None}


@dataclasses.dataclass
class RunReport:
    """One telemetry record: everything a run measured, self-describing."""

    kind: str                      # "fit" | "transform" | "serving"
    name: str                      # estimator, model or server class
    ts: float                      # unix seconds at write time
    git_sha: str
    device: dict                   # device_topology()
    shape: Optional[str] = None    # workload shape, free-form
    metrics: Optional[dict] = None  # registry snapshot (counters/gauges/timings)
    step_summary: Optional[dict] = None  # StepMetrics.summary()
    extra: Optional[dict] = None   # per-kind payload (epochs, pool delta, ...)

    def to_dict(self) -> dict:
        # shallow: the one caller serialises it at once, and asdict's deep
        # copy of every timing's dict ran inside every fit
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


def reports_dir() -> str:
    """``FMT_OBS_REPORTS`` if set, else ``<repo>/reports``."""
    return knobs.raw("FMT_OBS_REPORTS") or os.path.join(
        _REPO_ROOT, "reports"
    )


def _runs_path(directory: Optional[str] = None) -> str:
    return os.path.join(directory or reports_dir(), "runs.jsonl")


def write_run_report(report: RunReport, directory: Optional[str] = None) -> str:
    """Append one JSONL line; returns the file path."""
    path = _runs_path(directory)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(report.to_dict(), sort_keys=True) + "\n")
    return path


#: registry state already attributed to an earlier fit RunReport — fit
#: reports carry the DELTA since the previous fit, so a process running
#: several fits (a hyper-parameter sweep does) never misattributes earlier
#: fits' counters to a later one
_PREV_FIT_SNAPSHOT: dict = {"counters": {}, "timings": {}}
_PREV_FIT_RESET_GEN = 0


def _fit_delta_snapshot() -> dict:
    """Registry snapshot scoped to work since the last fit report.

    Counters subtract the previously-attributed totals; timings subtract
    count/total (mean derived), dropping stats with no new observations,
    and take their tail quantiles over the new observations themselves
    (the newest ``TimingStat.RESERVOIR`` of them).  It runs inside every
    fit, so it reads the registry's totals and sorts no reservoir: a key
    a warm fit observes once costs one sample, not a 512-sample sort.
    An ``obs.reset()`` in between invalidates the previous totals — the
    reset generation detects that even when post-reset totals happen to
    equal pre-reset ones (a shrunken-total guard alone misses equality).
    Gauges are last-value by nature and pass through."""
    global _PREV_FIT_SNAPSHOT, _PREV_FIT_RESET_GEN
    registry = _obs_registry()
    snap = registry.totals()
    gen = _obs_reset_gen()
    if gen != _PREV_FIT_RESET_GEN:
        _PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
        _PREV_FIT_RESET_GEN = gen
    prev = _PREV_FIT_SNAPSHOT
    counters = {}
    for k, v in snap["counters"].items():
        d = v - prev["counters"].get(k, 0)
        if d < 0:
            d = v
        if d:
            counters[k] = d
    timings = {}
    for k, t in snap["timings"].items():
        p = prev["timings"].get(k)
        dc = t["count"] - (p["count"] if p else 0)
        dt = t["total_s"] - (p["total_s"] if p else 0.0)
        if dc < 0 or dt < 0:
            # stale previous totals (an undetected reset/misattribution):
            # fall back to raw totals — a physically impossible NEGATIVE
            # duration must never reach a report (dc > 0 with dt < 0 slips
            # the count guard alone; see the r5 line 23 artifact)
            dc, dt = t["count"], t["total_s"]
        if dc > 0:
            new = sorted(registry.timing_recent(k, dc))
            timings[k] = {
                "count": dc,
                "total_s": dt,
                "mean_s": dt / dc,
                "p50_s": sample_quantile(new, 0.50),
                "p90_s": sample_quantile(new, 0.90),
                "p99_s": sample_quantile(new, 0.99),
            }
    _PREV_FIT_SNAPSHOT = {"counters": snap["counters"],
                          "timings": snap["timings"]}
    return {"counters": counters, "gauges": snap["gauges"],
            "timings": timings}


def _build_report(kind: str, name: str, shape=None, step_metrics=None,
                  extra=None) -> RunReport:
    summary = None
    if step_metrics is not None:
        try:
            summary = step_metrics.summary()
            # the last step's device-call window at the top level; what of
            # a fit was compiling is the metrics' ``compile.*`` timings
            last = step_metrics.steps[-1] if step_metrics.steps else {}
            if "call_latency_ms" in last:
                summary["call_latency_ms"] = last["call_latency_ms"]
        except Exception:  # noqa: BLE001 - never fail a fit over telemetry
            summary = None
    # fit reports scope metrics to the fit itself; the other kinds keep
    # the registry's whole since-reset snapshot
    metrics = (
        _fit_delta_snapshot() if kind == "fit"
        else _obs_registry().snapshot()
    )
    return RunReport(
        kind=kind,
        name=name,
        ts=time.time(),
        git_sha=git_sha(),
        device=device_topology(),
        shape=shape,
        metrics=metrics,
        step_summary=summary,
        extra=extra,
    )


def fit_report(name: str, shape=None, step_metrics=None, extra=None,
               directory: Optional[str] = None) -> Optional[str]:
    """Write a ``fit`` RunReport (no-op when obs is disabled).

    Called by training drivers at the end of every successful fit; errors
    (read-only FS, missing git) are swallowed — telemetry must never turn
    a trained model into an exception."""
    if not _obs_enabled():
        return None
    try:
        report = _build_report("fit", name, shape, step_metrics, extra)
        tid = _current_trace_id()
        if tid:  # link the fit report to its trace waterfall
            report.extra = {**(report.extra or {}), "trace_id": tid}
        return write_run_report(report, directory)
    except Exception:  # noqa: BLE001
        return None


#: serve-rate timing histograms whose tail quantiles ride along in every
#: transform RunReport (the registry's bounded-reservoir p50/p99)
_TRANSFORM_TIMING_KEYS = (
    "serve.deadline_ms", "pipeline.fused_call_ms",
    "serving.request_latency_ms",
)


def _transform_timing_quantiles() -> dict:
    """count/p50/p99 of the serve-rate timing stats (present ones only).
    The ``_s`` suffix is the TimingStat vocabulary — the underlying unit
    is whatever the histogram observes (ms for the serve timings)."""
    out = {}
    reg = _obs_registry()
    for k in _TRANSFORM_TIMING_KEYS:
        t = reg.timing(k)
        if t is not None and t.get("count"):
            out[k] = {"count": t["count"], "p50_s": t.get("p50_s", 0.0),
                      "p90_s": t.get("p90_s", 0.0),
                      "p99_s": t.get("p99_s", 0.0)}
    return out


def _drift_report_section() -> Optional[dict]:
    """The default drift monitor's compact record (ISSUE 11) — rides
    every transform RunReport while ``FMT_DRIFT`` is on so ``--check``
    and the ``obs drift`` CLI read drift off the same reports as
    everything else.  None when drift is off/idle."""
    try:
        from flink_ml_tpu.obs.drift import report_section

        return report_section()
    except Exception:  # noqa: BLE001 - telemetry must never fail a run
        return None


def _current_trace_id() -> Optional[str]:
    """The active trace id (None when tracing is off / nothing active)."""
    try:
        from flink_ml_tpu.obs.trace import current_trace_ids

        ids = current_trace_ids()
        return ids[0] if ids else None
    except Exception:  # noqa: BLE001 - telemetry must never fail a run
        return None


def transform_report(name: str, rows: int, serve_delta: dict,
                     extra: Optional[dict] = None,
                     directory: Optional[str] = None) -> Optional[str]:
    """Write a ``transform`` RunReport (no-op when obs is disabled).

    ``serve_delta`` is the serve-counter movement across the one transform
    (quarantined rows, fallbacks, device successes, dispatch retries) —
    computed by the caller so fit-report delta attribution stays
    untouched.  The full registry snapshot is deliberately omitted:
    transforms run at serving rate, and the serve delta is the whole
    signal ``--check`` judges.  The serve-rate timing quantiles
    (``timings``: p50/p99 of dispatch wall, fused call, request latency)
    and the active ``trace_id`` ride along so a slow transform links
    straight to its waterfall."""
    if not _obs_enabled():
        return None
    try:
        extra_out = {"rows": int(rows), "serve": dict(serve_delta),
                     **(extra or {})}
        timings = _transform_timing_quantiles()
        if timings:
            extra_out.setdefault("timings", timings)
        drift_section = _drift_report_section()
        if drift_section is not None:
            extra_out.setdefault("drift", drift_section)
        tid = _current_trace_id()
        if tid:
            extra_out.setdefault("trace_id", tid)
        report = RunReport(
            kind="transform",
            name=name,
            ts=time.time(),
            git_sha=git_sha(),
            device=device_topology(),
            extra=extra_out,
        )
        return write_run_report(report, directory)
    except Exception:  # noqa: BLE001 - telemetry must never fail a transform
        return None


def serving_report(name: str, extra: Optional[dict] = None,
                   directory: Optional[str] = None) -> Optional[str]:
    """Write a ``serving`` RunReport (no-op when obs is disabled).

    Emitted by ``ModelServer.shutdown``: the server's lifetime counters —
    requests/batches/shed (per reason)/swaps/deploy failures — plus the
    request-latency p50/p99 from the registry's timing quantiles.  Like
    ``transform_report`` the full registry snapshot is omitted; the
    serving delta IS the signal."""
    if not _obs_enabled():
        return None
    try:
        report = RunReport(
            kind="serving",
            name=name,
            ts=time.time(),
            git_sha=git_sha(),
            device=device_topology(),
            extra=dict(extra or {}),
        )
        return write_run_report(report, directory)
    except Exception:  # noqa: BLE001 - telemetry must never fail serving
        return None


def serve_degraded_runs(reports: List[dict]) -> List[dict]:
    """Transform reports that only completed via the CPU fallback.

    A transform whose serve delta shows fallbacks with ZERO successful
    device dispatches served every batch from the degraded path — the
    accelerator was effectively down for it.  Latest report per transform
    name only (the fault_assisted_runs rule: history must not bury the
    current signal).  Quarantine-only activity does not flag: dropping
    poison rows while the device serves is the system working as
    designed."""
    latest: Dict[str, dict] = {}
    for r in reports:
        if r.get("kind") == "transform":
            latest[str(r.get("name", ""))] = r
    flagged = []
    for _, r in sorted(latest.items()):
        serve = (r.get("extra") or {}).get("serve") or {}
        fallbacks = serve.get("serve.fallbacks", 0)
        device_ok = serve.get("serve.device_ok", 0)
        if fallbacks and not device_ok:
            flagged.append(
                {"name": r.get("name"), "ts": r.get("ts"),
                 "git_sha": r.get("git_sha"), "serve": serve,
                 "rows": (r.get("extra") or {}).get("rows")}
            )
    return flagged


def pallas_degraded_runs(reports: List[dict]) -> List[dict]:
    """Transform reports where a requested Pallas plan only served via the
    XLA path.

    ``FMT_SERVE_PALLAS`` was on but the serve delta shows Pallas
    fallbacks with ZERO Pallas launches — the plan could not lower
    (CSR chain, undeclared stage, int8 conflict) or every launch failed
    into the staged program.  Same visibility rule as SERVE-DEGRADED:
    latest report per transform name, informational (the XLA path is
    exact, just slower than what the operator asked for)."""
    latest: Dict[str, dict] = {}
    for r in reports:
        if r.get("kind") == "transform":
            latest[str(r.get("name", ""))] = r
    flagged = []
    for _, r in sorted(latest.items()):
        serve = (r.get("extra") or {}).get("serve") or {}
        fallbacks = serve.get("fused.pallas_fallbacks", 0)
        dispatches = serve.get("fused.pallas_dispatches", 0)
        if fallbacks and not dispatches:
            flagged.append(
                {"name": r.get("name"), "ts": r.get("ts"),
                 "git_sha": r.get("git_sha"), "serve": serve,
                 "rows": (r.get("extra") or {}).get("rows")}
            )
    return flagged


def warmstart_degraded_runs(reports: List[dict]) -> List[dict]:
    """Transform/serving reports whose warm-artifact reads degraded to
    recompiles (ISSUE 18).

    The serve delta shows ``warmstart.degraded.*`` — a torn write,
    corrupt entry, or fingerprint mismatch was DETECTED and the plan
    compiled fresh instead of replaying it.  Results are exact (the
    whole point of the sidecar CRC check); what the operator loses is
    the millisecond warm boot, so the flag carries the per-reason
    counters.  Same visibility rule as SERVE-/PALLAS-DEGRADED: latest
    report per name, informational."""
    latest: Dict[str, dict] = {}
    for r in reports:
        if r.get("kind") in ("transform", "serving"):
            latest[str(r.get("name", ""))] = r
    flagged = []
    for _, r in sorted(latest.items()):
        serve = (r.get("extra") or {}).get("serve") or {}
        if serve.get("warmstart.degraded", 0):
            flagged.append(
                {"name": r.get("name"), "ts": r.get("ts"),
                 "git_sha": r.get("git_sha"), "serve": serve,
                 "rows": (r.get("extra") or {}).get("rows")}
            )
    return flagged


def drift_runs(reports: List[dict]) -> List[dict]:
    """Transform/serving reports carrying a drift section (ISSUE 11) —
    latest per (kind, name), the fault_assisted_runs visibility rule.
    Each row summarizes the worst column against the recorded threshold;
    ``breaching`` is True when it crossed — the ``DRIFT`` line
    ``--check`` prints next to the perf gates, because a model serving a
    shifted population is degrading before any throughput number
    moves."""
    latest: Dict[tuple, dict] = {}
    for r in reports:
        if r.get("kind") in ("transform", "serving") and (
            (r.get("extra") or {}).get("drift")
        ):
            latest[(r.get("kind"), str(r.get("name", "")))] = r
    out = []
    for (kind, name), r in sorted(latest.items()):
        section = (r.get("extra") or {}).get("drift") or {}
        row = {
            "kind": kind,
            "name": name,
            "ts": r.get("ts"),
            "git_sha": r.get("git_sha"),
            "reference_complete": bool(section.get("reference_complete")),
            "live_rows": section.get("live_rows"),
            "threshold": section.get("threshold"),
        }
        cols = section.get("columns") or []
        if cols:
            worst = cols[0]
            row.update(
                worst_column=worst.get("column"),
                psi=worst.get("psi"),
                ks=worst.get("ks"),
                breaching=bool(
                    section.get("threshold")
                    and worst.get("psi", 0) > section["threshold"]
                ),
            )
        else:
            row.update(worst_column=None, psi=None, ks=None,
                       breaching=False)
        out.append(row)
    return out


def analysis_summary(directory: Optional[str] = None) -> Optional[dict]:
    """The latest fmtlint ``--check`` summary (``analysis.json`` in the
    reports dir), or None when no analysis report is present — feeds the
    ANALYSIS line alongside FAULT-ASSISTED/SERVE-DEGRADED/DRIFT."""
    path = os.path.join(directory or reports_dir(), "analysis.json")
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None
    return data if data.get("kind") == "analysis" else None


#: per-fit timing stats worth a tail-quantile line in ``--check`` output
_FIT_TIMING_KEYS = ("train.dispatch", "train.sync", "train.place")


def timing_quantile_summary(reports: List[dict]) -> Dict[str, dict]:
    """p50/p99 tail quantiles from the LATEST fit/transform report per
    name (the satellite surfacing of TimingStat quantiles beyond the
    serving reservoir): ``{"fit": {name: {stat: {p50_s, p99_s}}},
    "transform": {...}}``.  Fit stats are seconds; transform stats keep
    the unit their histogram observes (the serve timings are ms)."""
    latest: Dict[str, Dict[str, dict]] = {"fit": {}, "transform": {}}
    for r in reports:
        kind = r.get("kind")
        if kind in latest:
            latest[kind][str(r.get("name", ""))] = r
    out: Dict[str, dict] = {"fit": {}, "transform": {}}

    def quantiles(t: dict) -> dict:
        return {"p50_s": t.get("p50_s", 0.0), "p90_s": t.get("p90_s", 0.0),
                "p99_s": t.get("p99_s", 0.0)}

    for name, r in latest["fit"].items():
        timings = (r.get("metrics") or {}).get("timings") or {}
        stats = {
            k: quantiles(t) for k, t in timings.items()
            if k in _FIT_TIMING_KEYS and any(quantiles(t).values())
        }
        if stats:
            out["fit"][name] = stats
    for name, r in latest["transform"].items():
        timings = (r.get("extra") or {}).get("timings") or {}
        stats = {
            k: quantiles(t) for k, t in sorted(timings.items())
            if any(quantiles(t).values())
        }
        if stats:
            out["transform"][name] = stats
    return out


def _timing_lines(summary: Dict[str, dict]) -> List[str]:
    lines = []
    for kind in ("fit", "transform"):
        unit_scale = 1e3 if kind == "fit" else 1.0  # fit stats are seconds
        suffix = "ms" if kind == "fit" else ""
        for name, stats in sorted(summary.get(kind, {}).items()):
            parts = [
                f"{k} p50={t['p50_s'] * unit_scale:.2f}{suffix} "
                f"p90={t.get('p90_s', 0.0) * unit_scale:.2f}{suffix} "
                f"p99={t['p99_s'] * unit_scale:.2f}{suffix}"
                for k, t in sorted(stats.items())
            ]
            lines.append(f"TIMING {kind} {name}: " + "; ".join(parts))
    return lines


def load_reports(directory: Optional[str] = None) -> List[dict]:
    """All RunReport dicts from ``runs.jsonl`` (empty list when absent)."""
    path = _runs_path(directory)
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


#: per-fit counters that mean the run leaned on the fault layer to pass —
#: surfaced by the CLI so a chronically-retrying deployment is visible
_FAULT_COUNTER_PREFIXES = (
    "fault.retries", "fault.rollbacks", "fault.fallbacks",
    "fault.emergency_checkpoints", "fault.spill_rebuilds", "fault.giveups",
)


def fault_assisted_runs(reports: List[dict]) -> List[dict]:
    """Fit reports whose per-fit counter delta shows fault-layer activity
    (retries, rollbacks, fallbacks, emergency checkpoints): the run
    PASSED, but only because something recovered — a fleet where these
    trend up is degrading before it starts failing.

    Only the LATEST fit report per name is judged: runs.jsonl is
    append-only, and re-printing every historical fault-assisted fit
    forever would bury the current signal under runs long since fixed.
    Runs whose delta also carries ``fault.injected`` are marked
    ``injected: True``: those faults were deliberate chaos (a chaos-smoke
    or test run), not environment degradation, and the CLI labels them so
    they never bury the real signal."""
    latest_fit: Dict[str, dict] = {}
    for r in reports:
        if r.get("kind") == "fit":
            latest_fit[str(r.get("name", ""))] = r
    flagged = []
    for _, r in sorted(latest_fit.items()):
        counters = (r.get("metrics") or {}).get("counters") or {}
        hits = {
            k: v for k, v in counters.items()
            if v and any(k == p or k.startswith(p + ".")
                         for p in _FAULT_COUNTER_PREFIXES)
        }
        if hits:
            flagged.append(
                {"name": r.get("name"), "ts": r.get("ts"),
                 "git_sha": r.get("git_sha"), "fault_counters": hits,
                 "injected": bool(counters.get("fault.injected"))}
            )
    return flagged


#: the stages a compile is timed by (``obs/registry.py``), in order
_COMPILE_STAGES = ("trace", "lower", "backend", "cache_read")


def compiled_fits(reports: List[dict]) -> List[dict]:
    """Fit reports whose own delta holds ``compile.backend``: the fit
    compiled a program, or read one from the persistent cache, inside
    itself.  EVERY such fit, in file order with its place among the fit
    reports (``fit_index``), not the latest per name: "which fit
    recompiled" is a question about one fit of hundreds alike.  Seconds by
    stage (``backend_s`` holds ``cache_read_s``), the cache's hits and
    misses, and the stages' seconds by the span they ran under."""
    out = []
    fits = (r for r in reports if r.get("kind") == "fit")
    for index, r in enumerate(fits):
        metrics = r.get("metrics") or {}
        timings = metrics.get("timings") or {}
        if "compile.backend" not in timings:
            continue
        counters = metrics.get("counters") or {}
        row = {"name": r.get("name"), "ts": r.get("ts"),
               "git_sha": r.get("git_sha"), "fit_index": index,
               "programs": timings["compile.backend"].get("count", 0),
               "cache_hits": counters.get("compile.cache_hits", 0),
               "cache_misses": counters.get("compile.cache_misses", 0),
               "under": {k[len("compile.under/"):]: t.get("total_s", 0.0)
                         for k, t in sorted(timings.items())
                         if k.startswith("compile.under/")}}
        for stage in _COMPILE_STAGES:
            row[stage + "_s"] = (timings.get("compile." + stage)
                                 or {}).get("total_s", 0.0)
        out.append(row)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m flink_ml_tpu.obs",
        description="Summarise the RunReports: degraded and "
                    "fault-assisted runs, drift, the fits that compiled, "
                    "timing tails.",
    )
    parser.add_argument("--reports", default=None,
                        help="reports directory (default: repo reports/)")
    parser.add_argument("--check", action="store_true",
                        help="exit 1 when there are no reports to read")
    parser.add_argument("--last", type=int, default=0, metavar="N",
                        help="read only the newest N RunReports (0 = all) "
                             "— bounds the cost of an append-only "
                             "runs.jsonl that has grown for months")
    parser.add_argument("--json", action="store_true",
                        help="emit ONE machine-readable JSON object "
                             "instead of the human text; exit semantics "
                             "unchanged")
    args = parser.parse_args(argv)

    reports = load_reports(args.reports)
    if not reports:
        # a missing/empty reports dir is an operator mistake (wrong path,
        # FMT_OBS never enabled), not a clean summary: one diagnostic
        # line, never a traceback, and --check fails on it
        where = args.reports or reports_dir()
        msg = (f"obs --check: no RunReports under {where} (runs.jsonl "
               "missing or empty) — run a fit or a transform with "
               "FMT_OBS=1, or point --reports at the right directory")
        if args.json:
            print(json.dumps({"ok": not args.check, "check": bool(args.check),
                              "error": msg},
                             sort_keys=True, indent=1))
        else:
            print(msg)
        return 1 if args.check else 0
    if args.last > 0:
        reports = reports[-args.last:]
    fault_assisted = fault_assisted_runs(reports)
    serve_degraded = serve_degraded_runs(reports)
    pallas_degraded = pallas_degraded_runs(reports)
    warmstart_degraded = warmstart_degraded_runs(reports)
    drift_rows = drift_runs(reports)
    compiled = compiled_fits(reports)
    analysis = analysis_summary(args.reports)
    timing_summary = timing_quantile_summary(reports)

    if args.json:
        print(json.dumps({
            "ok": True,
            "check": bool(args.check),
            "reports": len(reports),
            "fault_assisted": fault_assisted,
            "serve_degraded": serve_degraded,
            "pallas_degraded": pallas_degraded,
            "warmstart_degraded": warmstart_degraded,
            "drift": drift_rows,
            "compiled_fits": compiled,
            "analysis": analysis,
            "timings": timing_summary,
        }, sort_keys=True, indent=1))
        return 0

    # static-analysis state, when fmtlint's --check has left a report —
    # same visibility rule as the FAULT-ASSISTED/SERVE-DEGRADED/DRIFT
    # lines: the serving numbers read differently when the invariant
    # gate behind them is red
    if analysis is not None:
        verdict = "clean" if analysis.get("ok") else "FAIL"
        rules = analysis.get("rules") or {}
        detail = (" " + ", ".join(f"{r}={n}" for r, n in sorted(rules.items()))
                  if rules else "")
        print(f"ANALYSIS fmtlint {verdict}: "
              f"{analysis.get('findings', 0)} finding(s), "
              f"{analysis.get('suppressed', 0)} suppressed, "
              f"{analysis.get('files_scanned', 0)} files{detail}")

    # a run that only passed by retrying is one environment blip from
    # not passing
    for fr in fault_assisted:
        counters = ", ".join(
            f"{k}={v:g}" for k, v in sorted(fr["fault_counters"].items())
        )
        tag = " (injected chaos)" if fr.get("injected") else ""
        print(f"FAULT-ASSISTED fit {fr['name']}{tag} "
              f"[{fr.get('git_sha', '')}]: {counters}")
    # transforms that only completed via the CPU fallback: the device path
    # was effectively down — same visibility rule as FAULT-ASSISTED
    for sr in serve_degraded:
        counters = ", ".join(
            f"{k}={v:g}" for k, v in sorted(sr["serve"].items())
        )
        print(f"SERVE-DEGRADED transform {sr['name']} "
              f"[{sr.get('git_sha', '')}]: {counters}")
    # a requested Pallas plan that only served via XLA: exact results,
    # but not the kernel the operator turned on — same visibility rule
    for pr in pallas_degraded:
        counters = ", ".join(
            f"{k}={v:g}" for k, v in sorted(pr["serve"].items())
            if k.startswith("fused.pallas")
        )
        print(f"PALLAS-DEGRADED transform {pr['name']} "
              f"[{pr.get('git_sha', '')}]: {counters}")
    # a warm-artifact read that degraded to a recompile: exact results,
    # slow boot — the reason-coded counters say whether it was a torn
    # write, rot, or a fingerprint (jax/backend) mismatch
    for wr in warmstart_degraded:
        counters = ", ".join(
            f"{k}={v:g}" for k, v in sorted(wr["serve"].items())
            if k.startswith("warmstart.")
        )
        print(f"WARMSTART-DEGRADED transform {wr['name']} "
              f"[{wr.get('git_sha', '')}]: {counters}")
    # data-plane drift per surface: the worst column against the deploy
    # reference — same visibility rule as the flags above
    for dr in drift_rows:
        if not dr["reference_complete"]:
            print(f"DRIFT {dr['kind']} {dr['name']} "
                  f"[{dr.get('git_sha', '')}]: reference filling "
                  f"({dr.get('live_rows', 0)} rows)")
        elif dr["worst_column"] is None:
            print(f"DRIFT {dr['kind']} {dr['name']} "
                  f"[{dr.get('git_sha', '')}]: no comparable columns")
        else:
            verdict = "BREACH" if dr["breaching"] else "ok"
            print(f"DRIFT {dr['kind']} {dr['name']} "
                  f"[{dr.get('git_sha', '')}]: worst "
                  f"{dr['worst_column']} psi={dr['psi']:g} "
                  f"ks={dr['ks']:g} (threshold {dr['threshold']:g}) "
                  f"{verdict}")
    # the fits that compiled inside themselves, by stage, cache and span:
    # a fit that recompiled in a warm loop names itself here
    for cf in compiled:
        stages = " ".join(f"{stage}={cf[stage + '_s']:.3f}s"
                          for stage in _COMPILE_STAGES)
        under = ", ".join(f"{k}={v:.3f}s" for k, v in cf["under"].items())
        print(f"COMPILED fit {cf['name']} #{cf['fit_index']} "
              f"[{cf.get('git_sha', '')}]: {cf['programs']:g} program(s) "
              f"{stages} hits={cf['cache_hits']:g} "
              f"misses={cf['cache_misses']:g}; under {under}")
    # tail-quantile lines for the latest fit/transform per name
    for line in _timing_lines(timing_summary):
        print(line)
    print(f"{len(reports)} RunReport(s) read")
    return 0


if __name__ == "__main__":
    sys.exit(main())
