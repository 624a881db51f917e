"""End-to-end request tracing: Dapper-style spans with explicit handoff.

The registry (``obs/registry.py``) answers "how much, in aggregate"; this
module answers "where did THIS request's time go".  A trace is a tree of
spans sharing one ``trace_id``, minted at a top-level entry point —
``ModelServer.submit``, a guarded ``fit``, a top-level ``transform`` —
and propagated by EXPLICIT context handoff across every thread boundary
the serving stack crosses (the dispatcher thread, the prefetch producer
threads, fused-plan dispatch), so one served request renders as one
causally-nested waterfall::

    serving.request (root, minted at submit)
      submit          admission + enqueue, on the caller thread
      queue_wait      enqueue -> batch take, recorded by the dispatcher
      coalesce        request tables -> one batch table
      transform       the coalesced dispatch
        place_h2d       host prep + H2D staging (prefetch thread)
        serve.dispatch  breaker-guarded device call
          fused_dispatch  the ONE jitted call of a fused plan
            device_sync     the bundled fetch (device execution)
      demux           outputs + quarantine side-tables back per caller

Since the replica fleet (PRs 13–15) the tree also crosses PROCESSES: the
router mints ``router.request`` and ships its context over the replica
wire protocol; the replica installs it with :func:`adopt`, so its
``serving.request`` (and everything under it) lands in the SAME trace.
Each process writes its own ``traces-<pid>.jsonl`` sink;
``python -m flink_ml_tpu.obs fleet`` merges them by trace id into one
clock-corrected timeline (offsets measured by the router's ``/healthz``
probe, :func:`note_clock_offset`)::

    router.request (root, router process)
      submit / queue_wait    admission + router queue
      router.dispatch        one span PER ATTEMPT — retries are siblings
        serving.request        the replica's root, adopted context
          ... the in-process waterfall above ...

Design rules, in the obs-registry tradition:

* **Off by default, one-bool hooks.**  ``span()`` returns a shared
  ``nullcontext`` after a single module-bool check when tracing is off,
  and again when no trace is active on the calling thread — instrumented
  hot paths pay nothing measurable.  Enable with
  ``FMT_TRACE=1`` or :func:`enable`.
* **Head sampling.**  ``FMT_TRACE_SAMPLE`` (0..1, default 1.0) decides at
  trace-mint time; an unsampled request carries no context and every
  downstream hook stays one boolean check.  An ADOPTED context skips the
  coin flip — the remote minting process already decided.
* **Tail sampling.**  ``FMT_TRACE_TAIL=slow|shed|error`` (comma-combinable)
  buffers each trace in memory and writes it to the sink only when its
  local boundary span is anomalous: slower than ``FMT_TRACE_SLOW_MS``,
  shed, or errored.  Always-on production tracing then persists only the
  traces worth reading.
* **Explicit handoff, never ambient.**  A cross-thread consumer installs
  the submitting request's context with :func:`use` (the dispatcher
  installs EVERY coalesced request's context at once — batch-scope spans
  fan out to each sampled trace with shared timestamps, so each caller's
  waterfall is complete on its own).  A thread with no installed context
  records nothing: a racing sibling's spans can never attach to the
  wrong trace.
* **Spans are JSONL.**  Every finished span appends one line to
  ``FMT_TRACE_DIR``'s ``traces-<pid>.jsonl`` (default: the reports dir),
  rotated at ``FMT_TRACE_MAX_MB`` with a reports-style commit sidecar —
  ``python -m flink_ml_tpu.obs trace`` renders one process's waterfall,
  ``... obs fleet`` the stitched multi-process one.
* **Phase attribution.**  Every record carries a ``phase`` class
  (``queue``/``coalesce``/``compile``/``h2d``/``compute``/``demux``/
  ``net``); :func:`note_compile` additionally keys compile-bearing
  dispatches by (kernel, bucket rung, mesh, dtype) into a persistent
  ``reports/compile_ledger.jsonl`` — the per-rung cost table ROADMAP
  item 2's AOT warm-start needs as its before/after evidence.

Knobs (README.md, "Tracing & flight recorder"): ``FMT_TRACE``,
``FMT_TRACE_SAMPLE``, ``FMT_TRACE_DIR``, ``FMT_TRACE_TAIL``,
``FMT_TRACE_SLOW_MS``, ``FMT_TRACE_MAX_MB``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "RequestTrace",
    "SpanContext",
    "adopt",
    "attr",
    "compile_ledger_path",
    "current",
    "current_trace_ids",
    "enable",
    "enabled",
    "fleet_main",
    "flush",
    "load_clock_offsets",
    "load_spans",
    "main",
    "note_clock_offset",
    "note_compile",
    "phase_of",
    "phase_totals",
    "record_span",
    "render_waterfall",
    "reset",
    "root_span",
    "sample_rate",
    "set_tail",
    "sink_status",
    "span",
    "start_request",
    "stitch",
    "tail_modes",
    "trace_dir",
    "traces_path",
    "use",
]


from flink_ml_tpu.obs.registry import set_trace_hook
from flink_ml_tpu.utils import knobs

_ENABLED = knobs.knob_bool("FMT_TRACE")

#: the serving shed vocabulary (serving/errors.py SHED_* codes) — spans
#: ended by an exception carrying one of THESE reasons are load sheds,
#: not failures.  Matched by value, not type: this module must stay
#: importable without the serving package (and stdlib exceptions like
#: UnicodeDecodeError carry an unrelated ``.reason`` attribute).
_SHED_REASONS = frozenset(
    ("queue_full", "deadline_expired", "breaker_open", "shutdown")
)
_SAMPLE = knobs.knob_float("FMT_TRACE_SAMPLE")

_RNG = random.Random()  # OS-seeded; head-sampling only, never correctness

#: tail-sampling modes: keep a trace only when its boundary span is...
_TAIL_MODES = frozenset(("slow", "shed", "error"))


def _parse_tail(spec: str) -> frozenset:
    toks = [t.strip().lower() for t in str(spec or "").replace(",", " ").split()]
    return frozenset(t for t in toks if t in _TAIL_MODES)


_TAIL = _parse_tail(knobs.knob_str("FMT_TRACE_TAIL"))


def enabled() -> bool:
    """Is span tracing on for this process?"""
    return _ENABLED


def enable(on: bool = True, sample: Optional[float] = None) -> None:
    """Turn tracing on/off; optionally set the head-sampling rate."""
    global _ENABLED, _SAMPLE
    _ENABLED = bool(on)
    # obs.span is live under a request trace too
    set_trace_hook(_open_child if _ENABLED else None)
    if sample is not None:
        _SAMPLE = float(sample)


def sample_rate() -> float:
    return _SAMPLE


def set_tail(spec: str) -> None:
    """Set the tail-sampling modes (``"slow,error"``; ``""`` turns tail
    sampling off).  Buffered not-yet-judged traces are dropped — a mode
    change must not leak half-a-trace under the OLD policy."""
    global _TAIL
    with _SINK_LOCK:
        _TAIL = _parse_tail(spec)
        _TRACE_BUF.clear()


def tail_modes() -> Tuple[str, ...]:
    """Active tail-sampling modes (empty tuple: every trace persists)."""
    return tuple(sorted(_TAIL))


def _sampled() -> bool:
    if _SAMPLE >= 1.0:
        return True
    if _SAMPLE <= 0.0:
        return False
    return _RNG.random() < _SAMPLE


def _mint_id() -> str:
    return os.urandom(8).hex()


class SpanContext:
    """One (trace, parent span) coordinate a child span attaches under.

    Immutable and tiny by design: contexts cross thread boundaries inside
    queued requests and prefetch closures."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):  # pragma: no cover - debugging nicety
        return f"SpanContext({self.trace_id}, {self.span_id})"


# -- phase attribution --------------------------------------------------------

#: the cost-attribution vocabulary every span record is classed into
PHASES = ("queue", "coalesce", "compile", "h2d", "compute", "demux", "net")

#: span name -> phase, for every span this codebase mints.  Names not
#: listed fall through the substring rules below, then to "compute" —
#: an unknown span is most likely wrapping work, not waiting.
_PHASE_BY_NAME = {
    "submit": "queue",
    "queue_wait": "queue",
    "serving.request": "queue",
    "router.request": "queue",
    "coalesce": "coalesce",
    "compile": "compile",
    "place_h2d": "h2d",
    "transform": "compute",
    "serve.dispatch": "compute",
    "fused_dispatch": "compute",
    "device_sync": "compute",
    "plan_fallback": "compute",
    "demux": "demux",
    "router.dispatch": "net",
}

_PHASE_RULES = (
    ("compile", "compile"),
    ("h2d", "h2d"),
    ("place", "h2d"),
    ("coalesce", "coalesce"),
    ("demux", "demux"),
    ("queue", "queue"),
    ("wait", "queue"),
    ("submit", "queue"),
    ("request", "queue"),
    ("probe", "net"),
    ("dispatch", "compute"),
)


def phase_of(name: str) -> str:
    """The cost-attribution phase class for a span name.  Request-root
    spans class as ``queue``: their SELF time (total minus children) is
    admission + future-resolution overhead, which is queueing."""
    p = _PHASE_BY_NAME.get(name)
    if p is not None:
        return p
    low = str(name).lower()
    for needle, phase in _PHASE_RULES:
        if needle in low:
            return phase
    return "compute"


# -- the sink -----------------------------------------------------------------

#: recent finished spans, in-memory (tests; waterfall without a file)
_RECENT_CAP = 4096
_SINK_LOCK = threading.Lock()
_RECENT: deque = deque(maxlen=_RECENT_CAP)
_FILE = None
_FILE_PATH: Optional[str] = None
_WRITE_FAILED = False
_WRITTEN = 0
_ROTATIONS = 0

#: tail-sampling buffers: trace_id -> serialized lines awaiting the
#: boundary span's verdict.  Bounded both ways — a trace that never
#: completes locally is evicted FIFO, a runaway trace stops buffering.
_TRACE_BUF: Dict[str, list] = {}
_TAIL_MAX_TRACES = 256
_TAIL_MAX_SPANS = 2048
_TAIL_DROPPED = 0


def trace_dir() -> str:
    """Where this process's trace sinks live: ``FMT_TRACE_DIR``, else the
    reports dir.  Shared by every process of a fleet — per-pid filenames
    keep the writers from interleaving."""
    d = knobs.raw("FMT_TRACE_DIR")
    if not d:
        from flink_ml_tpu.obs.report import reports_dir

        d = reports_dir()
    return d


def traces_path() -> str:
    """THIS process's sink: ``traces-<pid>.jsonl`` under :func:`trace_dir`.
    The pid is read per call, not cached — a forked child naturally
    switches to its own file on its first flush."""
    return os.path.join(trace_dir(), f"traces-{os.getpid()}.jsonl")


#: lines not yet flushed to the sink file — flushed when a BOUNDARY span
#: lands (a trace just completed locally: make it readable) or the buffer
#: grows past the cap, NOT per span: per-span flushes put file I/O inside
#: every sampled request's hot path and were the dominant enabled-at-1%
#: cost.  A boundary span is a parentless root OR a request root whose
#: parent lives in another process (an adopted context never records a
#: parentless line, so parent-lessness alone would never trigger).
_PENDING: list = []
_PENDING_CAP = 256


def _tail_keep(record: dict) -> bool:
    status = record.get("status")
    if "error" in _TAIL and status == "error":
        return True
    if "shed" in _TAIL and status == "shed":
        return True
    if "slow" in _TAIL and (
        record.get("dur_s", 0.0) * 1e3 >= knobs.knob_float("FMT_TRACE_SLOW_MS")
    ):
        return True
    return False


def _emit(record: dict, boundary: bool = False) -> None:
    """Append one finished span to the in-memory ring and the (buffered)
    JSONL sink.  I/O failures are swallowed after one flag flip —
    tracing must never fail the request it is describing."""
    global _TAIL_DROPPED
    boundary = boundary or not record.get("parent_id")
    with _SINK_LOCK:
        _RECENT.append(record)
        if _WRITE_FAILED:
            return
        line = json.dumps(record, sort_keys=True)
        if _TAIL:
            tid = record.get("trace_id") or ""
            buf = _TRACE_BUF.get(tid)
            if buf is None:
                if len(_TRACE_BUF) >= _TAIL_MAX_TRACES:
                    _TRACE_BUF.pop(next(iter(_TRACE_BUF)))
                    _TAIL_DROPPED += 1
                buf = _TRACE_BUF[tid] = []
            if len(buf) < _TAIL_MAX_SPANS:
                buf.append(line)
            if boundary:
                lines = _TRACE_BUF.pop(tid, [])
                if _tail_keep(record):
                    _PENDING.extend(lines)
                    _flush_locked()
                else:
                    _TAIL_DROPPED += 1
            return
        _PENDING.append(line)
        if boundary or len(_PENDING) >= _PENDING_CAP:
            _flush_locked()


def _flush_locked() -> None:
    global _FILE, _FILE_PATH, _WRITE_FAILED, _WRITTEN
    if not _PENDING:
        return
    try:
        path = traces_path()
        if _FILE is None or _FILE_PATH != path:
            if _FILE is not None:
                _FILE.close()
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _FILE = open(path, "a")  # noqa: SIM115 - cached handle
            _FILE_PATH = path
        _FILE.write("\n".join(_PENDING) + "\n")
        _FILE.flush()
        _WRITTEN += len(_PENDING)
        _PENDING.clear()
        _maybe_rotate_locked()
    except OSError:
        _WRITE_FAILED = True
        _PENDING.clear()


def _maybe_rotate_locked() -> None:
    """Size-cap the live sink: past ``FMT_TRACE_MAX_MB`` the file moves to
    ``<path>.1`` (one rotated generation, same crash-evident commit
    sidecar the reports dir uses) and the next flush starts fresh."""
    global _FILE, _ROTATIONS
    if _FILE is None or _FILE_PATH is None:
        return
    max_mb = knobs.knob_float("FMT_TRACE_MAX_MB")
    if max_mb <= 0 or _FILE.tell() < max_mb * 1024 * 1024:
        return
    _FILE.close()
    _FILE = None  # the next flush reopens a fresh file at the same path
    rotated = _FILE_PATH + ".1"
    os.replace(_FILE_PATH, rotated)
    _ROTATIONS += 1
    try:
        from flink_ml_tpu.serve.integrity import write_commit_record

        write_commit_record(rotated)
    except (OSError, ImportError):
        pass  # the sidecar is best-effort; the rotated data is already safe


def flush() -> None:
    """Force any buffered span lines to the sink file (tests; shutdown)."""
    with _SINK_LOCK:
        _flush_locked()


def recent_spans() -> List[dict]:
    """Finished spans still in the in-memory ring (newest last)."""
    with _SINK_LOCK:
        return list(_RECENT)


def sink_status() -> dict:
    """Sink health for ``/statusz``: where spans go and whether they are
    getting there."""
    with _SINK_LOCK:
        return {
            "enabled": _ENABLED,
            "sample": _SAMPLE,
            "tail": list(tail_modes()),
            "path": _FILE_PATH or traces_path(),
            "write_failed": _WRITE_FAILED,
            "pending": len(_PENDING),
            "buffered_traces": len(_TRACE_BUF),
            "written": _WRITTEN,
            "rotations": _ROTATIONS,
            "tail_dropped": _TAIL_DROPPED,
        }


def reset() -> None:
    """Drop the in-memory ring and the cached sink handle (tests)."""
    global _FILE, _FILE_PATH, _WRITE_FAILED, _WRITTEN, _ROTATIONS
    global _TAIL_DROPPED
    with _SINK_LOCK:
        _RECENT.clear()
        _PENDING.clear()
        _TRACE_BUF.clear()
        if _FILE is not None:
            try:
                _FILE.close()
            except OSError:
                pass
        _FILE = None
        _FILE_PATH = None
        _WRITE_FAILED = False
        _WRITTEN = 0
        _ROTATIONS = 0
        _TAIL_DROPPED = 0
    with _LEDGER_LOCK:
        _LEDGER_SEEN.clear()


# -- span frames --------------------------------------------------------------


class _Frame:
    """One open span on a thread's stack.

    ``parents`` is a tuple of :class:`SpanContext` — usually one, several
    when the dispatcher serves a coalesced batch (the span then records
    once per parent trace, same span_id and timestamps).  ``span_id`` of
    ``None`` marks a pass-through frame installed by :func:`use`: it
    parents children but records no span of its own."""

    __slots__ = ("parents", "span_id", "name", "ts", "t0", "attrs")

    def __init__(self, parents, span_id, name, attrs):
        self.parents = tuple(parents)
        self.span_id = span_id
        self.name = name
        self.ts = time.time()
        self.t0 = time.perf_counter()
        self.attrs = dict(attrs) if attrs else {}


_TLS = threading.local()
_NULL = contextlib.nullcontext()


def _frames() -> Optional[list]:
    return getattr(_TLS, "frames", None)


def current() -> Tuple[SpanContext, ...]:
    """The calling thread's active context(s) — what a child span (or a
    cross-thread handoff) should parent under.  Empty when no trace is
    active here."""
    frames = _frames()
    if not frames:
        return ()
    f = frames[-1]
    if f.span_id is None:  # pass-through (use()) frame
        return f.parents
    return tuple(SpanContext(p.trace_id, f.span_id) for p in f.parents)


def current_trace_ids() -> Tuple[str, ...]:
    """Trace ids active on this thread (deduplicated, order kept)."""
    seen = []
    for c in current():
        if c.trace_id not in seen:
            seen.append(c.trace_id)
    return tuple(seen)


def _record(parents, span_id, name, ts, dur_s, attrs, status,
            boundary: bool = False) -> None:
    thread = threading.current_thread().name
    phase = phase_of(name)
    pid = os.getpid()
    for p in parents:
        _emit({
            "trace_id": p.trace_id,
            "span_id": span_id,
            "parent_id": p.span_id,
            "name": name,
            "ts": ts,
            "dur_s": dur_s,
            "status": status,
            "thread": thread,
            "phase": phase,
            "pid": pid,
            "attrs": attrs or {},
        }, boundary=boundary)


@contextlib.contextmanager
def _span_cm(parents, name, attrs):
    frames = _frames()
    if frames is None:
        frames = _TLS.frames = []
    frame = _Frame(parents, _mint_id(), name, attrs)
    frames.append(frame)
    status = "ok"
    try:
        yield frame
    except BaseException as exc:
        status = ("shed" if getattr(exc, "reason", None) in _SHED_REASONS
                  else "error")
        frame.attrs.setdefault("error", type(exc).__name__)
        raise
    finally:
        dur = time.perf_counter() - frame.t0
        frames.pop()
        _record(frame.parents, frame.span_id, frame.name, frame.ts, dur,
                frame.attrs, status)


def span(name: str, attrs: Optional[dict] = None):
    """Context manager recording a child span of this thread's active
    trace(s).  One boolean check when tracing is off, and again when no
    trace is active on this thread — a no-trace hot path never builds a
    frame."""
    if not _ENABLED:
        return _NULL
    parents = current()
    if not parents:
        return _NULL
    return _span_cm(parents, name, attrs)


def root_span(name: str, attrs: Optional[dict] = None):
    """Context manager minting a NEW trace — unless a trace is already
    active on this thread, in which case it degrades to a plain child
    span (a transform inside a served request must not re-root).  Head
    sampling applies only at the true mint."""
    if not _ENABLED:
        return _NULL
    parents = current()
    if parents:
        return _span_cm(parents, name, attrs)
    if not _sampled():
        return _NULL
    return _span_cm((SpanContext(_mint_id(), ""),), name, attrs)


@contextlib.contextmanager
def _use_cm(parents):
    frames = _frames()
    if frames is None:
        frames = _TLS.frames = []
    frames.append(_Frame(parents, None, None, None))
    try:
        yield
    finally:
        frames.pop()


def use(parents: Sequence[SpanContext]):
    """Install already-minted context(s) on THIS thread without opening a
    span — the explicit cross-thread handoff.  The dispatcher installs
    every coalesced request's context at once; the prefetch producer
    installs its consumer's.  No-op (shared nullcontext) when tracing is
    off or ``parents`` is empty."""
    if not _ENABLED or not parents:
        return _NULL
    return _use_cm(tuple(parents))


def adopt(trace_id: Optional[str], parent_span_id: str = ""):
    """Install a REMOTE trace context on this thread — the cross-process
    handoff.  The replica data plane calls this with the ids the router
    shipped in the wire payload; everything recorded inside (the
    replica's ``serving.request`` and its whole subtree) lands in the
    router's trace, parented under its dispatch span.

    No sampling coin flip: the remote minting process already decided —
    a shipped context IS the sampled-in verdict.  No-op (shared
    nullcontext) when tracing is off here or ``trace_id`` is falsy."""
    if not _ENABLED or not trace_id:
        return _NULL
    return _use_cm(
        (SpanContext(str(trace_id), str(parent_span_id or "")),)
    )


def attr(key: str, value) -> None:
    """Set an attribute on the innermost OPEN span of this thread (skipping
    pass-through frames).  One boolean check when tracing is off."""
    if not _ENABLED:
        return
    frames = _frames()
    if not frames:
        return
    for f in reversed(frames):
        if f.span_id is not None:
            f.attrs[key] = value
            return


def record_span(parents: Sequence[SpanContext], name: str, dur_s: float,
                attrs: Optional[dict] = None, status: str = "ok",
                end_ts: Optional[float] = None) -> None:
    """Record a span whose boundaries were measured elsewhere (the
    dispatcher's ``queue_wait`` spans the enqueue-to-take window; the
    fused trainer's dispatch/sync splits are computed post-hoc).  ``ts``
    is derived as ``end_ts - dur_s`` (wall now when ``end_ts`` is None)."""
    if not _ENABLED or not parents:
        return
    ts = (end_ts if end_ts is not None else time.time()) - max(dur_s, 0.0)
    _record(tuple(parents), _mint_id(), name, ts, max(dur_s, 0.0),
            attrs, status)


def _open_child(name: str):
    """``obs.span``'s hook, registered while tracing is on: None where no
    trace is active on the thread, else the ``close(seconds, status)``
    that records the span as a child, with its true start."""
    parents = current()
    if not parents:
        return None
    ts = time.time()
    return lambda dur_s, status: record_span(
        parents, name, dur_s, status=status, end_ts=ts + dur_s)


if _ENABLED:  # FMT_TRACE=1 in the environment
    set_trace_hook(_open_child)


class RequestTrace:
    """A root span whose start and end live on DIFFERENT threads (minted
    at ``ModelServer.submit`` on the caller thread, ended by the
    dispatcher when the future resolves) — so it cannot ride the
    thread-local stack.  ``ctx`` is what children and handoffs parent
    under; :meth:`end` is single-shot and thread-safe.

    With ``parent`` (an adopted remote context) the "root" joins an
    existing trace instead of minting one — the replica's request span
    nests under the router's dispatch span.  Its end record is still the
    process-local BOUNDARY: it flushes the sink and, under tail
    sampling, is the span the keep/drop verdict reads."""

    __slots__ = ("trace_id", "ctx", "parent_id", "name", "ts", "t0",
                 "attrs", "_done")

    def __init__(self, name: str, attrs: Optional[dict] = None,
                 parent: Optional[SpanContext] = None):
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.trace_id = _mint_id()
            self.parent_id = ""
        self.ctx = SpanContext(self.trace_id, _mint_id())
        self.name = name
        self.ts = time.time()
        self.t0 = time.perf_counter()
        self.attrs = dict(attrs) if attrs else {}
        self._done = False

    def end(self, status: str = "ok",
            attrs: Optional[dict] = None) -> None:
        if self._done:  # benign double-end (error path + finally)
            return
        self._done = True
        if attrs:
            self.attrs.update(attrs)
        _record((SpanContext(self.trace_id, self.parent_id),),
                self.ctx.span_id, self.name, self.ts,
                time.perf_counter() - self.t0, self.attrs, status,
                boundary=True)


def start_request(name: str,
                  attrs: Optional[dict] = None) -> Optional[RequestTrace]:
    """Mint a request-scoped root trace (head sampling applies); ``None``
    when tracing is off or the request was sampled out — the whole
    request then costs one boolean per downstream hook.

    When a context is already active on this thread (the replica handler
    wrapped the call in :func:`adopt`; a nested in-process submit), the
    request JOINS it — same trace id, parented under the active span,
    no second coin flip."""
    if not _ENABLED:
        return None
    parents = current()
    if parents:
        return RequestTrace(name, attrs, parent=parents[0])
    if not _sampled():
        return None
    return RequestTrace(name, attrs)


# -- the compile ledger -------------------------------------------------------

_LEDGER_LOCK = threading.Lock()
_LEDGER_SEEN: set = set()


def compile_ledger_path() -> str:
    """The persistent per-rung compile ledger, next to the other report
    artifacts."""
    from flink_ml_tpu.obs.report import reports_dir

    return os.path.join(reports_dir(), "compile_ledger.jsonl")


def note_compile(kernel: str, bucket: int, mesh: int, dtype: str,
                 dur_s: float) -> None:
    """Record one compile-bearing dispatch: a ``compile``-phase span under
    the active trace(s), plus one line per distinct (kernel, bucket rung,
    mesh width, dtype) key in ``reports/compile_ledger.jsonl`` — the
    durable cost table a future AOT warm-start (ROADMAP item 2) proves
    itself against.  First-seen-per-process keys only; repeats are cache
    hits and carry no compile."""
    attrs = {"kernel": str(kernel), "bucket": int(bucket),
             "mesh": int(mesh), "dtype": str(dtype)}
    if _ENABLED:
        parents = current()
        if parents:
            record_span(parents, "compile", dur_s, attrs)
    ledger_on = _ENABLED
    if not ledger_on:
        try:
            from flink_ml_tpu import obs

            ledger_on = obs.enabled()
        except ImportError:  # pragma: no cover - partial installs
            return
    if not ledger_on:
        return
    key = (attrs["kernel"], attrs["bucket"], attrs["mesh"], attrs["dtype"])
    with _LEDGER_LOCK:
        if key in _LEDGER_SEEN:
            return
        _LEDGER_SEEN.add(key)
    entry = dict(attrs)
    entry["dur_s"] = float(dur_s)
    entry["ts"] = time.time()
    entry["pid"] = os.getpid()
    path = compile_ledger_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(entry, sort_keys=True) + "\n")
    except OSError:
        pass  # the ledger must never fail the dispatch it measures


# -- fleet clock offsets ------------------------------------------------------


def clock_offsets_path(directory: Optional[str] = None) -> str:
    return os.path.join(directory or trace_dir(), "clock_offsets.jsonl")


def note_clock_offset(pid: int, offset_s: float, rtt_s: float) -> None:
    """Append one router-measured clock-offset estimate for a replica
    process: ``offset_s`` is (replica wall clock - router wall clock),
    NTP-style — server timestamp against the probe's RTT midpoint.  The
    stitcher subtracts it to land every process on the router's
    timeline; lower-RTT estimates win."""
    entry = {"pid": int(pid), "offset_s": float(offset_s),
             "rtt_s": float(rtt_s), "ts": time.time()}
    path = clock_offsets_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(entry, sort_keys=True) + "\n")
    except OSError:
        pass


def load_clock_offsets(directory: Optional[str] = None) -> Dict[int, float]:
    """pid -> best (lowest-RTT) clock-offset estimate, seconds."""
    path = clock_offsets_path(directory)
    if not os.path.exists(path):
        return {}
    best: Dict[int, Tuple[float, float]] = {}
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return {}
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            e = json.loads(line)
            pid = int(e["pid"])
            rtt = float(e.get("rtt_s", 0.0))
            off = float(e.get("offset_s", 0.0))
        except (ValueError, KeyError, TypeError):
            continue
        if pid not in best or rtt < best[pid][0]:
            best[pid] = (rtt, off)
    return {pid: off for pid, (rtt, off) in best.items()}


# -- the waterfall ------------------------------------------------------------


def load_spans(path: Optional[str] = None) -> List[dict]:
    """All span records from the JSONL sink(s).  ``path`` may be one file
    or a directory — a directory (default: :func:`trace_dir`) merges
    every ``traces*.jsonl`` in it plus rotated ``.1`` generations, which
    is how a fleet's per-pid sinks become one span list.  Malformed
    lines — a crash or kill -9 mid-write tears at most the final line of
    a per-pid file — are skipped: a black box must open."""
    path = path or trace_dir()
    if os.path.isdir(path):
        try:
            names = sorted(os.listdir(path))
        except OSError:
            return []
        files = [
            os.path.join(path, n) for n in names
            if n.startswith("traces")
            and (n.endswith(".jsonl") or n.endswith(".jsonl.1"))
        ]
        # a file's rotated generation holds its OLDER spans: read it first
        files.sort(key=lambda p: (not p.endswith(".1"), p))
    else:
        files = [path]
    out = []
    for fp in files:
        if not os.path.exists(fp):
            continue
        try:
            with open(fp) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            continue
    return out


def trace_ids(spans: List[dict]) -> List[str]:
    """Distinct trace ids in first-seen order."""
    seen: List[str] = []
    for s in spans:
        t = s.get("trace_id")
        if t and t not in seen:
            seen.append(t)
    return seen


def stitch(spans: List[dict],
           offsets: Optional[Dict[int, float]] = None) -> List[dict]:
    """Merge multi-process spans onto ONE timeline: shift each span by
    its process's clock offset (:func:`load_clock_offsets`), then clamp
    children to start no earlier than their parent — offsets are RTT
    estimates, and a child that APPEARS to precede its cause renders as
    a lie.  Returns corrected copies; the input is untouched."""
    out = [dict(s) for s in spans]
    if offsets:
        for s in out:
            off = offsets.get(s.get("pid"))
            if off:
                s["ts"] = float(s.get("ts", 0.0)) - off
    by_key: Dict[tuple, List[dict]] = {}
    for s in out:
        by_key.setdefault((s.get("trace_id"), s.get("span_id")), []).append(s)
    for _ in range(8):  # bounded passes: deeper nesting than 8 hops is a bug
        changed = False
        for s in out:
            parent_id = s.get("parent_id")
            if not parent_id:
                continue
            parents = by_key.get((s.get("trace_id"), parent_id))
            if not parents:
                continue
            p_ts = min(float(p.get("ts", 0.0)) for p in parents)
            if float(s.get("ts", 0.0)) < p_ts:
                s["ts"] = p_ts
                changed = True
        if not changed:
            break
    return out


def _uniq_spans(spans: List[dict], trace_id: str) -> List[dict]:
    mine = [s for s in spans if s.get("trace_id") == trace_id]
    seen = set()
    uniq = []
    for s in mine:
        k = (s.get("span_id"), s.get("parent_id"), s.get("name"))
        if k in seen:
            continue
        seen.add(k)
        uniq.append(s)
    return uniq


def phase_totals(spans: List[dict], trace_id: str) -> Dict[str, float]:
    """Per-phase SELF time (a span's duration minus its children's) for
    one trace — where the request's wall clock actually went, with no
    double counting up the tree."""
    uniq = _uniq_spans(spans, trace_id)
    child_dur: Dict[str, float] = {}
    for s in uniq:
        parent_id = s.get("parent_id") or ""
        if parent_id:
            child_dur[parent_id] = (
                child_dur.get(parent_id, 0.0) + float(s.get("dur_s", 0.0))
            )
    totals: Dict[str, float] = {}
    for s in uniq:
        self_s = max(
            float(s.get("dur_s", 0.0))
            - child_dur.get(s.get("span_id") or "", 0.0),
            0.0,
        )
        phase = s.get("phase") or phase_of(s.get("name", ""))
        totals[phase] = totals.get(phase, 0.0) + self_s
    return totals


def _fmt_attrs(attrs: dict) -> str:
    if not attrs:
        return ""
    parts = [f"{k}={attrs[k]}" for k in sorted(attrs)]
    s = " ".join(parts)
    return s if len(s) <= 72 else s[:69] + "..."


def render_waterfall(spans: List[dict], trace_id: str,
                     width: int = 40) -> str:
    """One trace's spans as an indented text waterfall.

    Rows sort children under parents in start order; the bar shows each
    span's [offset, offset+dur) window against the trace's full extent.
    Duplicate (span_id, parent) lines — a resumed sink — keep the first.
    A multi-process (stitched) trace annotates each row with its pid.
    """
    uniq = _uniq_spans(spans, trace_id)
    if not uniq:
        return f"no spans for trace {trace_id}"
    by_parent: Dict[str, List[dict]] = {}
    for s in uniq:
        by_parent.setdefault(s.get("parent_id") or "", []).append(s)
    for kids in by_parent.values():
        kids.sort(key=lambda s: s.get("ts", 0.0))
    t_lo = min(s.get("ts", 0.0) for s in uniq)
    t_hi = max(s.get("ts", 0.0) + s.get("dur_s", 0.0) for s in uniq)
    total = max(t_hi - t_lo, 1e-9)
    name_w = max(
        len(s.get("name", "")) + 2 * _depth_of(s, uniq) for s in uniq
    )
    pids = sorted({s.get("pid") for s in uniq if s.get("pid")})
    multi = len(pids) > 1
    head = f"trace {trace_id}  ({total * 1e3:.1f} ms, {len(uniq)} span(s)"
    head += f", {len(pids)} process(es))" if multi else ")"
    lines = [head]

    def walk(parent_id: str, depth: int):
        for s in by_parent.get(parent_id, ()):
            off = s.get("ts", 0.0) - t_lo
            dur = s.get("dur_s", 0.0)
            lo = int(round(off / total * width))
            hi = max(int(round((off + dur) / total * width)), lo + 1)
            bar = " " * lo + "█" * min(hi - lo, width - lo)
            label = "  " * depth + s.get("name", "?")
            status = s.get("status", "ok")
            mark = "" if status == "ok" else f" !{status}"
            if multi:
                mark += f" @{s.get('pid', '?')}"
            lines.append(
                f"  {label:<{name_w}} {off * 1e3:>8.2f}ms "
                f"{dur * 1e3:>8.2f}ms |{bar:<{width}}|{mark}"
                + (f"  {_fmt_attrs(s.get('attrs') or {})}"
                   if s.get("attrs") else "")
            )
            walk(s.get("span_id", ""), depth + 1)

    walk("", 0)
    # orphans (parent span lost — e.g. the ring rolled): render flat
    known = {s.get("span_id") for s in uniq} | {""}
    for s in uniq:
        if s.get("parent_id") not in known:
            off = s.get("ts", 0.0) - t_lo
            lines.append(
                f"  ~{s.get('name', '?'):<{name_w}} {off * 1e3:>7.2f}ms "
                f"{s.get('dur_s', 0.0) * 1e3:>8.2f}ms (orphan)"
            )
    return "\n".join(lines)


def _depth_of(s: dict, spans: List[dict]) -> int:
    by_id = {x.get("span_id"): x for x in spans}
    d, cur, hops = 0, s, 0
    while cur.get("parent_id") and hops < 32:
        cur = by_id.get(cur["parent_id"])
        if cur is None:
            break
        d += 1
        hops += 1
    return d


def main(argv=None) -> int:
    """``python -m flink_ml_tpu.obs trace [TRACE_ID]`` — render one
    trace's waterfall from the JSONL sink (latest root trace when no id
    is given); ``--list`` enumerates traces instead."""
    parser = argparse.ArgumentParser(
        prog="python -m flink_ml_tpu.obs trace",
        description="Render a span waterfall from the trace sink.",
    )
    parser.add_argument("trace_id", nargs="?", default=None,
                        help="trace to render (default: the latest)")
    parser.add_argument("--traces", default=None,
                        help="trace sink file or directory (default: "
                             "FMT_TRACE_DIR or the reports dir)")
    parser.add_argument("--list", action="store_true",
                        help="list trace ids with their root span instead")
    parser.add_argument("--width", type=int, default=40)
    args = parser.parse_args(argv)

    spans = load_spans(args.traces)
    if not spans:
        print(f"no spans in {args.traces or trace_dir()} — run with "
              "FMT_TRACE=1 first")
        return 1
    if args.list:
        roots = {
            s["trace_id"]: s for s in spans if not s.get("parent_id")
        }
        for tid in trace_ids(spans):
            r = roots.get(tid)
            desc = (f"{r.get('name')}  {r.get('dur_s', 0) * 1e3:.1f}ms "
                    f"[{r.get('status')}]" if r else "(no root span)")
            print(f"{tid}  {desc}")
        return 0
    tid = args.trace_id
    if tid is None:
        ids = trace_ids(spans)
        tid = ids[-1]
    print(render_waterfall(spans, tid, width=args.width))
    return 0


def fleet_main(argv=None) -> int:
    """``python -m flink_ml_tpu.obs fleet [TRACE_ID]`` — stitch every
    per-pid sink in the trace dir into one clock-corrected timeline and
    render it, with a per-phase self-time rollup.  Default trace: the
    latest one spanning >= 2 processes (else the latest)."""
    parser = argparse.ArgumentParser(
        prog="python -m flink_ml_tpu.obs fleet",
        description="Stitch per-process trace sinks into one waterfall.",
    )
    parser.add_argument("trace_id", nargs="?", default=None,
                        help="trace to render (default: the latest "
                             "multi-process trace)")
    parser.add_argument("--traces", default=None,
                        help="trace dir holding traces-<pid>.jsonl files "
                             "(default: FMT_TRACE_DIR or the reports dir)")
    parser.add_argument("--list", action="store_true",
                        help="list traces with their process counts instead")
    parser.add_argument("--width", type=int, default=40)
    args = parser.parse_args(argv)

    directory = args.traces or trace_dir()
    spans = load_spans(directory)
    if not spans:
        print(f"no spans in {directory} — run a traced fleet first "
              "(FMT_TRACE=1)")
        return 1
    offset_dir = directory if os.path.isdir(directory) else (
        os.path.dirname(directory) or "."
    )
    spans = stitch(spans, load_clock_offsets(offset_dir))
    ids = trace_ids(spans)
    pids_of = {
        tid: sorted({
            s.get("pid") for s in spans
            if s.get("trace_id") == tid and s.get("pid")
        })
        for tid in ids
    }
    if args.list:
        roots = {
            s["trace_id"]: s for s in spans if not s.get("parent_id")
        }
        for tid in ids:
            r = roots.get(tid)
            desc = (f"{r.get('name')}  {r.get('dur_s', 0) * 1e3:.1f}ms "
                    f"[{r.get('status')}]" if r else "(no root span)")
            print(f"{tid}  {desc}  processes={len(pids_of[tid])}")
        return 0
    tid = args.trace_id
    if tid is None:
        stitched = [t for t in ids if len(pids_of[t]) >= 2]
        tid = stitched[-1] if stitched else ids[-1]
    print(render_waterfall(spans, tid, width=args.width))
    totals = phase_totals(spans, tid)
    if totals:
        whole = sum(totals.values()) or 1e-9
        print("\nphase self-time:")
        for phase in sorted(totals, key=totals.get, reverse=True):
            ms = totals[phase] * 1e3
            print(f"  {phase:<10} {ms:>9.2f}ms  {totals[phase] / whole:5.1%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
