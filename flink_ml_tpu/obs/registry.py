"""Process-wide metrics registry + the program's one span API.

Counters (monotonic totals: chunks parsed, spill blocks written, epochs
run), gauges (last-value observations: HBM watermarks), and timing
histograms (count/total/min/max per named phase).

**Off by default.**  Every hook in a hot path reduces to one module-level
boolean check when disabled — ``span()`` / ``phase()`` return a shared
``contextlib.nullcontext`` and the record functions return immediately —
so instrumented code pays nothing measurable.  Enable
with :func:`enable` or ``FMT_OBS=1`` in the environment.

Phase timers nest: ``phase("fit")`` around ``phase("pack_csr")`` records
``phase.fit`` and ``phase.fit/pack_csr`` — the path separates host-side
packing, dispatch/compile, device sync, and spill I/O in one run's
snapshot.  The stack is thread-local, so the out-of-core prefetch thread's
phases land under their own root rather than a racing parent's.

:func:`span` is the one way to time a piece of host code: a registry
timing under its name, a ``fmt.<name>`` scope on the profiler's clock and
a child span of the thread's request trace, from one pair of clock
readings.  ``phase`` is a span whose name nests.

While recording is on the registry also listens to ``jax.monitoring``
(:func:`_listen`): every program JAX traces, lowers and compiles (or reads
from the persistent cache) is timed under ``compile.*`` and under the span
that was open on the compiling thread, and leaves one ``compile`` event in
the flight recorder.  A warm call compiles nothing and calls no listener.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, Optional
from flink_ml_tpu.utils import knobs


_ENABLED = knobs.knob_bool("FMT_OBS")


def enabled() -> bool:
    """True when telemetry recording is on."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Turn telemetry recording on (or off with ``enable(False)``): the
    switch, and with it the compile listeners (:func:`_listen`)."""
    global _ENABLED
    _ENABLED = bool(on)
    _listen(_ENABLED)


def disable() -> None:
    enable(False)


def sample_quantile(sorted_samples, q: float) -> float:
    """Nearest-rank ``q``-quantile (0..1) over already-sorted samples —
    the ONE copy of the rule, shared by :class:`TimingStat` and the
    serving runtime's per-server latency reservoir so the two can never
    disagree about what a p99 means.  Empty input -> 0.0."""
    if not sorted_samples:
        return 0.0
    i = min(int(round(q * (len(sorted_samples) - 1))),
            len(sorted_samples) - 1)
    return sorted_samples[i]


class TimingStat:
    """count/total/min/max + tail quantiles of one named duration (seconds).

    Quantiles come from a bounded ring of the most recent ``RESERVOIR``
    samples (overwritten round-robin): exact for short runs, a sliding
    recent-window estimate for long ones — the shape a serving p99 wants
    anyway (the p99 of last week's requests is not an alert signal).
    Mutation happens only under the owning registry's lock."""

    __slots__ = ("count", "total", "min", "max", "samples")

    RESERVOIR = 512

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0
        self.samples: list = []

    def observe(self, seconds: float) -> None:
        if len(self.samples) < self.RESERVOIR:
            self.samples.append(seconds)
        else:
            self.samples[self.count % self.RESERVOIR] = seconds
        self.count += 1
        self.total += seconds
        self.min = min(self.min, seconds)
        self.max = max(self.max, seconds)

    def quantile(self, q: float) -> float:
        """The ``q``-quantile (0..1) over the retained sample window."""
        return sample_quantile(sorted(self.samples), q)

    def recent(self, k: int) -> list:
        """The last ``k`` observations in arrival order (fewer when the
        stat has seen fewer) — the ring's newest slice, so a rolling
        window consumer (the SLO monitor) can judge exactly the
        observations its count delta says are new."""
        if k <= 0:
            return []
        if self.count <= len(self.samples):
            ordered = self.samples
        else:  # ring wrapped: count % RESERVOIR is the oldest slot
            i = self.count % self.RESERVOIR
            ordered = self.samples[i:] + self.samples[:i]
        return list(ordered[-int(k):])

    def _copy(self) -> "TimingStat":
        """Cheap field-wise copy (O(reservoir) list slice) — lets
        :meth:`MetricsRegistry.snapshot` release the registry lock
        before the O(n log n) quantile sorts, so a telemetry scrape
        never stalls a hot-path ``observe``/``add`` behind them."""
        out = TimingStat()
        out.count = self.count
        out.total = self.total
        out.min = self.min
        out.max = self.max
        out.samples = list(self.samples)
        return out

    def to_dict(self) -> Dict[str, float]:
        ordered = sorted(self.samples)
        return {
            "count": self.count,
            "total_s": self.total,
            # exporter vocabulary (ISSUE 10): the monotonic count/sum an
            # OpenMetrics summary needs for rate math — ``sum_s`` is
            # ``total_s`` under the name scrapers expect
            "sum_s": self.total,
            "min_s": self.min if self.count else 0.0,
            "max_s": self.max,
            "mean_s": self.total / self.count if self.count else 0.0,
            "p50_s": sample_quantile(ordered, 0.50),
            "p90_s": sample_quantile(ordered, 0.90),
            "p99_s": sample_quantile(ordered, 0.99),
        }


class MetricsRegistry:
    """Thread-safe bag of counters, gauges, and timing stats."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._timings: Dict[str, TimingStat] = {}

    def add(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            stat = self._timings.get(name)
            if stat is None:
                stat = self._timings[name] = TimingStat()
            stat.observe(seconds)

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def gauge(self, name: str) -> Optional[float]:
        with self._lock:
            return self._gauges.get(name)

    def timing(self, name: str) -> Optional[Dict[str, float]]:
        """One timing stat as its dict form (None when never observed)."""
        with self._lock:
            stat = self._timings.get(name)
            return stat.to_dict() if stat is not None else None

    def timing_recent(self, name: str, k: int) -> list:
        """The last ``k`` observations of one timing stat, in arrival
        order (empty when never observed) — see :meth:`TimingStat.recent`."""
        with self._lock:
            stat = self._timings.get(name)
            return stat.recent(k) if stat is not None else []

    def totals(self) -> dict:
        """Counters, gauges and each timing's ``{"count", "total_s"}``:
        the monotonic part of :meth:`snapshot`, with no reservoir copied
        or sorted — what a per-fit delta (``obs.report``) takes inside
        every fit."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timings": {
                    k: {"count": v.count, "total_s": v.total}
                    for k, v in self._timings.items()
                },
            }

    def snapshot(self) -> dict:
        """Plain-dict view of everything recorded (JSON-serializable).
        The lock covers only shallow copies; the per-stat quantile
        sorts run outside it (a scraper's snapshot must never block a
        hot-path record behind an O(n log n) critical section)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            stats = {k: v._copy() for k, v in self._timings.items()}
        return {
            "counters": counters,
            "gauges": gauges,
            "timings": {k: v.to_dict() for k, v in stats.items()},
        }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._timings.clear()


_REGISTRY = MetricsRegistry()
_RESET_GEN = 0


def registry() -> MetricsRegistry:
    """The process-wide default registry."""
    return _REGISTRY


def reset() -> None:
    """Clear the default registry (per-run scoping; tests)."""
    global _RESET_GEN
    _REGISTRY.reset()
    # consumers holding "previously seen" snapshots (the per-fit delta in
    # obs.report) key off this: value comparison alone cannot tell a reset
    # from no-change when totals happen to land on the same number
    _RESET_GEN += 1


def reset_generation() -> int:
    """Bumped by every :func:`reset` — lets snapshot-delta consumers
    detect a reset even when post-reset totals equal pre-reset ones."""
    return _RESET_GEN


def counter_add(name: str, n: float = 1) -> None:
    if not _ENABLED:
        return
    _REGISTRY.add(name, n)


def gauge_set(name: str, value: float) -> None:
    if not _ENABLED:
        return
    _REGISTRY.set_gauge(name, value)


def observe(name: str, seconds: float) -> None:
    if not _ENABLED:
        return
    _REGISTRY.observe(name, seconds)


_PHASE_LOCAL = threading.local()
_NULL_CTX = contextlib.nullcontext()

_TRACE_ANNOTATION = None
#: ``obs.trace``'s door while request tracing is on, else None:
#: ``hook(name)`` gives None where no trace is active on the thread, else
#: the ``close(seconds, status)`` that records the child span
_TRACE_HOOK = None


def profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` — the program's ONE door to
    the profiler.  With a profiler session running (an operator's, or a
    benchmark's ``--trace 1``) the scope lands on the trace's clock,
    beside the device's operations; with none it is a TraceMe that records
    nothing.  ``jax.profiler`` is imported on first use, here and nowhere
    else."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


def set_trace_hook(hook) -> None:
    """``obs.trace`` registers its child-span recorder here while request
    tracing is on (None takes it away): the span API stays below it."""
    global _TRACE_HOOK
    _TRACE_HOOK = hook


class _Span:
    """One open span (see :func:`span`).  ``seconds`` holds the measured
    duration once the block has ended."""

    __slots__ = ("name", "seconds", "_nest", "_observe", "_t0",
                 "_annotation", "_close_child", "_parent")

    def __init__(self, name: str, nest: bool = False):
        self.name = name
        self.seconds = None
        self._nest = nest

    def __enter__(self):
        # decided here: a span open while recording is toggled off still
        # records (the open timer was paid for, a lone record is harmless)
        self._observe = _ENABLED
        self._close_child = None
        if self._nest:
            stack = getattr(_PHASE_LOCAL, "stack", None)
            if stack is None:
                stack = _PHASE_LOCAL.stack = []
            stack.append(self.name)
            self.name = "phase." + "/".join(stack)
        elif _TRACE_HOOK is not None:
            self._close_child = _TRACE_HOOK(self.name)
        # the thread's innermost open span: what a compile is booked under
        self._parent = getattr(_PHASE_LOCAL, "span", None)
        _PHASE_LOCAL.span = self
        self._annotation = profiler_annotation("fmt." + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = dt = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        _PHASE_LOCAL.span = self._parent
        if self._nest:
            _PHASE_LOCAL.stack.pop()
        if self._observe:
            _REGISTRY.observe(self.name, dt)
        if self._close_child is not None:
            self._close_child(dt, "ok" if exc_type is None else "error")
        return False


def span(name: str):
    """THE span of the program: ``with obs.span("train.dispatch"): ...``.

    One pair of clock readings, three records: a timing stat in the
    registry under ``name``; a ``fmt.<name>`` scope in the profiler's trace
    (:func:`profiler_annotation`), so host work sits on the device trace's
    clock; and, where a request trace is active on the thread
    (``obs.trace.current()``), a child span there with its true start.
    ``with ... as s`` gives an object whose ``s.seconds`` is the duration
    after the block.  Returns the shared no-op context (``as`` gives
    ``None``) when telemetry and request tracing are both off; with
    tracing alone on, the registry gets nothing."""
    if not _ENABLED and _TRACE_HOOK is None:
        return _NULL_CTX
    return _Span(name)


def phase(name: str):
    """A :func:`span` whose name nests: ``with obs.phase("pack_csr"): ...``
    records under ``phase.pack_csr`` (``phase.outer/pack_csr`` inside
    ``phase("outer")``; the stack is per thread) and is written to the
    profiler as ``fmt.phase.<path>``.  Not copied into a request trace:
    the serving paths that carry phases have their own trace spans.
    Returns the shared no-op context when telemetry is off."""
    if not _ENABLED:
        return _NULL_CTX
    return _Span(name, nest=True)


def phased(name: str):
    """Decorator form of :func:`phase` — times every call of the wrapped
    function under ``phase.<name>``.  One boolean check of overhead when
    telemetry is off."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            with _Span(name, nest=True):
                return fn(*args, **kwargs)

        return wrapper

    return deco


# -- compiles on the program's clock -------------------------------------------
#
# JAX 0.9.0 reports every stage of a compile through ``jax.monitoring``, on
# the compiling thread, with the program's name: ``jit(bundled)`` for
# lowering and backend, the bare ``bundled`` for tracing.  A warm call
# reports nothing.  The registry names (PERF.md section 3):
#
#   compile.trace, compile.lower, compile.backend
#       timings, one observation a lowered / compiled program.
#       ``compile.backend`` is JAX's event around ``compile_or_get_cached``
#       and so HOLDS the cache reads: the seconds truly compiled are
#       ``compile.backend`` less ``compile.cache_read``.
#   compile.cache_read
#       timing, a persistent-cache hit's read
#   compile.cache_hits, compile.cache_misses
#       counters (a miss is counted where the compiled program is written
#       to the cache)
#   compile.under/<span>
#       the three stages' seconds again, by the innermost ``obs.span`` open
#       on the thread (``none`` outside any): the totals sum to trace +
#       lower + backend
#
# and each backend stage leaves one ``compile`` event in the flight recorder.

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: JAX's cache event -> (the flight event's ``cache``, the counter)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": ("hit", "compile.cache_hits"),
    "/jax/compilation_cache/cache_misses": ("miss", "compile.cache_misses"),
}

_LISTENING = False


class _CompileLocal(threading.local):
    """What the compiling thread has reported and no later stage has taken."""

    def __init__(self):
        #: disjoint (start, end) trace intervals since the last lowering
        self.traces = []
        #: program -> [trace_s, lower_s] since its last backend stage
        self.pending = {}
        #: the cache event inside the running backend stage, and its read
        self.cache = "off"
        self.cache_read_s = 0.0


_COMPILE_LOCAL = _CompileLocal()


def _open_span_name() -> Optional[str]:
    open_span = getattr(_PHASE_LOCAL, "span", None)
    return open_span.name if open_span is not None else None


def _observe_stage(name: str, seconds: float) -> None:
    """One stage's seconds, under its own name and under the open span's."""
    _REGISTRY.observe(name, seconds)
    _REGISTRY.observe("compile.under/" + (_open_span_name() or "none"),
                      seconds)


def _on_time_span(event, start, end, **_kw) -> None:
    # a jitted function traced inside another reports its own interval
    # before the outer one's, which holds it: keep the union, not the sum
    if event != _TRACE_EVENT:
        return
    traces = _COMPILE_LOCAL.traces
    while traces and traces[-1][1] >= start:
        inner = traces.pop()
        start, end = min(start, inner[0]), max(end, inner[1])
    traces.append((start, end))


def _on_duration(event, seconds, fun_name="", **_kw) -> None:
    local = _COMPILE_LOCAL
    if event == _LOWER_EVENT:
        trace_s = sum(end - start for start, end in local.traces)
        local.traces.clear()
        pending = local.pending.setdefault(fun_name, [0.0, 0.0])
        pending[0] += trace_s
        pending[1] += seconds
        _observe_stage("compile.trace", trace_s)
        _observe_stage("compile.lower", seconds)
    elif event == _BACKEND_EVENT:
        trace_s, lower_s = local.pending.pop(fun_name, (0.0, 0.0))
        cache, cache_read_s = local.cache, local.cache_read_s
        local.cache, local.cache_read_s = "off", 0.0
        _observe_stage("compile.backend", seconds)
        from flink_ml_tpu.obs import flight

        flight.record("compile", program=fun_name, span=_open_span_name(),
                      trace_s=trace_s, lower_s=lower_s, backend_s=seconds,
                      cache=cache, cache_read_s=cache_read_s)
    elif event == _CACHE_READ_EVENT:
        local.cache_read_s += seconds
        _REGISTRY.observe("compile.cache_read", seconds)


def _on_event(event, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _COMPILE_LOCAL.cache, counter = _CACHE_EVENTS[event]
        _REGISTRY.add(counter)


def _listen(on: bool) -> None:
    """Put the three ``jax.monitoring`` listeners up, or take them down
    (twice is harmless).  ``jax.monitoring`` is imported here and nowhere
    else in the package; an image without JAX compiles nothing to hear."""
    global _LISTENING
    if on == _LISTENING:
        return
    try:
        from jax import monitoring
    except ImportError:
        return
    _LISTENING = on
    if on:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_time_span)
    else:
        for unregister, callback in (
                (monitoring.unregister_event_listener, _on_event),
                (monitoring.unregister_event_duration_listener, _on_duration),
                (monitoring.unregister_event_time_span_listener,
                 _on_time_span)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):
                # someone's ``clear_event_listeners`` took it down already
                pass


def record_hbm_gauges(prefix: str = "hbm") -> None:
    """Record device-memory watermark gauges from ``device.memory_stats()``.

    Max over local devices of ``bytes_in_use`` / ``peak_bytes_in_use`` /
    ``bytes_limit``.  A no-op when telemetry is off or the backend exposes
    no memory stats (the CPU backend returns None)."""
    if not _ENABLED:
        return
    try:
        import jax

        peaks, in_use, limits = [], [], []
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", lambda: None)()
            if not stats:
                continue
            if "peak_bytes_in_use" in stats:
                peaks.append(stats["peak_bytes_in_use"])
            if "bytes_in_use" in stats:
                in_use.append(stats["bytes_in_use"])
            if "bytes_limit" in stats:
                limits.append(stats["bytes_limit"])
        if peaks:
            gauge_set(f"{prefix}.peak_bytes_in_use", max(peaks))
        if in_use:
            gauge_set(f"{prefix}.bytes_in_use", max(in_use))
        if limits:
            gauge_set(f"{prefix}.bytes_limit", max(limits))
    except Exception:  # noqa: BLE001 - telemetry must never break training
        pass


if _ENABLED:  # FMT_OBS=1: recording is on from import, the listeners with it
    _listen(True)
