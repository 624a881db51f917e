"""Memory-pressure resilience: OOM classification, adaptive batch
bisection, and AIMD recovery (ISSUE 9).

A TPU-native stack dies differently from the reference: the dominant
production failure is device ``RESOURCE_EXHAUSTED`` from the allocator,
and it is *deterministic* — retrying the identical batch size fails
identically, so the PR-3 transient-retry policy only tripled the latency
of every OOM before giving up.  This module is the recovery path those
failures route to instead:

* :func:`is_oom` — recognizes allocator-exhaustion failures (XLA/PJRT
  ``RESOURCE_EXHAUSTED`` messages that talk about memory/allocation,
  host ``MemoryError``, the deterministic ``fault.oom`` injection) and
  distinguishes them from *genuinely transient* quota/RPC exhaustion,
  which stays retryable (``fault/retry.py`` consults this first);
* :class:`PressureState` — one per dispatch surface: remembers the last
  working batch size so one OOM doesn't re-bisect every subsequent
  batch, and probes back up additively after ``FMT_PRESSURE_PROBE_S``
  seconds of calm (AIMD: multiplicative decrease on OOM, additive
  increase on recovery, full batch restored once the probe reaches the
  largest size the surface has ever served);
* :func:`run_bisected` — the generic driver: run ``fn(lo, hi)`` over the
  row range under the surface's cap, halve the failing range on OOM
  (after one :func:`~flink_ml_tpu.table.slab_pool.SlabPool.
  evict_for_pressure` attempt frees unpinned slabs), and concatenate the
  per-chunk results host-side.  Exact-parity contract: callers split
  only along the row dimension of row-independent computations, so the
  concatenated output is bit-identical to the unsplit call;
* :func:`maybe_oom` — the planted injection hook
  (``FMT_FAULT_INJECT="fault.oom>256"`` fires while the dispatch's row
  count exceeds 256), which makes bisection convergence testable on CPU.

Wired through every device-dispatch surface: fused-plan inference
(``common/fused.py``), the serving dispatcher (``serving/server.py``
splits a coalesced batch at request boundaries and demuxes per-caller
outputs bit-identically), the staged mapper applies (KMeans assign / Knn
scan chunking via ``lib/common.apply_batched``), and dense GLM training
(``lib/common.train_glm`` falls back to micro-batch execution with
sum-based gradient accumulation).

Telemetry: ``pressure.ooms`` / ``pressure.bisections`` /
``pressure.evictions`` / ``pressure.resizes`` counters (+ per-surface
variants), the ``pressure.cap.<surface>`` gauge, flight-recorder events
for every OOM/shrink/recovery, and a post-hoc ``pressure.recovery``
trace span on sampled traces.

Knobs (README.md, "Memory-pressure resilience"): ``FMT_PRESSURE`` (default on; off
restores fail-fast OOM), ``FMT_PRESSURE_PROBE_S`` (default 30).
Off-path overhead is one state lookup and a try/except per dispatch —
within the existing <= 2% disabled-overhead contract.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.fault.injection import InjectedFault, maybe_fail
from flink_ml_tpu.utils import knobs

__all__ = [
    "OOM_POINT",
    "PressureState",
    "current_caps",
    "current_limits",
    "enabled",
    "is_compile_failure",
    "is_oom",
    "maybe_oom",
    "note_oom",
    "reset_states",
    "run_bisected",
    "state",
]


#: the injection point every pressure-aware dispatch plants: a spec term
#: like ``fault.oom>256`` simulates a fixed HBM capacity of 256 rows
OOM_POINT = "fault.oom"


def enabled() -> bool:
    """Is the pressure-recovery layer on?  ``FMT_PRESSURE=0`` restores
    fail-fast behavior on allocator OOM (classification still applies —
    an OOM is never retried at the same size either way)."""
    return knobs.knob_bool("FMT_PRESSURE")


def probe_interval_s() -> float:
    """``FMT_PRESSURE_PROBE_S`` (default 30): seconds of calm before an
    additive probe back toward full batch size."""
    return knobs.knob_float("FMT_PRESSURE_PROBE_S")


# -- OOM classification -------------------------------------------------------


#: message fragments that mark a failure as allocator exhaustion outright
_OOM_MARKERS = (
    "out of memory",
    "out_of_memory",
    "ran out of memory",
    "memory space exhausted",
)

#: with a RESOURCE_EXHAUSTED status, these mark the *allocator* flavor
#: (quota/RPC exhaustion — "quota exceeded", "too many requests" — carries
#: none of them and stays transient/retryable)
_ALLOC_MARKERS = (
    "allocat",       # "allocating", "failed to allocate", "allocator"
    "out of memory",
    "hbm",
    "memory",
    "bytes",
)


def is_compile_failure(exc: BaseException) -> bool:
    """Is this a kernel that does not fit the chip's VMEM — a COMPILE
    failure wearing the allocator's status code?

    VMEM is the compiler's scratchpad, sized per kernel when Mosaic/XLA
    compiles it; running out means the kernel does not compile at its
    tiling, whatever the batch.  Both texts were taken from a v5e (jax
    0.9.0, libtpu 0.0.34, PR 21):

    * ``RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while
      allocating on stack for %tpu_custom_call.1 ... Scoped allocation
      with size 32.00M and limit 16.00M exceeded scoped vmem limit``;
    * ``RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed
      memory (size=134217728) :: #allocation2 [shape = ..., space=vmem``.

    Neither is HBM pressure (a smaller batch compiles the same kernel) nor
    transient (a retry compiles it again), so :func:`is_oom` and
    :func:`~flink_ml_tpu.fault.retry.is_transient` both answer False and
    the error propagates out of ``serve.dispatch`` and ``train_glm``
    instead of being bisected, retried, or served from the fallback.  A
    real HBM exhaustion names ``HBM`` and never ``vmem``."""
    return isinstance(exc, Exception) and "vmem" in str(exc).lower()


def is_oom(exc: BaseException) -> bool:
    """Is this failure deterministic allocator exhaustion?

    True for XLA/PJRT allocator messages (``RESOURCE_EXHAUSTED`` talking
    about memory/allocation/bytes, "out of memory", "ran out of memory"),
    host ``MemoryError``, and the synthetic ``fault.oom`` injection.
    False for everything else — including RESOURCE_EXHAUSTED quota/RPC
    errors, which a retry plausibly fixes, and a kernel that overflows
    VMEM at compile time (:func:`is_compile_failure`)."""
    if isinstance(exc, InjectedFault):
        return getattr(exc, "point", None) == OOM_POINT
    if isinstance(exc, MemoryError):
        return True
    if not isinstance(exc, Exception) or is_compile_failure(exc):
        return False
    low = str(exc).lower()
    if any(m in low for m in _OOM_MARKERS):
        return True
    if "resource_exhausted" in low or "resource exhausted" in low:
        return any(m in low for m in _ALLOC_MARKERS)
    return False


def maybe_oom(rows: int) -> None:
    """The planted hook pressure-aware dispatch sites call with the row
    count they are about to make device-resident.  One module-bool check
    when injection is inactive; under ``fault.oom>N`` it raises an
    :class:`~flink_ml_tpu.fault.injection.InjectedFault` (classified as
    OOM by :func:`is_oom`) while ``rows > N`` — a deterministic HBM
    ceiling the bisection provably converges under."""
    maybe_fail(OOM_POINT, value=rows)


# -- per-surface pressure state ----------------------------------------------


class PressureState:
    """AIMD memory of one dispatch surface's workable batch size.

    ``cap`` is the current per-dispatch row limit (None = no pressure).
    :meth:`shrink` halves it on OOM (multiplicative decrease);
    :meth:`admit` runs the additive probe — after ``FMT_PRESSURE_PROBE_S``
    of calm the cap steps up by 1/8 of the largest size ever admitted,
    and clears entirely once it reaches that size (full recovery,
    counted in ``pressure.resizes``).

    **Per-device denomination** (ISSUE 15): ``cap``/``full`` are stored
    in PER-DEVICE rows.  A mesh-sharded surface passes its data-axis
    width as ``n_dev``: the failing global batch divides by the device
    count before the halving, so an OOM on an 8-device mesh shrinks to
    what ONE device could not hold — not to a 1-device floor for the
    whole mesh — and a cap learned at one mesh width admits the right
    global row count at another.  Single-device callers (``n_dev=1``,
    the default) see exactly the original semantics; the
    ``pressure.cap.<surface>`` gauge publishes the per-device number."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self.cap: Optional[int] = None   # per-device rows
        self.full = 0            # largest per-device row count ever admitted
        self.ooms = 0
        self._last_change = 0.0  # monotonic stamp of the last cap move
        self.n_dev = 1           # row-shard width of the last admit/shrink

    def _publish_locked(self) -> None:
        obs.gauge_set(f"pressure.cap.{self.name}",
                      float(self.cap if self.cap is not None else 0))

    def admit(self, n: int, n_dev: int = 1) -> int:
        """GLOBAL rows allowed per dispatch for a request of ``n`` rows
        over ``n_dev`` row shards — runs the additive up-probe when the
        surface has been calm."""
        n_dev = max(1, int(n_dev))
        per = -(-int(n) // n_dev)  # ceil: this dispatch's per-device rows
        with self._lock:
            self.n_dev = n_dev
            if per > self.full:
                self.full = per
            if self.cap is None:
                return n
            now = time.monotonic()
            if now - self._last_change >= probe_interval_s():
                self.cap += max(1, self.full // 8)
                self._last_change = now
                obs.counter_add("pressure.resizes")
                obs.counter_add(f"pressure.resizes.{self.name}")
                if self.cap >= self.full:
                    # fully recovered: the next dispatch runs unsplit
                    self.cap = None
                    self._publish_locked()
                    obs.flight.record("pressure.recovered",
                                      surface=self.name)
                    return n
                self._publish_locked()
                obs.flight.record("pressure.resize", surface=self.name,
                                  cap=self.cap)
            return min(n, self.cap * n_dev)

    def shrink(self, failed_rows: int, floor: int = 1,
               n_dev: int = 1) -> int:
        """Multiplicative decrease after a GLOBAL batch of
        ``failed_rows`` OOM'd across ``n_dev`` shards; returns the new
        per-device cap (never below ``floor``'s per-device share)."""
        n_dev = max(1, int(n_dev))
        per_failed = -(-int(failed_rows) // n_dev)
        per_floor = max(1, -(-int(floor) // n_dev))
        with self._lock:
            self.n_dev = n_dev
            new_cap = max(per_floor, per_failed // 2)
            if self.cap is None or new_cap < self.cap:
                self.cap = new_cap
            self._last_change = time.monotonic()
            self.ooms += 1
            self._publish_locked()
            return self.cap

    def current_cap(self) -> Optional[int]:
        """The PER-DEVICE cap (None = no pressure)."""
        with self._lock:
            return self.cap

    def current_limit(self) -> Optional[int]:
        """The cap in GLOBAL rows at the surface's last dispatch width
        (None = no pressure) — the readiness-floor denomination."""
        with self._lock:
            return None if self.cap is None else self.cap * self.n_dev

    def limit_rows(self, n_dev: int = 1) -> Optional[int]:
        """The cap in GLOBAL rows for an ``n_dev``-shard dispatch (None
        = no pressure)."""
        cap = self.current_cap()
        return None if cap is None else cap * max(1, int(n_dev))

    def capped_below(self, n: int, n_dev: int = 1) -> bool:
        """Would a dispatch of ``n`` global rows over ``n_dev`` shards
        exceed the current cap?  The cheap pre-check callers use to skip
        work (pooled full-size placement) that pressure would
        immediately undo."""
        limit = self.limit_rows(n_dev)
        return limit is not None and limit < n


_STATES: Dict[str, PressureState] = {}
_STATES_LOCK = threading.Lock()


def state(name: str) -> PressureState:
    """The process-wide pressure state for one dispatch surface."""
    with _STATES_LOCK:
        st = _STATES.get(name)
        if st is None:
            st = _STATES[name] = PressureState(name)
        return st


def current_caps() -> Dict[str, int]:
    """Every surface currently under pressure: ``{surface: cap}`` for
    states whose cap is active (a cleared surface drops out).  Caps are
    PER-DEVICE rows (ISSUE 15) — the ``pressure.cap.<surface>`` gauge's
    denomination; readiness floors compare against
    :func:`current_limits` instead."""
    with _STATES_LOCK:
        states = list(_STATES.values())
    out: Dict[str, int] = {}
    for st in states:
        cap = st.current_cap()
        if cap is not None:
            out[st.name] = cap
    return out


def current_limits() -> Dict[str, int]:
    """Every surface currently under pressure: ``{surface: limit}`` in
    GLOBAL rows per dispatch — the per-device cap multiplied by the
    row-shard width the surface last dispatched at.  The telemetry
    plane's ``/readyz`` floor check reads this: an 8-device surface
    serving 32-row batches is capped at 4 rows PER DEVICE, which must
    not read as below an 8-global-row floor."""
    with _STATES_LOCK:
        states = list(_STATES.values())
    out: Dict[str, int] = {}
    for st in states:
        limit = st.current_limit()
        if limit is not None:
            out[st.name] = limit
    return out


def reset_states() -> None:
    """Drop all pressure state (tests; per-run scoping)."""
    with _STATES_LOCK:
        _STATES.clear()


# -- the bisection driver -----------------------------------------------------


def _concat_rows(pieces):
    """Row-concatenate per-chunk results: arrays along axis 0; lists by
    extension; dicts per key; tuples elementwise.  One piece passes
    through untouched (the unsplit fast path copies nothing)."""
    if len(pieces) == 1:
        return pieces[0]
    head = pieces[0]
    if isinstance(head, np.ndarray):
        return np.concatenate(pieces, axis=0)
    if isinstance(head, dict):
        return {
            k: _concat_rows([p[k] for p in pieces]) for k in head
        }
    if isinstance(head, tuple):
        return tuple(
            _concat_rows([p[i] for p in pieces]) for i in range(len(head))
        )
    if isinstance(head, list):
        out = []
        for p in pieces:
            out.extend(p)
        return out
    raise TypeError(
        f"run_bisected cannot concatenate {type(head).__name__} results; "
        "pass an explicit concat="
    )


def _evict_pools(surface: str) -> int:
    """Shed slab-pool pressure before shrinking work: drop every unpinned
    pooled slab (the pool is an optimization, never a correctness
    dependency) and report the bytes released."""
    from flink_ml_tpu.table import slab_pool

    dropped = slab_pool.evict_for_pressure()
    if dropped:
        obs.counter_add("pressure.evictions")
        obs.counter_add(f"pressure.evictions.{surface}")
        obs.flight.record("pressure.evict", surface=surface,
                          bytes=int(dropped))
    return dropped


def _note_oom(st: PressureState, surface: str, rows: int,
              exc: BaseException) -> None:
    obs.counter_add("pressure.ooms")
    obs.counter_add(f"pressure.ooms.{surface}")
    obs.flight.record("pressure.oom", surface=surface, rows=int(rows),
                      error=type(exc).__name__, detail=str(exc)[:200])


def note_oom(surface: str, rows: int, exc: BaseException,
             floor: int = 1, n_dev: int = 1) -> PressureState:
    """Record one allocator OOM against ``surface`` and shrink its cap
    (counters + flight event + AIMD decrease) — for recovery paths that
    switch execution strategy instead of bisecting in place (the training
    micro-batch fallback, the serving dispatcher's request-boundary
    split).  ``n_dev`` denominates the cap per device for mesh-sharded
    surfaces.  Returns the surface's state."""
    st = state(surface)
    _note_oom(st, surface, rows, exc)
    st.shrink(rows, floor=floor, n_dev=n_dev)
    return st


def run_bisected(fn: Callable, n: int, *, surface: str, floor: int = 1,
                 concat: Optional[Callable] = None, evict: bool = True,
                 n_dev: int = 1):
    """Run ``fn(lo, hi)`` over the row range ``[0, n)`` with adaptive
    OOM recovery; returns the row-concatenated results.

    ``fn`` must compute a row-independent result for any contiguous
    sub-range (the exact-parity contract: concatenating sub-results is
    bit-identical to the unsplit call).  Under no pressure this is ONE
    ``fn(0, n)`` call returned untouched.  On allocator OOM: one
    slab-pool eviction attempt retries the same size; still OOM halves
    the range (``pressure.bisections``) down to ``floor`` rows, below
    which the OOM re-raises (the device genuinely cannot serve a
    floor-sized batch).  The surface's :class:`PressureState` remembers
    the working size so subsequent batches chunk directly instead of
    re-discovering it, and AIMD probes restore full batches once
    pressure clears.  ``n_dev`` is the dispatch's row-shard count: the
    surface's cap is per-device-denominated (see
    :class:`PressureState`), so a mesh-wide OOM halves the PER-DEVICE
    share rather than collapsing the global batch toward a one-device
    floor."""
    if n <= 0 or not enabled():
        return fn(0, n)
    st = state(surface)
    limit = st.admit(n, n_dev=n_dev)
    pieces = []
    lo = 0
    evicted_once = False
    recovered_from = 0
    t0 = None
    while lo < n:
        size = min(n - lo, max(limit, floor))
        try:
            pieces.append(fn(lo, lo + size))
            lo += size
            cap = st.limit_rows(n_dev)
            limit = min(n - lo, cap) if cap is not None else n - lo
            continue
        except Exception as exc:  # noqa: BLE001 - OOM-filtered below
            if not is_oom(exc):
                raise
            if t0 is None:
                t0 = time.perf_counter()
            _note_oom(st, surface, size, exc)
            recovered_from = max(recovered_from, size)
            if evict and not evicted_once:
                evicted_once = True
                if _evict_pools(surface):
                    continue  # retry the same size with the slabs freed
            if size <= floor:
                raise  # cannot shrink further: surface the true error
            st.shrink(size, floor=floor, n_dev=n_dev)
            limit = st.limit_rows(n_dev) or floor
            obs.counter_add("pressure.bisections")
            obs.counter_add(f"pressure.bisections.{surface}")
            obs.flight.record("pressure.bisect", surface=surface,
                              rows=int(size), cap=int(limit))
    if t0 is not None:
        # a recovery happened: land it as a span on any sampled trace
        parents = obs.trace.current()
        if parents:
            obs.trace.record_span(
                parents, "pressure.recovery", time.perf_counter() - t0,
                {"surface": surface, "from_rows": int(recovered_from),
                 "cap": st.current_cap() or 0},
            )
    return (concat or _concat_rows)(pieces)
