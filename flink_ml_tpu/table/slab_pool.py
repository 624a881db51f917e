"""Cross-fit device slab pool — the warm-fit placement cache (ISSUE 2).

A full ``fit()`` pays host-side pack plus host->device placement, and
repeats both even when the SAME table is fit again (hyperparameter sweeps,
warm restarts, CV folds), while the fused device program itself is short
per epoch (the split on the chip is to be re-measured, ROADMAP S0).  The
reference design this repo reproduces (PAPER.md §4: broadcast-model bulk
iteration) materializes the training set once and re-iterates; per-fit
re-placement is overhead the architecture never intended.

This module generalizes the per-``Table``-instance ``cached_pack`` memo
into a first-class, PROCESS-WIDE pool of placed training batches:

  * **keying** — ``(table content identity, mesh, layout/pack variant)``.
    Content identity is buffer identity: a token of each column's backing
    buffer (address, shape, strides, dtype) plus a weakref guard, so two
    Table objects sharing column buffers (selects, re-wraps — the immutable
    Table contract) hit the same slab, and a token can never outlive the
    buffer it describes (dead weakref => the entry silently drops);
  * **budget** — entries are LRU-evicted once the pool exceeds
    ``FMT_SLAB_POOL_BUDGET_MB`` (default 4096).  Multi-process the budget
    is agreed once via :func:`~flink_ml_tpu.parallel.mesh.agree_max`
    (per-process env drift must not produce per-process cache behavior);
  * **multi-process hit agreement** — builders may dispatch collective
    device programs; a process that hit the pool while a peer missed would
    skip its half of the collective and hang the mesh.  Under
    ``jax.process_count() > 1`` every lookup agrees hit/miss via
    ``agree_max`` — any miss forces a (re)build everywhere (miss wins
    ties);
  * **refcounting** — drivers pin a checked-out slab for the duration of
    the device call (:meth:`SlabPool.pinned`); eviction skips pinned
    entries and never calls ``.delete()`` — it only drops the pool's
    reference, so a buffer still referenced by an in-flight program (or a
    donating ``donate_argnums=(0,)`` dispatch) can never be freed under it;
  * **telemetry** — hits/misses/evictions/bytes-placed land in the obs
    registry (``slab_pool.*``), so every fit RunReport carries its own
    pool delta and the warm-path CI gate can assert the hit branch.

Placement itself is double-buffered and chunked
(:func:`~flink_ml_tpu.parallel.mesh.shard_batch_prefetched`): host staging
of slice N+1 overlaps the async H2D DMA of slice N, the ``_prefetch``
idiom from ``lib/out_of_core.py``.

``FMT_SLAB_POOL=0`` disables pooling entirely (every lookup builds) — the
bench uses it for the uncached-parity comparison.
"""

from __future__ import annotations

import contextlib
import threading
import warnings
import weakref
from collections import OrderedDict
from typing import Callable, Optional, Tuple

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.utils import knobs

__all__ = [
    "SlabPool",
    "array_token",
    "enabled",
    "evict_for_pressure",
    "place_batch",
    "pool",
    "pool_active",
    "pytree_nbytes",
    "reset_pool",
    "table_token",
]


def enabled() -> bool:
    """Pooling on?  ``FMT_SLAB_POOL=0`` turns every lookup into a build."""
    return knobs.knob_bool("FMT_SLAB_POOL")


#: cross-process agreement on the on/off switch (None = unresolved).  The
#: master switch must not drift per process any more than the budget may:
#: a process with FMT_SLAB_POOL=0 would skip the hit/miss agreement its
#: peers block in — a hang.  Disabled wins ties (any process off => all
#: off), resolved lazily at the first AGREED lookup so the collective fires
#: at an aligned point.
_AGREED_ENABLED: Optional[bool] = None


def _agreed_enabled() -> bool:
    global _AGREED_ENABLED
    if _AGREED_ENABLED is None:
        from flink_ml_tpu.parallel.mesh import agree_max

        (any_disabled,) = agree_max(int(not enabled()))
        _AGREED_ENABLED = not any_disabled
    return _AGREED_ENABLED


# -- content identity tokens --------------------------------------------------


#: per-window sample size of the mutation canary; arrays at or under
#: 4 windows hash in full
_CANARY_WINDOW = 16 << 10


def _canary(a: np.ndarray) -> int:
    """Cheap content checksum folded into the identity token: CRC of the
    head/middle/tail byte windows (whole buffer when small).  Tables are
    immutable BY CONTRACT, but a zero-copy column shares the caller's
    buffer — someone normalizing it in place and re-wrapping a fresh Table
    would otherwise HIT on pure buffer identity and silently train on the
    pre-mutation slab.  The canary turns any bulk in-place mutation into a
    key change (stale entries then age out through the dead/budget
    sweeps); byte-surgical edits inside unsampled windows remain the
    caller's contract violation."""
    import zlib

    try:
        if a.ndim == 0:
            return zlib.crc32(a.tobytes())
        if not a.flags.c_contiguous:
            # strided view: hash a bounded head-row copy, never O(n) bytes
            a = np.ascontiguousarray(a[: min(a.shape[0], 4096)])
        flat = a.reshape(-1).view(np.uint8)
    except (ValueError, TypeError):  # object dtype etc: identity only
        return 0
    n = flat.size
    if n <= 4 * _CANARY_WINDOW:
        return zlib.crc32(flat.tobytes())
    w = _CANARY_WINDOW
    mid = (n // 2) - w // 2
    sample = np.concatenate(
        [flat[:w], flat[mid : mid + w], flat[n - w :]]
    )
    return zlib.crc32(sample.tobytes())


def array_token(a, refs: list):
    """Identity token for one host column/array + weakref liveness guards.

    Buffer identity stands in for content identity: Tables are immutable
    values sharing column buffers across transformations, so (owner id,
    data address, shape, strides, dtype) pins exact content while the
    owner lives.  ``refs`` receives a weakref per owning buffer — a pool
    entry whose guards die is discarded on lookup, so a recycled id/address
    can never resurrect a stale slab.  A sampled content canary
    (:func:`_canary`) guards the remaining hole — in-place mutation of a
    shared buffer.  Equal content in DIFFERENT buffers misses (rebuild) —
    safe, just cold."""
    from flink_ml_tpu.ops.batch import CsrRows

    if isinstance(a, CsrRows):
        return ("csr", a.dim,
                array_token(a.indptr, refs),
                array_token(a.indices, refs),
                array_token(a.values, refs))
    if isinstance(a, np.ndarray):
        base = a
        while isinstance(getattr(base, "base", None), np.ndarray):
            base = base.base
        try:
            refs.append(weakref.ref(base))
        except TypeError:  # exotic buffer owner: identity only, no guard
            pass
        data = a.__array_interface__.get("data") or (0, True)
        canary = _canary(a) if a.dtype != object else 0
        return ("nd", id(base), int(data[0]), a.shape, str(a.dtype),
                a.strides, canary)
    try:
        refs.append(weakref.ref(a))
    except TypeError:
        pass
    try:
        size = len(a)
    except TypeError:
        size = -1
    return ("obj", id(a), size)


def table_token(table, cols=None) -> Tuple[tuple, list]:
    """Content-identity token for a Table: one column token per field, in
    schema order.  Returns ``(token, weakref guards)``.

    ``cols`` restricts the token to the columns a layout actually reads
    (feature + label): a ``select()``/``with_column()`` re-wrap sharing
    those buffers then still HITS, and unused columns of wide tables never
    pay the canary pass.  Defaults to every schema field."""
    refs: list = []
    if cols is None:
        names = table.schema.field_names
    else:
        names = [table.schema.resolve(c) for c in cols if c is not None]
    token = tuple(
        (name, array_token(table.col(name), refs)) for name in names
    )
    return token, refs


def pytree_nbytes(value) -> int:
    """Total backing bytes of a pytree of host/device arrays."""
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0) or 0)
        for leaf in jax.tree_util.tree_leaves(value)
    )


def _devices_held(value) -> int:
    """On how many devices a placed pytree lies (``place.devices``)."""
    import jax

    held = set()
    for leaf in jax.tree_util.tree_leaves(value):
        if isinstance(leaf, jax.Array):
            held |= leaf.sharding.device_set
    return len(held)


# -- the pool -----------------------------------------------------------------


class _Entry:
    __slots__ = ("value", "nbytes", "refs", "pins")

    def __init__(self, value, nbytes: int, refs: list):
        self.value = value
        self.nbytes = int(nbytes)
        self.refs = list(refs)
        self.pins = 0

    def alive(self) -> bool:
        return all(r() is not None for r in self.refs)


class SlabPool:
    """Process-wide budgeted LRU cache of placed training batches."""

    def __init__(self, budget_bytes: Optional[int] = None):
        self._lock = threading.RLock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._by_value: dict = {}  # id(entry.value) -> key (pin lookup)
        self._budget = budget_bytes
        #: keys whose source buffers were garbage-collected — appended by
        #: weakref DEATH CALLBACKS (no locking: list.append is atomic under
        #: the GIL, and a GC callback must never take the pool lock), and
        #: drained under the lock at the next pool access.  Without this, a
        #: dropped table's device slab would persist until the next insert
        #: — a lifetime regression vs the per-Table cached_pack it replaces
        #: (whose slab died with the table).
        self._dead_keys: list = []
        #: entries displaced from the table while PINNED (replaced under a
        #: running device call): the pool must keep referencing them until
        #: the pin releases — the documented pin invariant — then the next
        #: drain lets them go
        self._displaced: list = []
        #: eviction listeners (ISSUE 20: the tenant registry's reason-coded
        #: fault-out events).  Drops queue ``(key, reason, nbytes)`` under
        #: the lock; listeners fire OUTSIDE it — one may re-enter the pool
        #: to fault an entry back in
        self._listeners: list = []
        self._events: list = []
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- budget ---------------------------------------------------------------

    def budget_bytes(self, collective_ok: bool = True) -> int:
        """``FMT_SLAB_POOL_BUDGET_MB`` (default 4096), agreed ONCE across
        processes via ``agree_max`` — divergent per-process budgets would
        evict (and later re-place, possibly with collectives) on different
        fits.

        ``collective_ok=False`` (the ``agreed=False`` insert path —
        inference, contractually collective-free) must not fire the
        agreement: if unresolved, the LOCAL env value is used uncached and
        the agreement happens at the next training-path access."""
        if self._budget is None:
            import jax

            mb = knobs.knob_int("FMT_SLAB_POOL_BUDGET_MB")
            if jax.process_count() > 1 and not collective_ok:
                return mb << 20  # local, uncached: no collective here
            from flink_ml_tpu.parallel.mesh import agree_max

            (mb,) = agree_max(mb)
            self._budget = mb << 20
        return self._budget

    # -- core -----------------------------------------------------------------

    def counters(self) -> Tuple[int, int]:
        """(hits, misses) monotonic totals — per-fit deltas come from
        subtracting a snapshot taken at fit start."""
        with self._lock:
            return self.hits, self.misses

    def items(self) -> list:
        """``[(key, placed value)]`` snapshot of the live entries — a read
        for diagnostics (``chip_smoke.py`` checks which devices hold the
        training slab); it neither touches LRU order nor counts a hit."""
        with self._lock:
            return [(k, e.value) for k, e in self._entries.items()]

    def _guarded_refs(self, key, refs) -> list:
        """Re-wrap the token pass's weakrefs with death callbacks that
        queue ``key`` for reaping — the callback only appends (atomic, no
        lock), the drop happens at the next locked pool access."""
        dead = self._dead_keys
        out = []
        for r in refs:
            obj = r() if isinstance(r, weakref.ref) else None
            if obj is None:
                out.append(r)  # already dead: entry invalid from birth
                continue
            out.append(
                weakref.ref(obj, lambda _r, _k=key: dead.append(_k))
            )
        return out

    def _drain_dead_locked(self) -> None:
        """Reap entries whose source buffers were GC'd (under the lock).

        A dead entry that is still PINNED cannot drop yet (the pin
        invariant) — its key goes BACK on the queue so the drain after
        the pin releases reclaims it.  The old code popped and discarded
        the key, so a buffer that died mid-pin left a permanently
        unreapable entry whose bytes squatted the budget alongside its
        replacement's — the double-count that evicted innocent entries
        under a tight ``FMT_SLAB_POOL_BUDGET_MB``."""
        retry: list = []
        while self._dead_keys:
            key = self._dead_keys.pop()
            entry = self._entries.get(key)
            if entry is None or entry.alive():
                continue  # already dropped, or the key was re-inserted
            if entry.pins > 0:
                retry.append(key)
                continue
            self._drop_locked(key, entry, reason="dead")
        if retry:
            self._dead_keys.extend(retry)
        if self._displaced:
            self._displaced = [e for e in self._displaced if e.pins > 0]

    def _lookup_locked(self, key) -> Optional[_Entry]:
        """Hit path under the lock: validates liveness, refreshes LRU."""
        self._drain_dead_locked()
        entry = self._entries.get(key)
        if entry is None:
            return None
        if not entry.alive():
            # dead-but-pinned: a miss, but the pool's reference stays until
            # the in-flight device call releases the pin (the pin invariant
            # _drain_dead_locked/_evict_over_budget_locked also honor)
            if entry.pins == 0:
                self._drop_locked(key, entry, reason="dead")
            return None
        self._entries.move_to_end(key)
        return entry

    def _drop_locked(self, key, entry: _Entry,
                     reason: Optional[str] = None) -> None:
        self._entries.pop(key, None)
        self._by_value.pop(id(entry.value), None)
        self.bytes -= entry.nbytes
        if reason is not None and self._listeners:
            self._events.append((key, reason, entry.nbytes))

    # -- eviction listeners ---------------------------------------------------

    def add_eviction_listener(self, fn: Callable) -> None:
        """Register ``fn(key, reason, nbytes)`` to observe entry drops
        (reasons: ``dead`` / ``budget`` / ``pressure`` / ``replaced`` /
        ``explicit``).  Listeners fire outside the pool lock."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_eviction_listener(self, fn: Callable) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def _notify_evictions(self) -> None:
        """Deliver queued drop events outside the lock — a listener may
        re-enter the pool (a tenant registry faulting a model back in),
        and must never be able to break the drop that notified it."""
        with self._lock:
            if not self._events:
                return
            events, self._events = self._events, []
        for fn in list(self._listeners):
            for key, reason, nbytes in events:
                try:
                    fn(key, reason, nbytes)
                except Exception:  # noqa: BLE001 - advisory telemetry
                    pass

    def discard(self, key, reason: str = "explicit") -> bool:
        """Drop ONE entry by key (the tenant registry's resident-cap
        fault-out).  Honors the pin invariant — a pinned entry stays put
        and ``False`` comes back."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or entry.pins > 0:
                return False
            self._drop_locked(key, entry, reason=reason)
            self.evictions += 1
            obs.counter_add("slab_pool.evictions")
            self._record_gauges_locked()
        self._notify_evictions()
        return True

    def get_or_build(self, key, builder: Callable, refs=(),
                     nbytes: Optional[int] = None, agreed: bool = True):
        """The one lookup: pooled value on a hit, ``builder()`` on a miss.

        ``refs`` are the weakref guards from the token pass (content
        identity holds only while the source buffers live).  Multi-process,
        hit/miss is AGREED across processes first — any miss rebuilds
        everywhere, so collective-bearing builders stay aligned.

        ``agreed=False`` skips every cross-process collective for this
        lookup — REQUIRED on paths the multi-process contract declares
        collective-free (inference: each process scores its own rows on its
        own local mesh, with per-process batch counts no peer mirrors).
        Only safe when the builder itself dispatches nothing collective."""
        import jax

        from flink_ml_tpu.fault.injection import maybe_fail
        from flink_ml_tpu.fault.retry import with_retry

        multi = jax.process_count() > 1 and agreed
        if not (_agreed_enabled() if multi else enabled()):
            return builder()
        try:
            maybe_fail("slab.lookup")
            with self._lock:
                entry = self._lookup_locked(key)
        except Exception as exc:  # noqa: BLE001 - transient-only, see below
            # graceful degradation, for EVERY pool consumer (training
            # wrappers, KNN model load, the batched-apply path): the pool
            # is an optimization, never a correctness dependency, so a
            # TRANSIENT failure of the pool machinery itself builds
            # direct.  Gated off agreed multi-process lookups — peers
            # already synchronized on this lookup's hit/miss, and a
            # unilateral local fallback would desync the collective
            # schedule.  Non-transient errors are real bugs: re-raise.
            from flink_ml_tpu.fault.retry import is_transient

            if multi or not is_transient(exc):
                raise
            obs.counter_add("fault.fallbacks")
            obs.counter_add("fault.fallbacks.slab_pool")
            warnings.warn(
                f"slab-pool lookup failed transiently ({exc!r}); falling "
                "back to direct placement for this call",
                RuntimeWarning,
                stacklevel=3,
            )
            return builder()
        local_hit = entry is not None
        if multi:
            from flink_ml_tpu.parallel.mesh import agree_max

            (any_miss,) = agree_max(int(not local_hit))
            if any_miss:
                local_hit = False  # rebuild with the peers: miss wins ties
        if local_hit:
            with self._lock:
                self.hits += 1
            obs.counter_add("slab_pool.hits")
            return entry.value
        # outside the lock: placement is the slow part.  Cold placement is
        # a transient-failure surface (runtime UNAVAILABLE/ABORTED blips,
        # injected chaos) — retried with backoff; single-process
        # only, because a multi-process builder's collectives must dispatch
        # exactly once per peer agreement round.  The span is the
        # pack+place cost a warm fit skips — recorded HERE because
        # estimator paths resolve placement before the fused driver runs
        # (its own train.place covers only driver-internal placement)
        with obs.span("slab_pool.build"):
            if jax.process_count() == 1:
                value = with_retry(builder, "slab.build")
            else:
                value = builder()
        if nbytes is None:
            nbytes = pytree_nbytes(value)
        with self._lock:
            self.misses += 1
            old = self._entries.get(key)
            if old is not None and old.pins > 0:
                # replaced while a device call still runs over it: park the
                # entry so the pool keeps its reference until the pin drops
                self._displaced.append(old)
                self._by_value.pop(id(old.value), None)
                self._entries.pop(key, None)
                self.bytes -= old.nbytes
            elif old is not None:
                self._drop_locked(key, old, reason="replaced")
            self._entries[key] = _Entry(
                value, nbytes, self._guarded_refs(key, refs)
            )
            self._by_value[id(value)] = key
            self.bytes += nbytes
            self._evict_over_budget_locked(keep=key, collective_ok=multi or
                                    jax.process_count() == 1)
            obs.counter_add("slab_pool.misses")
            obs.counter_add("slab_pool.bytes_placed", nbytes)
            obs.counter_add("place.devices", _devices_held(value))
            self._record_gauges_locked()
        self._notify_evictions()
        return value

    def _evict_over_budget_locked(self, keep=None, collective_ok: bool = True) -> None:
        """LRU eviction down to the budget; pinned entries and ``keep``
        (the entry just produced) are never evicted.  Eviction only drops
        the pool's reference — the runtime frees device memory when the
        last holder (an in-flight program included) lets go."""
        # dead sweep first: entries whose source buffers died can never be
        # hit again (their keys carry recycled identities), but only a
        # lookup of the SAME key would notice — transient-batch entries get
        # unique keys, so without this sweep they would pin device memory
        # until budget pressure
        for key, entry in list(self._entries.items()):
            if not entry.alive() and entry.pins == 0:
                self._drop_locked(key, entry, reason="dead")
        budget = self.budget_bytes(collective_ok)
        if self.bytes <= budget:
            return
        for key in list(self._entries):
            if self.bytes <= budget:
                break
            entry = self._entries[key]
            if key == keep or entry.pins > 0:
                continue
            self._drop_locked(key, entry, reason="budget")
            self.evictions += 1
            obs.counter_add("slab_pool.evictions")

    def _record_gauges_locked(self) -> None:
        obs.gauge_set("slab_pool.bytes", float(self.bytes))
        obs.gauge_set("slab_pool.entries", float(len(self._entries)))

    @contextlib.contextmanager
    def pinned(self, value):
        """Refcount a checked-out slab for the duration of a device call:
        while pinned, eviction keeps the entry (and thus a live reference),
        so no donation or budget pressure can free the buffers under the
        running program.  A no-op for values the pool does not own."""
        with self._lock:
            key = self._by_value.get(id(value))
            entry = self._entries.get(key) if key is not None else None
            if entry is not None:
                entry.pins += 1
        try:
            yield
        finally:
            if entry is not None:
                with self._lock:
                    entry.pins -= 1

    def evict_for_pressure(self) -> int:
        """Drop EVERY unpinned entry under device memory pressure (ISSUE
        9) and return the bytes released.  The pool is an optimization,
        never a correctness dependency: on an allocator OOM the pressure
        layer frees cached slabs first — the cheapest HBM to reclaim —
        before shrinking the failing batch.  Pinned entries (in-flight
        device calls) keep their reference, honoring the pin invariant;
        the runtime frees device memory when the last holder lets go."""
        with self._lock:
            dropped = 0
            for key, entry in list(self._entries.items()):
                if entry.pins > 0:
                    continue
                dropped += entry.nbytes
                self._drop_locked(key, entry, reason="pressure")
                self.evictions += 1
            if dropped:
                obs.counter_add("slab_pool.pressure_evictions")
                obs.counter_add("slab_pool.pressure_evicted_bytes", dropped)
                self._record_gauges_locked()
        self._notify_evictions()
        return dropped

    def reap(self) -> None:
        """Drop entries whose source buffers died (queued by the weakref
        death callbacks).  O(queued keys), no-op when nothing died — cheap
        enough for paths that never otherwise touch the pool (the batched
        inference loop calls it per batch), so a dropped training table's
        slab cannot sit in device memory for the process lifetime just
        because no later fit happened to run."""
        with self._lock:
            self._drain_dead_locked()
        self._notify_evictions()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_value.clear()
            self.bytes = 0
            self._record_gauges_locked()


_POOL: Optional[SlabPool] = None


def pool() -> SlabPool:
    """The process-wide default pool."""
    global _POOL
    if _POOL is None:
        _POOL = SlabPool()
    return _POOL


def reset_pool() -> None:
    """Drop the default pool (tests; bench uncached runs)."""
    global _POOL
    _POOL = None


def evict_for_pressure() -> int:
    """Module-level pressure-eviction entry point: drop every unpinned
    slab from the default pool (no-op — and no pool construction — when
    none exists yet).  Returns bytes released."""
    if _POOL is None:
        return 0
    return _POOL.evict_for_pressure()


# -- placement entry points ---------------------------------------------------


def pool_active(agreed: bool = True) -> bool:
    """Should a caller tokenize + consult the pool at all?  The cheap
    front gate: with pooling off, the token pass (weakref chasing + CRC
    canaries) would be pure waste.  ``agreed`` lookups resolve the
    CROSS-PROCESS switch (a locally-disabled process must still join its
    peers' hit/miss agreement decision — or rather, force it off for
    everyone); collective-free lookups read the local env only."""
    import jax

    if jax.process_count() > 1 and agreed:
        return _agreed_enabled()
    return enabled()


def get_or_place(table, layout_key, mesh, builder: Callable, cols=None):
    """Pool a device placement keyed by TABLE CONTENT + mesh + layout.

    The estimator-facing entry point: re-fitting the same table content
    (same object or a column-sharing copy) with the same layout and mesh
    returns the already-placed batch; anything else builds.  ``builder``
    produces the placed pytree (and may itself dispatch device programs —
    multi-process alignment is handled by the pool's hit agreement).
    ``cols`` names the columns the layout reads (see
    :func:`table_token`)."""
    if not pool_active():
        return builder()
    # the token pass (the CRC canaries) and the locked lookup, hit or
    # miss; on a miss it encloses slab_pool.build
    with obs.span("slab_pool.lookup"):
        token, refs = table_token(table, cols=cols)
        return pool().get_or_build(
            ("table", token, mesh, layout_key), builder, refs=refs
        )


def place_batch(mesh, batch, axis: str = "data"):
    """Pooled :func:`~flink_ml_tpu.parallel.mesh.shard_batch_prefetched`.

    Keyed by the identity of the host leaves — callers that re-place the
    SAME host arrays (a retained MinibatchStack across fits) hit; transient
    arrays miss, and their entries self-drop when the weakref guards die.
    The placement itself is double-buffered/chunked single-process."""
    import jax

    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched

    if not pool_active():
        return shard_batch_prefetched(mesh, batch, axis=axis)
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    refs: list = []
    token = tuple(array_token(leaf, refs) for leaf in leaves)
    # transient pool-machinery failures degrade to a direct placement
    # inside get_or_build — the pool is never a correctness dependency
    return pool().get_or_build(
        ("place", mesh, axis, treedef, token),
        lambda: shard_batch_prefetched(mesh, batch, axis=axis),
        refs=refs,
    )
