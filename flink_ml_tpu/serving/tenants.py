"""Tenant-keyed model registry — many models behind one ModelServer.

ISSUE 20 (ROADMAP item 3, the last unserved scale axis): the reference's
Pipeline/Model abstraction was built to host MANY small models per
deployment, but this repo served exactly one model per fleet.  This
module is the control-plane half of multi-tenant serving:

* **registry** — ``register(tenant, source)`` binds a tenant key to a
  model artifact (a saved-model directory, reloaded with the standard
  integrity-verified loaders) or an in-memory model object.  Tenant keys
  are validated at the admission door (``[A-Za-z0-9._-]``, length-capped)
  so a malformed key fails loudly instead of minting a garbage tenant;
* **LRU residency over the slab pool** — resolved models live in the
  process-wide :mod:`~flink_ml_tpu.table.slab_pool` under
  ``("tenant_model", tenant, ...)`` keys, so tenant models share one
  budget (``FMT_SLAB_POOL_BUDGET_MB``) with every other cached placement
  and honor the pool's pin invariant: the dispatcher pins a tenant's
  model for the duration of its batch, and neither budget pressure nor
  the registry's own residency cap (``FMT_TENANT_MAX_RESIDENT``) can
  drop it mid-dispatch;
* **evict-under-pressure, reason-coded** — the registry listens on the
  pool's eviction events and stamps each tenant fault-out into the
  flight recorder (``serving.tenant.evicted`` with the pool's reason:
  ``budget`` / ``pressure`` / ``resident_cap``) and the
  ``serving.tenant.evictions`` counter;
* **millisecond fault-in** — a cold load re-reads the artifact (ms) but
  pays no compile: same-family tenants share executables through the
  family cache (``common/fused._FAMILY_FNS``) and PR 18's warm-artifact
  store, whose entry keys were already family-structural;
* **per-tenant accounting** — requests/rows/sheds/cold-loads/evictions
  per tenant, a top-N-by-traffic table for ``/statusz``, and the
  ``FMT_TENANT_QUOTA_ROWS`` quota the server's admission door enforces.

Knobs (README.md, "Multi-tenant serving"): ``FMT_TENANT_MAX_RESIDENT``,
``FMT_TENANT_QUOTA_ROWS``, ``FMT_TENANT_MUX``.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import Counter, OrderedDict
from typing import Dict, List, Optional

from flink_ml_tpu import obs
from flink_ml_tpu.utils import knobs

__all__ = [
    "DEFAULT_TENANT",
    "TENANT_KEY_MAX",
    "TenantRegistry",
    "validate_tenant_key",
]

#: the wire-compatible tenant old callers land on: a ``submit()`` with no
#: tenant key serves the VersionManager's active version exactly as before
DEFAULT_TENANT = "default"

TENANT_KEY_MAX = 64
_TENANT_KEY_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]*$")

#: floor estimate for an in-memory model object whose footprint the
#: registry cannot cheaply walk (path artifacts use their on-disk size)
_MODEL_NBYTES_FLOOR = 1 << 20


def validate_tenant_key(tenant: str) -> str:
    """The admission-door key check: non-empty, ``[A-Za-z0-9._-]`` with a
    leading alphanumeric, at most ``TENANT_KEY_MAX`` chars.  Raises
    ``ValueError`` — a malformed tenant key is a caller bug (like an
    empty request table), never a shed."""
    if not isinstance(tenant, str) or not tenant:
        raise ValueError("tenant key must be a non-empty string")
    if len(tenant) > TENANT_KEY_MAX:
        raise ValueError(
            f"tenant key exceeds {TENANT_KEY_MAX} chars: {tenant[:80]!r}"
        )
    if not _TENANT_KEY_RE.match(tenant):
        raise ValueError(
            f"malformed tenant key {tenant!r}: use [A-Za-z0-9._-] with a "
            "leading letter or digit"
        )
    return tenant


def _artifact_nbytes(path: str) -> int:
    """On-disk artifact size as the resident-footprint estimate for a
    path-registered tenant (the placed params are within a small factor
    of the serialized form, and the estimate only steers LRU order)."""
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


class _TenantState:
    __slots__ = ("tenant", "source", "version", "model_obj", "counts",
                 "last_request_s", "family_token")

    def __init__(self, tenant: str, source, version: str):
        self.tenant = tenant
        #: a saved-model directory path (str) or an in-memory model object
        self.source = source
        self.version = version
        #: strong ref kept ONLY when the slab pool is disabled (without a
        #: pool there is nowhere to be resident — reloading per request
        #: would be absurd) or the source IS the object
        self.model_obj = None
        self.counts: Counter = Counter()
        self.last_request_s = 0.0
        #: structural plan token of this tenant's model (None until its
        #: first serve computes one) — the dispatcher's same-family check
        self.family_token: Optional[str] = None


class TenantRegistry:
    """Tenant -> model map, LRU-resident over the slab pool."""

    def __init__(self, tally=None):
        self._lock = threading.RLock()
        self._tenants: Dict[str, _TenantState] = {}
        #: tenants with a pool-resident model, LRU order (synced from the
        #: pool's eviction events; approximate is fine — the pool is the
        #: source of truth and a stale entry just re-faults)
        self._resident: "OrderedDict[str, tuple]" = OrderedDict()
        #: per-server tally hook (ModelServer._tally) so tenant events
        #: land in the server's own stats alongside the global counters
        self._tally = tally if tally is not None else (lambda *_: None)
        from flink_ml_tpu.table import slab_pool

        self._pool = slab_pool.pool()
        self._pool.add_eviction_listener(self._on_pool_evict)

    def close(self) -> None:
        self._pool.remove_eviction_listener(self._on_pool_evict)

    # -- registration ---------------------------------------------------------

    def register(self, tenant: str, source, version: str = "v1") -> None:
        """Bind ``tenant`` to a saved-model path or model object.  Lazy:
        the model loads (faults in) on the tenant's first request."""
        validate_tenant_key(tenant)
        if tenant == DEFAULT_TENANT:
            raise ValueError(
                "the default tenant is the server's deployed model — "
                "use deploy(), not register_tenant()"
            )
        if not isinstance(source, (str, os.PathLike)) and source is None:
            raise ValueError("tenant source must be a path or a model")
        with self._lock:
            self._tenants[tenant] = _TenantState(
                tenant, str(source) if isinstance(source, os.PathLike)
                else source, version,
            )

    def known(self, tenant: str) -> bool:
        with self._lock:
            return tenant in self._tenants

    def tenants(self) -> List[str]:
        with self._lock:
            return sorted(self._tenants)

    def quota_rows(self) -> int:
        """Per-tenant queued-row quota (0 = unenforced)."""
        return knobs.knob_int("FMT_TENANT_QUOTA_ROWS")

    # -- residency / fault-in -------------------------------------------------

    def _state(self, tenant: str) -> _TenantState:
        with self._lock:
            state = self._tenants.get(tenant)
        if state is None:
            raise KeyError(f"unknown tenant {tenant!r}")
        return state

    def _pool_key(self, state: _TenantState) -> tuple:
        src = (state.source if isinstance(state.source, str)
               else f"obj:{id(state.source)}")
        return ("tenant_model", state.tenant, src, state.version)

    def _load(self, state: _TenantState):
        """One cold load: the integrity-verified standard loaders, timed
        and flight-recorded.  Compiles do NOT ride here — the family
        executable cache and the warm-artifact store make the faulted-in
        tenant's first dispatch a cache hit."""
        t0 = time.perf_counter()
        if isinstance(state.source, str):
            from flink_ml_tpu.serving.versioning import _load_model

            model = _load_model(state.source)
        else:
            model = state.source
        ms = (time.perf_counter() - t0) * 1e3
        state.counts["cold_loads"] += 1
        obs.counter_add("serving.tenant.cold_loads")
        self._tally("serving.tenant.cold_loads")
        obs.flight.record("serving.tenant.cold_load", tenant=state.tenant,
                          ms=round(ms, 3))
        return model

    def resolve(self, tenant: str):
        """The tenant's (model, version label), faulting the model in when
        it is not resident.  The model is pool-owned — callers pin it
        (``pool().pinned(model)``) for the duration of their dispatch."""
        from flink_ml_tpu.table import slab_pool

        state = self._state(tenant)
        version = f"{tenant}:{state.version}"
        if not isinstance(state.source, str):
            # object-registered tenant: the object IS the resident model
            if state.model_obj is None:
                state.model_obj = self._load(state)
            return state.model_obj, version
        if not slab_pool.enabled():
            if state.model_obj is None:
                state.model_obj = self._load(state)
            return state.model_obj, version
        key = self._pool_key(state)
        nbytes = max(_artifact_nbytes(state.source), _MODEL_NBYTES_FLOOR)
        model = self._pool.get_or_build(
            key, lambda: self._load(state), refs=(), nbytes=nbytes,
            agreed=False,  # inference is collective-free by contract
        )
        with self._lock:
            self._resident[tenant] = key
            self._resident.move_to_end(tenant)
            over = len(self._resident) - max(
                1, knobs.knob_int("FMT_TENANT_MAX_RESIDENT")
            )
            victims = []
            if over > 0:
                for t, k in self._resident.items():
                    if t != tenant:
                        victims.append((t, k))
                        over -= 1
                        if over <= 0:
                            break
        for _t, k in victims:
            # discard honors the pin invariant: a tenant mid-dispatch
            # stays resident and retries at the next resolve
            self._pool.discard(k, reason="resident_cap")
        return model, version

    def note_family(self, tenant: str, token: Optional[str]) -> None:
        """Record the structural plan token of a tenant's model (computed
        at its first serve; None pins "not mux-eligible") — the
        dispatcher's same-family batch-cut check reads it lock-free."""
        with self._lock:
            state = self._tenants.get(tenant)
            if state is None and tenant == DEFAULT_TENANT:
                state = self._tenants[tenant] = _TenantState(
                    tenant, None, "active")
        if state is not None:
            state.family_token = token

    def family_token(self, tenant: str) -> Optional[str]:
        with self._lock:
            state = self._tenants.get(tenant)
        return state.family_token if state is not None else None

    def _on_pool_evict(self, key, reason: str, nbytes: int) -> None:
        """Pool eviction listener: reason-coded tenant fault-out events
        (the registry's keys only — everything else in the pool is not
        ours to narrate)."""
        if not (isinstance(key, tuple) and key and key[0] == "tenant_model"):
            return
        tenant = key[1]
        with self._lock:
            self._resident.pop(tenant, None)
            state = self._tenants.get(tenant)
        if state is not None:
            state.counts["evictions"] += 1
        obs.counter_add("serving.tenant.evictions")
        self._tally("serving.tenant.evictions")
        obs.flight.record("serving.tenant.evicted", tenant=tenant,
                          reason=reason, nbytes=int(nbytes))

    # -- traffic accounting ---------------------------------------------------

    def note_request(self, tenant: str, rows: int) -> None:
        with self._lock:
            state = self._tenants.get(tenant)
            if state is None and tenant == DEFAULT_TENANT:
                # the default tenant is implicit — minted on first use so
                # its traffic shows in the same table
                state = self._tenants[tenant] = _TenantState(
                    tenant, None, "active")
        if state is None:
            return
        state.counts["requests"] += 1
        state.counts["rows"] += rows
        state.last_request_s = time.monotonic()
        obs.counter_add("serving.tenant.requests")
        self._tally("serving.tenant.requests")

    def note_shed(self, tenant: str) -> None:
        with self._lock:
            state = self._tenants.get(tenant)
        if state is not None:
            state.counts["sheds"] += 1
        obs.counter_add("serving.tenant.sheds")
        self._tally("serving.tenant.sheds")

    def top(self, n: int = 10) -> List[dict]:
        """Top-N tenants by request count — the ``/statusz`` table."""
        with self._lock:
            states = list(self._tenants.values())
            resident = set(self._resident)
        states.sort(key=lambda s: s.counts["requests"], reverse=True)
        return [
            {
                "tenant": s.tenant,
                "requests": int(s.counts["requests"]),
                "rows": int(s.counts["rows"]),
                "sheds": int(s.counts["sheds"]),
                "cold_loads": int(s.counts["cold_loads"]),
                "evictions": int(s.counts["evictions"]),
                "resident": (s.tenant in resident
                             or s.model_obj is not None
                             or s.tenant == DEFAULT_TENANT),
            }
            for s in states[:max(0, n)]
        ]

    def status(self) -> dict:
        with self._lock:
            n_tenants = len(self._tenants)
            n_resident = len(self._resident)
        return {
            "tenants": n_tenants,
            "resident": n_resident,
            "max_resident": knobs.knob_int("FMT_TENANT_MAX_RESIDENT"),
            "quota_rows": self.quota_rows(),
            "top": self.top(10),
        }
