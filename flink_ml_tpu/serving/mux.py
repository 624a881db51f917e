"""Cross-tenant dispatch multiplexing — N tenants, ONE fused dispatch.

The perf core of ISSUE 20.  Residency (serving/tenants.py) makes a
thousand registered models *storable*; this module makes them *servable*
at single-model dispatch cost.  Most tenants of a real fleet are the
same model FAMILY — the same stage chain (scaler -> GLM), the same
feature schema, different fitted parameters — so serving them as N
separate fused dispatches pays N times the dispatch latency for math
that differs only in its per-row constants.  The mux folds them:

* **eligibility** is structural, decided once per (model, schema): the
  model's FULL stage chain must assemble into one fused run
  (``_build_run(min_stages=1)`` — even a single-stage family amortizes)
  whose device chain carries a declared ``pallas_op`` per stage
  (``affine_sub_mul`` / ``affine_mul_add`` / ``glm_score``: exactly the
  ``(pa, pb)``-shaped per-stage params the mux can stack), one dense
  data desc, and no host stages.  :func:`family_token` digests that
  structure plus the input/exit schemas — two tenants coalesce iff
  their tokens match, so "same family, same schema" is a hash compare
  at batch-cut time, not a plan walk;
* **the stacked-param program**: per stage, every batch-mate tenant's
  ``(pa, pb)`` stacks into ``(T, d)`` operands (T padded to a power-of-
  two tenant rung so the executable is reused across batch mixes), each
  row carries an ``int32`` tenant index, and the jitted program computes
  ``(x - A[tid]) * B[tid]`` (and friends) — one gather per stage turns
  per-tenant math into batch-aligned math.  Under a multi-device mesh
  the program shard_maps rows (``P('data')`` on x and tid) with the
  stacked params replicated, exactly as the single-tenant plan does;
* **one coordinate space**: validation runs host-side over the FULL
  coalesced table (the family's validator is structural — same dim,
  same columns — so the verdict is bit-identical to each tenant's own)
  and emits ONE side-table per validator with coalesced-table offsets;
  the server's existing demux walks it unchanged and hands every caller
  the same request-local quarantine rows solo serving would;
* **parity** is the fused-plan contract verbatim (common/fused.py):
  affine stages are elementwise — bit-identical to solo; the score
  stage's gathered form ``sum(x * A[tid]) + b`` reassociates the
  reduction vs solo's ``x @ w + b``, so discrete predictions are
  bit-identical and float scores agree to accumulation tolerance.
  The mux always serves f32 (the strictest parity point);
* **compile economics**: the executable is keyed on (family, bucket,
  mesh, tenant rung, f32) — never on a tenant — through the shared
  family cache AND the warm-artifact store, so the compile ledger stays
  flat as tenants multiply, and a restarted replica replays the mux
  executable the same way PR 18 replays single-model ones.

Telemetry: ``serving.mux.dispatches`` / ``serving.mux.rows`` /
``serving.mux.tenants_coalesced`` (sum of batch-mates per dispatch —
divide by dispatches for the coalescing factor), plus the standard
``pipeline.fused_dispatches`` / ``pipeline.fused_rows`` so existing
dashboards count mux batches as what they are: one fused dispatch.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Tuple

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.common.fused import (
    FusedRun,
    _active_store,
    _build_run,
    _dev_f32,
    _family_fn_get,
    _family_fn_put,
    _mark_dispatch_warm,
    _note_first_dispatch,
    _padded_rows,
    _try_place,
)
from flink_ml_tpu.common.mapper import ColumnSink
from flink_ml_tpu.fault import pressure
from flink_ml_tpu.table.table import Table

__all__ = [
    "MuxSpan",
    "family_token",
    "mux_enabled",
    "mux_run_for",
    "serve_mux",
]

#: the stage ops the stacked-param program knows how to gather-index —
#: deliberately the Pallas serve-chain vocabulary: those are exactly the
#: stages declaring ``(pa, pb)`` params of knowable shape
_MUX_OPS = ("affine_sub_mul", "affine_mul_add", "glm_score")

_MUX_RUN_CACHE = "_mux_run_cache"
_MUX_RUN_CAPACITY = 4

#: memoized warm-store executables, process-wide (a mux executable
#: belongs to a FAMILY, not to any one tenant's run object)
_WARM_MUX: "OrderedDict[str, object]" = OrderedDict()
_WARM_MUX_LOCK = threading.Lock()
_WARM_MUX_CAPACITY = 64

#: memoized stacked-and-placed param operands per exact span composition
#: — steady-state traffic repeats tenant mixes, and restacking plus
#: re-placing 2*stages (T, d) operands was the dominant mux overhead
_STACKED: "OrderedDict[tuple, tuple]" = OrderedDict()
_STACKED_LOCK = threading.Lock()
_STACKED_CAPACITY = 32


def mux_enabled() -> bool:
    from flink_ml_tpu.utils import knobs

    return knobs.knob_bool("FMT_TENANT_MUX")


def mux_run_for(model, schema, batch_size) -> Optional[FusedRun]:
    """The model's whole-chain fused run when it is mux-eligible, else
    None.  Cached on the model (an evicted tenant takes its plans with
    it).  Eligibility: EVERY stage fuses (no host stages, no staged
    tail — a partial plan would leave per-tenant host work the mux
    cannot coalesce) and the device chain lowers to the ``(pa, pb)``
    op vocabulary (``run.pallas_chain``), which also pins a single
    dense/matrix data desc and at most one entry validator."""
    stages = list(getattr(model, "stages", None) or (model,))
    key = (tuple(schema.field_names), tuple(schema.field_types),
           batch_size)
    cache = model.__dict__.setdefault(_MUX_RUN_CACHE, OrderedDict())
    if key in cache:
        cache.move_to_end(key)
        return cache[key]
    run: Optional[FusedRun] = None
    try:
        built, _bkey = _build_run(stages, 0, schema, batch_size,
                                  min_stages=1)
        if (built is not None and not built.host_stages
                and built.n_stages == len(stages)
                and built.pallas_chain is not None
                and built.device_stages[-1].fetch
                and len(built.validators) <= 1):
            run = built
    except Exception:
        run = None  # an unplannable model simply is not mux-eligible
    cache[key] = run
    while len(cache) > _MUX_RUN_CAPACITY:
        cache.popitem(last=False)
    return run


def family_token(run: FusedRun) -> str:
    """The coalescing key: the plan's structural digest (stage classes,
    ops, wiring, data descs, kernel cache tokens) plus the input and
    exit schema signatures.  Two runs with equal tokens accept each
    other's rows in one dispatch — params are the ONLY difference."""
    sig = (
        run._plan_cache_token(),
        tuple(run.run_input_schema.field_names),
        tuple(str(t) for t in run.run_input_schema.field_types),
        tuple(run.exit_schema.field_names),
        tuple(str(t) for t in run.exit_schema.field_types),
    )
    return hashlib.sha1(repr(sig).encode()).hexdigest()[:16]


class MuxSpan:
    """One tenant's contiguous row span inside a coalesced mux batch."""

    __slots__ = ("tenant", "run", "lo", "hi")

    def __init__(self, tenant: str, run: FusedRun, lo: int, hi: int):
        self.tenant = tenant
        self.run = run
        self.lo = lo
        self.hi = hi


def _tenant_rung(t: int) -> int:
    """Tenant-count bucket: the next power of two, so a fleet mixing
    17-tenant and 23-tenant batches reuses ONE 32-rung executable
    instead of tracing per mix."""
    return 1 << max(0, (t - 1).bit_length())


def _mux_fused_fn(kinds: Tuple[str, ...], fetch: Tuple[bool, ...]):
    """The traced program: per stage, gather the row's tenant params and
    apply the stage op.  Row-aligned by construction (a gather is
    elementwise over rows) — pad rows carry tid 0 and zero features,
    contribute nothing, and are sliced off host-side like every fused
    plan's pad."""

    def fused(x, tid, *stacked):
        x = _dev_f32(x)
        outs = []
        for si, kind in enumerate(kinds):
            pa = _dev_f32(stacked[2 * si])[tid]
            pb = _dev_f32(stacked[2 * si + 1])[tid]
            if kind == "glm_score":
                outs.append((x * pa).sum(axis=-1) + pb[:, 0])
            else:
                x = x * pa + pb if kind == "affine_mul_add" \
                    else (x - pa) * pb
                if fetch[si]:
                    outs.append(x)
        return tuple(outs)

    return fused


def _mux_apply_fn(run0: FusedRun, token: str, mesh, width: int):
    """The jitted mux program for (family, mesh) — family-cached like
    any other structural executable (two sibling servers in one process
    share it)."""
    kinds, _d = run0.pallas_chain
    fetch = tuple(ds.fetch for ds in run0.device_stages)
    key = ("mux", token, kinds, fetch, mesh, width > 1)
    fn = _family_fn_get(key)
    if fn is not None:
        return fn
    import jax

    fused = _mux_fused_fn(kinds, fetch)
    if width == 1:
        fn = jax.jit(fused)
    else:
        from jax.sharding import PartitionSpec as P

        from flink_ml_tpu.parallel.collectives import shard_map

        n_out = sum(
            1 for si, k in enumerate(kinds)
            if k == "glm_score" or fetch[si]
        )
        n_margs = 2 * len(kinds)
        fn = jax.jit(shard_map(
            fused, mesh=mesh,
            in_specs=tuple([P("data")] * 2 + [P()] * n_margs),
            out_specs=tuple([P("data")] * n_out),
            check_vma=False,
        ))
    _family_fn_put(key, fn)
    return fn


def _mux_dispatch_fn(run0: FusedRun, token: str, mesh, width: int,
                     placed, b: int, t_pad: int):
    """The callable for one mux dispatch plus its warm-store provenance —
    the :meth:`FusedRun._dispatch_fn` contract transplanted to a
    family-owned executable: the entry key carries the family token and
    the tenant rung, never a tenant, so every same-family replica in
    the fleet replays one artifact."""
    store = _active_store()
    if store is None:
        return _mux_apply_fn(run0, token, mesh, width), False
    try:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(list(placed))
        sig = ",".join(
            f"{tuple(getattr(x, 'shape', ()))}/"
            f"{getattr(x, 'dtype', type(x).__name__)}"
            for x in leaves
        ) + f"|{treedef}"
        key = store.entry_key(
            "mux:" + run0.serve_name, b, width, "float32",
            extra=(f"t{t_pad}-" + token + "-"
                   + hashlib.sha1(sig.encode()).hexdigest()[:16]),
        )
        with _WARM_MUX_LOCK:
            memo = _WARM_MUX.get(key)
            if memo is not None:
                _WARM_MUX.move_to_end(key)
        if memo is not None:
            return memo, False
        loaded = store.load(
            key,
            recompile=lambda: _mux_apply_fn(run0, token, mesh, width),
        )
        if loaded is not None:
            fn = loaded
        else:
            fn = _mux_apply_fn(run0, token, mesh, width).lower(
                *placed
            ).compile()
            store.save(key, fn)
        with _WARM_MUX_LOCK:
            _WARM_MUX[key] = fn
            while len(_WARM_MUX) > _WARM_MUX_CAPACITY:
                _WARM_MUX.popitem(last=False)
        return fn, loaded is not None
    except Exception as exc:
        # the warm layer can slow a dispatch down, never break it — but
        # the degrade is counted
        store.note_degraded("dispatch", exc)
        return _mux_apply_fn(run0, token, mesh, width), False


def _stack_params(spans: List[MuxSpan], d: int) -> Tuple[list, int]:
    """Per stage, every span tenant's ``(pa, pb)`` stacked to the tenant
    rung — rung pads repeat span 0's params (real params, so tracing
    never meets a degenerate operand; no pad row indexes them)."""
    t_pad = _tenant_rung(len(spans))
    kinds, _ = spans[0].run.pallas_chain
    stacked: list = []
    for si, kind in enumerate(kinds):
        pas, pbs = [], []
        for span in spans:
            ds = span.run.device_stages[si]
            pa, pb = span.run.model_args[ds.marg_lo:ds.marg_hi]
            pas.append(np.asarray(pa, dtype=np.float32).reshape(d))
            want_b = 1 if kind == "glm_score" else d
            pbs.append(np.asarray(pb, dtype=np.float32).reshape(want_b))
        while len(pas) < t_pad:
            pas.append(pas[0])
            pbs.append(pbs[0])
        stacked.append(np.stack(pas))
        stacked.append(np.stack(pbs))
    return stacked, t_pad


def _stacked_placed(spans: List[MuxSpan], d: int) -> Tuple[list, int]:
    """:func:`_stack_params` memoized by the exact run composition, with
    the stacks already device-placed (replicated) — a repeated tenant mix
    pays neither the numpy restack nor the host->device copies.  The
    cache value holds the runs themselves, so an entry's ``id()`` keys
    cannot be recycled while the entry lives."""
    key = (tuple(id(s.run) for s in spans), d)
    with _STACKED_LOCK:
        hit = _STACKED.get(key)
        if hit is not None:
            _STACKED.move_to_end(key)
            return hit[0], hit[1]
    import jax.numpy as jnp

    stacked, t_pad = _stack_params(spans, d)
    placed = [jnp.asarray(a) for a in stacked]
    with _STACKED_LOCK:
        _STACKED[key] = (placed, t_pad, tuple(s.run for s in spans))
        while len(_STACKED) > _STACKED_CAPACITY:
            _STACKED.popitem(last=False)
    return placed, t_pad


def serve_mux(table: Table, spans: List[MuxSpan], mesh) -> Table:
    """Serve one coalesced multi-tenant batch as ONE fused dispatch.

    ``table`` is the spans' tables concatenated in span order (the
    server coalesces per-tenant-contiguous, so each span is one row
    range).  Returns the combined exit table — validation survivors in
    input order — which the server's existing demux splits per request
    exactly as a single-tenant batch.  Quarantine emissions (if any)
    carry coalesced-table offsets in ONE side-table per validator.

    Raises on any dispatch failure: the server discards this attempt's
    quarantine capture and re-serves the spans solo (counters double-
    bump on that rare path; futures and side-tables never do)."""
    from flink_ml_tpu.serve import quarantine

    run0 = spans[0].run
    kinds, d = run0.pallas_chain
    n_total = table.num_rows()

    # -- validation: one structural verdict over the whole batch ---------
    good_all: Optional[np.ndarray] = None
    t = table
    if quarantine.enabled() and run0.validators:
        mapper = run0.validators[0]
        verdict = mapper.validate_batch(table)
        if verdict is not None:
            good, reasons = verdict
            good = np.asarray(good, dtype=bool)
            quarantine.emit(mapper.serve_name(), table, good, reasons,
                            row_offset=0)
            if not good.all():
                t = table.filter_rows(good)
                good_all = good
    if run0.validators:
        obs.drift.observe_input(run0.validators[0], t)
    n = t.num_rows()

    # survivor-space span bounds (quarantined rows drop out of the
    # dispatch; demux re-aligns callers through the emitted side-table)
    kept: List[int] = []
    for span in spans:
        kept.append(
            int(good_all[span.lo:span.hi].sum()) if good_all is not None
            else span.hi - span.lo
        )

    field_order = run0.exit_schema.field_names
    out_names = sorted(run0.device_cols, key=field_order.index)
    out_types = [run0.exit_schema.type_of(nm) for nm in out_names]
    if n == 0:
        cols = ColumnSink(out_names, out_types, 0).columns()
    else:
        row_multiple = run0._mesh_width(mesh)
        b = run0._bucket(n, row_multiple)
        pressure.maybe_oom(n)
        with obs.trace.span("mux_dispatch", {
            "rows": n, "tenants": len(spans),
            "plan": run0.serve_name, "bucket": b,
        }):
            args = run0._extract(t, b, mesh, row_multiple, mode=None)
            b = _padded_rows(args) or b
            tid = np.zeros(b, dtype=np.int32)
            lo = 0
            for k, span in enumerate(spans):
                tid[lo:lo + kept[k]] = k
                lo += kept[k]
            placed = [args[0], _try_place(tid, mesh, row_multiple)]
            stacked, t_pad = _stacked_placed(spans, d)
            placed.extend(stacked)
            import jax
            import jax.numpy as jnp

            from flink_ml_tpu.lib.common import fetch_flat

            placed = [
                a if isinstance(a, jax.Array)
                or not isinstance(a, np.ndarray) else jnp.asarray(a)
                for a in placed
            ]
            width = run0._mesh_width(mesh)
            token = family_token(run0)
            t_disp = time.perf_counter()
            fn, warm_hit = _mux_dispatch_fn(
                run0, token, mesh, width, placed, b, t_pad
            )
            res = fn(*placed)
            plan = f"mux:{run0.serve_name}@t{t_pad}"
            if warm_hit:
                _mark_dispatch_warm(plan, b, width, dtype="float32",
                                    pallas=False)
            else:
                _note_first_dispatch(
                    plan, b, width, time.perf_counter() - t_disp,
                    dtype="float32", pallas=False,
                )
            with obs.trace.span("device_sync"):
                fetched = fetch_flat(*res)
        if width > 1:
            obs.counter_add("fused.shard_map_dispatches")
        obs.counter_add("serving.mux.dispatches")
        obs.counter_add("serving.mux.rows", n)
        obs.counter_add("serving.mux.tenants_coalesced", len(spans))
        obs.counter_add("pipeline.fused_dispatches")
        obs.counter_add("pipeline.fused_rows", n)

        # -- per-span finalize: each tenant's own host tail --------------
        trimmed = [np.asarray(v)[:n] for v in fetched]
        sink = ColumnSink(out_names, out_types, n)
        lo = 0
        for k, span in enumerate(spans):
            n_k = kept[k]
            out_k: dict = {}
            for fi, (ds0, key) in enumerate(run0.fetch_layout):
                ds = span.run.device_stages[ds0.index]
                vals = {key: trimmed[fi][lo:lo + n_k]}
                cols_k = ds.kernel.finalize(vals, n_k)
                for c, v in cols_k.items():
                    if span.run.exit_schema.contains(c):
                        canon = span.run.exit_schema.resolve(c)
                        if span.run.exit_src.get(canon) == ds.index:
                            out_k[canon] = v
            sink.append(out_k, n_k)
            lo += n_k
        cols = sink.columns()

    passthrough = [
        nm for nm in run0.exit_schema.field_names
        if run0.exit_src[nm] == "input"
    ]
    if passthrough:
        src = t.select(passthrough)
        for nm in passthrough:
            cols[nm] = src.col(nm)
    return Table.from_columns(run0.exit_schema, cols)
