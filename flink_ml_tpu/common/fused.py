"""Fused device-resident pipeline inference — one dispatch per batch.

The reference applies pipeline stages sequentially (PipelineModel.java:53-59)
and the staged port reproduces that literally: every stage places its batch
on device, runs one jitted call, and fetches results back to host numpy
before the next stage re-uploads them.  An S-stage serving pipeline so
pays S dispatches plus 2·S host<->device transfers per batch, each with
its own sync (the per-dispatch cost on the chip is to be re-measured,
ROADMAP S0).  This module closes that gap — the inference-side twin of the
warm-fit dispatch gap the slab pool closed for training:

* every shipped mapper publishes an optional **pure device kernel**
  (:meth:`~flink_ml_tpu.common.mapper.Mapper.fused_kernel` -> a
  :class:`FusedKernel`: jnp-in/jnp-out, no host materialization);
* the planner walks a ``PipelineModel``'s stage chain, greedily groups
  maximal runs of kernel-capable mappers, and compiles each run into ONE
  jitted program per batch: the vector/feature columns stay device-resident
  across fused stages (the ``env``), host-lookup stages (StringIndexer,
  OneHotEncoder) ride along as host pre-kernels without a dispatch of
  their own;
* quarantine's validation runs once at plan entry instead of once per
  stage; host prep (feature extraction + H2D staging) of batch i+1 is
  double-buffered under batch i's compute via the shared
  :func:`~flink_ml_tpu.utils.prefetch.prefetch_iter` idiom;
* the whole fused call dispatches through :func:`~flink_ml_tpu.serve.
  dispatch` under a **per-plan circuit breaker** whose fallback is the
  existing per-stage path — a mapper without a kernel, an incompatible
  column flow, or a tripped breaker transparently splits the plan and
  serves exactly as today (bit-identical on discrete outputs);
* column bookkeeping (OutputColsHelper merges, reserved cols, quarantine
  side-tables with original row offsets) is computed once at plan build
  and applied at plan exit: reserved passthrough columns come straight off
  the run-input table's buffers, never copied per batch.

Parity contract: a fused run computes exactly the per-stage device math on
exactly the per-stage batch buckets; the only difference is that
intermediate f32 columns skip their host round-trip (f32 -> host -> f32 is
value-exact), so discrete outputs are bit-identical and float scores agree
to accumulation tolerance.  Entry-only validation is the one sanctioned
semantic difference: a mid-chain stage never re-validates device-produced
values (the staged path would), so a kernel that *manufactures* NaNs from
clean inputs flows them onward — the same contract as any single fused
device program.

SPMD multi-chip serving (ISSUE 15): every fused dispatch is sharded over
the session mesh's ``data`` axis through :func:`~flink_ml_tpu.parallel.
collectives.shard_map` — dense batches place row-sharded
(``P('data')``), segment-CSR batches re-lay out shard-major
(:class:`~flink_ml_tpu.ops.batch.ShardedCsrBatch`: per-shard nnz padded
to one agreed width, the ``agree_max`` idiom from the sparse training
pack), and every batch pads to a bucket divisible by the data-axis size
with weight-0 pad rows (zero features -> zero contributions, sliced off
before finalize), so outputs, quarantine side-table offsets, and
bisection sub-ranges are identical to the 1-device path.  The per-device
outputs come back in the ONE bundled fetch and demux by row position —
contiguous row sharding keeps output row i = input row i.  The fused
kernels are row-aligned by contract (no collectives), so the serving
mesh never gathers; a mesh that spans processes (never the default
``inference_mesh``) agrees its breaker verdict open-wins through
``serve.dispatch(agreed=True)``.

Telemetry: ``pipeline.fused_dispatches`` (exactly one per batch per fused
run), ``pipeline.fused_rows``, ``pipeline.plan_fallback_batches``, the
``pipeline.fusion_ratio`` gauge (fused stages / total stages), the
``pipeline.fused_call_ms`` timing histogram, and the mesh plane:
``fused.mesh_devices`` gauge, ``fused.shard_map_dispatches`` counter
(the proof the sharded path ran — the bypass detector),
``fused.padded_rows`` per-batch pad accounting, and the per-device
row-share breakdown ``/statusz`` renders (:func:`mesh_status`).

Pallas hot path + low precision (ISSUE 17): a run whose device chain is
one dense feature flow through declared ``pallas_op`` stages (scaler ->
GLM today) can lower to ONE ``serve_chain`` Pallas launch — the
quarantine NaN/Inf scan, the scaling, and the score in a single HBM pass
(``FMT_SERVE_PALLAS``, default off; ``interpret=True`` off-TPU).  When
the plan's sole validator reduces to the pure finite scan
(:func:`~flink_ml_tpu.serve.quarantine.finite_scan_only`), validation
DEFERS into that same launch: the kernel emits a per-row ok mask, bad
rows are zeroed in-kernel, and the executor emits the identical
quarantine side-table (offsets and all) after the dispatch.
``FMT_SERVE_PRECISION=bf16|int8`` ships the batch placement (and model
args) low-precision — compute upcasts to f32 on device, so discrete
predictions stay bit-identical to f32 on margin-separated data while
float scores carry a documented quantization tolerance; int8 keeps host
validation (NaN is unrepresentable post-quantization) and falls back to
the XLA fused program when Pallas is also requested.

Knobs: ``FMT_FUSE_TRANSFORM`` (default on; off restores the stage-at-a-
time transform verbatim), ``FMT_SERVE_MESH`` (default on; off pins fused
serving to a single logical device — plain jit, no row sharding),
``FMT_SERVE_CSR_PAD`` (per-shard nnz pad multiple for sharded CSR),
``FMT_FUSE_DONATE`` (donate placed batch buffers to the dispatch;
ignored on the CPU backend), ``FMT_SERVE_PALLAS`` /
``FMT_SERVE_PALLAS_TILE`` (the Pallas serving kernel and its row-tile
size), ``FMT_SERVE_PRECISION`` (f32 | bf16 | int8 serving precision).
"""

from __future__ import annotations

import hashlib
import sys
import threading
import time
from collections import OrderedDict, namedtuple
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.common.mapper import ColumnSink, _kept_indices
from flink_ml_tpu.fault import pressure
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils import knobs

__all__ = [
    "FusedInput",
    "FusedKernel",
    "fusion_enabled",
    "mesh_status",
    "reset_compile_keys",
    "reset_family_fns",
    "reset_mesh_stats",
    "serve_mesh_enabled",
    "serve_pallas_enabled",
    "serve_precision",
    "transform_fused",
]


def fusion_enabled() -> bool:
    """Is fused pipeline inference on?  ``FMT_FUSE_TRANSFORM`` (default 1)."""
    return knobs.knob_bool("FMT_FUSE_TRANSFORM")


def serve_pallas_enabled() -> bool:
    """Is the Pallas-fused serving kernel on?  ``FMT_SERVE_PALLAS``
    (default 0 — opt-in while the measured delta accrues per backend)."""
    return knobs.knob_bool("FMT_SERVE_PALLAS")


def serve_precision() -> str:
    """The serving numeric precision: ``f32`` (default), ``bf16`` or
    ``int8`` (``FMT_SERVE_PRECISION``).  Unrecognized values degrade to
    f32 — precision is an optimization knob, never a failure mode."""
    p = knobs.knob_str("FMT_SERVE_PRECISION").strip().lower()
    if p in ("bf16", "bfloat16"):
        return "bf16"
    if p in ("int8", "i8"):
        return "int8"
    return "f32"


#: gauge value (``serve.precision``) and compile-ledger dtype per precision
_PRECISION_BITS = {"f32": 32, "bf16": 16, "int8": 8}
_PRECISION_DTYPE = {"f32": "float32", "bf16": "bfloat16", "int8": "int8"}


#: per-execute dispatch mode — computed once per :meth:`FusedRun.execute`
#: from the knobs so a knob flipped mid-feed never splits one run's
#: batches across modes
_ServeMode = namedtuple("_ServeMode", ["precision", "pallas", "defer"])


#: the executor-internal output key the deferred in-kernel validation
#: mask rides under (popped before any column reaches the sink)
_ROW_OK_KEY = "__row_ok__"


#: (plan, bucket rung, mesh width, dtype) keys whose first dispatch this
#: process has already timed into the compile ledger — the first dispatch
#: of a key is the compile-bearing one (jit traces + compiles inline),
#: repeats are cache hits
_COMPILE_SEEN: set = set()
_COMPILE_LOCK = threading.Lock()


def reset_compile_keys() -> None:
    """Forget which dispatch shapes this process has ledgered (tests)."""
    with _COMPILE_LOCK:
        _COMPILE_SEEN.clear()


def _note_first_dispatch(plan: str, b: int, width: int, dur_s: float,
                         dtype: str = "float32",
                         pallas: bool = False) -> None:
    """First dispatch of a (plan, bucket, mesh, dtype) shape: record the
    compile-attributed span + ledger line (obs.trace.note_compile).
    The dtype key is the placement precision (``FMT_SERVE_PRECISION``);
    a Pallas-lowered plan ledgers under a ``pallas:`` key prefix so
    ``obs fleet`` rollups tell Mosaic compiles from XLA compiles."""
    name = ("pallas:" + plan) if pallas else plan
    key = (name, b, width, dtype)
    with _COMPILE_LOCK:
        if key in _COMPILE_SEEN:
            return
        _COMPILE_SEEN.add(key)
    obs.trace.note_compile(name, b, width, dtype, dur_s)


def _active_store():
    """The warm-artifact store, WITHOUT importing the serving package on
    processes that never configured one.  A training-only worker (think
    the two-process gloo suite) must keep its exact pre-warmstart
    dispatch timing: the serving package only loads here if something
    already imported it (a path-deploy configured a store) or the
    process was handed a store via ``FMT_WARM_DIR`` (a spawned
    replica)."""
    mod = sys.modules.get("flink_ml_tpu.serving.warmstart")
    if mod is None:
        if not knobs.knob_str("FMT_WARM_DIR"):
            return None
        from flink_ml_tpu.serving import warmstart as mod
    return mod.active()


def _mark_dispatch_warm(plan: str, b: int, width: int,
                        dtype: str = "float32",
                        pallas: bool = False) -> None:
    """A dispatch whose executable came off the warm-artifact store paid
    no compile: claim its (plan, bucket, mesh, dtype) key WITHOUT a
    ledger line, so the compile-ledger delta of a warm process stays
    empty (``tests/test_warmstart.py`` holds it)."""
    name = ("pallas:" + plan) if pallas else plan
    with _COMPILE_LOCK:
        _COMPILE_SEEN.add((name, b, width, dtype))
    obs.counter_add("warmstart.compile_skips")


def serve_mesh_enabled() -> bool:
    """Is SPMD fused serving over the mesh on?  ``FMT_SERVE_MESH``
    (default 1).  Off pins every fused dispatch to one logical device —
    the pre-ISSUE-15 single-device behavior, kept as an escape hatch."""
    return knobs.knob_bool("FMT_SERVE_MESH")


# -- family-shared executables (ISSUE 20) -------------------------------------
#
# Two same-family models (identical pipeline structure, different fitted
# params) build structurally identical fused programs: the jitted fn closes
# over stage wiring only — params arrive as call arguments.  Keying the
# compiled program per FusedRun instance made every tenant of a family pay
# its own trace+compile; sharing it across instances by the plan's
# structural token makes tenant N+1's first dispatch a cache hit.  Correct
# by the same contract the warm-artifact entry key already relies on:
# everything a program's lowering depends on beyond argument shapes is in
# the plan token (stage classes, wiring, declared cache_token constants).

_FAMILY_FNS_CAPACITY = 64
_FAMILY_FNS: "OrderedDict[tuple, object]" = OrderedDict()
_FAMILY_FNS_LOCK = threading.Lock()


def _family_fn_get(key):
    with _FAMILY_FNS_LOCK:
        fn = _FAMILY_FNS.get(key)
        if fn is not None:
            _FAMILY_FNS.move_to_end(key)
        return fn


def _family_fn_put(key, fn) -> None:
    with _FAMILY_FNS_LOCK:
        _FAMILY_FNS[key] = fn
        while len(_FAMILY_FNS) > _FAMILY_FNS_CAPACITY:
            _FAMILY_FNS.popitem(last=False)


def reset_family_fns() -> None:
    """Drop the family-shared executable cache (tests)."""
    with _FAMILY_FNS_LOCK:
        _FAMILY_FNS.clear()


# -- per-device row-share accounting (ISSUE 15) -------------------------------
#
# Contiguous row sharding means device d of a width-D dispatch serves rows
# [d*b/D, (d+1)*b/D) of the padded bucket; the tally below records how many
# REAL rows each data-axis position received, which /statusz renders as the
# mesh row-share breakdown (a chronically starved tail device means batches
# are too small for the mesh).

_MESH_ROWS_LOCK = threading.Lock()
_MESH_ROWS: Dict[int, int] = {}


def _note_device_rows(n: int, b: int, width: int) -> None:
    if width <= 1 or b <= 0:
        return
    share = b // width
    with _MESH_ROWS_LOCK:
        for d in range(width):
            real = max(0, min(n - d * share, share))
            _MESH_ROWS[d] = _MESH_ROWS.get(d, 0) + real


def mesh_status() -> dict:
    """The ``/statusz`` mesh section: per-device REAL-row counts and
    shares over every sharded fused dispatch since process start (or
    :func:`reset_mesh_stats`)."""
    with _MESH_ROWS_LOCK:
        rows = {str(d): int(r) for d, r in sorted(_MESH_ROWS.items())}
    total = sum(rows.values())
    return {
        "devices": len(rows),
        "device_rows": rows,
        "device_row_share": {
            d: round(r / total, 4) if total else 0.0
            for d, r in rows.items()
        },
    }


def reset_mesh_stats() -> None:
    """Drop the per-device row tally (tests; per-run scoping)."""
    with _MESH_ROWS_LOCK:
        _MESH_ROWS.clear()


@dataclass(frozen=True)
class FusedInput:
    """One feature input a device kernel reads — the same column-selection
    vocabulary as ``serve_validation_spec`` (one vector column or a list of
    numeric columns, with the model's width pinned)."""

    dim: int
    vector_col: Optional[str] = None
    feature_cols: Optional[Tuple[str, ...]] = None


@dataclass
class FusedKernel:
    """A mapper's declaration of how it participates in a fused plan.

    Device kernels: ``fn(*inputs, *model_args) -> {key: jnp array}`` is the
    pure jnp computation (``csr_fn`` the sparse-input variant, both
    row-aligned with input rows); ``finalize(fetched, n) -> {col: values}``
    converts the fetched (host, row-sliced) arrays into the mapper's
    declared output columns — the cheap elementwise host tail of
    ``map_batch`` (sigmoid, class-id lookup, sqrt).  ``env_outputs`` names
    the keys whose device values flow onward as device-resident dense
    columns: ``{key: (output column name, width)}``.

    Host kernels (``host=True``, everything else ignored): the mapper's
    ``map_batch`` is already a pure host lookup with no device dispatch —
    it joins a run as a pre-kernel so a chain like
    indexer -> encoder -> sparse LR still fuses into one dispatch.
    """

    host: bool = False
    inputs: Sequence[FusedInput] = ()
    fn: Optional[Callable] = None
    csr_fn: Optional[Callable] = None
    out_keys: Sequence[str] = ()
    model_args: tuple = ()
    finalize: Optional[Callable] = None
    env_outputs: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    #: this stage's op in the Pallas serve chain (an ``ops.pallas_kernels.
    #: SERVE_CHAIN_OPS`` name, exactly two model args) — None keeps the
    #: stage XLA-only; a whole-run chain of declared ops lowers to one
    #: ``serve_chain`` launch under ``FMT_SERVE_PALLAS``
    pallas_op: Optional[str] = None
    #: program-shaping constants the kernel ``fn`` closes over that are
    #: NOT visible in argument shapes (knn's k/chunk/vote width, a
    #: bf16-distances flag).  They join the warm-artifact entry key
    #: (serving/warmstart) — two models whose kernels differ only in a
    #: closure constant must never replay each other's executable.
    cache_token: tuple = ()


# -- plan assembly ------------------------------------------------------------


class _DeviceStage:
    """One device-kernel stage inside a fused run (planner-internal)."""

    __slots__ = (
        "index", "mapper", "kernel", "input_refs", "call_fn", "marg_lo",
        "marg_hi", "fetch", "out_keys", "validates",
    )

    def __init__(self, index, mapper, kernel):
        self.index = index
        self.mapper = mapper
        self.kernel = kernel
        self.input_refs: List[Tuple[str, object]] = []  # ('env', col)|('arg', i)
        self.call_fn = kernel.fn
        self.marg_lo = self.marg_hi = 0
        self.fetch = False
        self.out_keys: Tuple[str, ...] = tuple(kernel.out_keys)
        self.validates = False  # reads host-sourced features -> entry check


def _stage_infos(stages, start: int, schema: Schema):
    """Consecutive kernel-capable (stage, mapper, kernel) triples from
    ``start``, chaining schemas through each mapper's OutputColsHelper."""
    from flink_ml_tpu.lib.model_base import TableModelBase

    infos = []
    s = schema
    for j in range(start, len(stages)):
        stage = stages[j]
        if not isinstance(stage, TableModelBase):
            break
        mapper = stage.loaded_mapper(s)
        kernel = mapper.fused_kernel()
        if kernel is None:
            break
        infos.append((stage, mapper, kernel))
        s = mapper.get_output_schema()
    return infos


class FusedRun:
    """A compiled maximal run of kernel-capable stages: plan metadata plus
    the per-mesh jitted fused program and the per-batch executor."""

    def __init__(self, host_stages, device_stages, data_descs, model_args,
                 validators, exit_schema, exit_src, run_input_schema,
                 post_host_schema, batch_size, has_csr, serve_name):
        self.host_stages = host_stages          # [(stage, mapper, kernel)]
        self.device_stages = device_stages      # [_DeviceStage]
        self.data_descs = data_descs            # extraction descriptors
        self.model_args = tuple(model_args)
        self.validators = validators            # mappers validated at entry
        self.exit_schema = exit_schema
        self.exit_src = exit_src                # field -> 'input'|'batch'|j
        self.run_input_schema = run_input_schema
        self.post_host_schema = post_host_schema
        self.batch_size = batch_size
        self.has_csr = has_csr
        self.serve_name = serve_name
        self.n_stages = len(host_stages) + len(device_stages)
        self._apply_fns: Dict = {}
        self._warm_fns: Dict = {}   # warm-artifact entry key -> executable
        self._cache_token = None
        # flat fetch layout: [(device stage, key)] in program output order
        self.fetch_layout = [
            (ds, key)
            for ds in device_stages if ds.fetch
            for key in ds.out_keys
        ]
        self.batch_cols = [
            name for name in exit_schema.field_names
            if exit_src[name] == "batch"
        ]
        self.device_cols = {
            name for name in exit_schema.field_names
            if isinstance(exit_src[name], int)
        }
        self.pallas_chain = self._pallas_chain()

    def _pallas_chain(self) -> Optional[Tuple[Tuple[str, ...], int]]:
        """``(per-stage op kinds, feature width)`` when this run's device
        chain lowers to ONE ``serve_chain`` Pallas launch, else None: a
        single dense/matrix data desc feeding stage 0, every stage a
        declared ``pallas_op`` with exactly ``(pa, pb)`` model args and
        one output key, each later stage consuming the previous stage's
        width-d env column, and a GLM score only in final position (it
        narrows the row to one lane)."""
        from flink_ml_tpu.ops.pallas_kernels import SERVE_CHAIN_OPS

        if self.has_csr or len(self.data_descs) != 1:
            return None
        if self.data_descs[0][0] not in ("dense", "matrix"):
            return None
        d = int(self.data_descs[0][2])
        kinds = []
        for i, ds in enumerate(self.device_stages):
            op = ds.kernel.pallas_op
            if (op not in SERVE_CHAIN_OPS or len(ds.out_keys) != 1
                    or ds.marg_hi - ds.marg_lo != 2):
                return None
            if op == "glm_score" and i != len(self.device_stages) - 1:
                return None
            # the chain kernel assumes (d,)-sized stage params and a
            # scalar intercept for the score — a multi-class weight
            # matrix (or any other layout) stays on the XLA program
            pa, pb = self.model_args[ds.marg_lo:ds.marg_hi]
            want_b = 1 if op == "glm_score" else d
            if np.asarray(pa).size != d or np.asarray(pb).size != want_b:
                return None
            if i == 0:
                if ds.input_refs != [("arg", 0)]:
                    return None
            else:
                prev = self.device_stages[i - 1].kernel
                env_cols = {
                    col.lower() for col, w in prev.env_outputs.values()
                    if int(w) == d
                }
                if (len(ds.input_refs) != 1
                        or ds.input_refs[0][0] != "env"
                        or ds.input_refs[0][1] not in env_cols):
                    return None
            kinds.append(op)
        return tuple(kinds), d

    # -- the one jitted program ----------------------------------------------

    def _fused_fn(self):
        from flink_ml_tpu.ops.batch import ShardedCsrBatch

        device_stages = self.device_stages
        n_data = len(self.data_descs)

        def fused(*args):
            # inside a shard_map a ShardedCsrBatch's leaves are this
            # shard's slice with local row ids: reassemble the ordinary
            # local CsrBatch the kernels consume; low-precision args
            # (bf16 arrays, int8 (q, scale) pairs) upcast to the f32
            # compute type here, so only the H2D bytes shrink
            data = tuple(
                a.local() if isinstance(a, ShardedCsrBatch)
                else _dev_f32(a)
                for a in args[:n_data]
            )
            margs = tuple(_dev_f32(m) for m in args[n_data:])
            env: Dict[str, object] = {}
            outs = []
            for ds in device_stages:
                ins = [
                    env[ref] if kind == "env" else data[ref]
                    for kind, ref in ds.input_refs
                ]
                res = ds.call_fn(*ins, *margs[ds.marg_lo:ds.marg_hi])
                for key, (col, _w) in ds.kernel.env_outputs.items():
                    env[col.lower()] = res[key]
                if ds.fetch:
                    outs.extend(res[k] for k in ds.out_keys)
            return tuple(outs)

        return fused

    def _pallas_fused_fn(self, masked: bool):
        """The whole-chain Pallas program: ONE ``serve_chain`` launch for
        scan (+mask, when validation is deferred) + every stage's math.
        Same call signature as :meth:`_fused_fn`'s program — the single
        data arg arrives column-padded to the kernel's 128-lane width
        (:meth:`_extract`), outputs carry that padding back out and are
        trimmed host-side in :meth:`_device_batch`."""
        from flink_ml_tpu.ops.pallas_kernels import serve_chain

        kinds, d = self.pallas_chain
        fetch = tuple(ds.fetch for ds in self.device_stages)
        chain = serve_chain(
            kinds, fetch, d, masked=masked,
            tile_rows=knobs.knob_int("FMT_SERVE_PALLAS_TILE"),
        )
        slices = [(ds.marg_lo, ds.marg_hi) for ds in self.device_stages]

        def fused(x, *margs):
            margs = tuple(_dev_f32(m) for m in margs)
            pairs = [tuple(margs[lo:hi]) for lo, hi in slices]
            return tuple(chain(_dev_f32(x), *pairs))

        return fused

    def _mesh_width(self, mesh) -> int:
        """The dispatch's row-shard count: the mesh's data-axis size, or
        1 when ``FMT_SERVE_MESH`` pins serving to one logical device."""
        from flink_ml_tpu.parallel.mesh import data_parallel_size

        if not serve_mesh_enabled():
            return 1
        return data_parallel_size(mesh)

    def _donate_argnums(self) -> tuple:
        """Data-arg positions donated to the fused program (ISSUE 15,
        dispatch-cost satellite): the placed batch buffers are built
        fresh per batch by :meth:`_extract` — never slab-pooled, so no
        pin can alias them — and nothing reads them after the dispatch,
        so XLA may reuse their device memory for the outputs instead of
        holding input + output live simultaneously.  Model args are
        NEVER donated (they persist across batches).  CPU ignores
        donation (and would warn per call), so the list is empty there —
        same contract as mesh._concat_placed_fn."""
        import jax

        if not knobs.knob_bool("FMT_FUSE_DONATE"):
            return ()
        if jax.default_backend() == "cpu":
            return ()
        return tuple(range(len(self.data_descs)))

    def _apply_fn(self, mesh, pallas: Optional[str] = None):
        """The compiled program for (mesh, donation, pallas variant):
        ``pallas`` is None for the XLA chain, ``"raw"`` for the Pallas
        chain, ``"masked"`` for the Pallas chain with deferred in-kernel
        validation (one extra leading per-row ok output)."""
        width = self._mesh_width(mesh)
        donate = self._donate_argnums()
        key = (mesh, width > 1, donate, pallas)
        fn = self._apply_fns.get(key)
        if fn is not None:
            return fn
        # family-shared hit (ISSUE 20): another same-family run (a sibling
        # tenant's model) already built this structural program — reuse it,
        # params pass as call args so the math is the other model's own
        family_key = (self._plan_cache_token(),) + key
        fn = _family_fn_get(family_key)
        if fn is not None:
            self._apply_fns[key] = fn
            obs.counter_add("fused.family_fn_hits")
            return fn
        import jax

        if pallas is None:
            fused = self._fused_fn()
            n_out = len(self.fetch_layout)
        else:
            fused = self._pallas_fused_fn(masked=pallas == "masked")
            n_out = len(self.fetch_layout) + (pallas == "masked")
        # a name the program owns on every operation of the serve program
        # (metadata: the compiled program does not change)
        fused = jax.named_scope("fmt.serve")(fused)
        if width == 1:
            # a 1-wide data axis (or FMT_SERVE_MESH=0) degenerates to the
            # plain single-logical-device program
            fn = jax.jit(fused, donate_argnums=donate)
        else:
            from jax.sharding import PartitionSpec as P

            from flink_ml_tpu.parallel.collectives import shard_map

            # P('data') is a pytree-prefix spec: a dense batch shards its
            # rows (an int8 (q, scale) pair both its leaves), a
            # ShardedCsrBatch each flat (n_shards*nnz_pad,) leaf —
            # handing every device exactly its rows' entries
            in_specs = tuple(
                [P("data")] * len(self.data_descs)
                + [P()] * len(self.model_args)
            )
            out_specs = tuple([P("data")] * n_out)
            fn = jax.jit(shard_map(
                fused, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ), donate_argnums=donate)
        self._apply_fns[key] = fn
        _family_fn_put(family_key, fn)
        return fn

    def _plan_cache_token(self) -> str:
        """Structural digest of this plan for the warm-artifact entry key:
        stage classes, output keys, pallas ops, input wiring, data-desc
        layout, and each kernel's declared ``cache_token`` closure
        constants.  Everything else an executable depends on (shapes,
        dtypes, mesh, donation, jax/backend) is keyed separately."""
        if getattr(self, "_cache_token", None) is None:
            parts = [self.serve_name, repr(tuple(self.data_descs))]
            for ds in self.device_stages:
                parts.append("|".join((
                    type(ds.mapper).__name__,
                    ",".join(ds.out_keys),
                    str(ds.kernel.pallas_op),
                    repr(ds.input_refs),
                    repr(tuple(ds.kernel.cache_token)),
                )))
            self._cache_token = hashlib.sha1(
                "||".join(parts).encode()
            ).hexdigest()[:12]
        return self._cache_token

    def _dispatch_fn(self, mesh, variant, placed, margs, b: int,
                     width: int, dtype: str, pallas: bool):
        """The callable for one fused dispatch, plus whether it was just
        loaded off the warm-artifact store (-> the caller skips the
        compile ledger).  With no store active this is exactly
        :meth:`_apply_fn`; any warm-layer failure degrades to the same —
        the store can slow a dispatch down, never break it."""
        store = _active_store()
        if store is None:
            return self._apply_fn(mesh, variant), False
        try:
            import jax

            leaves, treedef = jax.tree_util.tree_flatten(
                (list(placed), list(margs))
            )
            sig = ",".join(
                f"{tuple(getattr(x, 'shape', ()))}/"
                f"{getattr(x, 'dtype', type(x).__name__)}"
                for x in leaves
            ) + f"|{treedef}|v{variant}|d{self._donate_argnums()}"
            key = store.entry_key(
                ("pallas:" + self.serve_name) if pallas else self.serve_name,
                b, width, dtype,
                extra=(self._plan_cache_token() + "-"
                       + hashlib.sha1(sig.encode()).hexdigest()[:16]),
            )
            memo = self._warm_fns.get(key)
            if memo is not None:
                return memo, False
            loaded = store.load(
                key, recompile=lambda: self._apply_fn(mesh, variant)
            )
            if loaded is not None:
                self._warm_fns[key] = loaded
                return loaded, True
            compiled = self._apply_fn(mesh, variant).lower(
                *placed, *margs
            ).compile()
            store.save(key, compiled)
            self._warm_fns[key] = compiled
            return compiled, False
        except Exception as exc:
            # never let the warm layer take down a dispatch — but count
            # it: the plain program below recompiles, and a real compile
            # error raises again from its call
            store.note_degraded("dispatch", exc)
            return self._apply_fn(mesh, variant), False

    # -- per-batch execution --------------------------------------------------

    def _bucket(self, n: int, row_multiple: int) -> int:
        from flink_ml_tpu.lib.common import _bucket_for

        # every input — dense AND segment-CSR — rides the shared batch-
        # shape ladder (utils/compile_cache.bucket_batch_rows, via
        # _bucket_for), rounded up to the mesh's data-axis size: fused
        # plans, staged applies, and serving micro-batches pad
        # identically, and every shard_map sees equal row shards
        return _bucket_for(n, 256, row_multiple)

    def _extract(self, batch: Table, b: int, mesh, row_multiple: int,
                 mode: Optional[_ServeMode] = None):
        """Host half of one batch's device inputs: feature extraction +
        pad-to-bucket + best-effort async placement (runs on the prefetch
        producer thread, overlapping the previous batch's compute).  A
        Pallas-bound batch additionally zero-pads its columns to the
        kernel's 128-lane width here (host-side, once) so the launch
        never re-lays out the batch; a low-precision mode quantizes the
        dense placement (CSR values stay f32)."""
        from flink_ml_tpu.lib.common import _pad_rows_to

        pallas = mode is not None and mode.pallas
        precision = mode.precision if mode is not None else "f32"

        def _dense(X):
            if pallas:
                d_pad = -(-max(X.shape[1], 1) // 128) * 128
                if d_pad != X.shape[1]:
                    Xp = np.zeros((X.shape[0], d_pad), dtype=X.dtype)
                    Xp[:, : X.shape[1]] = X
                    X = Xp
            return _quantize(_pad_rows_to(X, b), precision)

        args = []
        for desc in self.data_descs:
            kind = desc[0]
            if kind == "dense":
                _, col, dim = desc
                X = np.asarray(
                    batch.features_dense(col, dim=dim), dtype=np.float32
                )
                args.append(_dense(X))
            elif kind == "matrix":
                _, cols, _dim = desc
                X = np.asarray(batch.numeric_matrix(list(cols)),
                               dtype=np.float32)
                args.append(_dense(X))
            else:  # csr
                from flink_ml_tpu.ops.batch import CsrBatch, ShardedCsrBatch

                _, col, dim = desc
                csr = batch.features_csr(col, n_cols=dim)
                padded = CsrBatch(
                    csr.indices, csr.values, csr.row_ids,
                    n_rows=b, n_cols=csr.n_cols,
                )
                if row_multiple > 1:
                    # SPMD serving (ISSUE 15): re-lay out shard-major so
                    # P('data') placement hands each device its rows'
                    # entries; per-shard nnz pads to one agreed width
                    # (the agree_max idiom — pad entries are weight-0)
                    args.append(ShardedCsrBatch.from_csr_batch(
                        padded, n_shards=row_multiple,
                        rows_per_shard=b // row_multiple,
                        pad_multiple=knobs.knob_int("FMT_SERVE_CSR_PAD"),
                    ))
                else:
                    args.append(padded)
        placed = []
        for a in args:
            placed.append(_try_place(a, mesh, row_multiple))
        return placed

    def _validate_entry(self, batch: Table, offset: int):
        """Plan-entry quarantine: each entry validator (a device stage
        whose features are host-sourced) checks the batch in stage order,
        bad rows land in ITS side-table with original-feed row offsets,
        survivors flow on.  Mid-run (device-produced) inputs are not
        re-checked — the entry-only contract documented on the module."""
        from flink_ml_tpu.serve import quarantine

        if not quarantine.enabled() or not self.validators:
            return batch, None
        n = batch.num_rows()
        b = batch
        orig: Optional[np.ndarray] = None  # b's rows as ORIGINAL indices
        for mapper in self.validators:
            if b.num_rows() == 0:
                break
            verdict = mapper.validate_batch(b)
            if verdict is None:
                continue
            good, reasons = verdict
            good = np.asarray(good, bool)
            if orig is None:
                quarantine.emit(mapper.serve_name(), b, good, reasons,
                                row_offset=offset)
                orig = np.nonzero(good)[0]
            else:
                # a later validator sees the FILTERED batch: expand its
                # verdict back to original-batch coordinates before
                # emitting, or the side-table's _quarantine_row would
                # point at the wrong source-feed row
                bad_orig = orig[~good]
                g2 = np.ones(n, dtype=bool)
                g2[bad_orig] = False
                r2 = np.full(n, None, dtype=object)
                r2[bad_orig] = np.asarray(reasons, dtype=object)[~good]
                quarantine.emit(mapper.serve_name(), batch, g2, r2,
                                row_offset=offset)
                orig = orig[good]
            b = b.filter_rows(good)
        if orig is None:
            return b, None
        good_all = np.zeros(n, dtype=bool)
        good_all[orig] = True
        return b, good_all

    def _margs_for(self, mode: Optional[_ServeMode]) -> tuple:
        """The model args at the mode's placement precision (memoized —
        params are static per run, so the low-precision copies are built
        once): bf16 casts, int8 symmetric-quantizes to ``(q, scale)``
        pairs the device program dequantizes.  Only stages with DECLARED
        ``pallas_op`` semantics (affine params, GLM weights) quantize —
        an opaque kernel's args may be categorical (kNN labels) or feed
        tie-breaking argmins (centroids), where lossy params would break
        the discrete-parity contract; those stay f32, the batch
        placement low-precision either way."""
        precision = mode.precision if mode is not None else "f32"
        if precision == "f32":
            return self.model_args
        memo = self.__dict__.setdefault("_marg_memo", {})
        margs = memo.get(precision)
        if margs is None:
            out = list(self.model_args)
            for ds in self.device_stages:
                if ds.kernel.pallas_op is None:
                    continue
                for i in range(ds.marg_lo, ds.marg_hi):
                    out[i] = _quantize(
                        np.asarray(out[i], dtype=np.float32), precision
                    )
            margs = memo[precision] = tuple(out)
        return margs

    def _defer_ok(self, t: Table) -> bool:
        """May THIS batch's validation defer into the masked Pallas
        launch?  Only when the plan's single validator would reduce to
        the pure NaN/Inf row scan over the one data desc the kernel
        already reads (:func:`quarantine.finite_scan_only`)."""
        from flink_ml_tpu.serve import quarantine

        kind, col, dim = self.data_descs[0]
        if kind == "dense":
            return quarantine.finite_scan_only(t, dim, vector_col=col)
        return quarantine.finite_scan_only(t, dim,
                                           feature_cols=list(col))

    def _prep_batches(self, table: Table, mesh, row_multiple: int,
                      mode: _ServeMode):
        batch_size = self.batch_size
        if batch_size is None or table.num_rows() <= batch_size:
            batches = [table]
        else:
            batches = table.iter_batches(batch_size)
        offset = 0
        for batch in batches:
            n_in = batch.num_rows()
            t = batch
            for _stage, mapper, _k in self.host_stages:
                out = mapper._map_checked(t, validated=False)
                t = mapper._helper.get_result_table(t, out)
            deferred = (
                mode.defer and t.num_rows() > 0 and self._defer_ok(t)
            )
            if deferred:
                # in-kernel validation: the masked Pallas launch scans,
                # flags, and zeroes bad rows; the executor emits the
                # identical side-table after the dispatch
                good = None
            else:
                t, good = self._validate_entry(t, offset)
            n = t.num_rows()
            args = None
            if n:
                b = self._bucket(n, row_multiple)
                # host prep + H2D staging — on the prefetch producer
                # thread when batched, under the consumer's trace context
                # (prefetch_iter hands it off explicitly)
                with obs.trace.span("place_h2d",
                                    {"rows": n, "bucket": b}):
                    args = self._extract(t, b, mesh, row_multiple, mode)
            yield offset, n_in, n, good, t, args, deferred
            offset += n_in

    def _device_batch(self, mesh, n: int, args,
                      mode: Optional[_ServeMode] = None,
                      deferred: bool = False):
        """The single fused dispatch for one batch: (re)place -> one jitted
        call -> one bundled fetch -> per-stage host finalize.  On a
        multi-device mesh the call is the shard_map program — one SPMD
        dispatch whose per-device outputs come back in the same single
        bundled fetch (``fused.shard_map_dispatches`` proves the path).
        On the Pallas path that one call is exactly ONE kernel launch
        (``fused.pallas_dispatches`` counts them); its column-padded
        outputs trim back to the plan's widths here, and a deferred
        validation's per-row ok mask rides out under ``_ROW_OK_KEY``."""
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import fetch_flat

        pressure.maybe_oom(n)
        width = self._mesh_width(mesh)
        b = _padded_rows(args)
        pallas = mode is not None and mode.pallas
        variant = ("masked" if deferred else "raw") if pallas else None
        kinds = self.pallas_chain[0] if pallas else None
        d = self.pallas_chain[1] if pallas else 0
        margs = self._margs_for(mode)
        t0 = time.perf_counter()
        with obs.trace.span("fused_dispatch", {
            "rows": n, "plan": self.serve_name,
            "stages": len(self.device_stages), "mesh_devices": width,
        }):
            placed = [
                a if isinstance(a, jax.Array)
                or not isinstance(a, np.ndarray)
                else jnp.asarray(a)
                for a in args
            ]
            dtype = _PRECISION_DTYPE[mode.precision] if mode else "float32"
            t_disp = time.perf_counter()
            fn, warm_hit = self._dispatch_fn(
                mesh, variant, placed, margs, b, width, dtype, pallas
            )
            res = fn(*placed, *margs)
            if warm_hit:
                # executable came off the warm-artifact store: no compile
                # happened, so no ledger line (the warm process's
                # compile-ledger delta must stay empty)
                _mark_dispatch_warm(self.serve_name, b, width,
                                    dtype=dtype, pallas=pallas)
            else:
                # a first-seen (plan, bucket, mesh, dtype) shape pays its
                # XLA (or Mosaic, on the pallas: key) compile inside THAT
                # call — ledger it (phase: compile)
                _note_first_dispatch(
                    self.serve_name, b, width,
                    time.perf_counter() - t_disp,
                    dtype=dtype, pallas=pallas,
                )
            # the bundled fetch is the one sync point: its span IS the
            # device-execution window of the fused program
            with obs.trace.span("device_sync"):
                fetched = fetch_flat(*res)
        if width > 1:
            obs.counter_add("fused.shard_map_dispatches")
            _note_device_rows(n, b, width)
        if b > n:
            obs.counter_add("fused.padded_rows", b - n)
        if pallas:
            from flink_ml_tpu.ops.pallas_kernels import launch_interpreted

            obs.counter_add("fused.pallas_dispatches")
            if launch_interpreted():
                # the CPU parity harness; a chip run asserts this is zero
                obs.counter_add("fused.pallas_interpreted")
        out: Dict[str, Sequence] = {}
        i = 0
        if variant == "masked":
            out[_ROW_OK_KEY] = (
                np.asarray(fetched[0][:n]).reshape(-1) > 0
            )
            i = 1
        for si, ds in enumerate(self.device_stages):
            if not ds.fetch:
                continue
            vals = {}
            for key in ds.out_keys:
                v = fetched[i][:n]
                if pallas:
                    # trim the kernel's 128-lane column pad back to the
                    # plan's widths: affine stages to d, the score to 1-D
                    v = (np.asarray(v)[:, 0] if kinds[si] == "glm_score"
                         else np.asarray(v)[:, :d])
                vals[key] = v
                i += 1
            cols = ds.kernel.finalize(vals, n)
            for c, v in cols.items():
                # finalize hands back every declared output col; keep only
                # the ones the exit schema attributes to THIS stage (a col
                # overwritten in place by a later fused stage is dropped)
                if self.exit_schema.contains(c):
                    canon = self.exit_schema.resolve(c)
                    if self.exit_src.get(canon) == ds.index:
                        out[canon] = v
        obs.counter_add("pipeline.fused_dispatches")
        obs.counter_add("pipeline.fused_rows", n)
        dt_ms = (time.perf_counter() - t0) * 1e3
        obs.observe("pipeline.fused_call_ms", dt_ms)
        obs.observe(f"pipeline.fused_call_ms.{self.serve_name}", dt_ms)
        return out

    def _bisected_batch(self, mesh, t: Table, n: int, args,
                        row_multiple: int,
                        mode: Optional[_ServeMode] = None,
                        deferred: bool = False):
        """Pressure-aware fused dispatch for one batch (ISSUE 9).

        The unsplit fast path IS :meth:`_device_batch` on the
        pre-extracted args — zero extra work when no pressure.  On an
        allocator OOM, :func:`~flink_ml_tpu.fault.pressure.run_bisected`
        frees unpinned slabs, then halves the batch's row range:
        sub-ranges re-extract their features (padded to their own ladder
        bucket) and dispatch independently, and the fetched output
        columns concatenate host-side.  Exact parity: every fused kernel
        is row-independent (scores, assignments, scaling — pad rows never
        feed real rows), so the concatenation is bit-identical to the
        unsplit dispatch.  Validation already ran at plan entry on the
        FULL batch, so quarantine side-tables and their original-feed row
        offsets are untouched by the split."""

        def fn(lo, hi):
            if lo == 0 and hi == n:
                use = args
                if _args_deleted(args):
                    # a previous donated dispatch consumed the buffers
                    # (an OOM'd attempt whose donation already landed):
                    # re-extract rather than dispatch deleted arrays
                    b = self._bucket(n, row_multiple)
                    use = self._extract(t, b, mesh, row_multiple, mode)
                return self._device_batch(mesh, n, use, mode, deferred)
            sub = t.slice_rows(lo, hi)
            b = self._bucket(hi - lo, row_multiple)
            sub_args = self._extract(sub, b, mesh, row_multiple, mode)
            return self._device_batch(mesh, hi - lo, sub_args, mode,
                                      deferred)

        return pressure.run_bisected(
            fn, n, surface=self.serve_name, floor=max(1, row_multiple),
            n_dev=row_multiple,
        )

    def _staged_batch(self, t: Table, offset: int,
                      mode: Optional[_ServeMode] = None,
                      deferred: bool = False):
        """The per-stage fallback for one batch (breaker open / device
        failure): each device stage's own ``_apply_batch`` — which routes
        through its own ``serve.dispatch`` and CPU fallback — serves the
        batch exactly as the unfused pipeline would.  Entry validation
        already ran, so per-stage re-validation is skipped (same rows in,
        same rows out: the sink's row accounting stays aligned).  When
        validation was DEFERRED into the (now failed) Pallas launch, the
        host verdict runs here first and rides out under ``_ROW_OK_KEY``
        — same survivors, same side-table, exactly as the kernel would
        have flagged them."""
        if mode is not None and mode.pallas:
            obs.counter_add("fused.pallas_fallbacks")
        row_ok = None
        if deferred:
            verdict = self.validators[0].validate_batch(t)
            row_ok = (np.ones(t.num_rows(), dtype=bool)
                      if verdict is None
                      else np.asarray(verdict[0], dtype=bool))
            t = t.filter_rows(row_ok)
        obs.flight.record("plan.fallback", plan=self.serve_name,
                          rows=t.num_rows())
        with obs.trace.span("plan_fallback", {"plan": self.serve_name}):
            for ds in self.device_stages:
                t = ds.mapper._apply_batch(t, row_offset=offset,
                                           validate=False)
        obs.counter_add("pipeline.plan_fallback_batches")
        out = {name: t.col(name) for name in self.device_cols}
        if row_ok is not None:
            out[_ROW_OK_KEY] = row_ok
        return out

    def execute(self, table: Table) -> Table:
        from flink_ml_tpu import serve
        from flink_ml_tpu.parallel.mesh import inference_mesh, \
            mesh_spans_processes
        from flink_ml_tpu.serve import quarantine
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory
        from flink_ml_tpu.utils.prefetch import prefetch_iter

        obs.counter_add("inference.rows", table.num_rows())
        mesh = inference_mesh(MLEnvironmentFactory.get_default().get_mesh())
        row_multiple = self._mesh_width(mesh)
        obs.gauge_set("fused.mesh_devices", row_multiple)
        # a mesh spanning processes (never the default inference_mesh)
        # must agree its breaker verdict open-wins across the mesh, or a
        # collective-bearing program would split device-vs-fallback
        agreed = mesh_spans_processes(mesh)
        # dispatch mode, pinned for the whole run: placement precision
        # (int8 keeps host validation — NaN is unrepresentable after
        # quantization — and keeps the XLA program), the Pallas chain
        # when this plan lowers, and scan deferral when the single
        # validator reduces to the kernel's own finite scan (a
        # process-spanning mesh agrees verdicts on the HOST mask, so it
        # never defers)
        precision = serve_precision()
        pallas = (self.pallas_chain is not None and serve_pallas_enabled()
                  and precision != "int8")
        mode = _ServeMode(
            precision,
            pallas,
            pallas and len(self.validators) == 1 and not agreed
            and quarantine.enabled(),
        )
        obs.gauge_set("serve.precision", _PRECISION_BITS[precision])
        if serve_pallas_enabled() and not pallas:
            # the operator asked for Pallas and this plan can't lower
            # (CSR/multi-input chain, undeclared stage, int8): one XLA
            # fallback per run keeps the PALLAS-DEGRADED check honest
            obs.counter_add("fused.pallas_fallbacks")
        field_order = self.exit_schema.field_names
        out_names = sorted(
            self.device_cols | set(self.batch_cols), key=field_order.index
        )
        out_types = [self.exit_schema.type_of(n) for n in out_names]
        sink = ColumnSink(out_names, out_types, table.num_rows())
        kept_parts: List[Tuple[int, int, Optional[np.ndarray]]] = []
        filtered = False

        gen = self._prep_batches(table, mesh, row_multiple, mode)
        many = (
            self.batch_size is not None
            and table.num_rows() > self.batch_size
        )
        if many:
            # double-buffer: batch i+1's host prep + H2D staging runs on
            # the producer thread under batch i's compute (the shared
            # prefetch idiom, utils/prefetch.py)
            gen = prefetch_iter(gen, depth=2, name="fused-prefetch")
        for offset, n_in, n, good, t, args, deferred in gen:
            if n == 0:
                out = {
                    name: np.zeros(0, dtype=DataTypes.numpy_dtype(typ))
                    for name, typ in zip(out_names, out_types)
                    if name in self.device_cols
                }
            else:
                if self.validators and not deferred:
                    # fused-plan-entry drift tap (ISSUE 11): the entry-
                    # validated survivors, observed on the CONSUMER
                    # thread (the prefetch producer has no tap scope);
                    # the scope's owner rule dedupes against the staged
                    # fallback's per-stage boundary
                    obs.drift.observe_input(self.validators[0], t)
                out = serve.dispatch(
                    self.serve_name,
                    device=lambda: self._bisected_batch(
                        mesh, t, n, args, row_multiple, mode, deferred
                    ),
                    fallback=lambda: self._staged_batch(
                        t, offset, mode, deferred
                    ),
                    agreed=agreed,
                )
            row_ok = out.pop(_ROW_OK_KEY, None)
            if row_ok is not None:
                # deferred validation's verdict (in-kernel mask, or the
                # fallback's host scan): emit the SAME side-table the
                # entry path would have — original-feed offsets, nan_inf
                # reasons (finite_scan_only guarantees no other code) —
                # then keep the survivors
                row_ok = np.asarray(row_ok, dtype=bool)
                reasons = np.full(n, None, dtype=object)
                reasons[~row_ok] = quarantine.REASON_NAN_INF
                quarantine.emit(self.validators[0].serve_name(), t,
                                row_ok, reasons, row_offset=offset)
                k = int(row_ok.sum())
                if k != n:
                    for name, v in list(out.items()):
                        # device-path cols are still full-batch; the
                        # staged fallback already served survivors only
                        if len(v) == n:
                            out[name] = np.asarray(v)[row_ok]
                    t = t.filter_rows(row_ok)
                    n = k
                good = row_ok
                obs.drift.observe_input(self.validators[0], t)
            for name in self.batch_cols:
                out[name] = t.col(name)
            sink.append(out, n)
            filtered = filtered or n != n_in
            kept_parts.append((offset, n_in, good))
        cols = sink.columns()
        passthrough = [
            name for name in self.exit_schema.field_names
            if self.exit_src[name] == "input"
        ]
        if passthrough:
            src = table.select(passthrough)
            if filtered:
                src = src.take_rows(_kept_indices(kept_parts))
            for name in passthrough:
                cols[name] = src.col(name)
        return Table.from_columns(self.exit_schema, cols)


def _quantize(X: np.ndarray, precision: str):
    """One dense placement at the serving precision.  ``bf16`` casts in
    place (H2D ships half the bytes; compute upcasts on device).
    ``int8`` symmetric-quantizes per buffer — ``scale = absmax/127``
    over the FINITE values, ``q = clip(rint(X/scale))`` — and returns
    ``(q, scale_column)``: the f32 scale broadcasts as a per-row column
    so both leaves row-shard under ``P('data')``.  Non-finite values
    quantize to 0 (int8 has no NaN; host validation is mandatory on
    this path, so they never reach a real dispatch)."""
    if precision == "bf16":
        import ml_dtypes

        return X.astype(ml_dtypes.bfloat16)
    if precision == "int8":
        flat = X.ravel()
        finite = flat[np.isfinite(flat)]
        amax = float(np.abs(finite).max()) if finite.size else 0.0
        scale = (amax / 127.0) or 1.0
        with np.errstate(invalid="ignore"):
            q = np.clip(np.rint(X / scale), -127, 127)
        q = np.where(np.isfinite(q), q, 0.0).astype(np.int8)
        rows = X.shape[0] if X.ndim > 1 else 1
        return q, np.full((rows, 1) if X.ndim > 1 else (),
                          scale, dtype=np.float32)
    return X


def _dev_f32(a):
    """Upcast one placed arg to the f32 compute type inside the traced
    program: int8 ``(q, scale)`` pairs dequantize, bf16 upcasts, f32
    (and CSR pytrees) pass through untouched."""
    import jax.numpy as jnp

    if isinstance(a, tuple):
        q, s = a
        return q.astype(jnp.float32) * s
    if getattr(a, "dtype", None) == jnp.bfloat16:
        return a.astype(jnp.float32)
    return a


def _padded_rows(args) -> int:
    """The padded row count a batch's extracted args carry (0 when the
    args hold no row-shaped value — never the case for a real plan)."""
    from flink_ml_tpu.ops.batch import CsrBatch, ShardedCsrBatch

    for a in args:
        if isinstance(a, ShardedCsrBatch):
            return a.n_shards * a.rows_per_shard
        if isinstance(a, CsrBatch):
            return a.n_rows
        if isinstance(a, tuple):  # int8 (q, scale): q carries the rows
            a = a[0]
        shape = getattr(a, "shape", None)
        if shape:
            return int(shape[0])
    return 0


def _args_deleted(args) -> bool:
    """Any leaf buffer already consumed by a donated dispatch?"""
    import jax

    return any(
        hasattr(x, "is_deleted") and x.is_deleted()
        for x in jax.tree_util.tree_leaves(list(args))
    )


def _try_place(a, mesh, row_multiple: int):
    """Best-effort async H2D on the producer thread; a transient placement
    failure hands the host array/pytree through so the consumer's retried
    dispatch (and, past that, the per-stage fallback) still gets its shot.
    An allocator OOM passes the host value through too: the placement
    retried at dispatch time raises INSIDE the bisection wrapper, where
    pressure recovery can split the batch (an OOM raised here would
    surface on the prefetch producer thread, outside any recovery scope).

    Ragged rows (ISSUE 15 satellite): a ``P('data')`` placement needs dim
    0 divisible by the data-axis size.  The bucket ladder hands every
    fused surface a divisible row count already, but a caller arriving
    with a ragged batch (a bisection sub-range below ``row_multiple``, a
    hand-built batch) is PADDED here with zero rows — weight-0/masked on
    every row-aligned fused kernel, sliced off with the bucket's own pad
    before finalize — instead of erroring out of the sharded path."""
    import jax

    from flink_ml_tpu.fault.pressure import is_oom
    from flink_ml_tpu.fault.retry import is_transient
    from flink_ml_tpu.ops.batch import ShardedCsrBatch

    sharded_csr = isinstance(a, ShardedCsrBatch)
    if not sharded_csr and not isinstance(a, (np.ndarray, tuple)):
        return a  # unsharded CsrBatch pytrees place at call time, as staged
    try:
        if row_multiple > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if isinstance(a, np.ndarray) and a.shape[0] % row_multiple:
                from flink_ml_tpu.lib.common import _pad_rows_to

                a = _pad_rows_to(
                    a, -(-a.shape[0] // row_multiple) * row_multiple
                )
            # device_put maps a single sharding over a pytree's leaves:
            # a ShardedCsrBatch's three flat arrays are (n_shards *
            # nnz_pad,), so P('data') lands each shard's slice on its
            # device
            return jax.device_put(a, NamedSharding(mesh, P("data")))
        return jax.device_put(a)
    except Exception as exc:  # noqa: BLE001 - transient-filtered
        if not is_transient(exc) and not is_oom(exc):
            raise
        return a


def _build_run(stages, start: int, schema: Schema,
               batch_size,
               min_stages: int = 2) -> Tuple[Optional[FusedRun], tuple]:
    """Assemble the maximal fused run starting at ``start``.

    Returns ``(run, cache_key)``; ``run`` is None when fewer than
    ``min_stages`` stages fuse or no device kernel joins (for the default
    transform path a one-stage "run" is exactly the staged path already;
    the multi-tenant mux passes ``min_stages=1`` because even a
    single-stage family still amortizes its dispatch across tenants).
    The key captures every mapper's identity (``mapper_uid`` — a reloaded
    model rebuilds its mapper and thereby the plan) plus the schema/batch
    signature, so callers can reuse a previously compiled run."""
    infos = _stage_infos(stages, start, schema)
    # host pre-kernels: only a PREFIX joins (a host lookup downstream of a
    # device kernel would force a mid-run fetch — the plan splits instead)
    n_host = 0
    while n_host < len(infos) and infos[n_host][2].host:
        n_host += 1
    host_stages = infos[:n_host]

    sch = schema
    avail: Dict[str, object] = {
        n.lower(): "input" for n in schema.field_names
    }
    for _stage, mapper, _k in host_stages:
        outs = {n.lower() for n in mapper._helper.output_col_names}
        sch = mapper.get_output_schema()
        avail = {
            n.lower(): ("batch" if n.lower() in outs else avail[n.lower()])
            for n in sch.field_names
        }
    post_host_schema = sch

    device_stages: List[_DeviceStage] = []
    data_descs: List[tuple] = []
    desc_index: Dict[tuple, int] = {}
    model_args: List = []
    validators: List = []
    has_csr = False

    def _arg(desc) -> int:
        i = desc_index.get(desc)
        if i is None:
            i = desc_index[desc] = len(data_descs)
            data_descs.append(desc)
        return i

    for j, (stage, mapper, kernel) in enumerate(infos[n_host:]):
        if kernel.host:
            break  # host kernel mid-run: the run ends here
        ds = _DeviceStage(j, mapper, kernel)
        ok = True
        for inp in kernel.inputs:
            if inp.vector_col is not None:
                try:
                    canon = sch.resolve(inp.vector_col)
                except (KeyError, ValueError):
                    ok = False
                    break
                src = avail.get(canon.lower())
                if isinstance(src, tuple) and src[0] == "env":
                    if src[1] != int(inp.dim):
                        ok = False  # width mismatch: staged padding rules
                        break       # don't hold on-device — split instead
                    ds.input_refs.append(("env", canon.lower()))
                elif src in ("input", "batch"):
                    if sch.type_of(canon) == DataTypes.SPARSE_VECTOR:
                        if kernel.csr_fn is None:
                            ok = False
                            break
                        ds.input_refs.append(
                            ("arg", _arg(("csr", canon, int(inp.dim))))
                        )
                        ds.call_fn = kernel.csr_fn
                        has_csr = True
                    else:
                        ds.input_refs.append(
                            ("arg", _arg(("dense", canon, int(inp.dim))))
                        )
                    ds.validates = True
                else:
                    ok = False  # opaque device output (a prediction col)
                    break
            else:
                canon_cols = []
                for c in inp.feature_cols or ():
                    try:
                        cc = sch.resolve(c)
                    except (KeyError, ValueError):
                        ok = False
                        break
                    if avail.get(cc.lower()) not in ("input", "batch"):
                        ok = False
                        break
                    canon_cols.append(cc)
                if not ok:
                    break
                ds.input_refs.append(
                    ("arg", _arg(("matrix", tuple(canon_cols),
                                  int(inp.dim))))
                )
                ds.validates = True
        if not ok:
            break
        ds.marg_lo = len(model_args)
        model_args.extend(kernel.model_args)
        ds.marg_hi = len(model_args)
        device_stages.append(ds)
        if ds.validates:
            validators.append(mapper)
        outs = {n.lower() for n in mapper._helper.output_col_names}
        env_cols = {
            col.lower(): int(width)
            for _key, (col, width) in kernel.env_outputs.items()
        }
        sch = mapper.get_output_schema()
        new_avail: Dict[str, object] = {}
        for n in sch.field_names:
            low = n.lower()
            if low in outs:
                new_avail[low] = (
                    ("env", env_cols[low], j) if low in env_cols
                    else ("dev", j)
                )
            else:
                new_avail[low] = avail[low]
        avail = new_avail

    if not device_stages or len(host_stages) + len(device_stages) < min_stages:
        return None, ()

    exit_schema = sch
    exit_src: Dict[str, object] = {}
    for n in exit_schema.field_names:
        src = avail[n.lower()]
        # ('env', width, j) and ('dev', j) both resolve to producing stage j
        exit_src[n] = src[-1] if isinstance(src, tuple) else src
    for ds in device_stages:
        ds.fetch = any(
            isinstance(s, int) and s == ds.index for s in exit_src.values()
        )

    names = [m.serve_name() for _s, m, _k in host_stages]
    names += [ds.mapper.serve_name() for ds in device_stages]
    serve_name = "FusedPlan[" + "+".join(names) + "]"
    key = (
        start,
        tuple(m.mapper_uid
              for _s, m, _k in host_stages) + tuple(
            ds.mapper.mapper_uid for ds in device_stages),
        tuple(schema.field_names), tuple(schema.field_types),
        batch_size,
    )
    run = FusedRun(
        host_stages, device_stages, data_descs, model_args, validators,
        exit_schema, exit_src, schema, post_host_schema, batch_size,
        has_csr, serve_name,
    )
    return run, key


_RUN_CACHE_CAPACITY = 8


def _run_for(model, stages, start: int, schema: Schema, batch_size):
    """The (cached) fused run starting at ``start``, or None.

    Assembly is cheap dict-walking and re-runs every transform; the
    expensive compiled state (the per-mesh jitted fused program) lives on
    the cached FusedRun, keyed by the mapper identities — a reloaded model
    builds a fresh mapper, which keys a fresh plan."""
    run, key = _build_run(stages, start, schema, batch_size)
    if run is None:
        return None
    cache = model.__dict__.setdefault("_fused_run_cache", OrderedDict())
    cached = cache.get(key)
    if cached is not None:
        cache.move_to_end(key)
        return cached
    cache[key] = run
    while len(cache) > _RUN_CACHE_CAPACITY:
        cache.popitem(last=False)
    return run


def transform_fused(model, inputs: Tuple[Table, ...]) -> Tuple[Table, ...]:
    """``PipelineModel.transform`` with fused-run grouping: maximal runs of
    kernel-capable stages execute as one dispatch per batch; everything
    else (kernel-less mappers, AlgoOperators, multi-table hops) serves
    through the stage-at-a-time path in place."""
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    stages = model.stages
    batch_size = MLEnvironmentFactory.get_default().default_batch_size
    last = inputs
    n_fused = 0
    i = 0
    while i < len(stages):
        run = None
        if len(last) == 1 and last[0].num_rows() > 0:
            run = _run_for(model, stages, i, last[0].schema, batch_size)
        if run is not None:
            last = (run.execute(last[0]),)
            n_fused += run.n_stages
            i += run.n_stages
        else:
            last = stages[i].transform(*last)
            i += 1
    if stages:
        obs.gauge_set("pipeline.fusion_ratio", n_fused / len(stages))
    return last
