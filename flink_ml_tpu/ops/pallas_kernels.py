"""Pallas TPU kernels for the hot ops XLA fusion leaves on the table.

Kernel inventory.  What is recorded here is only what has been SEEN on the
chip, and when; a statement about speed is a line of ``PERF_LEDGER.jsonl``:

  ==================  ==========================  =========================
  kernel              hot path                    seen on the chip
  ==================  ==========================  =========================
  :func:`glm_grad`    the dense fused fit's       2026-10-01, TPU v5 lite,
                      minibatch step: scores,     jax 0.9.0: compiles with
                      loss, error and the         Mosaic at slabs (13,
                      gradient's accumulate in    32768, 2002), (62, 32768,
                      ONE pass over the           786) and (13, 32768, 39),
                      minibatch, read from the    row tiles 512 / 1024 /
                      resident slab in place      2048; a fit's coefficients
                      (rows on lanes).  Chosen    within 6e-8 of the XLA
                      by what the program         step's and of the plain
                      observes, no knob           reference's, a repeated
                      (lib/common.py:             fit the same bytes; reads
                      _onepass_rows)              a minibatch at 739-748
                                                  GB/s (PERF.md 5); under
                                                  strict check_vma on 1 and
                                                  4 chips (chip_smoke.py)
  :func:`serve_chain` fused serving hot path      2026-09-26, TPU v5 lite,
                      (quarantine NaN/Inf scan    jax 0.9.0 / libtpu 0.0.34:
                      + affine scalers + GLM      compiles with Mosaic at
                      score in one launch)        4096 x 512 and x 28, raw
                                                  and masked, f32 and bf16
                                                  placement, buckets 1 and
                                                  8 included; scores match
                                                  fused XLA to 1e-5.  Opt-in
                                                  via FMT_SERVE_PALLAS.
                                                  Speed against fused XLA:
                                                  not measured on the chip
                                                  (interpret mode on CPU is
                                                  ~5x slower, which says
                                                  nothing about Mosaic)
  :func:`hot_scores`  the plain sparse fit's      2026-10-02, TPU v5 lite,
  :func:`hot_grad`    step where the pack split   jax 0.9.0: compile with
                      the table by frequency      Mosaic at planes (39,
                      (lib/common.py:             32768), K 16384 and 4096;
                      make_hot_ell_grad_step):    0.72 + 0.74 ms a step of
                      the weight of a hot         1.28 M slots where XLA's
                      feature's code found by a   take and scatter-add take
                      one-hot product and a       9.2 + 9.6 ms; a fit's
                      lane mask, not by its       coefficients within 1e-6
                      address; the gradient the   of the unsplit step's, a
                      transposed product.         repeated fit the same
                      float32 in three bfloat16   bytes (PERF.md 5, 6)
                      pieces; no knob
                      (lib/common.py:
                      _hot_split_wins)
  :func:`lloyd_sums`  the centroid fit's pass     2026-10-03, TPU v5 lite,
                      over its rows               jax 0.9.0: compiles with
                      (lib/clustering.py:         Mosaic at 2,025,000 x 896,
                      _lloyd_pass): distance      k 100, row tiles 512 /
                      product (float32 in six     1024 / 2048 in 1.5 / 2.0 /
                      bfloat16 passes), argmin,   3.7 s; 23.4 / 22.6 / 22.3
                      cost, and the per-cluster   ms a Lloyd iteration where
                      sums from the tile's three  the two XLA operations it
                      bfloat16 pieces in ONE      replaces take 28.4 (two
                      read of a row tile, nine    reads, twelve passes): 352
                      MXU passes a row; the       us a 32768 rows against
                      (k, tile) distances never   the nine passes' 343 at
                      leave VMEM.  7.3 MB a       the MXU's peak; an
                      2048-row tile, read where   iteration's centroids
                      the table lies.  No knob    within 4.4e-5 of the plain
                      (lib/clustering.py:         reference's, a repeated
                      _lloyd_kernel_rows)         fit the same bytes; strict
                                                  check_vma on 1 and 4 chips
                                                  (PERF.md 5, 6)
  (sparse grad)       segment-CSR minibatch grad  REJECTED — every path
                                                  that ADDRESSES a slot
                                                  lost to XLA's scatter
                                                  lowering; note below.
                                                  The kernels above
                                                  address nothing.
  ==================  ==========================  =========================

:func:`glm_grad` supersedes the row-tiled, features-on-lanes kernel of the
same name (and its ``make_pallas_grad_fn`` drop-in) that PRs 1–24 carried
and no estimator selected: that one took a minibatch XLA had already sliced
out of the slab and padded it into a fresh array, an HBM pass of its own.
This one takes the slab as the chip lays it (rows minor: a minibatch's rows
on the lanes, its features, label and weight on the sublanes), so a row tile
arrives in VMEM by one strided DMA, both products are float32 multiplies on
the VPU (a matrix-vector product at precision ``highest`` would bind the MXU
before HBM), and the accumulators stay in VMEM across the sequential grid.
It reads a minibatch's row tiles only up to its last row of nonzero weight
(:func:`glm_grad_schedule`, counted once a fit from the slab's weight row):
on epsilon's shape 50 of the last minibatch's 64 tiles are the pack's
padding, 6.0% of a fit's tiles (seen on a TPU v5 lite, 2026-10-17: the
kernel's time a fit 45.9 → 43.1 ms).
:func:`lloyd_sums` lays the other way, CENTROIDS on the sublanes and a tile's
rows on the lanes: every product streams 128 to 384 centroid rows past a
latched (128, 128) piece of the table (as :func:`hot_grad` does), so the
distances come out (k, tile), argmin and cost are sublane reductions on the
VPU, and the membership is the left operand of the sums' product as it
stands; its three accumulators stay in VMEM across the sequential grid.
:func:`serve_chain` is embarrassingly parallel over row tiles (no cross-tile
accumulators): each tile is scanned for NaN/Inf, scaled through the affine
stages, and scored without leaving VMEM — the three serving HBM passes
collapse into one.

Which lowering runs is decided by the device platform, three ways
(:func:`launch_interpreted`): ``tpu`` compiles with Mosaic or raises;
``cpu`` runs ``interpret=True`` (the tier-1 parity harness — same kernel
body, numerically identical); any other platform raises.  Nothing degrades
to interpret mode silently: what is built on the interpreter says so
(``pallas_interpret``), and the drivers count every interpreted fit and
dispatch (``train.pallas_interpreted`` / ``fused.pallas_interpreted``) so a
chip run can assert zero.

Sparse-grad kernel (measured before PR 1, record removed — XLA retained)
------------------------------------------------------------------------
The sparse GLM minibatch (lib/common.py ``make_sparse_glm_train_fn``:
gather ``w[idx]`` → segment_sum over rows → gather ``err[rid]`` →
segment_sum over the 1M-dim feature axis) was micro-benchmarked on v5e at
the bench shape (mb=8192, nnz=320k, dim=1M); all numbers per op, readback-
synced and dedup-proofed:

  =============================  =========  ====================
  op                             time/op    rate
  =============================  =========  ====================
  XLA gather 320k from 1M        3.2 ms     ~100 M entries/s
  XLA segment_sum -> 8192        2.9 ms     ~110 M entries/s
  XLA segment_sum -> 1M          3.2 ms     ~100 M entries/s
  XLA dense 1M-dim SGD update    1.1 ms     (7.5 GB/s effective)
  =============================  =========  ====================

Three Pallas replacements were built and measured:
  1. scalar-loop scatter into VMEM — rejected by Mosaic
     ("Cannot store scalars to VMEM");
  2. scalar-loop with SMEM accumulator + scalar VMEM loads — rejected
     ("index in dimension 1 must be a multiple of 128": dynamic VMEM
     access must be tile-aligned);
  3. SMEM-blocked entry streaming + lane-masked (iota-select) vector
     loads from a (dim/128, 128) weight tile — compiles, but runs at
     **8 M entries/s, ~7x slower than XLA** (each random access costs a
     full 128-lane read-mask-reduce on the VPU).

Conclusion: on v5e (no SparseCore) every programmable path — XLA scatter,
Mosaic scalar loop, lane-masked vector RMW — is bound by the same ~10
cycles/random-access wall, and XLA's lowering is already at it.  The
segment-CSR XLA formulation therefore remained the default and no Pallas
kernel that addresses a slot ships.  Re-measured with the chip benchmark
(PERF.md 5, PR 27 to 30): a slot still costs 7.1 ns to gather and 6.6 ns to
scatter whatever the table's size, 10^6 or 4096 entries.  What PR 30 ships
leaves the address out: :func:`hot_scores` and :func:`hot_grad` find the
hot features' weights by comparison, on the MXU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def launch_interpreted() -> bool:
    """Does a kernel launched now run interpreted?  False on ``tpu`` (the
    launch compiles with Mosaic — a failure to compile raises, it never
    falls back), True on ``cpu`` (the parity harness), and an error on any
    other platform: a platform string this module does not know must not
    quietly select the interpreter."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels support platforms 'tpu' (Mosaic) and 'cpu' "
        f"(interpret mode); found {platform!r}"
    )


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


#: what one launch may take of v5e's 16 MiB default scoped-VMEM limit
#: (the rest is headroom for Mosaic's own temporaries)
_VMEM_BUDGET_BYTES = 12 << 20

_LANES = 128
_SUBLANES = 8
#: feature groups (eight sublanes each) a trip of the kernel's two loops
#: takes: a constant of the kernel, whatever the shape
_GROUPS_PER_TRIP = 4
#: a block index, as an int32 spelled out: with x64 on a bare 0 is an int64,
#: which Mosaic refuses beside the grid's i32
_I32_ZERO = np.int32(0)
_I32_ONE = np.int32(1)
#: beyond this a longer row tile buys nothing (seen on the chip: 512 to 2048
#: rows read within 1% of each other) and its first copy, which no compute
#: hides, grows
_MAX_TILE_ROWS = 2048


def _glm_grad_kernel(kind: str, d: int, tile_rows: int, step_ref, slab_ref,
                     w_ref, b_ref, gw_ref, stats_ref):
    """One row tile of one minibatch, ROWS ON LANES: scores, loss, error
    and the gradient's accumulate from the one tile in VMEM, for the first
    ``step_ref[1]`` tiles of the minibatch.  The tiles after those hold
    rows of weight 0 only: their block index stays on the last tile read,
    so the pipeline copies nothing for them, and the body adds nothing.

    Refs:
      step_ref  (2,) SMEM    the minibatch's index in the slab (it picked
                             the block), then the row tiles up to its last
                             row of nonzero weight, at least 1
                             (:func:`glm_grad_schedule`)
      slab_ref  (d+2, TM)    features, then label, then weight, on
                             sublanes; TM rows on lanes
      w_ref     (D8, 128)    weights, each repeated along the lanes
      b_ref     (1, 1) SMEM  intercept
      gw_ref    (D8, 128)    the gradient, still spread over the lanes
                             (same block every step: the accumulator)
      stats_ref (8, 128)     rows 0..2: error, loss and weight sums,
                             spread over the lanes likewise

    Every product is a float32 multiply on the VPU and every sum a float32
    add in one fixed order, so a repeated call returns the same bytes.  The
    two loops run over feature groups of eight sublanes; their trip count is
    a number in the program, not a length of it.  A skipped tile's rows
    would have added exact zeros (an error, loss and weight of 0, and
    ``x * 0`` to the gradient), so the sums are those of a read of every
    tile, up to the sign of an exact zero.
    """
    @pl.when(pl.program_id(0) == 0)
    def _():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    pl.when(pl.program_id(0) < step_ref[1])(functools.partial(
        _glm_grad_tile_sums, kind, d, tile_rows, slab_ref, w_ref, b_ref,
        gw_ref, stats_ref))


def _glm_grad_tile_sums(kind: str, d: int, tile_rows: int, slab_ref, w_ref,
                        b_ref, gw_ref, stats_ref):
    """The body of :func:`_glm_grad_kernel` for a tile it reads."""
    chunks = tile_rows // _LANES
    trip_rows = _SUBLANES * _GROUPS_PER_TRIP
    trips, left = divmod(d, trip_rows)

    def along_lanes(a):  # (r, 128) -> (r, TM), the same lanes again
        return jnp.concatenate([a] * chunks, axis=1) if chunks > 1 else a

    def fold_lanes(a):  # (r, TM) -> (r, 128), lane chunk on lane chunk
        out = a[:, :_LANES]
        for c in range(1, chunks):
            out = out + a[:, c * _LANES:(c + 1) * _LANES]
        return out

    # the rows the loops leave: whole groups, then a part of one
    rest = [(trips * trip_rows + g * _SUBLANES, _SUBLANES)
            for g in range(left // _SUBLANES)]
    if left % _SUBLANES:
        rest.append((d - left % _SUBLANES, left % _SUBLANES))

    def products(lo, rows):
        return slab_ref[pl.ds(lo, rows), :] * along_lanes(
            w_ref[pl.ds(lo, rows), :])

    # a loop carries its own first row as an int32 and steps it: with x64
    # on, the trip counter fori_loop hands out is an int64, which Mosaic
    # neither multiplies with its own i32 counter nor converts
    first_row, step_rows = _I32_ZERO, np.int32(trip_rows)

    def trip_groups(lo):
        lo = pl.multiple_of(lo, trip_rows)
        return [pl.multiple_of(lo + np.int32(g * _SUBLANES), _SUBLANES)
                for g in range(_GROUPS_PER_TRIP)]

    def score_trip(_, carry):
        lo, acc = carry
        for row in trip_groups(lo):
            acc = acc + products(row, _SUBLANES)
        return lo + step_rows, acc

    acc = jnp.zeros((_SUBLANES, tile_rows), jnp.float32)
    if trips:
        _, acc = jax.lax.fori_loop(0, trips, score_trip, (first_row, acc))
    for lo, rows in rest:
        if rows == _SUBLANES:
            acc = acc + products(lo, rows)
    logits = jnp.sum(acc, axis=0, keepdims=True) + b_ref[0, 0]
    for lo, rows in rest:
        if rows < _SUBLANES:
            logits = logits + jnp.sum(products(lo, rows), axis=0,
                                      keepdims=True)

    y = slab_ref[d:d + 1, :]
    sw = slab_ref[d + 1:d + 2, :]
    if kind == "logistic":
        err = (jax.nn.sigmoid(logits) - y) * sw
        loss = sw * (jnp.logaddexp(0.0, logits) - y * logits)
    else:
        err = (logits - y) * sw
        loss = 0.5 * err * (logits - y)
    err_rows = jnp.broadcast_to(err, (_SUBLANES, tile_rows))

    def grad_of(lo, rows):
        gw_ref[pl.ds(lo, rows), :] += fold_lanes(
            slab_ref[pl.ds(lo, rows), :] * err_rows[:rows, :])

    def grad_trip(_, lo):
        for row in trip_groups(lo):
            grad_of(row, _SUBLANES)
        return lo + step_rows

    if trips:
        jax.lax.fori_loop(0, trips, grad_trip, first_row)
    for lo, rows in rest:
        grad_of(lo, rows)
    stats_ref[0:1, :] += fold_lanes(err)
    stats_ref[1:2, :] += fold_lanes(loss)
    stats_ref[2:3, :] += fold_lanes(sw)


def _vma_of(*operands):
    """``(vma, promote)``: under ``shard_map(check_vma=True)`` a kernel's
    outputs must declare how they vary across mesh axes, and they vary
    wherever any input does; ``promote`` gives an operand that same vma so
    the kernel sees matching axes."""
    vma = frozenset()
    for operand in operands:
        vma = vma | jax.typeof(operand).vma

    def promote(a):
        need = vma - jax.typeof(a).vma
        return jax.lax.pcast(a, tuple(need), to="varying") if need else a

    return vma, promote


def glm_grad_tile(rows: int, d: int) -> int:
    """The kernel's row tile for minibatches of ``rows`` rows and ``d``
    features, by arithmetic on the shape alone (nothing is compiled or timed
    to find it): the longest run of whole 128-lane chunks that divides
    ``rows``, fits :data:`_VMEM_BUDGET_BYTES` and is no longer than
    :data:`_MAX_TILE_ROWS`.  0 where no tile does: rows that do not fill
    whole lane chunks, or a width whose shortest tile is over the budget;
    such a fit keeps the XLA step."""
    if rows <= 0 or d <= 0 or rows % _LANES:
        return 0
    d8 = _round_up(d, _SUBLANES)
    # the weights and the gradient accumulator, (D8, 128) each, two buffers
    fixed = 2 * 2 * d8 * _LANES * 4
    # per row: its column of the slab block in two buffers, and the
    # kernel's own row vectors (scores, error, loss: eight sublanes each)
    per_row = (2 * _round_up(d + 2, _SUBLANES) + 8 * _SUBLANES) * 4
    fit = (_VMEM_BUDGET_BYTES - fixed) // per_row
    for tile in range(min(rows, _MAX_TILE_ROWS, fit) // _LANES * _LANES, 0,
                      -_LANES):
        if rows % tile == 0:
            return tile
    return 0


def glm_grad_schedule(slab, tile_rows: int):
    """For every minibatch of ``slab`` (steps, rows, d+2) the pair that
    :func:`glm_grad` takes as its ``step``: the minibatch's index, and the
    row tiles of ``tile_rows`` it reads, those up to the minibatch's last
    row of nonzero weight and at least one (a minibatch of padding alone
    reads one tile of zeros).  int32 (steps, 2), counted from the slab's own
    weight row: the pack pads the last minibatch with rows of weight 0, and
    the count needs no row count of the table, so the program that holds it
    is one for every table of a slab shape.  A scan over its rows hands the
    kernel its one scalar operand a step as it is, with no operation
    between (an index and a count handed over apart cost 1.5 us a step
    more on a TPU v5e)."""
    steps, rows = slab.shape[:2]
    weighted = slab[:, :, -1] != 0
    ends = jnp.max(jnp.where(weighted, jnp.arange(1, rows + 1,
                                                  dtype=jnp.int32), 0),
                   axis=1)
    tiles = jnp.maximum((ends + (tile_rows - 1)) // tile_rows, 1)
    return jnp.stack([jnp.arange(steps, dtype=jnp.int32),
                      tiles.astype(jnp.int32)], axis=1)


@functools.partial(
    jax.jit, static_argnames=("kind", "tile_rows", "interpret")
)
def glm_grad(slab, step, wts, b, kind: str = "logistic",
             tile_rows: int = 0, interpret: bool = False):
    """The dense GLM minibatch gradient in ONE pass over HBM, read from the
    resident slab where it lies.

    Args: ``slab`` (steps, rows, d+2) float32, the dense combined layout
    (features, label, sample weight a row); ``step`` the minibatch's
    index, which reads every row tile, or a row of
    :func:`glm_grad_schedule`, its index and the tiles to read; ``wts``
    (d,), ``b`` scalar.  Returns ``(g_w (d,), g_b, loss_sum, w_sum)``, the
    sums over the minibatch that the jnp grad fns of lib/regression.py /
    lib/classification.py return.

    The pack still lays the last minibatch's padding, rows of weight 0; the
    kernel reads none of the tiles after the last weighted row, from HBM or
    VMEM.  Those sums are the same bytes as a read of every tile's, up to
    the sign of an exact zero.

    The kernel wants the rows on the lanes.  On the chip a slab of the
    benchmark's shapes lies so already (device layout ``{1,2,0}``: rows
    minor, features next, steps major), and the ``swapaxes`` below is a
    bitcast: no copy of the slab or of a minibatch is made.  Whoever
    selects the kernel checks that layout first
    (``lib/common.py:_onepass_rows``); on any other the same line is a
    transposing copy of the whole slab, correct and slow.
    """
    _, rows, width = slab.shape
    d = width - 2
    tile_rows = tile_rows or glm_grad_tile(rows, d)
    if not tile_rows or rows % tile_rows or tile_rows % _LANES:
        raise ValueError(
            f"glm_grad: no row tile for minibatches of {rows} rows x {d} "
            f"features (tile {tile_rows}; whole {_LANES}-lane chunks within "
            f"{_VMEM_BUDGET_BYTES} bytes of VMEM)"
        )
    d8 = _round_up(d, _SUBLANES)
    n_tiles = rows // tile_rows
    step = jnp.asarray(step).astype(jnp.int32)
    if step.ndim == 0:
        step = jnp.stack([step, jnp.int32(n_tiles)])
    rows_on_lanes = jnp.swapaxes(slab, 1, 2)
    w_lanes = jnp.broadcast_to(
        jnp.pad(wts.astype(jnp.float32), (0, d8 - d))[:, None], (d8, _LANES))
    operands = [
        step, rows_on_lanes, w_lanes,
        jnp.reshape(b, (1, 1)).astype(jnp.float32),
    ]

    vma, _promote = _vma_of(*operands)

    def same_block(i, step):  # weights, intercept, both accumulators
        return _I32_ZERO, _I32_ZERO

    def row_tile(i, step):  # the last tile read, once past it
        return step[0], _I32_ZERO, jnp.minimum(i, step[1] - _I32_ONE)

    gw, stats = pl.pallas_call(
        functools.partial(_glm_grad_kernel, kind, d, tile_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                # minibatch `step`, all d+2 sublane rows, row tile i
                pl.BlockSpec((None, width, tile_rows), row_tile),
                pl.BlockSpec((d8, _LANES), same_block),
                pl.BlockSpec((1, 1), same_block, memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((d8, _LANES), same_block),
                pl.BlockSpec((_SUBLANES, _LANES), same_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((d8, _LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.float32, vma=vma),
        ],
        # the grid axis carries the accumulators: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="glm_grad",
    )(*(_promote(a) for a in operands))
    sums = jnp.sum(stats[:3], axis=1)
    return jnp.sum(gw[:d], axis=1), sums[0], sums[1], sums[2]


# -- the sparse step's hot lookup --------------------------------------------

#: slots of a plane a grid step of the two kernels takes, at most, and the
#: planes a trip of their loop takes.  Seen on the chip at planes (39,
#: 32768), K 16384, forward + backward ms a step; seconds to the first
#: call's return (PERF.md 5; my chip run, PR 30): 512 slots a plane a trip
#: 0.99 + 1.09; 1.1 s.  1024 slots, 3 planes: 0.72 + 0.74; 1.3 s.  2048
#: slots, 3 planes: 0.69 + 0.71; 2.0 s.  1024 slots, all 39 planes
#: unrolled: 0.67 + 0.68 and no lower anywhere; 10.2 s
_HOT_TILE = 1024
_HOT_UNROLL = 3
#: the float32 bits a bfloat16 keeps: sign, exponent, seven of mantissa
_TOP_HALF = np.int32(-65536)


def _f32_pieces(x):
    """``x`` (float32) as three float32 arrays, each a bfloat16 value,
    that sum to it exactly: 24 bits of mantissa cut 8 + 8 + 8 by masking
    (a convert and its way back are a pair a compiler may drop as excess
    precision).  A one-hot times each piece, summed in float32, is the
    float32 itself: the MXU picks a float32 in three bfloat16 passes."""
    pieces = []
    for _ in range(3):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32) & _TOP_HALF,
            jnp.float32)
        pieces.append(top)
        x = x - top
    return pieces


#: half of the last place a bfloat16 keeps, in a float32's bits
_HALF_PLACE = np.int32(0x8000)


def _f32_nearest_pieces(x):
    """:func:`_f32_pieces` with the first two pieces ROUNDED to the nearest
    bfloat16 (half a last place added to the magnitude's bits, then the
    mask: still no convert to drop), the third what is left: they sum to
    ``x`` exactly too, and they are the pieces ``Precision.HIGHEST`` makes.
    A product of two float32 values in six passes drops mid·lo, lo·mid and
    lo·lo.  Pieces cut by the mask alone all have their value's sign, so over
    a row of non-negative values the dropped terms add up: 0.6 of a float32's
    last place of the product, one way, where rounded pieces' terms cancel
    to 0.02 (PERF.md 6, PR 32).  For a product, not a pick, take these."""
    pieces = []
    for _ in range(2):
        top = jax.lax.bitcast_convert_type(
            (jax.lax.bitcast_convert_type(x, jnp.int32) + _HALF_PLACE)
            & _TOP_HALF, jnp.float32)
        pieces.append(top)
        x = x - top
    return pieces + [x]


def _hot_masks(code, rows: int):
    """A plane of codes ``(1, T)`` as its one-hot masks, slots on the
    lanes: ``(rows, T)`` for the code's row of the hot table as bfloat16,
    ``(128, T)`` for its lane as booleans."""
    tile = code.shape[1]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0) == (
        code >> 7)
    lane_of = jax.lax.broadcasted_iota(
        jnp.int32, (_LANES, tile), 0) == (code & 127)
    return row_of.astype(jnp.float32).astype(jnp.bfloat16), lane_of


def _hot_scores_kernel(width: int, rows: int, table_ref, codes_ref, vals_ref,
                       out_ref):
    """A tile of slots of every plane: ``sum over planes of vals *
    w_hot[codes]``.

    Refs: table_ref (3*128, rows) bf16, the hot weights' three pieces,
    each transposed (lane, row); codes_ref / vals_ref (width, T); out_ref
    (1, T)."""
    table = table_ref[...]

    def plane(carry):
        j, acc = carry
        code, val = codes_ref[pl.ds(j, 1), :], vals_ref[pl.ds(j, 1), :]
        row_of, lane_of = _hot_masks(code, rows)
        picked = jnp.dot(table, row_of, preferred_element_type=jnp.float32)
        picked = (picked[:_LANES] + picked[_LANES:2 * _LANES]
                  + picked[2 * _LANES:])
        return j + _I32_ONE, acc + val * jnp.sum(
            jnp.where(lane_of, picked, 0.0), axis=0, keepdims=True)

    out_ref[...] = _over_planes(
        width, plane, jnp.zeros(out_ref.shape, jnp.float32))


def _over_planes(width: int, plane, acc):
    """``plane`` applied to ``(plane number, accumulator)`` for every plane,
    :data:`_HOT_UNROLL` planes a trip of one loop (Mosaic unrolls a
    ``fori_loop`` whole or not at all), the planes left over after it.  The
    loop carries its own int32 plane number: see :func:`_glm_grad_kernel`."""
    trips, left = divmod(width, _HOT_UNROLL)

    def trip(_, carry):
        for _i in range(_HOT_UNROLL):
            carry = plane(carry)
        return carry

    carry = (_I32_ZERO, acc)
    if trips:
        carry = jax.lax.fori_loop(0, trips, trip, carry)
    for _i in range(left):
        carry = plane(carry)
    return carry[1]


def _hot_grad_kernel(width: int, rows: int, codes_ref, vals_ref, err_ref,
                     g_ref):
    """The transposed product: ``g[row, piece * 128 + lane] += sum over
    the tile's slots of onehot_row * piece(err * val) * onehot_lane``.

    Refs: codes_ref / vals_ref (width, T); err_ref (1, T); g_ref (rows,
    3*128), the same block every step (the accumulator)."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    err = err_ref[...]
    g_ref[...] += _hot_grad_planes(width, rows, codes_ref, vals_ref, err,
                                   g_ref.shape)


def _hot_grad_planes(width: int, rows: int, codes_ref, vals_ref, err, shape):
    """:func:`_hot_grad_kernel`'s sum over a tile's ``width`` planes of the
    error ``err`` (1, T): ``shape`` (rows, 3*128) float32."""

    def plane(carry):
        j, acc = carry
        row_of, lane_of = _hot_masks(codes_ref[pl.ds(j, 1), :], rows)
        spread = jnp.concatenate(
            [jnp.where(lane_of, p, 0.0).astype(jnp.bfloat16)
             for p in _f32_pieces(err * vals_ref[pl.ds(j, 1), :])])
        return j + _I32_ONE, acc + jax.lax.dot_general(
            row_of, spread, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    return _over_planes(width, plane, jnp.zeros(shape, jnp.float32))


def _hot_tile(mb: int) -> int:
    """The longest run of whole lane chunks, at most :data:`_HOT_TILE`
    slots, that divides ``mb`` (a multiple of 128)."""
    return next(t for t in range(_HOT_TILE, 0, -_LANES) if mb % t == 0)


def _hot_operands(codes, vals):
    """The planes with their slots padded to whole lane chunks (code 0 at
    value 0.0 adds nothing either way)."""
    pad = -codes.shape[1] % _LANES
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
    return codes.astype(jnp.int32), vals.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hot_scores(w_hot, codes, vals, interpret: bool = False):
    """``sum over the planes of vals * w_hot[codes]``, ``(mb,)``, with the
    weight of a code found by comparison: ``w_hot`` (K,) float32, K a
    multiple of 128; ``codes`` / ``vals`` (width, mb), codes in [0, K).
    Exact: each product is ``vals * w_hot[codes]`` in float32."""
    width, mb = codes.shape
    rows = w_hot.shape[0] // _LANES
    codes, vals = _hot_operands(codes, vals)
    tile = _hot_tile(codes.shape[1])
    table = jnp.concatenate(
        [p.reshape(rows, _LANES).T
         for p in _f32_pieces(w_hot.astype(jnp.float32))]
    ).astype(jnp.bfloat16)
    vma, promote = _vma_of(table, codes, vals)
    out = pl.pallas_call(
        functools.partial(_hot_scores_kernel, width, rows),
        grid=(codes.shape[1] // tile,),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i: (_I32_ZERO, _I32_ZERO)),
            pl.BlockSpec((width, tile), lambda i: (_I32_ZERO, i)),
            pl.BlockSpec((width, tile), lambda i: (_I32_ZERO, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (_I32_ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((1, codes.shape[1]), jnp.float32,
                                       vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="hot_scores",
    )(promote(table), promote(codes), promote(vals))
    return out[0, :mb]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def hot_grad(err, codes, vals, k: int, interpret: bool = False):
    """:func:`hot_scores` transposed: the gradient ``(k,)`` of the hot
    weights, ``g[c] = sum over the slots with code c of err * vals``
    (``err`` (mb,), a row's; float32 sums in the MXU's accumulator)."""
    width, mb = codes.shape
    rows = k // _LANES
    codes, vals = _hot_operands(codes, vals)
    err = jnp.pad(err.astype(jnp.float32), (0, codes.shape[1] - mb))[None]
    tile = _hot_tile(codes.shape[1])
    vma, promote = _vma_of(codes, vals, err)
    g = pl.pallas_call(
        functools.partial(_hot_grad_kernel, width, rows),
        grid=(codes.shape[1] // tile,),
        in_specs=[
            pl.BlockSpec((width, tile), lambda i: (_I32_ZERO, i)),
            pl.BlockSpec((width, tile), lambda i: (_I32_ZERO, i)),
            pl.BlockSpec((1, tile), lambda i: (_I32_ZERO, i)),
        ],
        out_specs=pl.BlockSpec((rows, 3 * _LANES),
                               lambda i: (_I32_ZERO, _I32_ZERO)),
        out_shape=jax.ShapeDtypeStruct((rows, 3 * _LANES), jnp.float32,
                                       vma=vma),
        # the grid axis carries the accumulator: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hot_grad",
    )(promote(codes), promote(vals), promote(err))
    g = g.reshape(rows, 3, _LANES)
    return (g[:, 0] + g[:, 1] + g[:, 2]).reshape(k)


# -- the same lookup over rows of many widths, a row tile's planes as data ----


def _hot_block_runs(nb: int, sched_ref):
    """``(first, live)`` of the grid step: its block is its row tile's
    first (the tile's scores start from zero there), and it is the block
    the schedule lists for this step (a pad past the step's blocks reads
    the last one again and adds nothing)."""
    i = pl.program_id(0)
    before = jnp.maximum(i - _I32_ONE, _I32_ZERO)
    first = (i == 0) | (sched_ref[nb + i] != sched_ref[nb + before])
    return first, sched_ref[i] == i


def _hot_scores_blocks_kernel(nb: int, rows: int, step_ref, sched_ref,
                              table_ref, codes_ref, vals_ref, out_ref,
                              part_ref):
    """A block of planes of one row tile, summed by
    :func:`_hot_scores_kernel` into ``part_ref`` and added to the tile's
    scores."""
    first, live = _hot_block_runs(nb, sched_ref)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _():
        _hot_scores_kernel(codes_ref.shape[0], rows, table_ref, codes_ref,
                           vals_ref, part_ref)
        out_ref[...] += part_ref[...]


def _hot_grad_blocks_kernel(nb: int, rows: int, step_ref, sched_ref,
                            codes_ref, vals_ref, err_ref, g_ref):
    """:func:`_hot_grad_kernel` over the listed blocks alone, the
    accumulator zeroed at the first grid step whatever it holds."""
    _first, live = _hot_block_runs(nb, sched_ref)

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(live)
    def _():
        err = err_ref[...]
        g_ref[...] += _hot_grad_planes(codes_ref.shape[0], rows, codes_ref,
                                       vals_ref, err, g_ref.shape)


def _hot_blocks_specs(nb: int, planes: int, tile: int):
    """The index maps of the two block kernels: a block of the whole batch
    by its step and the schedule's first row, a row tile by its second."""

    def block(i, step, sched):
        return step[0], sched[i], _I32_ZERO, _I32_ZERO

    def row_tile(i, step, sched):
        return _I32_ZERO, sched[nb + i]

    def same_block(i, step, sched):
        return _I32_ZERO, _I32_ZERO

    return (pl.BlockSpec((None, None, planes, tile), block), row_tile,
            same_block)


@functools.partial(jax.jit, static_argnames=("mb", "interpret"))
def hot_scores_blocks(w_hot, codes, vals, step, sched, mb: int,
                      interpret: bool = False):
    """:func:`hot_scores` over rows of many widths, ``(mb,)`` scores of one
    step's rows in the order the blocks lay them.

    ``codes`` / ``vals`` are the WHOLE batch's blocks, ``(steps, nb,
    planes, tile)``: a block is ``planes`` planes of ``tile`` places (a
    multiple of 128) of one row tile, and a tile's blocks follow one
    another, as many as its widest row fills.  ``sched`` ``(2, nb)`` int32
    is the step's schedule, DATA: the block each grid step reads, and its
    row tile, tiles in ascending order; past the step's blocks, pads that
    name the last block and tile again (no copy, nothing added).  ``step``
    picks the step's blocks where they lie: no slice of them is made.  One
    program serves every table of one shape, whatever widths its rows draw;
    exact as :func:`hot_scores`."""
    _steps, nb, planes, tile = codes.shape
    rows = w_hot.shape[0] // _LANES
    table = jnp.concatenate(
        [p.reshape(rows, _LANES).T
         for p in _f32_pieces(w_hot.astype(jnp.float32))]
    ).astype(jnp.bfloat16)
    step = jnp.reshape(step, (1,)).astype(jnp.int32)
    sched = jnp.reshape(sched, (-1,)).astype(jnp.int32)
    operands = [step, sched, table, codes.astype(jnp.int32),
                vals.astype(jnp.float32)]
    vma, promote = _vma_of(*operands)
    block, row_tile, same_block = _hot_blocks_specs(nb, planes, tile)
    out = pl.pallas_call(
        functools.partial(_hot_scores_blocks_kernel, nb, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec(table.shape, same_block), block, block],
            out_specs=pl.BlockSpec((1, tile), row_tile),
            scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((1, _round_up(mb, tile)), jnp.float32,
                                       vma=vma),
        # a row tile's blocks carry its scores: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hot_scores",
    )(*(promote(a) for a in operands))
    return out[0, :mb]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def hot_grad_blocks(err, codes, vals, step, sched, k: int,
                    interpret: bool = False):
    """:func:`hot_scores_blocks` transposed, as :func:`hot_grad` is
    :func:`hot_scores`: the gradient ``(k,)`` of the hot weights from the
    error ``(mb,)`` of the step's rows in the blocks' order."""
    _steps, nb, planes, tile = codes.shape
    rows = k // _LANES
    n = err.shape[0]
    err = jnp.pad(err.astype(jnp.float32), (0, _round_up(n, tile) - n))[None]
    step = jnp.reshape(step, (1,)).astype(jnp.int32)
    sched = jnp.reshape(sched, (-1,)).astype(jnp.int32)
    operands = [step, sched, codes.astype(jnp.int32),
                vals.astype(jnp.float32), err]
    vma, promote = _vma_of(*operands)
    block, row_tile, same_block = _hot_blocks_specs(nb, planes, tile)
    g = pl.pallas_call(
        functools.partial(_hot_grad_blocks_kernel, nb, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[block, block, pl.BlockSpec((1, tile), row_tile)],
            out_specs=pl.BlockSpec((rows, 3 * _LANES), same_block),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 3 * _LANES), jnp.float32,
                                       vma=vma),
        # the grid axis carries the accumulator: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hot_grad",
    )(*(promote(a) for a in operands))
    g = g.reshape(rows, 3, _LANES)
    return (g[:, 0] + g[:, 1] + g[:, 2]).reshape(k)


# -- the Lloyd iteration's pass over the rows ---------------------------------

#: rows of the table a grid step of :func:`lloyd_sums` takes, at most.  Seen
#: on the chip at 2,025,000 x 896, k 100, ms an iteration of the whole Lloyd
#: program; seconds the program took to compile (PERF.md 6; my chip run,
#: PR 32): 512 rows 23.42; 1.5 s.  1024 rows 22.61; 2.0 s.  2048 rows
#: 22.31; 3.7 s: 352 us a 32768 rows, where the MXU's nine passes at its
#: peak are 343
_LLOYD_TILE = 2048
#: the scoped VMEM the call asks for (v5e holds 128 MiB; the default limit
#: is 16), and what :func:`lloyd_sums_tile` lets its own reckoning of a
#: tile's needs come to.  The compiler's own account at 896 wide, k 100:
#: 2048 rows between 32 and 40 MiB (reckoned 38.5), 1024 between 16 and 18
#: (reckoned 20.4)
_LLOYD_VMEM_BYTES = 64 << 20
_LLOYD_VMEM_BUDGET = 48 << 20
#: centroids a call takes, at most: two lane chunks (the accumulator, the
#: centroids' pieces and the (k, tile) distances grow with them)
_LLOYD_MAX_K = 2 * _LANES


def _lloyd_sums_kernel(kp: int, x_ref, w_ref, x2_ref, c_ref, c2_ref,
                       sums_ref, counts_ref, cost_ref):
    """One row tile of a Lloyd iteration, CENTROIDS ON SUBLANES, rows on
    lanes: the distance product, argmin, cost and the per-cluster sums from
    the one tile in VMEM.

    Refs:
      x_ref      (T, W) f32     the tile of the table, as it lies
      w_ref      (1, T) f32     the pack's row mask
      x2_ref     (1, T) f32     the rows' squared norms
      c_ref      (3*kp, W) bf16 the centroids' three pieces, hi on mid on lo
      c2_ref     (kp, 1) f32    their squared norms (+inf a pad centroid)
      sums_ref   (kp, W) f32    per-cluster sums   } the same block every
      counts_ref (kp, 128) f32  counts, over lanes } step: the accumulators
      cost_ref   (8, 128) f32   row 0: the cost, over the lanes

    The product is the float32 one in six bfloat16 passes (hi·hi, mid·hi,
    lo·hi, hi·mid, mid·mid, hi·lo: what ``Precision.HIGHEST`` multiplies);
    the sums are the membership, ONE bfloat16 piece, times the tile's three,
    exact term for term.  Every sum runs in one fixed order.
    """

    @pl.when(pl.program_id(0) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        cost_ref[...] = jnp.zeros_like(cost_ref)

    tile = x_ref.shape[0]
    hi, mid, lo = (p.astype(jnp.bfloat16)
                   for p in _f32_nearest_pieces(x_ref[...]))

    def times(rows, piece):  # (rows, W) x (T, W) -> (rows, T), rows on lanes
        return jax.lax.dot_general(
            c_ref[0:rows, :], piece, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    by_hi, by_mid, by_lo = times(3 * kp, hi), times(2 * kp, mid), times(kp, lo)
    # the smallest terms first
    xc = (by_lo + by_mid[kp:] + by_hi[2 * kp:]) \
        + (by_mid[:kp] + by_hi[kp:2 * kp]) + by_hi[:kp]
    d = jnp.maximum(x2_ref[...] - 2.0 * xc + c2_ref[...], 0.0)
    least = jnp.min(d, axis=0, keepdims=True)
    cluster = jax.lax.broadcasted_iota(jnp.int32, (kp, tile), 0)
    # the first of equals, as jnp.argmin
    nearest = jnp.min(jnp.where(d == least, cluster, np.int32(kp)),
                      axis=0, keepdims=True)
    w = w_ref[...]
    member = ((cluster == nearest) & (w > 0.0)).astype(jnp.float32)

    def fold_lanes(a):  # (r, T) -> (r, 128), lane chunk on lane chunk
        out = a[:, :_LANES]
        for at in range(_LANES, tile, _LANES):
            out = out + a[:, at:at + _LANES]
        return out

    one_piece = member.astype(jnp.bfloat16)
    sums_ref[...] += sum(
        jnp.dot(one_piece, piece, preferred_element_type=jnp.float32)
        for piece in (lo, mid, hi))
    counts_ref[...] += fold_lanes(member)
    cost_ref[0:1, :] += fold_lanes(least * w)


def lloyd_sums_tile(rows: int, width: int, k: int) -> int:
    """:func:`lloyd_sums`'s row tile for ``rows`` rows ``width`` wide under
    ``k`` centroids, by arithmetic on the shape alone (nothing is compiled
    or timed to find it): the most whole lane chunks, at most
    :data:`_LLOYD_TILE` rows, whose VMEM stays within
    :data:`_LLOYD_VMEM_BUDGET`.  0 where the kernel does not take the table:
    rows that are no multiple of the lanes wide, fewer rows than a lane
    chunk, more centroids than :data:`_LLOYD_MAX_K`, a width at which not
    even one lane chunk of rows fits."""
    if width <= 0 or width % _LANES or not 0 < k <= _LLOYD_MAX_K:
        return 0
    kp = _round_up(k, _LANES)
    # the accumulator and the centroids' pieces, two buffers each
    fixed = 2 * kp * width * 4 + 2 * 3 * kp * width * 2
    # per row: the tile in two buffers and its three bfloat16 pieces; the
    # six products' results, the distances, the membership twice
    per_row = width * (2 * 4 + 3 * 2) + kp * (6 + 4) * 4
    fit = (_LLOYD_VMEM_BUDGET - fixed) // per_row
    return max(0, min(_LLOYD_TILE, rows, fit) // _LANES * _LANES)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def lloyd_sums(x, w, x2, c, tile_rows: int, interpret: bool = False):
    """A Lloyd iteration's pass over the first ``rows // tile_rows`` whole
    tiles of ``x`` in ONE read: ``(cost, sums (k, W), counts (k,))`` of those
    rows under their nearest centroid of ``c`` (k, W).

    ``x`` (rows, W) float32, W a multiple of 128, read where it lies; ``w``
    (rows,) the row mask, ``x2`` (rows,) the rows' squared norms.  The rows a
    whole number of tiles leaves are the caller's.  The centroids go in as
    their three bfloat16 pieces, padded to whole lane chunks with centroids
    no row is nearest to (squared norm +inf)."""
    rows, width = x.shape
    k = c.shape[0]
    kp = _round_up(k, _LANES)
    if not tile_rows or tile_rows % _LANES or rows < tile_rows \
            or width % _LANES:
        raise ValueError(
            f"lloyd_sums: no row tile for {rows} rows x {width} (tile "
            f"{tile_rows}; whole {_LANES}-lane chunks both ways)")
    c = c.astype(jnp.float32)
    c2 = jnp.pad(jnp.sum(c * c, axis=1), (0, kp - k),
                 constant_values=jnp.inf)[:, None]
    pieces = jnp.concatenate(
        [jnp.pad(p, ((0, kp - k), (0, 0))) for p in _f32_nearest_pieces(c)]
    ).astype(jnp.bfloat16)
    operands = [x, w.astype(jnp.float32).reshape(1, rows),
                x2.astype(jnp.float32).reshape(1, rows), pieces, c2]
    vma, promote = _vma_of(*operands)

    def row_tile(i):
        return _I32_ZERO, i

    def same_block(i):
        return _I32_ZERO, _I32_ZERO

    sums, counts, cost = pl.pallas_call(
        functools.partial(_lloyd_sums_kernel, kp),
        grid=(rows // tile_rows,),
        in_specs=[
            pl.BlockSpec((tile_rows, width), lambda i: (i, _I32_ZERO)),
            pl.BlockSpec((1, tile_rows), row_tile),
            pl.BlockSpec((1, tile_rows), row_tile),
            pl.BlockSpec((3 * kp, width), same_block),
            pl.BlockSpec((kp, 1), same_block),
        ],
        out_specs=[
            pl.BlockSpec((kp, width), same_block),
            pl.BlockSpec((kp, _LANES), same_block),
            pl.BlockSpec((_SUBLANES, _LANES), same_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, width), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((kp, _LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.float32, vma=vma),
        ],
        # the grid axis carries the accumulators: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_LLOYD_VMEM_BYTES),
        interpret=interpret,
        name="lloyd_sums",
    )(*(promote(a) for a in operands))
    return jnp.sum(cost[0]), sums[:k], jnp.sum(counts[:k], axis=1)


# -- fused serving chain ------------------------------------------------------

#: per-stage (param count) of the serving chain ops the kernel understands:
#:   affine_sub_mul  h = (h - a) * b     (StandardScaler: shift, inv_scale)
#:   affine_mul_add  h = h * a + b       (MinMaxScaler: a, b)
#:   glm_score       h = h @ w + b       (dense logistic/linear score)
SERVE_CHAIN_OPS = ("affine_sub_mul", "affine_mul_add", "glm_score")


def serve_chain(kinds, fetch, d, masked=False, tile_rows=512):
    """A traced fn running the whole serving chain in ONE Pallas launch.

    ``kinds``: stage op names (see :data:`SERVE_CHAIN_OPS`), ``fetch``: which
    stage outputs the plan reads back, ``d``: the true feature width (the
    batch arrives host-padded to a 128 multiple).  With ``masked=True`` the
    kernel additionally emits a per-row finite mask as the FIRST output and
    zeroes non-finite rows before the chain runs (the deferred quarantine
    scan); without it, non-finite rows flow through exactly like the XLA
    fused path (row-independent math, NaN in -> NaN out).

    Returns ``fn(x, *stage_params)`` -> list of ``[mask?] + fetched outs``:
    the mask as an (n, 1) f32 0/1 column, affine outs (n, d_pad) (caller
    slices to d), the score (n, 1).  Stage params arrive in declaration
    shape ((d,) vectors, scalar intercept) and are zero-padded in-program —
    zero pads are exact through every stage ((0-0)*0, 0*0+0, pad weights
    contribute exact-zero dot terms), so padding never perturbs the first
    ``d`` columns.

    The returned fn carries no collectives and declares no ``vma``: its
    one product caller (``FusedRun._apply_fn``) runs every serving program
    under ``shard_map(check_vma=False)``, the rule for the collective-free
    serving plane.  ``fn.pallas_interpret`` says which lowering it uses.

    Memoized like :func:`make_pallas_grad_fn` (downstream jit caches key on
    fn identity).
    """
    return _serve_chain(tuple(kinds), tuple(bool(f) for f in fetch), int(d),
                        bool(masked), int(tile_rows), launch_interpreted())


@functools.lru_cache(maxsize=None)
def _serve_chain(kinds, fetch, d, masked, tile_rows, interpret):
    for kind in kinds:
        if kind not in SERVE_CHAIN_OPS:
            raise ValueError(f"unknown serve-chain op {kind!r}")
    if len(kinds) != len(fetch) or not kinds:
        raise ValueError((kinds, fetch))
    tile_rows = max(8, _round_up(tile_rows, 8))
    d_pad = _round_up(max(d, 1), 128)

    def kernel(*refs):
        x_ref = refs[0]
        stage_refs = [(refs[1 + 2 * i], refs[2 + 2 * i])
                      for i in range(len(kinds))]
        out_refs = list(refs[1 + 2 * len(kinds):])
        h = x_ref[...].astype(jnp.float32)
        if masked:
            ok = jnp.all(jnp.isfinite(h), axis=1, keepdims=True)
            out_refs.pop(0)[...] = ok.astype(jnp.float32)
            h = jnp.where(ok, h, 0.0)
        for kind, (pa_ref, pb_ref), keep in zip(kinds, stage_refs, fetch):
            pa = pa_ref[...].astype(jnp.float32)
            pb = pb_ref[...].astype(jnp.float32)
            if kind == "affine_sub_mul":
                h = (h - pa) * pb
            elif kind == "affine_mul_add":
                h = h * pa + pb
            else:  # glm_score
                h = jax.lax.dot_general(
                    h, pa, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                ) + pb[0, 0]
            if keep:
                out_refs.pop(0)[...] = h

    def fn(x, *stage_params):
        n = x.shape[0]
        if x.shape[1] != d_pad:
            raise ValueError((x.shape, d_pad))
        tm = math.gcd(n, tile_rows) if n else tile_rows
        n_pad = n
        if tm < 8:  # tiny/ragged bisection slices: pad rows to a legal tile
            n_pad = _round_up(max(n, 1), 8)
            tm = math.gcd(n_pad, tile_rows)
            x = jnp.zeros((n_pad, d_pad), x.dtype).at[:n].set(x)
        args, in_specs = [x], [pl.BlockSpec((tm, d_pad), lambda i: (i, 0))]
        for kind, (pa, pb) in zip(kinds, stage_params):
            if kind == "glm_score":
                wp = jnp.zeros((d_pad, 1), pa.dtype).at[:d, 0].set(
                    jnp.ravel(pa))
                bp = jnp.asarray(pb, jnp.float32).reshape(1, 1)
                args += [wp, bp]
                in_specs += [pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
                             pl.BlockSpec((1, 1), lambda i: (0, 0))]
            else:
                args += [
                    jnp.zeros((1, d_pad), pa.dtype).at[0, :d].set(
                        jnp.ravel(pa)),
                    jnp.zeros((1, d_pad), pb.dtype).at[0, :d].set(
                        jnp.ravel(pb)),
                ]
                in_specs += [pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
                             pl.BlockSpec((1, d_pad), lambda i: (0, 0))]
        out_specs, out_shape = [], []
        if masked:
            out_specs.append(pl.BlockSpec((tm, 1), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((n_pad, 1), jnp.float32))
        for kind, keep in zip(kinds, fetch):
            if not keep:
                continue
            width = 1 if kind == "glm_score" else d_pad
            out_specs.append(pl.BlockSpec((tm, width), lambda i: (i, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((n_pad, width), jnp.float32))
        outs = pl.pallas_call(
            kernel,
            grid=(n_pad // tm,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            # row tiles are independent: no cross-tile accumulator
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
        )(*args)
        return [o[:n] for o in outs]

    #: read by FusedRun._device_batch, which counts interpreted dispatches
    fn.pallas_interpret = interpret
    return fn
