"""Pallas TPU kernels for the hot ops XLA fusion leaves on the table.

Kernel inventory.  The rule for every entry: XLA stays the default until
a chip benchmark shows the kernel winning (ROADMAP S4/S5 decide that on
the ledger); what is recorded here is only what has been SEEN:

  ==================  ==========================  =========================
  kernel              hot path                    seen on the chip
  ==================  ==========================  =========================
  :func:`glm_grad`    training minibatch grad     2026-09-26, TPU v5 lite,
                      (forward matvec + rank-1    jax 0.9.0 / libtpu 0.0.34:
                      accumulate, one HBM pass)   compiles with Mosaic at
                                                  (16384, 512) and
                                                  (32768, 28) f32, matches
                                                  the XLA grad to 1e-6
                                                  relative, under strict
                                                  ``check_vma`` on 1- and
                                                  4-chip meshes.  Speed
                                                  against XLA: not measured
                                                  (``chip_smoke.py`` prints a
                                                  smoke timing only).  No
                                                  estimator selects it; it
                                                  is the opt-in drop-in
                                                  (make_pallas_grad_fn)
  :func:`serve_chain` fused serving hot path      same date and stack:
                      (quarantine NaN/Inf scan    compiles with Mosaic at
                      + affine scalers + GLM      4096 x 512 and x 28, raw
                      score in one launch)        and masked, f32 and bf16
                                                  placement, buckets 1 and
                                                  8 included; scores match
                                                  fused XLA to 1e-5.  Opt-in
                                                  via FMT_SERVE_PALLAS.
                                                  Speed against fused XLA:
                                                  not measured on the chip
                                                  (interpret mode on CPU is
                                                  ~5x slower, which says
                                                  nothing about Mosaic)
  (sparse grad)       segment-CSR minibatch grad  REJECTED — every
                                                  programmable path lost
                                                  to XLA's scatter
                                                  lowering; note below.
                                                  No sparse Pallas kernel
                                                  ships.
  ==================  ==========================  =========================

:func:`glm_grad` tiles rows, keeps each X tile VMEM-resident for both the
forward matvec and the gradient rank-1 accumulate, and accumulates ``g_w``
in VMEM across the sequential grid.  :func:`serve_chain` is embarrassingly
parallel over row tiles (no cross-tile accumulators): each tile is scanned
for NaN/Inf, scaled through the affine stages, and scored without leaving
VMEM — the three serving HBM passes collapse into one.

Which lowering runs is decided by the device platform, three ways
(:func:`launch_interpreted`): ``tpu`` compiles with Mosaic or raises;
``cpu`` runs ``interpret=True`` (the tier-1 parity harness — same kernel
body, numerically identical); any other platform raises.  Nothing degrades
to interpret mode silently: the factories stamp ``pallas_interpret`` on
what they return, and the drivers count every interpreted dispatch
(``train.pallas_interpreted`` / ``fused.pallas_interpreted``) so a chip
run can assert zero.

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
segment-CSR XLA formulation therefore remains the default and no sparse
Pallas kernel ships.  To be re-measured with the chip benchmark
(ROADMAP S0) before anyone retries it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
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


def _glm_grad_kernel(kind: str, x_ref, yw_ref, w_ref, b_ref,
                     gw_ref, stats_ref):
    """One row tile: forward matvec + loss stats + gradient accumulate.

    Refs (all VMEM):
      x_ref     (TM, D)   row tile of features
      yw_ref    (TM, 2)   [label, sample weight] per row
      w_ref     (D, 1)    weights (same block every step)
      b_ref     (1, 1)    intercept
      gw_ref    (D, 1)    accumulated weight gradient (same block every step)
      stats_ref (1, 128)  [g_b, loss_sum, w_sum, 0...] accumulators
    """
    # zero the cross-tile accumulators on the first sequential grid step
    @pl.when(pl.program_id(0) == 0)
    def _():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    x = x_ref[...]
    y = yw_ref[..., 0:1]
    w = yw_ref[..., 1:2]
    logits = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    logits = logits + b_ref[0, 0]
    if kind == "logistic":
        p = jax.nn.sigmoid(logits)
        err = (p - y) * w
        loss = jnp.sum(w * (jnp.logaddexp(0.0, logits) - y * logits))
    else:
        err = (logits - y) * w
        loss = 0.5 * jnp.sum(err * (logits - y))
    # rank-1 accumulate: X tile reused from VMEM — the second HBM pass
    # the two-matmul formulation would have paid
    gw_ref[...] += jax.lax.dot_general(
        x.T, err, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    # build the [g_b, loss, w_sum, 0...] row with an iota mask (dynamic
    # scatter does not lower in Pallas TPU kernels)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 128), dimension=1)
    stats = (
        jnp.where(col == 0, jnp.sum(err), 0.0)
        + jnp.where(col == 1, loss, 0.0)
        + jnp.where(col == 2, jnp.sum(w), 0.0)
    )
    stats_ref[...] += stats


#: what one launch may take of v5e's 16 MiB default scoped-VMEM limit
#: (the rest is headroom for Mosaic's own temporaries)
_VMEM_BUDGET_BYTES = 12 << 20


@functools.partial(
    jax.jit, static_argnames=("kind", "tile_rows", "interpret")
)
def glm_grad(x, y, w, wts, b, kind: str = "logistic",
             tile_rows: int = 512, interpret: bool = False):
    """Fused GLM minibatch gradient: one HBM pass over ``x``.

    Args: x (n, d), y (n,), w (n,) sample weights, wts (d,), b scalar.
    Returns (g_w (d,), g_b, loss_sum, w_sum) — identical semantics to the
    jnp grad fns in lib/regression.py / lib/classification.py.
    """
    n, d = x.shape
    d_pad = _round_up(max(d, 1), 128)
    # size the row tile to the VMEM the launch really takes: the (d_pad, 1)
    # weight and gradient blocks pad to 128 lanes and are double-buffered;
    # per row, the X block (two buffers), the in-kernel x.T temporary, and
    # the lane-padded (tm, 2) label/weight block (two buffers)
    fixed = 2 * 2 * d_pad * 128 * 4
    per_row = (3 * d_pad + 2 * 128) * 4
    vmem_rows = (_VMEM_BUDGET_BYTES - fixed) // per_row // 8 * 8
    if vmem_rows < 8:
        raise ValueError(
            f"glm_grad: {d} features need {fixed + 8 * per_row} bytes of "
            f"VMEM for the smallest row tile, over the "
            f"{_VMEM_BUDGET_BYTES}-byte budget"
        )
    tm = min(tile_rows, _round_up(max(n, 8), 8), vmem_rows)
    n_pad = _round_up(max(n, 1), tm)

    xp = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(x)
    yw = jnp.zeros((n_pad, 2), jnp.float32)
    yw = yw.at[:n, 0].set(y.astype(jnp.float32))
    yw = yw.at[:n, 1].set(w.astype(jnp.float32))  # pad rows weight 0
    wp = jnp.zeros((d_pad, 1), jnp.float32).at[:d, 0].set(
        wts.astype(jnp.float32)
    )
    bp = jnp.asarray(b, jnp.float32).reshape(1, 1)

    # under shard_map(check_vma=True) outputs must declare how they vary
    # across mesh axes: they vary wherever any input does.  Operands are
    # promoted to the same vma so in-kernel dots see matching axes.
    vma = frozenset()
    for operand in (xp, yw, wp, bp):
        vma = vma | jax.typeof(operand).vma

    def _promote(a):
        need = vma - jax.typeof(a).vma
        return jax.lax.pcast(a, tuple(need), to="varying") if need else a

    xp, yw, wp, bp = (_promote(a) for a in (xp, yw, wp, bp))

    grid = (n_pad // tm,)
    gw, stats = pl.pallas_call(
        functools.partial(_glm_grad_kernel, kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((tm, 2), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_pad, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((1, 128), jnp.float32, vma=vma),
        ],
        # the grid axis carries the g_w / stats accumulators: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(xp, yw, wp, bp)
    return gw[:d, 0], stats[0, 0], stats[0, 1], stats[0, 2]


def make_pallas_grad_fn(kind: str, with_intercept: bool, tile_rows: int = 512):
    """A drop-in GradFn (lib/common.py contract) backed by :func:`glm_grad`.

    Signature matches the jnp grad factories: (params, x, y, w) ->
    ((g_w, g_b), loss_sum, w_sum).  On CPU the kernel runs interpreted —
    numerically identical, just slower — so tests cover one code path.

    Memoized on the hyper-flags AND the lowering (like the jnp grad
    factories): downstream compiled-step caches key on grad-fn identity,
    so a fresh closure per call would force a recompile of the whole fused
    training program every fit.
    """
    return _make_pallas_grad_fn(kind, with_intercept, tile_rows,
                                launch_interpreted())


@functools.lru_cache(maxsize=None)
def _make_pallas_grad_fn(kind: str, with_intercept: bool, tile_rows: int,
                         interpret: bool):
    keep_b = 1.0 if with_intercept else 0.0

    def grad_fn(params, x, y, w):
        wts, b = params
        g_w, g_b, loss_sum, w_sum = glm_grad(
            x, y, w, wts, b, kind=kind, tile_rows=tile_rows,
            interpret=interpret,
        )
        return (g_w.astype(wts.dtype), (g_b * keep_b).astype(jnp.float32)), \
            loss_sum, w_sum

    #: read by train_glm, which counts every interpreted fit
    grad_fn.pallas_interpret = interpret
    # interpret-mode pallas_call internally mixes data-varying and unvarying
    # operands in a dynamic_slice, which strict-vma shard_map rejects
    # (JAX-internal limit; the Mosaic lowering passes strict — seen on 1-
    # and 4-chip v5e meshes, PR 21).  Training builders (fused + epoch-step)
    # read this to relax check_vma ONLY for the interpreted path, so the
    # CPU suite exercises the kernel through the full harness.
    grad_fn.shard_map_check_vma = not interpret
    return grad_fn


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
