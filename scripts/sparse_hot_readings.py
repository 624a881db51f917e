#!/usr/bin/env python
"""The chip readings behind the sparse step's frequency split (ISSUE 30),
on the sparse cell's table made from ``--seed``:

    python scripts/sparse_hot_readings.py --seed <n> [--rows N] [--fits 2]
            [--k 4096 ...] [--tile 512 ... --unroll 13 ...] [--no-fits]

* ``hot_share``   the share of the stored entries on the K most frequent
                  features, K = 1024 ... 65536 (a host count);
* ``ops``         one step's operations alone, milliseconds a call over
                  ``--calls`` enqueued calls (one wait at the end): XLA's
                  ``take`` of 1.28 M weights from a K-entry operand and from
                  the whole table, its scatter-add into K slots, the one-hot
                  lookup (``ops/pallas_kernels.py:hot_scores`` / ``hot_grad``)
                  both directions at each K, as shipped and at each ``--tile`` x
                  ``--unroll`` (slots a grid step, planes a loop trip; with
                  the seconds to the first call's return), and the cold
                  list's forward and backward alone (``lib/common.py:
                  _cold_planes_forward`` / ``_backward``: one take, the
                  planes' sum; the error's writes, one scatter), with the
                  planes the step holds;
* ``fits``        whole warm fits by the builders (``train_glm_sparse``):
                  the unsplit row-regular step, and the split step at each
                  K, with the pack's seconds, the leaves' bytes, the loss and
                  the coefficients' distance from the unsplit fit's.

A summary line each to standard output, everything to
``<--out>/readings.json`` (``chiprun_out/sparse_hot_readings/`` unless given).  Runs on whatever JAX finds; times mean
something only on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def per_call_ms(fn, args, calls):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    t = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / calls


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=0)
    parser.add_argument("--fits", type=int, default=2)
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--k", type=int, action="append", default=[])
    parser.add_argument("--tile", type=int, action="append", default=[])
    parser.add_argument("--unroll", type=int, action="append", default=[])
    parser.add_argument("--no-fits", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sparse_hot_readings"))
    args = parser.parse_args(argv)
    ks = args.k or [4096, 16384]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import data_sparse, run
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.ops import pallas_kernels
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    config = run.load_json(run.HERE, "configs", "criteo_sparse_lr.json")
    dim, batch = int(config["numFeatures"]), int(config["globalBatchSize"])
    indptr, indices, values, y = data_sparse.make_rows(
        config["data"], args.rows or int(config["rows"]), dim, args.seed)
    batch = min(batch, len(y))
    report = {"device": jax.devices()[0].device_kind, "rows": len(y),
              "entries": int(indptr[-1])}
    os.makedirs(args.out, exist_ok=True)

    def say(key, value):
        report[key] = value
        print(json.dumps({key: value}), flush=True)
        with open(os.path.join(args.out, "readings.json"), "w") as f:
            json.dump(report, f, indent=1)

    # (a) the skew
    t = time.perf_counter()
    counts = np.sort(np.bincount(indices, minlength=dim))[::-1]
    top = np.cumsum(counts) / max(1, len(indices))
    say("hot_share", {
        "count_s": time.perf_counter() - t,
        "touched": int((counts > 0).sum()),
        **{str(k): float(top[min(k, dim) - 1])
           for k in (1024, 4096, 16384, 65536)}})

    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    column = CsrRows(dim, indptr, indices, values)

    def pack(k):
        """The cell's table packed with the split at ``k`` forced (0: the
        unsplit step forced)."""
        saved = (common._HOT_K, common._hot_split_wins,
                 common._hot_split_measured)
        try:
            common._HOT_K = k or saved[0]
            common._hot_split_wins = lambda *a: bool(k)
            common._hot_split_measured = lambda: True
            t = time.perf_counter()
            stack = common.pack_sparse_minibatches(
                column, y, n_dev, batch, dim=dim, row_regular=True)
            return stack, time.perf_counter() - t
        finally:
            (common._HOT_K, common._hot_split_wins,
             common._hot_split_measured) = saved

    def fit_of(stack, placed):
        start = (jnp.zeros((dim,), jnp.float32), jnp.zeros((), jnp.float32))
        t = time.perf_counter()
        result = common.train_glm_sparse(
            start, stack, "logistic", mesh, 0.1, int(config["maxIter"]),
            device_batch=placed)
        return time.perf_counter() - t, result

    def fits_of(name, stack, pack_s, reference=None):
        placed = shard_batch_prefetched(mesh, stack.batch)
        first_s, first = fit_of(stack, placed)
        warm = [fit_of(stack, placed) for _ in range(args.fits)]
        coef = np.asarray(first.params[0])
        line = {
            "pack_s": pack_s, "first_fit_s": first_s,
            "warm_fit_s": sorted(s for s, _r in warm)[len(warm) // 2],
            "step_slots": stack.step_slots, "cold_slots": stack.cold_slots,
            "hot_entry_share": stack.n_hot_entries / max(1, stack.n_entries),
            "leaf_bytes": int(sum(a.nbytes for a in stack.batch)),
            "loss": float(first.losses[-1]),
            "same_bytes": all(np.array_equal(r.params[0], coef)
                              for _s, r in warm),
            "peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0),
        }
        if reference is not None:
            line["coef_gap"] = float(
                np.linalg.norm(coef - reference)
                / max(np.linalg.norm(reference), 1e-30))
        say(name, line)
        del placed
        return coef

    # (b), (c): one step's operations alone, on step 0 of the split pack
    ops = {}
    stacks = {}
    for k in ks:
        stacks[k] = pack(k)
        stack = stacks[k][0]
        codes = jnp.asarray(stack.ints[0])
        vals = jnp.asarray(stack.floats[0, : stack.width])
        err = jnp.asarray(stack.floats[0, stack.width + 1] * 0.37)
        w_hot = jnp.asarray(
            np.random.default_rng(k).standard_normal(k, dtype=np.float32))
        flat = codes.reshape(-1)
        ops[f"take_{k}"] = per_call_ms(
            jax.jit(lambda w, c: jnp.take(w, c, axis=0)), (w_hot, codes),
            args.calls)
        ops[f"scatter_add_{k}"] = per_call_ms(
            jax.jit(lambda e, v, c: jax.ops.segment_sum(
                (e[None] * v).reshape(-1), c, num_segments=k)),
            (err, vals, flat), args.calls)
        interpret = pallas_kernels.launch_interpreted()
        shipped = pallas_kernels._HOT_TILE, pallas_kernels._HOT_UNROLL
        jax.config.update("jax_enable_compilation_cache", False)
        for tile, unroll in [shipped] + [
                (t, u) for t in args.tile for u in args.unroll]:
            pallas_kernels._HOT_TILE, pallas_kernels._HOT_UNROLL = tile, unroll
            jax.clear_caches()
            try:
                for name, fn, operands in (
                    ("hot_scores", lambda w, c, v: pallas_kernels.hot_scores(
                        w, c, v, interpret=interpret), (w_hot, codes, vals)),
                    ("hot_grad", lambda e, c, v: pallas_kernels.hot_grad(
                        e, c, v, k=k, interpret=interpret),
                     (err, codes, vals)),
                ):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(*operands))
                    tag = f"{name}_{k}_tile{tile}_unroll{unroll}"
                    ops[tag + "_first_call_s"] = time.perf_counter() - t
                    ops[tag] = per_call_ms(fn, operands, args.calls)
            finally:
                pallas_kernels._HOT_TILE, pallas_kernels._HOT_UNROLL = shipped
                jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", True)
        cold_idx = jnp.asarray(stack.cold_idx[0])
        cold_vals = jnp.asarray(stack.cold_vals[0])
        cuts = jnp.asarray(stack.cold_cuts[0])
        w_all = jnp.zeros((dim,), jnp.float32) + 0.5
        ops[f"cold_forward_{k}"] = per_call_ms(
            jax.jit(lambda w, i, v, c: common._cold_planes_forward(
                w, i, v, c, stack.mb)),
            (w_all, cold_idx, cold_vals, cuts), args.calls)
        ops[f"cold_backward_{k}"] = per_call_ms(
            jax.jit(lambda e, i, v, c: common._cold_planes_backward(
                e, i, v, c, dim)),
            (err, cold_idx, cold_vals, cuts), args.calls)
        ops[f"cold_slots_{k}"] = stack.cold_slots
        ops[f"cold_planes_{k}"] = int(np.count_nonzero(stack.cold_cuts[0, 1]))
        say("ops", ops)
    ids0 = jnp.asarray(np.ascontiguousarray(
        indices[: batch * stack.width].reshape(batch, stack.width).T))
    ops["take_dim"] = per_call_ms(
        jax.jit(lambda w, c: jnp.take(w, c, axis=0)), (w_all, ids0),
        args.calls)
    ops["slots"] = int(codes.size)
    say("ops", ops)
    del codes, vals, err, flat, cold_idx, cold_vals, cuts, ids0

    if args.no_fits:
        return 0
    # whole fits
    plain, plain_s = pack(0)
    reference = fits_of("fit_unsplit", plain, plain_s)
    del plain
    for k in ks:
        stack, pack_s = stacks.pop(k)
        fits_of(f"fit_split_{k}", stack, pack_s, reference)
        del stack
    return 0


if __name__ == "__main__":
    sys.exit(main())
