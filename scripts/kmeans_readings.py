#!/usr/bin/env python
"""The chip readings behind the centroid fit's step (PR 31 and 32; PERF.md
§5, §6): what the Lloyd iteration as it was written before PR 31 costs on
the chip, and what each way of writing it over row tiles costs.

    python scripts/kmeans_readings.py --seed <n> [--rows N] [--k 100]
        [--iterations 20] [--tiles 8192,16384,32768] [--skip-as-written]
        [--kernel-tiles 512,1024,2048] [--skip-pr31] [--out DIR]

On the configuration's table (``chipbench/data_mixture.py``, from ``--seed``),
placed as the estimator places it, in ONE process:

* ``init``: seconds a pass of the k-means++ the estimator ran before PR 31
  (``np.sum((X - X[idx]) ** 2, axis=1)`` over a 100,000 x 784 float64 sample
  on the host; a fit made k - 1 of them), and the seconds of the whole init
  on the device (``kmeans_plus_plus_rows``), cold and warm;
* per variant of the iteration (each the fused scaffolding's ``epoch_fn``,
  bundled): seconds of the first and of a warm fit, the program's temporary
  bytes by the compiler's own account, and from a traced fit the program's
  device seconds and its self seconds by ``fmt.train*`` scope.  On the table
  as the estimator packs it since PR 31 (rows 896 wide: ``packed_width``):
  ``tiled@<rows>`` (the shipped iteration at that tile) and
  ``tiled_pieces@<rows>`` (its one-hot sums as three one-pass products over
  the rows' bfloat16 pieces in place of one at ``Precision.HIGHEST``).  Then on the table 784 wide, as PR 30
  packed it: ``tiled_unpadded@<rows>`` (what the lane padding buys),
  ``as_written_highest`` and ``as_written`` (PR 30's iteration: the whole
  table at once, ``segment_sum``, the product at JAX's default precision);
  PR 32's, on the packed table: ``operand_precision@<rows>`` (the one-hot
  product at ``(DEFAULT, HIGHEST)``: one piece of the membership, three of
  the rows, if the compiler honours it) and, for each of ``--kernel-tiles``,
  ``kernel@<rows>`` (``ops/pallas_kernels.py:lloyd_sums`` at that row tile:
  one read, nine passes), with ``compile_s``, the seconds the program took
  to compile, Mosaic's among them; ``--skip-pr31`` leaves out the host init
  and the 784-wide table, and ``--out chiprun_out/pr32`` is where PR 32's
  table in PERF.md comes from; ``--estimator`` adds ``estimator``: two
  fits of the table through ``KMeans.fit`` itself, as the cell makes them,
  their seconds, gaps and the ``train.kmeans*`` counters (which route the
  estimator's own rule took);
* ``gaps``: each variant's centroids and costs against the plain reference
  from the same init, beside the reference's own one-pass bfloat16 control:
  whether the iteration as it was written reads like the control.

A summary goes to standard output, everything to
``chiprun_out/kmeans_readings/readings.json``.  Runs on whatever JAX finds;
times mean something only on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))


def as_written_epoch(k, precision=None):
    """PR 30's ``lloyd_epoch``, word for word but for ``precision``."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.parallel.collectives import psum

    def dists(x, c):
        x2 = jnp.sum(x * x, axis=1, keepdims=True)
        c2 = jnp.sum(c * c, axis=1)
        xc = jnp.dot(x, c.T, precision=precision)
        return jnp.maximum(x2 - 2.0 * xc + c2, 0.0)

    def lloyd_epoch(c, batch):
        x, w = batch
        with jax.named_scope("fmt.train.kmeans.assign"):
            d = dists(x, c)
            assign = jnp.argmin(d, axis=1)
            cost = psum(jnp.sum(jnp.min(d, axis=1) * w), "data")
        with jax.named_scope("fmt.train.kmeans.update"):
            sums = psum(jax.ops.segment_sum(x * w[:, None], assign,
                                            num_segments=k), "data")
            counts = psum(jax.ops.segment_sum(w, assign, num_segments=k),
                          "data")
            new_c = jnp.where(counts[:, None] > 0,
                              sums / jnp.maximum(counts[:, None], 1.0), c)
            delta = jnp.sqrt(jnp.sum((new_c - c) ** 2))
        return new_c, cost, delta

    return lloyd_epoch


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=0)
    parser.add_argument("--k", type=int, default=0)
    parser.add_argument("--iterations", type=int, default=0)
    parser.add_argument("--tiles", default="8192,16384,32768")
    parser.add_argument("--skip-as-written", action="store_true")
    parser.add_argument("--kernel-tiles", default="")
    parser.add_argument("--skip-pr31", action="store_true")
    parser.add_argument("--estimator", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kmeans_readings"))
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import fit_gaps
    import sparse_step_trace
    from chipbench import data_mixture, references, run
    from flink_ml_tpu.lib import clustering, common
    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    config = run.load_json(run.HERE, "configs", "mnist8m_kmeans.json")
    rows = args.rows or int(config["rows"])
    dim = int(config["features"])
    k = args.k or int(config["k"])
    iterations = args.iterations or int(config["maxIter"])
    seed = 1  # the estimator's: the first of the mix's grid
    device = jax.devices()[0]

    def memory():
        stats = device.memory_stats() or {}
        return {"in_use": stats.get("bytes_in_use"),
                "peak": stats.get("peak_bytes_in_use")}

    report = {"rows": rows, "k": k, "iterations": iterations,
              "device": device.device_kind}
    t = time.perf_counter()
    X, _style = data_mixture.make_rows(config["data"], rows, dim, args.seed)
    report["data_s"] = time.perf_counter() - t
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    reference = references.load("kmeans_lloyd")

    # -- the init, before and after ------------------------------------------
    take = reference.sample_rows(rows, seed).astype(np.int32)
    report["init"] = {"sample_rows": len(take)}
    if not args.skip_pr31:
        sample64 = X[take].astype(np.float64)
        t = time.perf_counter()
        for idx in (3, 5, 7):
            np.sum((sample64 - sample64[idx]) ** 2, axis=1)
        host_pass = (time.perf_counter() - t) / 3
        del sample64
        report["init"].update(host_pass_s=host_pass,
                              host_fit_s_at_k=host_pass * (k - 1))

    w = np.ones((rows,), np.float32)
    width = clustering.packed_width(dim)

    def place(table_width):
        t0 = time.perf_counter()
        Xp = X if table_width == dim else np.pad(
            X, ((0, 0), (0, table_width - dim)))
        placed = shard_batch_prefetched(mesh, (Xp, w))
        jax.block_until_ready(placed)
        return placed, time.perf_counter() - t0

    placed, report["place_s"] = place(width)
    report["memory_placed"] = memory()
    for label in ("device_cold_s", "device_warm_s", "device_warm2_s"):
        t = time.perf_counter()
        init = clustering.kmeans_plus_plus_rows(placed, take, k, seed, mesh)
        report["init"][label] = time.perf_counter() - t

    # -- the iteration's variants --------------------------------------------
    def exact_pieces(member, x):
        # the sums over three bfloat16 pieces of the rows, a pass each
        oh = member.astype(jnp.bfloat16)
        hi = x.astype(jnp.bfloat16)
        rest = x - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        return sum(jax.lax.dot_general(
            oh, piece, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for piece in (hi, mid, lo))

    from flink_ml_tpu.ops import pallas_kernels

    # True off the chip only: a rehearsal of the kernel's variants
    interpreted = pallas_kernels.launch_interpreted()

    def one_piece_of_the_membership(member, x):
        # per-operand precision: 0 and 1 are one bfloat16 piece
        return jnp.dot(member.T, x, precision=(
            jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST))

    def tiled(tile, onehot_sums=None, kernel_rows=0):
        def epoch(c, batch):
            x, wt = batch
            cost, sums, counts = (
                clustering.psum(a, "data")
                for a in clustering._lloyd_pass(
                    x, wt, jnp.sum(x * x, axis=1), c, k, tile, kernel_rows,
                    interpreted))
            new_c = jnp.where(counts[:, None] > 0,
                              sums / jnp.maximum(counts[:, None], 1.0), c)
            return new_c, cost, jnp.sqrt(jnp.sum((new_c - c) ** 2))

        if onehot_sums is None:
            return epoch

        def patched(c, batch):
            sound = clustering._onehot_sums
            clustering._onehot_sums = onehot_sums
            try:
                return epoch(c, batch)
            finally:
                clustering._onehot_sums = sound

        return patched

    tiles = [int(t) for t in args.tiles.split(",")]
    middle = tiles[len(tiles) // 2]
    padded = [(f"tiled@{tile}", tiled(tile)) for tile in tiles]
    if not args.skip_pr31:
        padded.append((f"tiled_pieces@{middle}", tiled(middle, exact_pieces)))
    padded.append((f"operand_precision@{middle}",
                   tiled(middle, one_piece_of_the_membership)))
    padded += [(f"kernel@{rows_a_step}",
                tiled(middle, kernel_rows=int(rows_a_step)))
               for rows_a_step in args.kernel_tiles.split(",") if rows_a_step]
    unpadded = [(f"tiled_unpadded@{middle}", tiled(middle))]
    if not args.skip_as_written:
        unpadded.append(("as_written_highest", as_written_epoch(
            k, jax.lax.Precision.HIGHEST)))
        unpadded.append(("as_written", as_written_epoch(k)))

    os.makedirs(args.out, exist_ok=True)
    answers, report["variants"] = {}, {}

    def with_trail(epoch):
        # the state the estimator's program carries: the centroids, and
        # those every iteration started from (what ``gaps`` reads)
        def carried(state, batch):
            c, trail = state
            new_c, cost, delta = epoch(c, batch)
            return (new_c, jnp.concatenate([trail[1:], c[None]])), cost, delta

        return carried

    def read(name, epoch, placed, table_width):
        entry = report["variants"][name] = {"table_width": table_width}
        start = np.pad(np.asarray(init)[:, :dim],
                       ((0, 0), (0, table_width - dim)))
        start = (jnp.asarray(start, jnp.float32),
                 jnp.zeros((iterations,) + start.shape, jnp.float32))
        try:
            fn = common._build_fused_train_fn(
                ("kmeans_readings", name, mesh, k, iterations), None, mesh,
                0.0, 0.0, iterations, 0.0, epoch_fn=with_trail(epoch),
                bundle=True,
                check_vma=not (interpreted and name.startswith("kernel")))
            (program,) = [c.cell_contents for c in fn.__closure__
                          if hasattr(c.cell_contents, "lower")]
            t0 = time.perf_counter()
            analysis = program.lower(
                start, placed).compile().memory_analysis()
            entry["compile_s"] = time.perf_counter() - t0
            entry["temp_bytes"] = int(analysis.temp_size_in_bytes)
            entry["argument_bytes"] = int(analysis.argument_size_in_bytes)

            def fit():
                t0 = time.perf_counter()
                result = common._run_fused_train(
                    fn, start, placed, mesh, batch_preplaced=True,
                    n_rows=rows)
                return time.perf_counter() - t0, result

            entry["first_fit_s"], _r = fit()
            entry["warm_fit_s"], result = fit()
            entry["memory"] = memory()
            trace_dir = os.path.join(args.out, "trace")
            (entry["traced_fit_s"], _r), path = fit_gaps.traced(trace_dir, fit)
            program = sparse_step_trace.read_program(path)
            shutil.rmtree(trace_dir)
            entry["module_s"] = program["module_s"]
            entry["ms_an_iteration"] = (
                1e3 * program["module_s"] / iterations
                if program["module_s"] else None)
            entry["self_by_scope"] = program.get("self_by_scope")
            entry["ops"] = program["ops"][:14]
            centroids, trail = result.params
            answers[name] = {
                "centroids": np.asarray(centroids, np.float64)[:, :dim],
                "costs": np.asarray(result.losses, np.float64),
                "epochs": int(result.epochs),
                "trail": np.asarray(trail, np.float32)[:, :, :dim]}
        except Exception as exc:  # noqa: BLE001 - an OOM is a reading
            entry["error"] = repr(exc)[:600]
            entry["memory"] = memory()
        print(name, json.dumps({a: b for a, b in entry.items() if a != "ops"}),
              flush=True)

    import gc

    for name, epoch in padded:
        read(name, epoch, placed, width)
    del placed
    gc.collect()
    if width != dim and not args.skip_pr31:
        placed, report["place_unpadded_s"] = place(dim)
        report["memory_placed_unpadded"] = memory()
        for name, epoch in unpadded:
            read(name, epoch, placed, dim)
        del placed
        gc.collect()

    # -- the estimator itself, by its own rule ---------------------------------
    if args.estimator:
        from chipbench import program_kmeans
        from flink_ml_tpu import obs
        from flink_ml_tpu.table import slab_pool

        obs.enable()
        fits = []
        whole = program_kmeans.table(X)
        for _ in range(2):
            t = time.perf_counter()
            model = program_kmeans.kmeans(
                {"k": k, "maxIter": iterations, "tol": 0.0}, seed).fit(whole)
            fits.append(time.perf_counter() - t)
        answers["estimator"] = program_kmeans.fit_answer(model)
        report["estimator"] = {"fit_s": fits, "counters": {
            name: v for name, v in
            obs.registry().snapshot()["counters"].items()
            if name.startswith("train.")}}
        print("estimator", json.dumps(report["estimator"]), flush=True)
        del whole, model
        slab_pool.pool().clear()
        gc.collect()

    # -- against the plain reference, from the same init -----------------------
    table = reference.Table(X)
    ref = table.fit(seed, k, iterations)
    report["init"]["same_rows_as_reference"] = bool(np.array_equal(
        np.asarray(init, np.float32)[:, :dim],
        X[take][reference.plus_plus_rows(X[take], k, seed)]))
    report["gaps"] = {name: reference.gaps(answer, ref)
                      for name, answer in answers.items()}
    report["gaps"]["reference_bf16_control"] = reference.gaps(
        table.fit(seed, k, iterations, precision="bf16"), ref)
    # the rows rounding can move: two nearest centroids within 1e-5 relative
    c = jnp.asarray(ref["centroids"], jnp.float32)
    near = 0
    for block in table.blocks:
        d = jnp.sort(clustering._pairwise_sq_dists(block, c), axis=1)[:, :2]
        near += int(jnp.sum((d[:, 1] - d[:, 0]) < 1e-5 * d[:, 0]))
    report["borderline_share_1e-5"] = near / rows
    report["costs"] = [float(v) for v in ref["costs"]]

    with open(os.path.join(args.out, "readings.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({a: b for a, b in report.items() if a != "variants"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
