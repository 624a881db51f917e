#!/usr/bin/env python
"""The sparse formulations of ``LogisticRegression.fit`` on ONE table, by
hand on the chip (no cell):

    python scripts/sparse_routes.py --seed <n> [--keep 0.67 ...]
                                    [--thin 0.33 ...]

Makes ``criteo_sparse_lr``'s table from the seed with the benchmark's own
generator, then fits it (first fit: pack, split, place, compile; then
``--fits`` warm fits, timed on the host clock from the call to the model)
through

* the plain route as the estimator takes it (the pack lays this table
  row-regular since PR 28, and split by frequency since PR 30: the 16384
  most frequent features looked up by comparison, the rest in a cold
  list),
* the plain route's unsplit row-regular step (``plain_unsplit``) and plain
  segment-CSR, which the estimator no longer takes for this table: packed
  and trained by the builders themselves (``pack_sparse_minibatches``,
  ``train_glm_sparse``), the pack's rules lifted inside this script, no
  switch in the program.

Each ``--keep p`` then makes a RAGGED table (every stored entry of the
cell's table kept with probability ``p``) and fits it through both step
layouts by the builders, the row-regular one forced past the pack's rule
inside this script where the rule declines it: one reading on each side of
``mb x width <= _ELL_MAX_SLOT_RATIO x nnz_pad`` (both unsplit).  Each
``--thin q`` makes a table of the cell's shape with a THINNER skew (every
stored entry's feature redrawn uniformly with probability ``q``) and fits
it split and unsplit, each forced: one reading on each side of the split's
rule (``lib/common.py:_hot_split_wins``), with the hot share the pack counts.

One JSON line a route: warm fit seconds (median), stored entries a second,
the device's peak memory, the loss, and the must-be-zero counters.  Refuses
anything but a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def by_builders(name, column, y, config, row_regular, fits, split=False):
    """One layout of one CSR column, packed and trained by the builders:
    the JSON line's fields.  ``row_regular`` lifts the pack's rule on the
    row widths for this one pack (the row-regular layout even where the
    rule declines it) and ``split`` decides the frequency split in its
    rule's place."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import program
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    mesh = MLEnvironmentFactory.get_default().get_mesh()
    dim, batch = int(config["numFeatures"]), int(config["globalBatchSize"])
    program.release()
    rules = common._ELL_MAX_SLOT_RATIO, common._hot_split_wins
    shares = []

    def decide(hot_share, slots, nnz_pad):
        shares.append((hot_share, rules[1](hot_share, slots, nnz_pad)))
        return split

    t0 = time.perf_counter()
    try:
        if row_regular:
            common._ELL_MAX_SLOT_RATIO = float("inf")
        common._hot_split_wins = decide
        stack = common.pack_sparse_minibatches(
            column, y, 1, batch, dim=dim, row_regular=row_regular)
    finally:
        common._ELL_MAX_SLOT_RATIO, common._hot_split_wins = rules
    pack_s = time.perf_counter() - t0
    placed = shard_batch_prefetched(mesh, stack.batch)

    def fit():
        start = (jnp.zeros((dim,), jnp.float32), jnp.zeros((), jnp.float32))
        t = time.perf_counter()
        result = common.train_glm_sparse(
            start, stack, "logistic", mesh, 0.1, int(config["maxIter"]),
            with_intercept=True, device_batch=placed)
        return time.perf_counter() - t, result

    first_s, first = fit()
    warm = [fit() for _ in range(fits)]
    seconds = statistics.median(s for s, _r in warm)
    entries = stack.n_entries * int(config["maxIter"])
    line = {
        "route": name, "layout": "row_regular" if stack.row_regular else "segment_csr",
        "split": stack.hot_ids is not None,
        "ran": True, "pack_s": pack_s, "first_fit_s": first_s,
        "warm_fit_s": seconds, "entries_per_s": entries / seconds,
        "slots_a_step": stack.step_slots,
        "ns_a_slot": 1e9 * seconds / (stack.step_slots * len(stack.ints)),
        "same_bytes": all(np.array_equal(r.params[0], first.params[0])
                          for _s, r in warm),
        "loss": float(first.losses[-1]),
        "coef_norm": float(np.linalg.norm(np.asarray(first.params[0]))),
        "resident_bytes": int(sum(a.nbytes for a in placed)),
    }
    if shares:  # what the pack counted, and what its rule would have done
        line.update(hot_share=shares[0][0], rule_splits=shares[0][1],
                    cold_slots=stack.cold_slots)
    return line, np.asarray(first.params[0])


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--fits", type=int, default=2)
    parser.add_argument("--keep", type=float, action="append", default=[])
    parser.add_argument("--thin", type=float, action="append", default=[])
    args = parser.parse_args()

    import jax
    import numpy as np

    from chipbench import data_sparse, program, program_sparse, run
    from flink_ml_tpu import obs
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.ops.batch import CsrRows

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.stderr.write("sparse_routes: needs a TPU; refusing to run\n")
        return 3
    config = run.load_json(run.HERE, "configs", "criteo_sparse_lr.json")
    program.prepare(os.path.join(run.OUT, "sparse_routes"))
    dim = int(config["numFeatures"])
    t0 = time.perf_counter()
    indptr, indices, values, y = data_sparse.make_rows(
        config["data"], int(config["rows"]), dim, args.seed)
    entries = int(indptr[-1]) * int(config["maxIter"])
    print(json.dumps({"rows": len(y), "entries": entries,
                      "data_s": time.perf_counter() - t0,
                      "device": device.device_kind}), flush=True)
    table = program_sparse.table(dim, indptr, indices, values, y)
    program.release()
    before = program.snapshot()["counters"]

    def fit():
        estimator = program_sparse.logreg(config, 0.1, 0.0)
        t = time.perf_counter()
        answer = program.fit_answer(estimator.fit(table))
        return time.perf_counter() - t, answer

    first_s, first = fit()
    warm = [fit() for _ in range(args.fits)]
    seconds = statistics.median(s for s, _a in warm)
    after = program.snapshot()["counters"]
    line = dict(
        route="plain", ran=True, first_fit_s=first_s, warm_fit_s=seconds,
        entries_per_s=entries / seconds,
        same_bytes=all(np.array_equal(a["coef"], first["coef"])
                       for _s, a in warm),
        loss=float(first["losses"][-1]),
        coef_norm=float(np.linalg.norm(first["coef"])),
        peak_bytes=(device.memory_stats() or {}).get(
            "peak_bytes_in_use", 0),
        **{short: after.get(f"train.sparse_{short}", 0)
           - before.get(f"train.sparse_{short}", 0)
           for short in ("ell_fits", "hot_fits", "hot_declined",
                         "hot_entries", "entries")},
        hidden={k: after[k] - before.get(k, 0)
                for k in program.MUST_BE_ZERO
                if after.get(k, 0) - before.get(k, 0)},
        timings={k: round(v["total_s"], 3) for k, v in
                 obs.registry().snapshot()["timings"].items()
                 if k.startswith("phase.")})
    print(json.dumps(line), flush=True)
    table = None
    column = CsrRows(dim, indptr, indices, values)
    for name, row_regular in (("plain_unsplit", True),
                              ("plain_segment_csr", False)):
        print(json.dumps(by_builders(name, column, y, config, row_regular,
                                     args.fits)[0]), flush=True)
    rng = np.random.default_rng(args.seed)
    # thinner skews on the two sides of the split's rule
    for thin in args.thin:
        redrawn = rng.random(len(indices), dtype=np.float32) < thin
        ids = indices.copy()
        ids[redrawn] = rng.integers(0, dim, int(redrawn.sum()), np.int32)
        del redrawn
        thinned = CsrRows(dim, indptr, ids, values)
        (unsplit, ref), (split, coef) = [
            by_builders(f"thin_{thin:g}_{name}", thinned, y, config, True,
                        args.fits, split=on)
            for name, on in (("unsplit", False), ("split", True))]
        for line in (unsplit, split):
            line.update(
                thin=thin,
                split_speedup=unsplit["warm_fit_s"] / split["warm_fit_s"],
                coef_gap=float(np.linalg.norm(coef - ref)
                               / np.linalg.norm(ref)))
            print(json.dumps(line), flush=True)
        del thinned, ids
    # ragged tables on the two sides of the pack's rule on the row widths
    for keep in args.keep:
        kept = rng.random(len(indices), dtype=np.float32) < keep
        counts = np.add.reduceat(kept, indptr[:-1])
        ragged = CsrRows(dim, np.concatenate([[0], np.cumsum(counts)]),
                         indices[kept], values[kept])
        del kept
        pair = [by_builders(f"ragged_{keep:g}_{layout}", ragged, y, config,
                            row_regular, args.fits)[0]
                for layout, row_regular in (("segment_csr", False),
                                            ("row_regular", True))]
        ratio = pair[1]["slots_a_step"] / pair[0]["slots_a_step"]
        for line in pair:
            line.update(
                keep=keep, width=int(counts.max()),
                mean_width=float(counts.mean()), slot_ratio=ratio,
                rule_takes="row_regular"
                if ratio <= common._ELL_MAX_SLOT_RATIO else "segment_csr",
                row_regular_speedup=pair[0]["warm_fit_s"]
                / pair[1]["warm_fit_s"])
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
