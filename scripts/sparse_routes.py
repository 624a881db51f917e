#!/usr/bin/env python
"""The three sparse formulations of ``LogisticRegression.fit`` on ONE table,
by hand on the chip (ROADMAP D1 waits for these numbers; no cell):

    python scripts/sparse_routes.py --seed <n> [--hot 4096]

Makes ``criteo_sparse_lr``'s table from the seed with the benchmark's own
generator, then fits it (first fit: pack, split, place, compile; then
``--fits`` warm fits, timed on the host clock from the call to the model)
through

* the plain segment-CSR route (``numHotFeatures`` unset: the default),
* hot/cold with ``hotSlabMode`` ``stream`` (the hot columns densified inside
  the program, a step at a time),
* hot/cold with ``hotSlabMode`` ``resident`` (the hot columns as bf16 slabs
  on the device) where the slabs fit the chip: ``rows x hot x 2`` bytes, by
  the program's own ``hotcold_slab_bytes``; a slab that cannot fit is
  reported, not tried.

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


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--hot", type=int, default=4096)
    parser.add_argument("--fits", type=int, default=2)
    args = parser.parse_args()

    import jax
    import numpy as np

    from chipbench import data_sparse, program, program_sparse, run
    from flink_ml_tpu import obs
    from flink_ml_tpu.lib.common import hotcold_slab_bytes

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.stderr.write("sparse_routes: needs a TPU; refusing to run\n")
        return 3
    config = run.load_json(run.HERE, "configs", "criteo_sparse_lr.json")
    program.prepare(os.path.join(run.OUT, "sparse_routes"))
    dim, batch = int(config["numFeatures"]), int(config["globalBatchSize"])
    t0 = time.perf_counter()
    indptr, indices, values, y = data_sparse.make_rows(
        config["data"], int(config["rows"]), dim, args.seed)
    entries = int(indptr[-1]) * int(config["maxIter"])
    print(json.dumps({"rows": len(y), "entries": entries,
                      "data_s": time.perf_counter() - t0,
                      "device": device.device_kind}), flush=True)
    limit = (device.memory_stats() or {}).get("bytes_limit", 0)
    padded_rows = -(-len(y) // batch) * batch
    slab = hotcold_slab_bytes(padded_rows, args.hot)
    routes = [("plain", None, None), ("hotcold_stream", args.hot, "stream")]
    if limit and slab > 0.8 * limit:
        print(json.dumps({"route": "hotcold_resident", "hot": args.hot,
                          "slab_bytes": slab, "device_bytes": limit,
                          "ran": False, "why": "the slab does not fit"}),
              flush=True)
    else:
        routes.append(("hotcold_resident", args.hot, "resident"))
    # one table: the routes share its segment-CSR pack (and nothing placed)
    table = program_sparse.table(dim, indptr, indices, values, y)
    for name, hot, mode in routes:
        program.release()
        before = program.snapshot()["counters"]

        def fit():
            estimator = program_sparse.logreg(config, 0.1, 0.0)
            if hot:
                estimator = (estimator.set_num_hot_features(hot)
                             .set_hot_slab_mode(mode))
            t = time.perf_counter()
            answer = program.fit_answer(estimator.fit(table))
            return time.perf_counter() - t, answer

        line = {"route": name, "hot": hot}
        try:
            first_s, first = fit()
            warm = [fit() for _ in range(args.fits)]
        except Exception as exc:  # noqa: BLE001 - reported, next route
            line.update(ran=False, error=repr(exc)[:300])
            print(json.dumps(line), flush=True)
            continue
        seconds = statistics.median(s for s, _a in warm)
        after = program.snapshot()["counters"]
        line.update(
            ran=True, first_fit_s=first_s, warm_fit_s=seconds,
            entries_per_s=entries / seconds,
            same_bytes=all(np.array_equal(a["coef"], first["coef"])
                           for _s, a in warm),
            loss=float(first["losses"][-1]),
            coef_norm=float(np.linalg.norm(first["coef"])),
            peak_bytes=(device.memory_stats() or {}).get(
                "peak_bytes_in_use", 0),
            hidden={k: after[k] - before.get(k, 0)
                    for k in program.MUST_BE_ZERO
                    if after.get(k, 0) - before.get(k, 0)},
            timings={k: round(v["total_s"], 3) for k, v in
                     obs.registry().snapshot()["timings"].items()
                     if k.startswith("phase.")})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
