#!/usr/bin/env python
"""One traced warm fit of the sparse cell's table, every device operation of
its program listed: the per-operation split of PERF.md §5's sparse cell.

    python scripts/sparse_step_trace.py --seed <n> [--rows N]
                                        [--config criteo_sparse_lr]
                                        [--segment-csr | --unsplit]
                                        [--classes N] [--out DIR]

The benchmark's breakdown (``chipbench/trace_reduce.py``) keeps one of two
programs' operations where both name one alike (PERF.md §7 (e)), so half the
sparse cell's scatter is missing from its ``device_ops``.  This script holds
its own profiler session (``scripts/fit_gaps.py:traced``) around ONE fit of
ONE program, by the builders (``pack_sparse_minibatches``,
``train_glm_sparse``; the row-regular layout as the pack picks it: split by
frequency on this table since PR 30; its unsplit step with ``--unsplit``:
the split's rule lifted inside this script; or segment-CSR with
``--segment-csr``: the pack without ``row_regular``), and reads from the
profile

* ``module_s``       the ``jit_bundled`` program's device time, and how much
                     of it the listed operations cover;
* ``ops``            every operation inside it: count, inclusive and self
                     seconds, and its ``fmt.train*`` scope (the event
                     metadata's ``tf_op``, ``fit_gaps.py:op_metadata``);
* ``self_by_scope``  self seconds summed by scope: forward, backward, the
                     step's slices (``fmt.train``), the update;
* ``step_parts_ms``  the same by the step's parts, milliseconds a step
                     (over the steps the fit ran).  On the split step (PR
                     36): ``cold_take``
                     (``.take_weights``: the ONE take over the cold list),
                     ``cold_scatter`` (``.scatter``), ``planes`` (what lies
                     under ``.forward`` / ``.backward`` themselves: the
                     planes' slices and sum, the error's writes),
                     ``kernels`` (``.hot``: both Pallas calls, the hot
                     weights' take, the scatter of their sums), ``rest``;
                     on segment-CSR also ``row_sum`` and ``take_error``;
                     on the classed split (PR 43) also ``orders``
                     (``.orders``: the four takes of ``mb`` between the
                     table's order of rows and each part's), and
                     ``planes`` are the cold list's two loops;
* ``one_step``       the operations of one step in order, microseconds from
                     the step's start (a step: between two starts of the
                     dearest operation that runs once a step).

Data are made from ``--seed`` by the benchmark's generator at the
configuration's size (``--rows`` cuts it for a rehearsal); ``--config
url_ragged_lr`` takes the ragged table (PR 33), which the pack lays
row-regular in width classes by its own rule since PR 34 (a step's rows
ordered by width), and since PR 43 splits by frequency where the features'
counts pass the split's rule (on a TPU): the hot entries in blocks of a row
tile read by the two kernels, the cold list's take and scatter under
``fmt.train.sparse.take_weights`` and ``.scatter``.  ``--unsplit`` lifts the
split's rule inside this script and lays the classed step as the parent
did; ``--classes N`` moves the cap on the classes' number and lays that
unsplit step too, at N classes.  With ``--segment-csr``
the same table is laid as the parent laid it, and segment-CSR's four read
under ``.take_weights``, ``.row_sum``, ``.take_error`` and ``.scatter``: one
tree gives both sides.  A summary goes to standard output, everything to
``<--out>/<config>.<layout>.json`` (``chiprun_out/sparse_step_trace/``
unless given).
Runs on whatever JAX finds; times mean something only on the chip.
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


def _short(name):
    """``%fusion.22 = ...`` -> ``fusion.22``."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


#: a step's parts by the innermost ``fmt.*`` scope of an operation
PARTS = {"fmt.train.sparse.take_weights": "cold_take",
         "fmt.train.sparse.scatter": "cold_scatter",
         "fmt.train.sparse.row_sum": "row_sum",
         "fmt.train.sparse.take_error": "take_error",
         "fmt.train.sparse.hot": "kernels",
         "fmt.train.sparse.orders": "orders",
         "fmt.train.sparse.forward": "planes",
         "fmt.train.sparse.backward": "planes"}


def read_program(path, steps):
    """The report's fields from the profile at ``path``: the longest
    ``jit_bundled`` module and the operations that lie inside it, a fit of
    ``steps`` steps."""
    import fit_gaps

    _host, ops, modules = fit_gaps.read_profile(path)
    bundled = [m for m in modules if m[0].startswith("jit_bundled")]
    if not bundled:  # off the chip the profile holds no device plane
        return {"module_s": None, "ops": [], "one_step": []}
    _name, lo, hi = max(bundled, key=lambda m: m[2] - m[1])
    inside = [e for e in ops if lo <= e[1] and e[2] <= hi]
    inclusive, count = {}, {}
    for name, a, b in inside:
        key = _short(name)
        inclusive[key] = inclusive.get(key, 0.0) + (b - a) / 1e9
        count[key] = count.get(key, 0) + 1
    # summed by short name: two texts of one name are one operation here
    self_s = {}
    for name, ns in fit_gaps._self_times(inside).items():
        self_s[_short(name)] = self_s.get(_short(name), 0.0) + ns / 1e9
    scope = {_short(k): fit_gaps.scope_of(v.get("tf_op", ""))
             for k, v in fit_gaps.op_metadata(path).items()}
    by_scope = {}
    for key, s in self_s.items():
        by_scope[scope.get(key, "")] = by_scope.get(scope.get(key, ""), 0.0) + s
    covered = sum(b - a for a, b in fit_gaps._union(
        [(a, b) for _n, a, b in inside])) / 1e9
    # a step's parts: the scopes' seconds over the steps run; one step:
    # between two neighbouring starts of the dearest operation that runs
    # once a step (an operation inside a loop of the step runs more often)
    parts = {}
    for name, s in by_scope.items():
        part = PARTS.get(name, "rest")
        parts[part] = parts.get(part, 0.0) + 1e3 * s / steps
    anchor = max((k for k in inclusive if count[k] == steps),
                 key=inclusive.get, default=None)
    step_s, one_step = None, []
    if anchor is not None and steps > 2:
        starts = sorted(a for n, a, _b in inside if _short(n) == anchor)
        s_lo, s_hi = starts[len(starts) // 2], starts[len(starts) // 2 + 1]
        step_s = (s_hi - s_lo) / 1e9
        one_step = [
            [_short(n), round((a - s_lo) / 1e3, 1), round((b - a) / 1e3, 1),
             scope.get(_short(n), "")]
            for n, a, b in sorted(inside, key=lambda e: (e[1], -e[2]))
            if s_lo <= a < s_hi]
    return {
        "module_s": (hi - lo) / 1e9, "covered_s": covered,
        "n_ops": len(inside), "self_by_scope": by_scope,
        "step_parts_ms": parts,
        "ops": sorted(([k, count[k], inclusive[k], self_s.get(k, 0.0),
                        scope.get(k, "")] for k in inclusive),
                      key=lambda row: -row[2]),
        "anchor": anchor, "step_s": step_s, "one_step": one_step,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--rows", type=int, default=0)
    parser.add_argument("--config", default="criteo_sparse_lr")
    parser.add_argument("--segment-csr", action="store_true")
    parser.add_argument("--unsplit", action="store_true")
    parser.add_argument("--classes", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sparse_step_trace"))
    args = parser.parse_args(argv)

    import jax.numpy as jnp

    import fit_gaps
    from chipbench import data_ragged, data_sparse, run
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    config = run.load_json(run.HERE, "configs", args.config + ".json")
    dim, batch = int(config["numFeatures"]), int(config["globalBatchSize"])
    maker = data_ragged if "width_sigma" in config["data"] else data_sparse
    indptr, indices, values, y = maker.make_rows(
        config["data"], args.rows or int(config["rows"]), dim, args.seed)
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    if args.unsplit or args.classes:
        common._hot_split_wins = lambda *a: False
    if args.classes:
        common._ELL_MAX_CLASSES = args.classes
    t = time.perf_counter()
    stack = common.pack_sparse_minibatches(
        CsrRows(dim, indptr, indices, values), y, len(mesh.devices.flat),
        batch, dim=dim, row_regular=not args.segment_csr)
    pack_s = time.perf_counter() - t
    layout = "row_regular" if stack.row_regular else "segment_csr"
    if stack.hot_ids is not None:
        layout += "_split"
    if stack.ell_classes > 1:
        layout += f"_classed{stack.ell_classes}"
    placed = shard_batch_prefetched(mesh, stack.batch)

    def fit():
        start = (jnp.zeros((dim,), jnp.float32), jnp.zeros((), jnp.float32))
        t = time.perf_counter()
        common.train_glm_sparse(
            start, stack, "logistic", mesh, 0.1, int(config["maxIter"]),
            device_batch=placed)
        return time.perf_counter() - t

    report = {"layout": layout, "step_slots": stack.step_slots,
              "cold_slots": getattr(stack, "cold_slots", 0),
              "hot_slots": getattr(stack, "hot_slots", 0),
              "hot_entries": getattr(stack, "n_hot_entries", 0),
              "entries": stack.n_entries,
              "classes": getattr(stack, "classes", None),
              "pack_s": pack_s,
              "steps": len(stack.ints), "first_fit_s": fit(),
              "warm_fit_s": fit()}
    os.makedirs(args.out, exist_ok=True)
    trace_dir = os.path.join(args.out, "trace")
    report["traced_fit_s"], path = fit_gaps.traced(trace_dir, fit)
    report.update(read_program(path, stack.steps * int(config["maxIter"])))
    shutil.rmtree(trace_dir)  # read; the report is what goes back
    with open(os.path.join(args.out, f"{args.config}.{layout}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("ops", "one_step")}))
    for row in report["ops"][:25]:
        print(row)
    for row in report["one_step"]:
        print(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
