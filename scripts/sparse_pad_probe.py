#!/usr/bin/env python
"""Does a segment-CSR fit's time depend on the residue of its steps' padded
length?  The reading behind ``lib/common.py:padded_nnz`` (PR 33).

    chiprun -- python scripts/sparse_pad_probe.py <seed> 0 512 1024 1536 2560

One ragged table (``url_ragged_lr``'s, from the seed; ``PROBE_ROWS`` cuts it
for a rehearsal), packed segment-CSR at a floor of its natural width plus
each of the given extras; three warm fits timed and one traced each, device
seconds by ``fmt.train*`` scope.  On a TPU v5e the two takes read 6.63 ns an
address at an odd multiple of 512 and 7.13 at a multiple of 1024, the row
sum and the scatter the same (PERF.md section 5).  One JSON line a width on
standard output; times mean something only on the chip.
"""
import gc, json, os, shutil, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))
import jax.numpy as jnp
import fit_gaps, sparse_step_trace
from chipbench import data_ragged, run
from flink_ml_tpu.lib import common
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

seed = int(sys.argv[1]); extras = [int(a) for a in sys.argv[2:]]
config = run.load_json(run.HERE, "configs", "url_ragged_lr.json")
dim, batch = int(config["numFeatures"]), int(config["globalBatchSize"])
rows = int(os.environ.get("PROBE_ROWS", config["rows"]))
indptr, indices, values, y = data_ragged.make_rows(config["data"], rows, dim, seed)
mesh = MLEnvironmentFactory.get_default().get_mesh()
col = CsrRows(dim, indptr, indices, values)
natural = common.sparse_layout_floors(col.nnz_per_row(), 1, batch)[0]
out = os.path.join(ROOT, "chiprun_out", "pr33", "pad_probe"); os.makedirs(out, exist_ok=True)
for extra in extras:
    stack = common.pack_sparse_minibatches(col, y, 1, batch, dim=dim, min_nnz_pad=natural + extra)
    placed = shard_batch_prefetched(mesh, stack.batch)
    def fit():
        start = (jnp.zeros((dim,), jnp.float32), jnp.zeros((), jnp.float32))
        t = time.perf_counter()
        common.train_glm_sparse(start, stack, "logistic", mesh, 0.1, 1, reg=1e-4, device_batch=placed)
        return time.perf_counter() - t
    times = [fit() for _ in range(3)]
    trace_dir = os.path.join(out, "trace")
    traced, path = fit_gaps.traced(trace_dir, fit)
    prog = sparse_step_trace.read_program(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"seed": seed, "natural": natural, "nnz_pad": stack.nnz_pad, "over_512": stack.nnz_pad // 512,
                      "mod_1024": stack.nnz_pad % 1024, "mod_2048": stack.nnz_pad % 2048, "mod_4096": stack.nnz_pad % 4096,
                      "fits_s": times, "module_s": prog["module_s"], "by_scope": prog.get("self_by_scope")}), flush=True)
    del placed, stack; gc.collect()
