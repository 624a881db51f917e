"""Measure the persistent-compilation-cache effect on warm-process startup.

Runs the SAME LogisticRegression fit in two fresh subprocesses sharing a
fresh cache directory: the first (cold) pays the XLA compile and populates
the cache; the second (warm) should replay executables from disk.  Prints
one JSON line:

  {"cold_first_fit_s": ..., "warm_first_fit_s": ..., "speedup": ...,
   "cache_entries": N, "cache_bytes": B}

The reference's JVM equivalent starts in milliseconds every run;
`first_fit_s` is this framework's startup tax, and the warm number is what
every process after the first actually pays.  The parent never imports
JAX (a chip belongs to one process — the children need it), and places the
cache through JAX's own ``JAX_COMPILATION_CACHE_DIR``.

Usage: python scripts/compile_cache_warmstart.py [--cpu] [--rows N] [--dim D]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

CHILD = r"""
import json, sys, time
import jax
if {cpu!r} == "cpu":
    jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import flink_ml_tpu  # sets the cache thresholds; JAX reads the directory
from flink_ml_tpu.lib import LogisticRegression
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

rng = np.random.RandomState(0)
n, d = {rows}, {dim}
X = rng.randn(n, d).astype(np.float32)
w = rng.randn(d).astype(np.float32)
y = (X @ w > 0).astype(np.float32)
schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
table = Table.from_columns(schema, {{"features": X, "label": y}})

t0 = time.perf_counter()
model = (LogisticRegression().set_vector_col("features")
         .set_label_col("label").set_prediction_col("p")
         .set_global_batch_size(8192).set_max_iter(3).fit(table))
first_fit_s = time.perf_counter() - t0
print(json.dumps({{"first_fit_s": first_fit_s}}))
"""


def run_child(cache_dir: str, cpu: bool, rows: int, dim: int) -> float:
    env = dict(os.environ)
    env.pop("FMT_COMPILE_CACHE", None)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    code = CHILD.format(
        cpu="cpu" if cpu else "", repo=str(Path(__file__).parent.parent),
        rows=rows, dim=dim,
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        check=False,
    )
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise SystemExit(f"child failed ({out.returncode})")
    return float(json.loads(out.stdout.strip().splitlines()[-1])["first_fit_s"])


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--rows", type=int, default=100_000)
    p.add_argument("--dim", type=int, default=28)
    args = p.parse_args()

    with tempfile.TemporaryDirectory(prefix="fmt_xla_cache_") as cache_dir:
        cold = run_child(cache_dir, args.cpu, args.rows, args.dim)
        warm = run_child(cache_dir, args.cpu, args.rows, args.dim)
        entries = list(Path(cache_dir).rglob("*"))
        files = [e for e in entries if e.is_file()]
        print(json.dumps({
            "cold_first_fit_s": round(cold, 2),
            "warm_first_fit_s": round(warm, 2),
            "speedup": round(cold / max(warm, 1e-9), 2),
            "cache_entries": len(files),
            "cache_bytes": sum(e.stat().st_size for e in files),
            "backend": "cpu" if args.cpu else "default",
        }))


if __name__ == "__main__":
    main()
