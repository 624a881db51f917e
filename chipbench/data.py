"""Rows and labels from ``--seed``: one general generator, read from the
configuration's ``data`` block.

There is no network where the benchmark runs, so the public table's values
are replaced by seeded ones of the same shape and type (listed under
``assumed`` in each configuration): float32 features ``clip(scale * z +
shift)`` with ``z`` standard normal, and a binary label from a planted linear
model over ``z`` plus noise, cut at ``label_threshold`` standard deviations.
Rows are made in a fixed number of blocks, each from its own child of the
seed, by a few threads: the same seed gives the same bytes whatever the
number of cores.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCKS = 96
THREADS = 12


def make_rows(data: dict, n_rows: int, dim: int, seed: int,
              dtype: str = "float32"):
    """(X float32 (n_rows, dim), y float32 (n_rows,)) from the seed.  Any
    other ``dtype`` of the table is refused: the generator has none."""
    if dtype != "float32":
        raise SystemExit(f"chipbench: data.make_rows makes float32 tables, "
                         f"not {dtype!r}")
    root = np.random.SeedSequence([int(seed), n_rows, dim])
    model_seed, *block_seeds = root.spawn(BLOCKS + 1)
    w_true = (np.random.default_rng(model_seed).standard_normal(dim)
              / np.sqrt(dim)).astype(np.float32)
    scale = np.float32(data["scale"])
    shift = np.float32(data["shift"])
    clip = data.get("clip")
    noise = float(data["label_noise"])
    cut = float(data["label_threshold"]) * float(np.sqrt(1.0 + noise * noise))

    X = np.empty((n_rows, dim), np.float32)
    y = np.empty((n_rows,), np.float32)
    edges = np.linspace(0, n_rows, BLOCKS + 1).astype(np.int64)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        rng = np.random.default_rng(block_seeds[i])
        block = X[lo:hi]
        rng.standard_normal(out=block, dtype=np.float32)
        margin = block @ w_true
        margin += noise * rng.standard_normal(hi - lo, dtype=np.float32)
        y[lo:hi] = margin > cut
        block *= scale
        block += shift
        if clip is not None:
            np.clip(block, clip[0], clip[1], out=block)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(BLOCKS)))
    return X, y


def standardise(X, chunk: int = 2048) -> None:
    """Standardise the columns of ``X`` in place by their own mean and sample
    standard deviation: the table of a job whose scaling was done upstream.
    The sums are taken about a pivot row and accumulate in float64."""
    n, d = X.shape
    edges = np.linspace(0, n, BLOCKS + 1).astype(np.int64)
    blocks = [(int(lo), int(hi)) for lo, hi in zip(edges[:-1], edges[1:])
              if hi > lo]
    pivot = X[0].copy()

    def moments(block):
        s, ss = np.zeros(d, np.float64), np.zeros(d, np.float64)
        for lo in range(block[0], block[1], chunk):
            xc = X[lo:min(lo + chunk, block[1])] - pivot
            s += xc.sum(axis=0, dtype=np.float64)
            xc *= xc
            ss += xc.sum(axis=0, dtype=np.float64)
        return s, ss

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        parts = list(pool.map(moments, blocks))
        s = sum(p[0] for p in parts)
        ss = sum(p[1] for p in parts)
        mean = pivot.astype(np.float64) + s / n
        std = np.sqrt(np.maximum(ss - s * s / n, 0.0) / max(n - 1, 1))
        shift = mean.astype(np.float32)
        inv = (1.0 / np.where(std > 0.0, std, 1.0)).astype(np.float32)

        def apply(block):
            for lo in range(block[0], block[1], chunk):
                part = X[lo:min(lo + chunk, block[1])]
                part -= shift
                part *= inv

        list(pool.map(apply, blocks))
