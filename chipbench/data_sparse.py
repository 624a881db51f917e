"""Sparse click-log rows and labels from ``--seed``: the generator of the
hashed-categorical configurations (``criteo_sparse_lr``), read from the
configuration's ``data`` block.

There is no network where the benchmark runs, so the public file's values are
replaced by seeded ones that keep its shape: every row stores one entry a
field (``len(categorical_cardinalities) + numeric_fields`` of them, 39 for
Criteo's 26 + 13), every value is ``1 / sqrt(entries a row)`` (rows of unit
length), and an entry's feature is the fixed integer hash of (field, category)
into ``[0, dim)``.  A categorical field draws its category from a bounded
power law over its own cardinality (``P(rank k)`` proportional to the integral
of ``x ** -zipf_exponent`` over ``[k, k + 1)``: a Zipf law, by inverse CDF), a
numeric field one of ``numeric_bins`` bins, geometric.  Collisions of the hash
inside a row stay two entries.  The label comes from a planted linear model
over the hashed features plus noise, cut at the margin's own quantile so that
``positive_share`` of the rows are positive.  Rows are made in a fixed number
of blocks, each from its own child of the seed, by a few threads: the same
seed gives the same bytes whatever the number of cores.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCKS = 96
THREADS = 12
_MIX_A = np.uint64(0x9E3779B97F4A7C15)
_MIX_B = np.uint64(0xBF58476D1CE4E5B9)
_MIX_C = np.uint64(0x94D049BB133111EB)


def hash_slots(field: int, category: np.ndarray, dim: int) -> np.ndarray:
    """The fixed integer hash: (field, category) -> a slot in ``[0, dim)``
    (a splitmix64 round over ``category * A + (field + 1) * B``, its high 32
    bits scaled to ``dim``)."""
    x = category.astype(np.uint64)
    x *= _MIX_A
    x += np.uint64(((field + 1) * int(_MIX_B)) & 0xFFFFFFFFFFFFFFFF)
    x ^= x >> np.uint64(31)
    x *= _MIX_C
    x ^= x >> np.uint64(29)
    x >>= np.uint64(32)
    x *= np.uint64(dim)
    x >>= np.uint64(32)
    return x.astype(np.int32)


def entries_per_row(data: dict) -> int:
    return len(data["categorical_cardinalities"]) + int(data["numeric_fields"])


def make_rows(data: dict, n_rows: int, dim: int, seed: int,
              dtype: str = "float32"):
    """(indptr int64 (n_rows + 1,), indices int32, values float32, y float32
    (n_rows,)) from the seed: CSR rows of ``entries_per_row(data)`` entries
    each, ascending within a row.  Any other ``dtype`` of the values is
    refused: the generator has none."""
    if dtype != "float32":
        raise SystemExit(f"chipbench: data_sparse.make_rows makes float32 "
                         f"values, not {dtype!r}")
    cards = [int(c) for c in data["categorical_cardinalities"]]
    numeric = int(data["numeric_fields"])
    width = len(cards) + numeric
    exponent = float(data["zipf_exponent"])
    bins, bin_p = int(data["numeric_bins"]), float(data["numeric_bin_p"])
    noise = float(data["label_noise"])
    share = float(data["positive_share"])
    if exponent <= 1.0 or not 0.0 < share < 1.0:
        raise SystemExit("chipbench: data_sparse needs zipf_exponent > 1 and "
                         "0 < positive_share < 1")

    root = np.random.SeedSequence([int(seed), n_rows, dim, width])
    model_seed, *block_seeds = root.spawn(BLOCKS + 1)
    w_true = np.random.default_rng(model_seed).standard_normal(
        dim, dtype=np.float32)
    value = np.float32(1.0 / np.sqrt(width))

    indices = np.empty((n_rows, width), np.int32)
    margin = np.empty((n_rows,), np.float32)
    edges = np.linspace(0, n_rows, BLOCKS + 1).astype(np.int64)
    power = 1.0 - exponent  # < 0
    log_keep = np.log1p(-bin_p)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        if hi == lo:
            return
        rng = np.random.default_rng(block_seeds[i])
        u = rng.random((width, hi - lo))  # a field a row: contiguous
        slots = np.empty((width, hi - lo), np.int32)
        for f, card in enumerate(cards):
            x = u[f]
            # inverse CDF of x ** -exponent on [1, card + 1)
            x *= (card + 1.0) ** power - 1.0
            x += 1.0
            np.power(x, 1.0 / power, out=x)
            rank = np.minimum(x.astype(np.int64), card) - 1
            slots[f] = hash_slots(f, rank, dim)
        for f in range(len(cards), width):
            x = u[f]
            np.subtract(1.0, x, out=x)  # (0, 1]
            np.log(x, out=x)
            x /= log_keep
            slots[f] = hash_slots(f, np.minimum(x.astype(np.int64), bins - 1),
                                  dim)
        block = indices[lo:hi]
        block[:] = slots.T
        block.sort(axis=1)
        m = w_true[block].sum(axis=1, dtype=np.float32)
        m *= value
        m += noise * rng.standard_normal(hi - lo, dtype=np.float32)
        margin[lo:hi] = m

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(BLOCKS)))
    # the cut is the margin's own quantile: the share of positives is exact
    k = min(max(int(round((1.0 - share) * n_rows)), 0), n_rows - 1)
    cut = np.partition(margin.copy(), k)[k]
    y = (margin > cut).astype(np.float32)
    indptr = np.arange(n_rows + 1, dtype=np.int64) * width
    values = np.full((n_rows * width,), value, np.float32)
    return indptr, indices.reshape(-1), values, y
