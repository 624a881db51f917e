"""Ragged bag-of-words rows and labels from ``--seed``: the generator of the
ragged sparse configurations (``url_ragged_lr``), read from the
configuration's ``data`` block.

There is no network where the benchmark runs, so the public file's values are
replaced by seeded ones that keep its shape: rows in time order over ``days``
days (a day holds an equal share of the rows); a row's WIDTH, its stored
entry count, is log-normal (``width_sigma``) about its day's mean, which
rises linearly from ``width_mean_day0`` by ``width_growth`` over the days, and
is clipped to ``[width_min, width_max]``; the first ``real_features`` ids are
real-valued, each stored in a row with probability ``real_share`` at a value
uniform in (0, 1] (a row narrower than its real-valued draws keeps the lowest
ids of them); the rest of the row is binary features, DISTINCT ids drawn from
a bounded power law (``P(rank k)`` proportional to the integral of ``x **
-zipf_exponent`` over ``[k, k + 1)``: a Zipf law, by inverse CDF) over the
vocabulary alive on the row's day, which grows linearly from
``vocabulary_day0`` of the binary ids to all of them on the last day.  A
duplicate draw is dropped and drawn again until the row holds its width: a
binary feature is stored once.  Rank ``k`` is id ``real_features + k - 1``
(the public file numbers a feature when it first appears, so the frequent
ones are the low ids and the tail keeps arriving).  Indices ascend within a
row, as the LIBSVM format has them.  Every row is then scaled to unit
Euclidean length.  The label comes from a planted linear model over the
features plus noise, cut at the margin's own quantile so that
``positive_share`` of the rows are positive.

The widths come from one child of the seed (``row_widths``: the table's
``indptr`` is known before a row is made), every day's rows from a child of
their own, by a few threads, each into its own slice of the table: the same
seed gives the same bytes whatever the number of cores.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 12
#: rounds of drawing again what a row lacks; a row of 512 distinct ids out of
#: 646,000 needs a few dozen at the very most
_MAX_ROUNDS = 200


def _seeds(data: dict, n_rows: int, dim: int, seed: int):
    """(model seed, width seed, one seed a day), children of ``seed``."""
    days = int(data["days"])
    root = np.random.SeedSequence([int(seed), int(n_rows), int(dim), days])
    model_seed, width_seed, *day_seeds = root.spawn(days + 2)
    return model_seed, width_seed, day_seeds


def day_edges(data: dict, n_rows: int) -> np.ndarray:
    """Row number at which each day starts, and ``n_rows`` last: int64
    ``(days + 1,)``."""
    return np.linspace(0, n_rows, int(data["days"]) + 1).astype(np.int64)


def vocabulary(data: dict, dim: int) -> np.ndarray:
    """Binary ids alive on each day: int64 ``(days,)``, rising linearly from
    ``vocabulary_day0`` of the ``dim - real_features`` binary ids to all."""
    days, real = int(data["days"]), int(data["real_features"])
    first = float(data["vocabulary_day0"])
    share = first + (1.0 - first) * np.arange(days) / max(days - 1, 1)
    return np.maximum((share * (dim - real)).astype(np.int64),
                      int(data["width_max"]))


def row_widths(data: dict, n_rows: int, dim: int, seed: int) -> np.ndarray:
    """Stored entries of every row, int64 ``(n_rows,)``, in time order."""
    days = int(data["days"])
    _model, width_seed, _days = _seeds(data, n_rows, dim, seed)
    sigma = float(data["width_sigma"])
    growth = 1.0 + float(data["width_growth"]) * np.arange(days) \
        / max(days - 1, 1)
    mean = float(data["width_mean_day0"]) * np.repeat(
        growth, np.diff(day_edges(data, n_rows)))
    z = np.random.default_rng(width_seed).standard_normal(n_rows)
    # the day's mean is the law's MEAN (exp(sigma z) has mean exp(sigma^2/2))
    widths = np.rint(mean * np.exp(sigma * z - 0.5 * sigma * sigma))
    return np.clip(widths, int(data["width_min"]),
                   int(data["width_max"])).astype(np.int64)


def _zipf_ids(rng, count: int, alive: int, power: float, first_id: int):
    """``count`` ids of binary features, int64: ranks from the bounded power
    law over ``[1, alive]`` by inverse CDF, rank k being ``first_id + k - 1``."""
    x = rng.random(count)
    x *= (alive + 1.0) ** power - 1.0
    x += 1.0
    np.power(x, 1.0 / power, out=x)
    rank = np.minimum(x.astype(np.int64), alive)
    rank += first_id - 1
    return rank


def _distinct_binary(rng, wanted: np.ndarray, alive: int, power: float,
                     first_id: int) -> np.ndarray:
    """Keys ``row << 32 | id``, sorted, holding exactly ``wanted[row]``
    distinct ids a row: what a row lacks is drawn again until none lacks."""
    rows = np.arange(len(wanted), dtype=np.int64) << 32
    have = np.empty(0, np.int64)
    lacking = wanted.astype(np.int64)
    for _ in range(_MAX_ROUNDS):
        total = int(lacking.sum())
        if not total:
            return have
        drawn = _zipf_ids(rng, total, alive, power, first_id)
        drawn += np.repeat(rows, lacking)
        drawn = np.unique(drawn)
        if len(have):
            at = np.searchsorted(have, drawn)
            at[at == len(have)] = 0
            drawn = drawn[have[at] != drawn]
            have = np.concatenate([have, drawn])
            have.sort(kind="stable")  # two sorted runs: a merge
        else:
            have = drawn
        lacking = wanted - np.bincount(have >> 32, minlength=len(wanted))
    raise SystemExit("chipbench: data_ragged could not fill a row with "
                     "distinct ids: the vocabulary is too small for the width")


def make_rows(data: dict, n_rows: int, dim: int, seed: int,
              dtype: str = "float32"):
    """(indptr int64 (n_rows + 1,), indices int32, values float32, y float32
    (n_rows,)) from the seed: CSR rows of ragged width, ids ascending and
    distinct within a row, every row of unit length.  Any other ``dtype`` of
    the values is refused: the generator has none."""
    if dtype != "float32":
        raise SystemExit(f"chipbench: data_ragged.make_rows makes float32 "
                         f"values, not {dtype!r}")
    real = int(data["real_features"])
    real_share = float(data["real_share"])
    exponent = float(data["zipf_exponent"])
    noise = float(data["label_noise"])
    share = float(data["positive_share"])
    if exponent <= 1.0 or not 0.0 < share < 1.0:
        raise SystemExit("chipbench: data_ragged needs zipf_exponent > 1 and "
                         "0 < positive_share < 1")
    if dim - real < int(data["width_max"]):
        raise SystemExit("chipbench: data_ragged needs more binary features "
                         "than the widest row stores")
    power = 1.0 - exponent  # < 0
    model_seed, _width_seed, day_seeds = _seeds(data, n_rows, dim, seed)
    w_true = np.random.default_rng(model_seed).standard_normal(
        dim, dtype=np.float32)
    widths = row_widths(data, n_rows, dim, seed)
    indptr = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    edges = day_edges(data, n_rows)
    alive = vocabulary(data, dim)
    indices = np.empty(int(indptr[-1]), np.int32)
    values = np.empty(int(indptr[-1]), np.float32)
    margin = np.empty(n_rows, np.float32)

    def fill(day):
        lo, hi = int(edges[day]), int(edges[day + 1])
        m = hi - lo
        if not m:
            return
        rng = np.random.default_rng(day_seeds[day])
        width = widths[lo:hi]
        start = indptr[lo:hi] - indptr[lo]
        # the real-valued features: each stored with probability real_share,
        # the lowest ids of them where the row is narrower than its draws
        stored = rng.random((m, real)) < real_share
        stored &= np.cumsum(stored, axis=1) <= width[:, None]
        n_real = stored.sum(axis=1)
        real_row, real_id = np.nonzero(stored)  # row-major: ids ascend
        real_val = 1.0 - rng.random(len(real_row))  # (0, 1]
        keys = _distinct_binary(rng, width - n_real, int(alive[day]), power,
                                real)
        bin_row = keys >> 32
        ids = np.empty(int(width.sum()), np.int32)
        vals = np.ones(len(ids), np.float64)
        real_at = start[real_row] + (np.arange(len(real_row)) - np.repeat(
            np.cumsum(n_real) - n_real, n_real))
        ids[real_at] = real_id
        vals[real_at] = real_val
        n_bin = width - n_real
        bin_at = start[bin_row] + n_real[bin_row] + (
            np.arange(len(keys)) - np.repeat(np.cumsum(n_bin) - n_bin, n_bin))
        ids[bin_at] = keys & 0xFFFFFFFF
        # unit Euclidean length, row by row
        row = np.repeat(np.arange(m), width)
        vals /= np.sqrt(np.bincount(row, weights=vals * vals, minlength=m))[row]
        vals32 = vals.astype(np.float32)
        indices[indptr[lo]:indptr[hi]] = ids
        values[indptr[lo]:indptr[hi]] = vals32
        m_rows = np.bincount(row, weights=vals32 * w_true[ids], minlength=m)
        m_rows += noise * rng.standard_normal(m)
        margin[lo:hi] = m_rows

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(int(data["days"]))))
    # the cut is the margin's own quantile: the share of positives is exact
    k = min(max(int(round((1.0 - share) * n_rows)), 0), n_rows - 1)
    cut = np.partition(margin.copy(), k)[k]
    y = (margin > cut).astype(np.float32)
    return indptr, indices, values, y
