"""Rows with clusters from ``--seed``: the generator of the centroid fit's
table, read from the configuration's ``data`` block.

``data.py`` draws every pixel independently, and a table without clusters
makes every row a borderline row of k-means.  This one keeps the source's
shape (``mnist8m``: 784 columns, about a fifth of the pixels non-zero, values
clipped to 0..255) and gives it the source's structure: ``classes`` digit
classes, each of ``styles_per_class`` planted "styles" (ways of writing the
digit) of unequal weight.  A row is drawn as

    clip(shift + T[class] + (a[style] + deform * u) @ B + noise * z, lo, hi)

with ``T`` a template a class (every pixel its own draw: classes lie far
apart), ``B`` a basis of ``deform_rank`` deformation directions shared by all
rows, ``a[style]`` the style's place in that subspace, and ``u``, ``z``
standard normal a row: inside the subspace a class is a cloud of overlapping
blobs (neighbouring styles lie a few widths apart), with more
components in all than a fit has centroids, so that the planted centres are
not the answer.  A style's weight is a Dirichlet draw.  Values are real, not
whole numbers: a table stored or multiplied in bfloat16 is another table.

Rows are made in a fixed number of blocks, each from its own child of the
seed, by a few threads: the same seed gives the same bytes whatever the
number of cores, as ``data.py``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCKS = 96
THREADS = 12
#: rows drawn at a time inside a block: the temporaries stay in cache
CHUNK = 4096


def planted(data: dict, dim: int, seed: int) -> dict:
    """What the seed plants, before any row: the templates, the basis, the
    styles' places, classes and weights."""
    classes, styles = int(data["classes"]), int(data["styles_per_class"])
    rank = int(data["deform_rank"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), dim, 11]))
    templates = (float(data["class_scale"])
                 * rng.standard_normal((classes, dim))).astype(np.float32)
    basis = (float(data["deform_scale"])
             * rng.standard_normal((rank, dim))).astype(np.float32)
    places = rng.standard_normal((classes * styles, rank)).astype(np.float32)
    weights = rng.dirichlet(
        np.full(classes * styles, float(data["weight_concentration"])))
    return {
        "templates": templates, "basis": basis, "places": places,
        "style_class": np.repeat(np.arange(classes), styles),
        "weights": weights,
        # a style's mean before the clip: what a row scatters about
        "means": (np.float32(data["shift"]) + np.repeat(templates, styles, 0)
                  + places @ basis).astype(np.float32),
    }


def make_rows(data: dict, n_rows: int, dim: int, seed: int,
              dtype: str = "float32"):
    """(X float32 (n_rows, dim), style int32 (n_rows,)) from the seed.  Any
    other ``dtype`` of the table is refused: the generator has none."""
    if dtype != "float32":
        raise SystemExit(f"chipbench: data_mixture.make_rows makes float32 "
                         f"tables, not {dtype!r}")
    plant = planted(data, dim, seed)
    means, basis = plant["means"], plant["basis"]
    cumulative = np.cumsum(plant["weights"])
    cumulative[-1] = 1.0
    deform = np.float32(data["deform"])
    noise = np.float32(data["noise"])
    lo, hi = data["clip"]
    rank = basis.shape[0]

    block_seeds = np.random.SeedSequence([int(seed), n_rows, dim]).spawn(BLOCKS)
    X = np.empty((n_rows, dim), np.float32)
    style = np.empty((n_rows,), np.int32)
    edges = np.linspace(0, n_rows, BLOCKS + 1).astype(np.int64)

    def fill(i):
        rng = np.random.default_rng(block_seeds[i])
        for a in range(int(edges[i]), int(edges[i + 1]), CHUNK):
            b = min(a + CHUNK, int(edges[i + 1]))
            part = X[a:b]
            which = np.searchsorted(cumulative, rng.random(b - a))
            style[a:b] = which
            rng.standard_normal(out=part, dtype=np.float32)
            part *= noise
            part += means[which]
            u = rng.standard_normal((b - a, rank), dtype=np.float32)
            part += (deform * u) @ basis
            np.clip(part, lo, hi, out=part)

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(BLOCKS)))
    return X, style
