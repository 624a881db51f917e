"""The plain reference ``kmeans_lloyd``: k-means++ over a seeded sample, then
Lloyd iterations.

Straightforward ``jax.numpy``, float32, every matrix product under
``jax.default_matmul_precision("highest")``.  It imports nothing of
``flink_ml_tpu`` and takes nothing the program has made: it gets the rows that
the harness made from the seed, and the configuration's numbers.  A
configuration names it with ``"reference": "kmeans_lloyd"``; a job kind finds
it through ``chipbench.references.load``.

What it computes, as the estimator documents it:

* the sample: all rows of a table of at most ``SAMPLE_CAP`` rows, else that
  many drawn without replacement by ``numpy.random.RandomState(seed)``;
* k-means++ over the sample: the first centre uniform, each further one with
  probability proportional to its squared distance to the nearest centre so
  far (D² sampling), drawn as an exponential race (row i rings at
  ``e_i / d2_i``, the first to ring is chosen), the ``e_i`` from
  ``jax.random`` keyed by the seed;
* ``iterations`` Lloyd iterations over every row: each row to its nearest
  centroid (squared Euclidean distance, the lowest number on a tie), a
  centroid the mean of its rows, an empty cluster keeps its centroid; the
  cost of an iteration is the sum of the squared distances under the
  centroids it started from.

The same function computes the control and the planted faults that
``chipbench/limits.py`` and the tests put in the program's place:

* ``precision="bf16"`` — rows and centroids enter the distance product as
  bfloat16 (float32 accumulation): one pass of the MXU, what JAX's default
  precision makes of a float32 product on a TPU, the step below the float32
  that the configuration states.
* ``fault="unchanged"`` — every iteration returns its centroids unchanged.
* ``fault="half_table"`` — the second half of the table is left out of every
  iteration's sums, counts and cost.
* ``fault="one_row_init"`` — the init's centroids are all one row.

``gaps`` is the comparison of one answer with one reference answer,
iteration by iteration from the answer's own trail of centroids (see there
why), and ``NUMBERS`` names what it returns.  The table lies on the device in row
blocks (it runs once the program's slabs are freed; on the CPU in the tests).
"""

from __future__ import annotations

import functools

import numpy as np

#: the numbers ``gaps`` returns
NUMBERS = ("centroid_gap", "cost_gap")
#: variants of the reference that have to come out as not correct
CONTROLS = {
    "control_bf16": {"precision": "bf16"},
    "fault_unchanged": {"fault": "unchanged"},
    "fault_half_table": {"fault": "half_table"},
    "fault_one_row_init": {"fault": "one_row_init"},
}
#: the precision of the reference itself, by the configuration's ``dtype``
PRECISIONS = {"float32": "f32"}
#: rows k-means++ runs over at most (the estimator's documented bound)
SAMPLE_CAP = 100_000
#: rows to a device block
BLOCK_ROWS = 131_072


def precision_of(config: dict) -> str:
    """The reference's precision for a configuration; a ``dtype`` that this
    reference does not compute is refused."""
    if config["dtype"] not in PRECISIONS:
        raise SystemExit(f"chipbench: reference kmeans_lloyd has no dtype "
                         f"{config['dtype']!r} (known: {sorted(PRECISIONS)})")
    return PRECISIONS[config["dtype"]]


def sample_rows(n_rows: int, seed: int) -> np.ndarray:
    """The row numbers of the seeded sample."""
    if n_rows <= SAMPLE_CAP:
        return np.arange(n_rows)
    return np.random.RandomState(int(seed)).choice(
        n_rows, SAMPLE_CAP, replace=False)


@functools.lru_cache(maxsize=None)
def _plus_plus_fn(k):
    import jax
    import jax.numpy as jnp

    def plus_plus(sample, seed):
        size = sample.shape[0]
        key = jax.random.PRNGKey(seed)
        first = jax.random.randint(jax.random.fold_in(key, 0), (), 0, size,
                                   jnp.int32)

        def dist_to(row):
            return jnp.sum((sample - sample[row]) ** 2, axis=1)

        def draw(j, carry):
            d2, rows = carry
            clock = jax.random.exponential(
                jax.random.fold_in(key, j), (size,), jnp.float32)
            rate = jnp.where(jnp.sum(d2) > 0, d2, 1.0)
            row = jnp.argmax(rate / jnp.maximum(clock, 1e-30)).astype(jnp.int32)
            return jnp.minimum(d2, dist_to(row)), rows.at[j].set(row)

        rows0 = jnp.zeros((k,), jnp.int32).at[0].set(first)
        _d2, rows = jax.lax.fori_loop(1, k, draw, (dist_to(first), rows0))
        return rows

    return jax.jit(plus_plus)


def plus_plus_rows(sample: np.ndarray, k: int, seed: int) -> np.ndarray:
    """The sample rows k-means++ chooses, in the order it chooses them."""
    import jax.numpy as jnp

    rows = _plus_plus_fn(int(k))(jnp.asarray(sample, jnp.float32),
                                 np.uint32(int(seed) % 2**32))
    return np.asarray(rows)


def _iteration(k, precision):
    """One Lloyd iteration over the table's blocks: (centroids, blocks) ->
    (the next centroids, the cost under the centroids given)."""
    import jax.numpy as jnp

    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")

    def product(x, c):
        if precision == "bf16":
            x, c = x.astype(jnp.bfloat16), c.astype(jnp.bfloat16)
        return jnp.dot(x, c.T, preferred_element_type=jnp.float32)

    def block_sums(c, block):
        x, mask = block  # rows (m, d), 1 a row that counts, 0 one that does not
        d = jnp.maximum(jnp.sum(x * x, axis=1, keepdims=True)
                        - 2.0 * product(x, c) + jnp.sum(c * c, axis=1), 0.0)
        nearest = jnp.argmin(d, axis=1)
        member = (nearest[:, None] == jnp.arange(k)[None, :]) * mask[:, None]
        return (jnp.sum(jnp.min(d, axis=1) * mask), member.T @ x,
                jnp.sum(member, axis=0))

    def iteration(c, blocks):
        cost, sums, counts = 0.0, 0.0, 0.0
        for block in blocks:
            a, b, n = block_sums(c, block)
            cost, sums, counts = cost + a, sums + b, counts + n
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts[:, None], 1.0), c)
        return new, cost

    return iteration


@functools.lru_cache(maxsize=None)
def _lloyd_fn(k, iterations, precision, fault):
    import jax

    if fault not in (None, "unchanged", "half_table", "one_row_init"):
        raise ValueError(f"unknown fault {fault!r}")
    iteration = _iteration(k, precision)

    def step(c, blocks):
        new, cost = iteration(c, blocks)
        # what the iteration started from goes into the trail
        return (c if fault == "unchanged" else new), (cost, c)

    def lloyd(blocks, init):
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(lambda c, _: step(c, blocks), init, None,
                                length=iterations)

    return jax.jit(lloyd)


@functools.lru_cache(maxsize=None)
def _steps_fn(k):
    """One sound float32 iteration from EACH of a trail of centroids
    (t, k, d): (the centroids each leads to (t, k, d), its cost (t,))."""
    import jax

    iteration = _iteration(k, "f32")

    def steps(blocks, trail):
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(lambda _, c: (None, iteration(c, blocks)),
                                None, trail)[1]

    return jax.jit(steps)


class Table:
    """One table in row blocks, resident on the device for any number of
    reference fits."""

    def __init__(self, X):
        import jax.numpy as jnp

        self.X = X
        self.n = n = X.shape[0]
        blocks, halves = [], []
        for lo in range(0, n, BLOCK_ROWS):
            hi = min(lo + BLOCK_ROWS, n)
            blocks.append(jnp.asarray(X[lo:hi], jnp.float32))
            halves.append(np.arange(lo, hi) < n // 2)
        self.blocks = tuple(blocks)
        self.whole = tuple(jnp.ones((len(h),), jnp.float32) for h in halves)
        self.first_half = tuple(jnp.asarray(h, jnp.float32) for h in halves)

    def steps(self, trail):
        """One sound iteration over the whole table from each centroid
        matrix of ``trail``: (next centroids, costs) as host float64."""
        import jax.numpy as jnp

        k = np.shape(trail)[1]
        after, costs = _steps_fn(int(k))(
            tuple(zip(self.blocks, self.whole)),
            jnp.asarray(trail, jnp.float32))
        return np.asarray(after, np.float64), np.asarray(costs, np.float64)

    def fit(self, seed, k, iterations, precision="f32", fault=None) -> dict:
        """One fit's answer as host arrays, the program's answer's keys:
        the centroids, the cost of every iteration, the iterations run and
        the trail (the centroids every iteration started from); and, for
        ``gaps``, ``steps``: this table's sound iteration from any trail."""
        import jax.numpy as jnp

        sample = self.X[sample_rows(self.n, seed)]
        rows = plus_plus_rows(sample, k, seed)
        if fault == "one_row_init":
            rows = np.full_like(rows, rows[0])
        init = jnp.asarray(sample[rows], jnp.float32)
        masks = self.first_half if fault == "half_table" else self.whole
        centroids, (costs, trail) = _lloyd_fn(
            int(k), int(iterations), precision, fault)(
                tuple(zip(self.blocks, masks)), init)
        return {"centroids": np.asarray(centroids, np.float64),
                "costs": np.asarray(costs, np.float64),
                "epochs": int(iterations),
                "trail": np.asarray(trail, np.float32),
                "steps": self.steps}


def _relative(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def gaps(answer, ref) -> dict:
    """The numbers one answer is judged by, against the reference's answer
    for the same data and parameters, ITERATION BY ITERATION from the
    answer's own trail.

    Twenty Lloyd iterations are no smooth function of their arithmetic: a
    row whose two nearest centroids lie within a rounding of each other is
    assigned by the rounding, one such row moves a centroid, and the
    trajectories of two sound float32 implementations part company (on the
    chip the final centroids of program and reference differ by 1e-4 to
    2.5e-3 of their norm, the one-pass bfloat16 control's by 8e-3 to 7e-2:
    PERF.md §2).  So nothing is compared across iterations.  The init has to
    be the reference's (the same seeded race over the same sample).  Then
    from the centroids each iteration of the ANSWER started from, the
    reference makes one sound iteration of its own over the whole table, and

    ``centroid_gap``  is the largest, over the init and every iteration, of
                      the norm of (what the answer's iteration produced minus
                      what the reference's did) over the latter's norm;
    ``cost_gap``      the largest relative gap of an iteration's cost.

    An answer of another shape, length or number of iterations: inf."""
    trail, ref_trail = answer["trail"], ref["trail"]
    centroids = answer["centroids"]
    same = (trail.shape == ref_trail.shape
            and centroids.shape == ref["centroids"].shape
            and len(answer["costs"]) == len(ref["costs"]) == len(trail)
            and answer["epochs"] == ref["epochs"])
    if not same:
        return {"centroid_gap": float("inf"), "cost_gap": float("inf")}
    after, costs = ref["steps"](trail)
    produced = np.concatenate([trail[1:].astype(np.float64), centroids[None]])
    return {
        "centroid_gap": max(
            [_relative(trail[0].astype(np.float64),
                       ref_trail[0].astype(np.float64))]
            + [_relative(got, want) for got, want in zip(produced, after)]),
        "cost_gap": float(np.max(np.abs(answer["costs"] - costs)
                                 / np.abs(costs))),
    }
