"""Job kind ``refit_kmeans``: k-means restarts on one resident table.

The table's rows are seeded mixtures (``chipbench/data_mixture.py``).  Place
once (set-up: the pack and the placement of the rows), then re-fit the
``KMeans`` stage on the one resident table, cycling the mix's ``grid`` of
``seed`` values (each a k-means++ init of its own; one compiled program) in an
order drawn from ``--seed``.  A job is one ``KMeans.fit(table)`` call, from
the call to centroids and cost history on the host.  Every seed gives the
same set of jobs, in another order.

The mix's keys: ``grid`` (``seed``) and ``input`` (``raw``: the pixels as
distributed).  The configuration's: ``rows``, ``features``, ``dtype``,
``data``, ``reference`` and the estimator's ``k``, ``maxIter``, ``tol``.
"""

from __future__ import annotations

import numpy as np

from chipbench import data_mixture, jobs, program_kmeans, references
from chipbench import work_kmeans


class RefitKMeans:
    def __init__(self, config, mix, seed, spans):
        self.config, self.mix, self.spans = config, mix, spans
        self.reference = references.load(config["reference"])
        self.precision = self.reference.precision_of(config)
        if mix["input"] != "raw":
            raise SystemExit(f"chipbench: kind refit_kmeans takes the table "
                             f"as distributed (raw), not {mix['input']!r}")
        if float(config["tol"]) != 0.0:
            raise SystemExit("chipbench: kind refit_kmeans runs every "
                             "iteration; tol must be 0")
        with spans.span("setup.data"):
            self.X, _style = data_mixture.make_rows(
                config["data"], int(config["rows"]), int(config["features"]),
                seed, config["dtype"])
        self.points = [int(s) for s in mix["grid"]["seed"]]
        self.keys = list(range(len(self.points)))
        self.order = jobs.order(len(self.points), seed)
        self.rows_per_job = self.X.shape[0] * int(config["maxIter"])
        self.gaps = self.reference.gaps
        self._reference_table = None

    def setup(self):
        self.table = program_kmeans.table(self.X)
        # the first fit packs, places and compiles (the init's program and
        # the Lloyd program, one each for the whole grid); each further
        # seed draws its sample's rows once
        with self.spans.span("setup.first_fit"):
            self._fit(self.points[0])
        with self.spans.span("setup.warm_grid"):
            for point in self.points[1:] + self.points[:1]:
                self._fit(point)

    def _fit(self, point):
        model = program_kmeans.kmeans(self.config, point).fit(self.table)
        return program_kmeans.fit_answer(model)

    def job(self, i):
        key = self.order[i % len(self.order)]
        with self.spans.span("job.fit"):
            answer = self._fit(self.points[key])
        return key, self.rows_per_job, answer

    def release(self):
        self.table = None

    def work(self) -> dict:
        return work_kmeans.fit_work(self.config)

    def references(self, keys, precision=None, fault=None):
        """{key: reference answer} for the grid points in ``keys``.  The
        reference's table goes up once and answers for every variant."""
        if self._reference_table is None:
            self._reference_table = self.reference.Table(self.X)
        return {key: self._reference_table.fit(
            self.points[key], self.config["k"], self.config["maxIter"],
            precision or self.precision, fault) for key in keys}


def make(config, mix, seed, spans):
    return RefitKMeans(config, mix, seed, spans)


def numbers(config):
    return references.load(config["reference"]).NUMBERS


def controls(config):
    return references.load(config["reference"]).CONTROLS


def planted_faults(config):
    """Ways to break the timed path underneath a run: each has to come out as
    not correct by one of ``numbers``."""
    from flink_ml_tpu.lib import clustering

    sound_answer, sound_table = program_kmeans.fit_answer, program_kmeans.table
    sound_init = clustering.kmeans_plus_plus_rows

    inits = []

    def recording_init(device_batch, take, k, seed, mesh):
        inits[:] = [sound_init(device_batch, take, k, seed, mesh)]
        return inits[0]

    def unchanged(model):
        # iterations that return their state unchanged: the init's
        # centroids, the first iteration's cost every time
        answer = sound_answer(model)
        return dict(answer, centroids=inits[0].astype(np.float64),
                    costs=np.full_like(answer["costs"], answer["costs"][0]))

    def altered(model):
        # an answer altered where it is produced
        answer = sound_answer(model)
        centroids = answer["centroids"].copy()
        centroids[0, 0] += 2e-2 * np.linalg.norm(centroids)
        return dict(answer, centroids=centroids)

    def half_table(X):
        # the second half of the table left out of every iteration
        return sound_table(np.ascontiguousarray(X[: len(X) // 2]))

    def one_row_init(device_batch, take, k, seed, mesh):
        # an init whose centroids are all one row
        centres = sound_init(device_batch, take, k, seed, mesh)
        return np.repeat(centres[:1], k, axis=0)

    return {
        "state_unchanged": [(clustering, "kmeans_plus_plus_rows",
                             recording_init),
                            (program_kmeans, "fit_answer", unchanged)],
        "answer_altered": [(program_kmeans, "fit_answer", altered)],
        "half_table": [(program_kmeans, "table", half_table)],
        "one_row_init": [(clustering, "kmeans_plus_plus_rows", one_row_init)],
    }
