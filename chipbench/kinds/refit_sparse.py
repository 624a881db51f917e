"""Job kind ``refit_sparse``: warm re-fits of one resident SPARSE table over a
grid: the sparse twin of ``refit``.

The table's vector column is a CSR column of hashed click-log rows
(``chipbench/data_sparse.py``, from the seed).  Place once (set-up: the
segment-CSR pack and the placement of its two leaves), then re-fit the
LogisticRegression stage on the one resident table, cycling the mix's
``grid`` of (learningRate, reg) points in an order drawn from the seed.  A job
is one ``LogisticRegression.fit(table)`` call on the estimator's default
sparse route (``numHotFeatures`` unset), from the call to coefficients and
loss history on the host.  Every seed gives the same set of jobs, in another
order.

The mix's keys: ``grid`` (``learningRate`` x ``reg``) and ``input``
(``hashed``: the rows arrive hashed and scaled to unit length, as the public
file is).  The configuration's: ``rows``, ``numFeatures``, ``nnz_per_row``,
``dtype``, ``data``, ``reference`` and the estimator's ``globalBatchSize``,
``maxIter``, ``tol``, ``withIntercept``.
"""

from __future__ import annotations

import itertools

import numpy as np

from chipbench import data_sparse, jobs, program, program_sparse, references
from chipbench import work_sparse


class RefitSparse:
    def __init__(self, config, mix, seed, spans):
        self.config, self.mix, self.spans = config, mix, spans
        self.reference = references.load(config["reference"])
        self.precision = self.reference.precision_of(config)
        if mix["input"] != "hashed":
            raise SystemExit(f"chipbench: kind refit_sparse takes a table "
                             f"that arrives hashed, not {mix['input']!r}")
        self.dim = int(config["numFeatures"])
        self.width = int(config["nnz_per_row"])
        if data_sparse.entries_per_row(config["data"]) != self.width:
            raise SystemExit("chipbench: nnz_per_row is not the number of "
                             "fields the data block makes")
        with spans.span("setup.data"):
            self.indptr, self.indices, self.values, self.y = \
                data_sparse.make_rows(config["data"], int(config["rows"]),
                                      self.dim, seed, config["dtype"])
        grid = mix["grid"]
        self.points = [(float(lr), float(reg)) for lr, reg in
                       itertools.product(grid["learningRate"], grid["reg"])]
        self.keys = list(range(len(self.points)))
        self.order = jobs.order(len(self.points), seed)
        self.rows_per_job = len(self.y) * int(config["maxIter"])
        self.gaps = self.reference.gaps
        self._reference_table = None

    def setup(self):
        self.table = program_sparse.table(
            self.dim, self.indptr, self.indices, self.values, self.y)
        # the first fit packs, places and compiles; each further grid point
        # compiles its own program (the learning rate is a constant of it)
        with self.spans.span("setup.first_fit"):
            self._fit(self.points[0])
        with self.spans.span("setup.warm_grid"):
            for point in self.points[1:] + self.points[:1]:
                self._fit(point)

    def _fit(self, point):
        model = program_sparse.logreg(self.config, *point).fit(self.table)
        return program.fit_answer(model)

    def job(self, i):
        key = self.order[i % len(self.order)]
        with self.spans.span("job.fit"):
            answer = self._fit(self.points[key])
        return key, self.rows_per_job, answer

    def release(self):
        self.table = None

    def work(self) -> dict:
        return work_sparse.fit_work(self.config)

    def references(self, keys, precision=None, fault=None):
        """{key: reference answer} for the grid points in ``keys``.  The
        reference's table goes up once and answers for every variant."""
        if self._reference_table is None:
            shape = (len(self.y), self.width)
            self._reference_table = self.reference.Table(
                self.indices.reshape(shape), self.values.reshape(shape),
                self.y, self.dim, self.config["globalBatchSize"])
        return {key: self._reference_table.fit(
            *self.points[key], self.config["maxIter"],
            precision or self.precision, fault) for key in keys}


def make(config, mix, seed, spans):
    return RefitSparse(config, mix, seed, spans)


def numbers(config):
    return references.load(config["reference"]).NUMBERS


def controls(config):
    return references.load(config["reference"]).CONTROLS


def planted_faults(config):
    """Ways to break the timed path underneath a run (``refit``'s three):
    each has to come out as not correct by one of ``numbers``."""
    sound_table, sound_logreg = program_sparse.table, program_sparse.logreg
    batch = int(config["globalBatchSize"])
    # the answer is program.fit_answer's here too: refit's two faults of it
    dense = jobs.kind("refit").planted_faults(config)

    def half_batch_table(dim, indptr, indices, values, y):
        # the first half of every global batch only ...
        keep = (np.arange(len(y)) % batch) < batch // 2
        per_row = np.diff(indptr)
        entry_keep = np.repeat(keep, per_row)
        kept = np.concatenate([[0], np.cumsum(per_row[keep])])
        return sound_table(dim, kept, indices[entry_keep],
                           values[entry_keep], y[keep])

    def half_batch_logreg(config, lr, reg):
        # ... and the mean taken over that half
        return sound_logreg(dict(config, globalBatchSize=batch // 2), lr, reg)

    return {
        "state_unchanged": dense["state_unchanged"],
        "answer_altered": dense["answer_altered"],
        "half_batch": [(program_sparse, "table", half_batch_table),
                       (program_sparse, "logreg", half_batch_logreg)],
    }
