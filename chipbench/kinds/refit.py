"""Job kind ``refit``: warm re-fits of one resident table over a grid.

Place once (set-up), then re-fit the LogisticRegression stage on the one
resident table, cycling the mix's ``grid`` of (learningRate, reg) points in an
order drawn from the seed.  The table comes scaled (``"input":
"standardised"``: the harness standardises it while it makes it, as an
upstream job would have).  A job is one ``LogisticRegression.fit(table)``
call, from the call to coefficients and loss history on the host.  Every seed
gives the same set of jobs, in another order.

The mix's keys: ``grid`` (``learningRate`` x ``reg``) and ``input``.  The
configuration's: ``rows``, ``features``, ``dtype``, ``data``, ``reference``
and the estimator's ``globalBatchSize``, ``maxIter``, ``tol``,
``withIntercept``.
"""

from __future__ import annotations

import itertools

import numpy as np

from chipbench import data, jobs, program, references, work


class Refit:
    def __init__(self, config, mix, seed, spans):
        self.config, self.mix, self.spans = config, mix, spans
        self.reference = references.load(config["reference"])
        self.precision = self.reference.precision_of(config)
        if mix["input"] != "standardised":
            raise SystemExit(f"chipbench: kind refit takes a table that "
                             f"arrives standardised, not {mix['input']!r}")
        with spans.span("setup.data"):
            self.X, self.y = data.make_rows(
                config["data"], int(config["rows"]), int(config["features"]),
                seed, config["dtype"])
            data.standardise(self.X)
        grid = mix["grid"]
        self.points = [(float(lr), float(reg)) for lr, reg in
                       itertools.product(grid["learningRate"], grid["reg"])]
        self.keys = list(range(len(self.points)))
        self.order = jobs.order(len(self.points), seed)
        self.rows_per_job = self.X.shape[0] * int(config["maxIter"])
        self.gaps = self.reference.gaps

    def setup(self):
        self.table = program.table(self.X, self.y)
        # the first fit packs, places and compiles; each further grid point
        # compiles its own program (the learning rate is a constant of it)
        with self.spans.span("setup.first_fit"):
            self._fit(self.points[0])
        with self.spans.span("setup.warm_grid"):
            for point in self.points[1:] + self.points[:1]:
                self._fit(point)

    def _fit(self, point):
        model = program.logreg(self.config, *point).fit(self.table)
        return program.fit_answer(model)

    def job(self, i):
        key = self.order[i % len(self.order)]
        with self.spans.span("job.fit"):
            answer = self._fit(self.points[key])
        return key, self.rows_per_job, answer

    def release(self):
        self.table = None

    def work(self) -> dict:
        return work.fit_work(self.config)

    def references(self, keys, precision=None, fault=None):
        """{key: reference answer} for the grid points in ``keys``."""
        table = self.reference.Table(
            self.X, self.y, self.config["globalBatchSize"],
            precision or self.precision)
        return {key: table.fit(*self.points[key], self.config["maxIter"],
                               fault) for key in keys}


def make(config, mix, seed, spans):
    return Refit(config, mix, seed, spans)


def numbers(config):
    return references.load(config["reference"]).NUMBERS


def controls(config):
    return references.load(config["reference"]).CONTROLS


def planted_faults(config):
    """Ways to break the timed path underneath a run: each has to come out as
    not correct by one of ``numbers``."""
    sound_answer, sound_table = program.fit_answer, program.table
    sound_logreg = program.logreg
    batch = int(config["globalBatchSize"])

    def unchanged(model):
        # a step that returns its state unchanged
        answer = sound_answer(model)
        return dict(answer, coef=np.zeros_like(answer["coef"]), intercept=0.0,
                    losses=np.full_like(answer["losses"], np.log(2.0)))

    def altered(model):
        # an answer altered where it is produced
        answer = sound_answer(model)
        coef = answer["coef"].copy()
        coef[0] += 1e-3 * np.linalg.norm(coef)
        return dict(answer, coef=coef)

    def half_batch_table(X, y):
        # the first half of every global batch only ...
        keep = (np.arange(len(y)) % batch) < batch // 2
        return sound_table(np.ascontiguousarray(X[keep]), y[keep])

    def half_batch_logreg(config, lr, reg):
        # ... and the mean taken over that half
        return sound_logreg(dict(config, globalBatchSize=batch // 2), lr, reg)

    return {
        "state_unchanged": [(program, "fit_answer", unchanged)],
        "answer_altered": [(program, "fit_answer", altered)],
        "half_batch": [(program, "table", half_batch_table),
                       (program, "logreg", half_batch_logreg)],
    }
