"""Job kind ``refit_ragged``: warm re-fits of one resident RAGGED sparse table
over a grid: ``refit_sparse`` over rows of uneven width.

The table's vector column is a CSR column of bag-of-words rows whose stored
entry counts differ row by row (``chipbench/data_ragged.py``, from the seed).
Place once (set-up: the pack, which chooses the step layout from the row
widths it observes, and the placement of its leaves), then re-fit the
LogisticRegression stage on the one resident table, cycling the mix's
``grid`` of (learningRate, reg) points in an order drawn from the seed.  A job
is one ``LogisticRegression.fit(table)`` call on the estimator's default
sparse route (``numHotFeatures`` unset), from the call to coefficients and
loss history on the host.  Every seed gives the same set of jobs, in another
order.

The mix's keys: ``grid`` (``learningRate`` x ``reg``) and ``input`` (``csr``:
the rows arrive as a CSR column, scaled to unit length).  The configuration's:
``rows``, ``numFeatures``, ``dtype``, ``data``, ``reference`` and the
estimator's ``globalBatchSize``, ``maxIter``, ``tol``, ``withIntercept``.  No
key fixes the stored entries: the seed draws them, ``work()`` counts them
from the table, and set-up says them on standard error.
"""

from __future__ import annotations

import itertools
import sys

import numpy as np

from chipbench import data_ragged, jobs, program_ragged, references
from chipbench import work_ragged
from chipbench.kinds.refit_sparse import RefitSparse


class RefitRagged(RefitSparse):
    """``RefitSparse``'s set-up, job and release (the same calls into the
    program) over a table of ragged rows, with its own data, work and
    reference."""

    def __init__(self, config, mix, seed, spans):
        program_ragged.require_steady_pack()  # or exit, before any data
        self.config, self.mix, self.spans = config, mix, spans
        self.reference = references.load(config["reference"])
        self.precision = self.reference.precision_of(config)
        if mix["input"] != "csr":
            raise SystemExit(f"chipbench: kind refit_ragged takes a table "
                             f"that arrives as a CSR column, not "
                             f"{mix['input']!r}")
        self.dim = int(config["numFeatures"])
        with spans.span("setup.data"):
            self.indptr, self.indices, self.values, self.y = \
                data_ragged.make_rows(config["data"], int(config["rows"]),
                                      self.dim, seed, config["dtype"])
        grid = mix["grid"]
        self.points = [(float(lr), float(reg)) for lr, reg in
                       itertools.product(grid["learningRate"], grid["reg"])]
        self.keys = list(range(len(self.points)))
        self.order = jobs.order(len(self.points), seed)
        self.rows_per_job = len(self.y) * int(config["maxIter"])
        self.gaps = self.reference.gaps
        self._reference_table = None
        self._work = work_ragged.fit_work(config, self.indptr)
        widths = np.diff(self.indptr)
        sys.stderr.write(
            f"chipbench: refit_ragged table: {len(self.y)} rows, "
            f"{self._work['entries_per_epoch']} stored entries, widths "
            f"{int(widths.min())}-{int(widths.max())}, a segment-CSR step "
            f"{self._work['nnz_pad']} slots, a row-regular one "
            f"{self._work['ell_slots']}\n")

    def work(self) -> dict:
        return self._work

    def references(self, keys, precision=None, fault=None):
        """{key: reference answer} for the grid points in ``keys``.  The
        reference's table goes up once and answers for every variant."""
        if self._reference_table is None:
            self._reference_table = self.reference.Table(
                self.indptr, self.indices, self.values, self.y, self.dim,
                self.config["globalBatchSize"])
        return {key: self._reference_table.fit(
            *self.points[key], self.config["maxIter"],
            precision or self.precision, fault) for key in keys}


def make(config, mix, seed, spans):
    return RefitRagged(config, mix, seed, spans)


def numbers(config):
    return references.load(config["reference"]).NUMBERS


def controls(config):
    return references.load(config["reference"]).CONTROLS


def planted_faults(config):
    """``refit_sparse``'s three, which break the same calls this kind makes
    (``program_sparse.table`` and ``.logreg``, ``program.fit_answer``): a fit
    whose steps leave the state as it was, an answer altered on its way out,
    and the second half of every global batch left out of a table whose
    entries the fault cuts by ``indptr``, whatever the rows' widths."""
    return jobs.kind("refit_sparse").planted_faults(config)
