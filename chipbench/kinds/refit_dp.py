"""Job kind ``refit_dp``: ``refit``'s warm re-fits of one resident table over
a grid, the table laid over the chips of one host.

The same jobs, answers and planted faults as ``refit`` (a subclass of its
``Refit``: the same data from the seed, the same calls into the program).
What differs is where the table lies: set-up sets the default ML
environment's mesh to the configuration's ``mesh`` (``{"data": chips}``) over
the first ``chips`` devices, the public way (``program_dp.py``), so that the
public ``LogisticRegression.fit(table)`` packs, places and trains
data-parallel over them; ``release()`` puts the environment's mesh back.  The
kind refuses to run on fewer devices than ``chips``, and on a host that the
program's set-up would run out of memory (:func:`refuse_a_host_too_small`),
before any data is made.
``work()`` is ONE chip's share of a fit (``work_dp.py`` says why), and the
plain reference (``glm_sgd_over_chips``) lays its own table over the same
number of chips, one table at a time, for every variant of its precision.

The mix's keys are ``refit``'s.  The configuration's: ``refit``'s, and
``chips`` and ``mesh``.
"""

from __future__ import annotations

import numpy as np

from chipbench import jobs, program_dp, references, work_dp
from chipbench.kinds.refit import Refit

#: Under this many tables of free host memory the kind measures the program
#: before it makes any data.  Measured on the four-chip host (155.7 GB) at
#: the configuration's size, 25.4 GB a table (PR 37): a run on a set-up that
#: holds ONE packed form used 73-77 GB, 3 tables (the table, the slab, what
#: the runtime stages of them); on PR 36's, which holds two, it had used 146
#: GB, 5.75 tables, and was still placing when it was stopped with 6 GB
#: free, the reference's table still to come.
ROOM_IN_TABLES = 8
#: packed forms a set-up may hold at its peak on such a host: the readings
#: are 1.00 and 2.01 (``program_dp.packed_forms``)
PACKED_FORMS = 1.5


def mem_available():
    """``MemAvailable`` of /proc/meminfo in bytes, or None where there is no
    such file."""
    try:
        with open("/proc/meminfo") as meminfo:
            for line in meminfo:
                if line.startswith("MemAvailable:"):
                    return 1024 * int(line.split()[1])
    except OSError:
        pass
    return None


def refuse_a_host_too_small(config, devices) -> None:
    """Exit, before any data is made, where this host's free memory is
    under ``ROOM_IN_TABLES`` tables AND the program's set-up, measured at a
    small size, holds more than ``PACKED_FORMS`` packed forms of a table:
    such a run ends killed for memory minutes into set-up, not with a
    result.  A host with the room is not asked the question."""
    table = (int(config["rows"]) * int(config["features"])
             * np.dtype(config["dtype"]).itemsize)
    available = mem_available()
    if available is None or available >= ROOM_IN_TABLES * table:
        return
    forms = program_dp.packed_forms(config["mesh"], devices,
                                    config["features"], config["dtype"])
    if forms > PACKED_FORMS:
        raise SystemExit(
            f"chipbench: kind refit_dp: this program's set-up holds "
            f"{forms:.2f} packed forms of a table on the host at its peak "
            f"(at most {PACKED_FORMS} here); the table is {table / 1e9:.1f} "
            f"GB and the host has {available / 1e9:.1f} GB free, under "
            f"{ROOM_IN_TABLES} tables; refusing to run")


class RefitDp(Refit):
    def __init__(self, config, mix, seed, spans):
        self.chips = int(config["chips"])
        if config["mesh"] != {"data": self.chips}:
            raise SystemExit(f"chipbench: kind refit_dp runs data-parallel "
                             f"over the configuration's chips; mesh "
                             f"{config['mesh']!r} is not "
                             f"{{'data': {self.chips}}}")
        self.devices = program_dp.require_devices(self.chips)  # or exit
        refuse_a_host_too_small(config, self.devices)  # or exit
        super().__init__(config, mix, seed, spans)
        self._laid, self._mesh_before = (None, None), None

    def setup(self):
        self._mesh_before = program_dp.set_mesh(self.config["mesh"],
                                                self.devices)
        super().setup()

    def release(self):
        super().release()
        if self._mesh_before is not None:
            program_dp.restore_mesh(self._mesh_before)
            self._mesh_before = None

    def work(self) -> dict:
        return work_dp.fit_work(self.config)

    def references(self, keys, precision=None, fault=None):
        """{key: reference answer} for the grid points in ``keys``.  The
        reference's table (25 GB over the chips) stays up for the next call
        and answers for every variant of its precision; a call in another
        precision takes its place: a chip holds one table and a fit's
        temporaries (6.4 + 7.3 GB in float32), not two tables besides."""
        precision = precision or self.precision
        if self._laid[0] != precision:
            self._laid = (None, None)  # frees the other precision's first
            self._laid = (precision, self.reference.Table(
                self.X, self.y, self.config["globalBatchSize"], precision,
                chips=self.chips))
        return {key: self._laid[1].fit(
            *self.points[key], self.config["maxIter"], fault) for key in keys}


def make(config, mix, seed, spans):
    return RefitDp(config, mix, seed, spans)


def numbers(config):
    return references.load(config["reference"]).NUMBERS


def controls(config):
    return references.load(config["reference"]).CONTROLS


def planted_faults(config):
    """``refit``'s three, which break the same calls this kind makes
    (``program.table``, ``.logreg`` and ``.fit_answer``)."""
    return jobs.kind("refit").planted_faults(config)
