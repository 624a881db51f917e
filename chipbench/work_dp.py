"""The bytes and operations that the ALGORITHM needs for one fit of a dense
GLM on a table laid over ``chips`` chips, as ONE chip's share
(``fit_work``), which the ``refit_dp`` kind reports through its ``work()``:
``work.fit_work`` of the whole table with ``bytes`` and ``flops`` divided by
the configuration's ``chips``.  Counted from shapes only.

Why a chip's share: the readers that stand divide a job's least time by ONE
chip's peak (``work.least_seconds`` with ``peaks.json``'s per-chip numbers)
and by program seconds that ``trace_reduce.reduce`` AVERAGES over the chips.
With the whole table's bytes they would read four times too much: 350% where
a chip's step is at 88% of its HBM peak.  With a chip's share

* ``mfu.fit`` is the whole fit's least time over the four chips' peak (bytes
  / chips / 819 GB/s = bytes / (chips x 819 GB/s)) over the window's wall
  time, and
* ``train_program_roofline`` is a chip's share of the bytes over a chip's
  program time,

and neither can pass 100%.  Rows, steps, epochs and ``resident_bytes`` stay
the whole table's (the sizing arithmetic divides the last by ``chips``
itself); ``chips`` is added.
"""

from __future__ import annotations

from chipbench import work


def fit_work(config: dict) -> dict:
    """Work of one minibatch-SGD fit of a dense GLM, a chip's share."""
    whole, chips = work.fit_work(config), int(config["chips"])
    return dict(whole, chips=chips, bytes=whole["bytes"] / chips,
                flops=whole["flops"] / chips)
