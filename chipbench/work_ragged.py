"""The bytes and operations that the ALGORITHM needs for one fit of a sparse
GLM on a RAGGED table (``fit_work``), which the ``refit_ragged`` kind reports
through its ``work()``: ``work_sparse.py``'s count with the stored entries
taken from the table the seed made (its ``indptr``), since no configuration
key fixes them.  Counted from the table only, never from what the program
did.

Minibatch SGD on a sparse GLM has to read every stored entry (its feature
index, int32, and its value, float32: 8 bytes) and every row's label (float32)
once an epoch, and each stored entry costs a multiply and an add in the score
and again in the gradient: 4 operations.  The weights (12.9 MB at 3.2 million
features) are not counted.  What a random-access step really moves (a gathered
weight and a scattered gradient slot an entry, pads, row ids) is the program's
own, so its share of this roofline is small, and that is the finding.

``resident_bytes`` is what a segment-CSR layout of the table holds on the
device, every step padded to the fullest step's entries rounded up to an odd
multiple of 512, as the program's pack rounds them since PR 33
(``steps x 2 x nnz_pad`` int32 for feature and row ids, ``steps x (nnz_pad + 2
x batch)`` float32 for values, labels and weights), used for the sizing
arithmetic only; ``ell_slots`` is what a row-regular layout would walk a step
(``batch x`` the widest row), for the same.
"""

from __future__ import annotations

import numpy as np

from chipbench.work import BYTES_F32, steps_per_epoch
from chipbench.work_sparse import BYTES_ENTRY, PAD_MULTIPLE


def fit_work(config: dict, indptr) -> dict:
    """Work of one minibatch-SGD fit of a sparse GLM on the whole table whose
    rows ``indptr`` bounds."""
    rows, entries = len(indptr) - 1, int(indptr[-1])
    epochs, batch = int(config["maxIter"]), int(config["globalBatchSize"])
    steps = steps_per_epoch(rows, batch)
    starts = np.minimum(batch * np.arange(steps + 1), rows)
    fullest = int(np.max(indptr[starts[1:]] - indptr[starts[:-1]]))
    nnz_pad = (-(-fullest // PAD_MULTIPLE) | 1) * PAD_MULTIPLE
    widest = int(np.max(np.diff(indptr)))
    bytes_per_epoch = entries * BYTES_ENTRY + rows * BYTES_F32
    flops_per_epoch = 4 * entries
    return {
        "rows": rows, "steps_per_epoch": steps, "epochs": epochs,
        "entries_per_epoch": entries, "nnz_pad": nnz_pad,
        "widest_row": widest, "ell_slots": batch * widest,
        "bytes_per_epoch": bytes_per_epoch,
        "flops_per_epoch": flops_per_epoch,
        "bytes": bytes_per_epoch * epochs,
        "flops": flops_per_epoch * epochs,
        "resident_bytes": steps * (2 * nnz_pad + nnz_pad + 2 * batch)
        * BYTES_F32,
    }
