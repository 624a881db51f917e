"""The bytes and operations that the ALGORITHM needs for one fit of a sparse
GLM (``fit_work``), which the ``refit_sparse`` kind reports through its
``work()``: the sparse twin of ``work.py``'s ``fit_work``, counted from shapes
only, never from what the program did.

Minibatch SGD on a sparse GLM has to read every stored entry (its feature
index, int32, and its value, float32: 8 bytes) and every row's label (float32)
once an epoch: one pass can compute the row's score, its error and its share
of the gradient.  Each stored entry costs a multiply and an add in the score
and again in the gradient: 4 operations.  The weights are not counted: at a
million features they are 4 MB and could stay on the chip.  What a
random-access step really moves (a gathered weight and a scattered gradient
slot an entry, pads, the row ids the program stores) is the program's own, so
its share of this roofline is small, and that is the finding.

``resident_bytes`` is what the program's packed segment-CSR layout holds on
the device (``steps x 2 x nnz_pad`` int32 for feature and row ids, ``steps x
(nnz_pad + 2 x batch)`` float32 for values, labels and weights), used for the
sizing arithmetic only.
"""

from __future__ import annotations

from chipbench.work import BYTES_F32, steps_per_epoch

BYTES_ENTRY = 8  # int32 index + float32 value
PAD_MULTIPLE = 512  # the program's pack rounds a step's entries up to this


def fit_work(config: dict) -> dict:
    """Work of one minibatch-SGD fit of a sparse GLM on the whole table."""
    rows, per_row = int(config["rows"]), int(config["nnz_per_row"])
    epochs, batch = int(config["maxIter"]), int(config["globalBatchSize"])
    steps = steps_per_epoch(rows, batch)
    entries = rows * per_row
    nnz_pad = -(-batch * per_row // PAD_MULTIPLE) * PAD_MULTIPLE
    bytes_per_epoch = entries * BYTES_ENTRY + rows * BYTES_F32
    flops_per_epoch = 4 * entries
    return {
        "rows": rows, "steps_per_epoch": steps, "epochs": epochs,
        "entries_per_epoch": entries, "nnz_pad": nnz_pad,
        "bytes_per_epoch": bytes_per_epoch,
        "flops_per_epoch": flops_per_epoch,
        "bytes": bytes_per_epoch * epochs,
        "flops": flops_per_epoch * epochs,
        "resident_bytes": steps * (2 * nnz_pad + nnz_pad + 2 * batch)
        * BYTES_F32,
    }
