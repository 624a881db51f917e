"""The chip's peaks, and the bytes and operations that the ALGORITHM needs
for one job, which a job kind reports through its ``work()``: here for one
fit of a dense GLM (``fit_work``).  They are counted from shapes only, never
from what the program did, so that a roofline share reads the same work
whatever implements it.

Minibatch SGD on a dense GLM has to read every training row (its features and
its label, float32) once an epoch: one pass can compute the row's score, its
error and its share of the gradient.  A program that reads the minibatch twice
(a forward and a backward product) does more than the algorithm needs, and
reads under 50% here for that reason.  Each stored feature costs a multiply
and an add in the score and again in the gradient: 4 operations.

``resident_bytes`` is a different thing: what the program's packed layout
(``steps x batch x (features + label + weight)``, float32) holds on the device,
used for the sizing arithmetic only.
"""

from __future__ import annotations

import json
import os

BYTES_F32 = 4
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    """The chip's published peaks.  An unknown ``device_kind`` is an error,
    never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"chipbench/peaks.json has no peaks for device kind "
                       f"{device_kind!r} (known: {known}); add them with "
                       f"their source")
    return table[device_kind]


def steps_per_epoch(rows: int, batch: int) -> int:
    return -(-int(rows) // int(batch))


def fit_work(config: dict) -> dict:
    """Work of one minibatch-SGD fit of a dense GLM on the whole table."""
    rows = int(config["rows"])
    dim, epochs = int(config["features"]), int(config["maxIter"])
    batch = int(config["globalBatchSize"])
    steps = steps_per_epoch(rows, batch)
    bytes_per_epoch = rows * (dim + 1) * BYTES_F32
    flops_per_epoch = 4 * rows * dim
    return {
        "rows": rows, "steps_per_epoch": steps, "epochs": epochs,
        "bytes_per_epoch": bytes_per_epoch,
        "flops_per_epoch": flops_per_epoch,
        "bytes": bytes_per_epoch * epochs,
        "flops": flops_per_epoch * epochs,
        "resident_bytes": steps * batch * (dim + 2) * BYTES_F32,
    }


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which peak
    bounds it (``"hbm"`` or ``"flops"``)."""
    by_bytes = work["bytes"] / float(peak["hbm_bytes_per_s"])
    by_flops = work["flops"] / float(peak["flops_per_s"])
    return (by_bytes, "hbm") if by_bytes >= by_flops else (by_flops, "flops")
