"""What the ``refit_ragged`` kind asks of ``flink_ml_tpu`` beyond
``program_sparse.py``'s calls: a pack that fixes the length of a segment-CSR
step's gathers.

On a TPU v5e a gather of N addresses runs 7% faster where N is an odd
multiple of 512 than where it is a multiple of 1024, and a ragged table's
widths decide which a step's padded length is: a program that rounds it to
the next 512 fits one seed's table in 8.90 s and another's in 9.14 s (PR 33's
chip runs, PERF.md section 6), a spread of 2.6% between seeds where the cell
is admitted under 0.75%.  Since PR 33 the program's pack rounds to the faster
length (``lib/common.py:padded_nnz``); on a program without it the cell's
runs cannot be compared with one another, so the kind refuses it at once,
before any data is made, with nothing on standard output.
"""

from __future__ import annotations


def require_steady_pack() -> None:
    from flink_ml_tpu.lib import common

    if not hasattr(common, "padded_nnz"):
        raise SystemExit(
            "chipbench: kind refit_ragged needs a program whose sparse pack "
            "fixes the residue of a step's padded length "
            "(flink_ml_tpu.lib.common.padded_nnz, PR 33): without it a fit "
            "runs at one of two speeds by the table's widths, and the "
            "cell's runs cannot be compared; refusing to run")
