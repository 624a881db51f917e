"""The plain reference ``csr_glm_sgd``: minibatch SGD on the binary log loss
over sparse rows of ANY width.

Straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  It imports nothing of
``flink_ml_tpu`` and takes nothing the program has made: it gets the table as
the harness made it from the seed (``indptr``, ``indices``, ``values`` and the
labels) and the configuration's numbers.  A step over one global batch, rows
in table order, is ``sparse_glm_sgd``'s, with a row id an entry where that one
has a row axis:

    logits = segment_sum(vals * w[idx], row) + b
    err    = sigmoid(logits) - y
    g_w    = zeros(dim).at[idx].add(err[row] * vals) / rows of the batch
    w, b   = w - lr * (g_w + reg * w), b - lr * mean(err)

the same update and L2 term as ``glm_sgd``.  On the device the entries lie in
table order, each beside its row's number inside its step, in chunks of
``CHUNK_STEPS`` steps; a chunk's steps are padded to the entry count of the
chunk's own fullest step (a pad is value 0.0 at feature 0 of a row past the
batch, which the segment sum drops), so a chunk has its own shape and the
fit is one program over all of them.  The table goes up chunk by chunk and
stays for any number of fits (the program's slabs are released first).

The same function computes the control and the planted faults:

* ``precision="bf16"`` — values and weights are rounded to bfloat16 before
  every product (the score's and, with the error, the gradient's), sums in
  float32: the step below the float32 the configurations state.
* ``fault="half_batch"`` — the second half of every minibatch is left out and
  the mean taken over the rest.
* ``fault="unchanged"`` — every step returns its state unchanged.

``gaps``, ``NUMBERS`` and ``CONTROLS`` are imported from ``glm_sgd``: ``coef_gap``
and ``loss_gap``, the same control and faults.
"""

from __future__ import annotations

import functools

import numpy as np

# what an answer is judged by, the variants that have to come out as not
# correct and the precision by ``dtype`` are the dense reference's own
from chipbench.references.glm_sgd import (  # noqa: F401
    CONTROLS, NUMBERS, PRECISIONS, gaps)

#: SGD steps to a device chunk: the table goes up chunk by chunk
CHUNK_STEPS = 8
#: a chunk's steps are padded to a multiple of this many entries
PAD_MULTIPLE = 1024


def precision_of(config: dict) -> str:
    """The reference's precision for a configuration; a ``dtype`` or a
    ``withIntercept`` that this reference does not compute is refused."""
    if config["dtype"] not in PRECISIONS:
        raise SystemExit(f"chipbench: reference csr_glm_sgd has no dtype "
                         f"{config['dtype']!r} (known: {sorted(PRECISIONS)})")
    if config["withIntercept"] is not True:
        raise SystemExit("chipbench: reference csr_glm_sgd fits an "
                         "intercept; withIntercept must be true")
    return PRECISIONS[config["dtype"]]


@functools.lru_cache(maxsize=None)
def _fit_fn(dim, batch, epochs, precision, fault):
    import jax
    import jax.numpy as jnp

    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    if fault not in (None, "half_batch", "unchanged"):
        raise ValueError(f"unknown fault {fault!r}")

    def rounded(a):
        if precision == "bf16":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        return a

    def step(params, inp, lr, reg):
        w, b = params
        ib, rb, vb, yb, mb = inp  # (entries,) x 3, (batch,) x 2
        if fault == "half_batch":
            mb = mb * (jnp.arange(batch) < batch // 2).astype(jnp.float32)
        logits = jax.ops.segment_sum(rounded(vb) * rounded(w)[ib], rb,
                                     num_segments=batch) + b
        err = (jax.nn.sigmoid(logits) - yb) * mb
        count = jnp.maximum(jnp.sum(mb), 1.0)
        loss = jnp.sum(mb * (jnp.logaddexp(0.0, logits) - yb * logits))
        # a pad's row lies past the batch: it reads the appended zero
        err_of = jnp.concatenate([rounded(err), jnp.zeros((1,), jnp.float32)])
        g_w = jnp.zeros((dim,), jnp.float32).at[ib].add(
            err_of[rb] * rounded(vb))
        g_b = jnp.sum(err)
        new = (w - lr * (g_w / count + reg * w), b - lr * (g_b / count))
        if fault == "unchanged":
            new = params
        return new, (loss / count, jnp.sum(mb))

    def fit(chunks, lr, reg):
        # chunks: tuple of (idx, row, vals (steps, entries of the chunk's
        # fullest step), y, mask (steps, batch)), in row order

        def epoch(params, _):
            losses, counts = [], []
            for chunk in chunks:
                params, (l, c) = jax.lax.scan(
                    lambda p, i: step(p, i, lr, reg), params, chunk)
                losses.append(l)
                counts.append(c)
            losses, counts = jnp.concatenate(losses), jnp.concatenate(counts)
            total = jnp.maximum(jnp.sum(counts), 1.0)
            return params, jnp.sum(losses * counts) / total

        init = (jnp.zeros((dim,), jnp.float32), jnp.zeros((), jnp.float32))
        (w, b), hist = jax.lax.scan(epoch, init, None, length=epochs)
        return w, b, hist

    return jax.jit(fit)


def _chunk(indptr, indices, values, y, lo, hi, batch):
    """Rows ``[lo, hi)`` as host arrays in steps of ``batch`` rows: (idx, row,
    vals (steps, pad), y, mask (steps, batch))."""
    steps = -(-(hi - lo) // batch)
    starts = np.minimum(lo + batch * np.arange(steps + 1), hi)
    counts = indptr[starts[1:]] - indptr[starts[:-1]]
    pad = max(1, -(-int(counts.max()) // PAD_MULTIPLE)) * PAD_MULTIPLE
    idx = np.zeros((steps, pad), np.int32)
    row = np.full((steps, pad), batch, np.int32)  # a pad: past the batch
    vals = np.zeros((steps, pad), np.float32)
    yp = np.zeros((steps, batch), np.float32)
    mask = np.zeros((steps, batch), np.float32)
    for s in range(steps):
        r0, r1 = int(starts[s]), int(starts[s + 1])
        e0, e1 = int(indptr[r0]), int(indptr[r1])
        idx[s, :e1 - e0] = indices[e0:e1]
        vals[s, :e1 - e0] = values[e0:e1]
        row[s, :e1 - e0] = np.repeat(np.arange(r1 - r0, dtype=np.int32),
                                     np.diff(indptr[r0:r1 + 1]))
        yp[s, :r1 - r0] = y[r0:r1]
        mask[s, :r1 - r0] = 1.0
    return idx, row, vals, yp, mask


class Table:
    """One sparse table laid out in SGD steps, in row order, resident on the
    device for any number of reference fits."""

    def __init__(self, indptr, indices, values, y, dim, batch):
        import jax.numpy as jnp

        n = len(y)
        self.dim, self.batch = int(dim), int(batch)
        rows = CHUNK_STEPS * self.batch
        self.chunks = tuple(
            tuple(jnp.asarray(a) for a in _chunk(
                indptr, indices, values, y, lo, min(lo + rows, n),
                self.batch))
            for lo in range(0, n, rows))

    def fit(self, learning_rate, reg, epochs, precision="f32",
            fault=None) -> dict:
        """One fit's answer (coefficients, intercept, loss per epoch) as host
        float64: the same keys as the program's answer.  The laid-out table
        is the same for every precision: the rounding is the step's."""
        import jax

        with jax.default_matmul_precision("highest"):
            w, b, hist = _fit_fn(self.dim, self.batch, int(epochs), precision,
                                 fault)(
                self.chunks, np.float32(learning_rate), np.float32(reg))
        return {"coef": np.asarray(w, np.float64), "intercept": float(b),
                "losses": np.asarray(hist, np.float64)}
