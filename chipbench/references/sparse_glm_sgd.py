"""The plain reference ``sparse_glm_sgd``: minibatch SGD on the binary log
loss over sparse rows.

Straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  It imports nothing of
``flink_ml_tpu`` and takes nothing the program has made: it gets the rows in
ELL form (``idx`` and ``vals`` of shape ``(rows, entries a row)``: every row
of the hashed click logs stores the same number of entries) and the labels
that the harness made from the seed, and the configuration's numbers.  A step
over one global batch, rows in table order, is

    logits = (vals * w[idx]).sum(-1) + b
    err    = sigmoid(logits) - y
    g_w    = zeros(dim).at[idx].add(err[:, None] * vals) / rows of the batch
    w, b   = w - lr * (g_w + reg * w), b - lr * mean(err)

the same update and L2 term as ``glm_sgd``.  An index stored twice in a row
(a collision of the hash) is two entries, in the score and in the gradient.
On the device a chunk of steps lies entries-major, ``(steps, entries a row,
batch)``, so that the chip's tiles hold no padding; the table goes up chunk
by chunk and stays for any number of fits (the program's slabs are released
first: it fits beside nothing).

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
CHUNK_STEPS = 64


def precision_of(config: dict) -> str:
    """The reference's precision for a configuration; a ``dtype`` or a
    ``withIntercept`` that this reference does not compute is refused."""
    if config["dtype"] not in PRECISIONS:
        raise SystemExit(f"chipbench: reference sparse_glm_sgd has no dtype "
                         f"{config['dtype']!r} (known: {sorted(PRECISIONS)})")
    if config["withIntercept"] is not True:
        raise SystemExit("chipbench: reference sparse_glm_sgd fits an "
                         "intercept; withIntercept must be true")
    return PRECISIONS[config["dtype"]]


@functools.lru_cache(maxsize=None)
def _fit_fn(dim, epochs, precision, fault):
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
        ib, vb, yb, mb = inp  # (entries, batch) x 2, (batch,) x 2
        if fault == "half_batch":
            half = yb.shape[0] // 2
            mb = mb * (jnp.arange(yb.shape[0]) < half).astype(jnp.float32)
        logits = jnp.sum(rounded(vb) * rounded(w)[ib], axis=0) + b
        err = (jax.nn.sigmoid(logits) - yb) * mb
        count = jnp.maximum(jnp.sum(mb), 1.0)
        loss = jnp.sum(mb * (jnp.logaddexp(0.0, logits) - yb * logits))
        g_w = jnp.zeros((dim,), jnp.float32).at[ib.reshape(-1)].add(
            (rounded(err)[None, :] * rounded(vb)).reshape(-1))
        g_b = jnp.sum(err)
        new = (w - lr * (g_w / count + reg * w), b - lr * (g_b / count))
        if fault == "unchanged":
            new = params
        return new, (loss / count, jnp.sum(mb))

    def fit(chunks, lr, reg):
        # chunks: tuple of (idx, vals (steps, entries, batch), y, mask), in
        # row order

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


@functools.lru_cache(maxsize=None)
def _layout_fn(steps, batch, width):
    import jax
    import jax.numpy as jnp

    def layout(idx, vals, y):
        n = y.shape[0]
        pad = steps * batch - n

        def lay(a):  # (rows x entries,) flat -> (steps, entries, batch)
            a = jnp.pad(a.reshape(n, width), ((0, pad), (0, 0)))
            return a.reshape(steps, batch, width).transpose(0, 2, 1)

        yp = jnp.pad(y.astype(jnp.float32), (0, pad)).reshape(steps, batch)
        mask = (jnp.arange(steps * batch) < n).astype(jnp.float32)
        return lay(idx), lay(vals), yp, mask.reshape(steps, batch)

    return jax.jit(layout)


class Table:
    """One sparse table laid out in SGD steps, in row order, resident on the
    device for any number of reference fits."""

    def __init__(self, idx, vals, y, dim, batch):
        import jax.numpy as jnp

        n, width = idx.shape
        self.dim, self.batch = int(dim), int(batch)
        rows = CHUNK_STEPS * self.batch
        chunks = []
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            steps = -(-(hi - lo) // self.batch)
            # flat on the way up: a (rows, 39) array would be padded to the
            # chip's 128-wide tiles before the layout could transpose it
            chunks.append(_layout_fn(steps, self.batch, width)(
                jnp.asarray(idx[lo:hi].reshape(-1), jnp.int32),
                jnp.asarray(vals[lo:hi].reshape(-1), jnp.float32),
                jnp.asarray(y[lo:hi], jnp.float32)))
        self.chunks = tuple(chunks)

    def fit(self, learning_rate, reg, epochs, precision="f32",
            fault=None) -> dict:
        """One fit's answer (coefficients, intercept, loss per epoch) as host
        float64: the same keys as the program's answer.  The laid-out table
        is the same for every precision: the rounding is the step's."""
        import jax

        with jax.default_matmul_precision("highest"):
            w, b, hist = _fit_fn(self.dim, int(epochs), precision, fault)(
                self.chunks, np.float32(learning_rate), np.float32(reg))
        return {"coef": np.asarray(w, np.float64), "intercept": float(b),
                "losses": np.asarray(hist, np.float64)}
