"""The plain reference ``glm_sgd``: minibatch SGD on the binary log loss.

Straightforward ``jax.numpy``, float32, every matrix product at precision
``highest``.  It imports nothing of ``flink_ml_tpu`` and takes nothing the
program has made: it gets the rows and labels that the harness made from the
seed, and the configuration's numbers.  A configuration names it with
``"reference": "glm_sgd"``; a job kind finds it through
``chipbench.references.load``.

The same function computes the controls and the planted faults that
``chipbench/limits.py`` and the tests put in the program's place:

* ``precision="bf16"`` — the table and the weights enter both matrix products
  as bfloat16 (float32 accumulation): the step below the float32 that the
  configurations state, and the one a later PR is tempted by, since the fit
  is bound by the bytes of the table.
* ``fault="half_batch"`` — the second half of every minibatch is left out and
  the mean taken over the rest.
* ``fault="unchanged"`` — every step returns its state unchanged.

``gaps`` is the comparison of one answer with one reference answer, and
``NUMBERS`` names what it returns: every cell of a configuration that names
this reference has a limit for each in ``chipbench/limits/<cell>.json``.

It runs on whatever device JAX has (the chip in a benchmark run, after the
window has closed and the program's slabs are freed; the CPU in the tests).
"""

from __future__ import annotations

import functools

import numpy as np

#: the numbers ``gaps`` returns
NUMBERS = ("coef_gap", "loss_gap")
#: variants of the reference that have to come out as not correct:
#: the control (a precision lower) and the planted faults
CONTROLS = {
    "control_bf16": {"precision": "bf16"},
    "fault_half_batch": {"fault": "half_batch"},
    "fault_unchanged": {"fault": "unchanged"},
}
#: the precision of the reference itself, by the configuration's ``dtype``
PRECISIONS = {"float32": "f32"}


def precision_of(config: dict) -> str:
    """The reference's precision for a configuration; a ``dtype`` or a
    ``withIntercept`` that this reference does not compute is refused."""
    if config["dtype"] not in PRECISIONS:
        raise SystemExit(f"chipbench: reference glm_sgd has no dtype "
                         f"{config['dtype']!r} (known: {sorted(PRECISIONS)})")
    if config["withIntercept"] is not True:
        raise SystemExit("chipbench: reference glm_sgd fits an intercept; "
                         "withIntercept must be true")
    return PRECISIONS[config["dtype"]]


@functools.lru_cache(maxsize=None)
def _fit_fn(epochs, precision, fault):
    import jax
    import jax.numpy as jnp

    low = precision == "bf16"
    if precision not in ("f32", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    if fault not in (None, "half_batch", "unchanged"):
        raise ValueError(f"unknown fault {fault!r}")

    def dot(a, b):
        if low:
            a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def step(params, inp, lr, reg):
        w, b = params
        xb, yb, mb = inp
        if fault == "half_batch":
            half = xb.shape[0] // 2
            mb = mb * (jnp.arange(xb.shape[0]) < half).astype(jnp.float32)
        logits = dot(xb, w) + b
        err = (jax.nn.sigmoid(logits) - yb) * mb
        count = jnp.maximum(jnp.sum(mb), 1.0)
        loss = jnp.sum(mb * (jnp.logaddexp(0.0, logits) - yb * logits))
        g_w = dot(err, xb)
        g_b = jnp.sum(err)
        new = (w - lr * (g_w / count + reg * w), b - lr * (g_b / count))
        if fault == "unchanged":
            new = params
        return new, (loss / count, jnp.sum(mb))

    def fit(chunks, lr, reg):
        # chunks: tuple of (xs (steps, batch, d), y, mask), in row order

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

        d = chunks[0][0].shape[-1]
        init = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
        (w, b), hist = jax.lax.scan(epoch, init, None, length=epochs)
        return w, b, hist

    return jax.jit(fit)


@functools.lru_cache(maxsize=None)
def _layout_fn(steps, batch, precision):
    import jax
    import jax.numpy as jnp

    def layout(x, y):
        n, d = x.shape
        pad = steps * batch - n
        if precision == "bf16":
            x = x.astype(jnp.bfloat16)
        xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(steps, batch, d)
        yp = jnp.pad(y.astype(jnp.float32), (0, pad)).reshape(steps, batch)
        mask = (jnp.arange(steps * batch) < n).astype(jnp.float32)
        return xs, yp, mask.reshape(steps, batch)

    return jax.jit(layout)


#: SGD steps to a device chunk: the table goes up chunk by chunk
CHUNK_STEPS = 8


class Table:
    """One table laid out in SGD steps, in row order, resident on the device
    for any number of reference fits."""

    def __init__(self, X, y, batch, precision="f32"):
        import jax.numpy as jnp

        n = X.shape[0]
        self.batch = int(batch)
        self.precision = precision
        rows = CHUNK_STEPS * self.batch
        chunks = []
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            steps = -(-(hi - lo) // self.batch)
            chunks.append(_layout_fn(steps, self.batch, precision)(
                jnp.asarray(X[lo:hi], jnp.float32),
                jnp.asarray(y[lo:hi], jnp.float32)))
        self.chunks = tuple(chunks)

    def fit(self, learning_rate, reg, epochs, fault=None) -> dict:
        """One fit's answer (coefficients, intercept, loss per epoch) as host
        float64: the same keys as the program's answer."""
        w, b, hist = _fit_fn(int(epochs), self.precision, fault)(
            self.chunks, np.float32(learning_rate), np.float32(reg))
        return {"coef": np.asarray(w, np.float64), "intercept": float(b),
                "losses": np.asarray(hist, np.float64)}


def _vec(answer):
    return np.concatenate([answer["coef"], [answer["intercept"]]])


def gaps(answer, ref) -> dict:
    """The numbers one answer is judged by, against one reference answer:
    ``coef_gap``, the norm of (coefficients and intercept minus the
    reference's) over the reference's norm; ``loss_gap``, the largest relative
    gap of a per-epoch loss."""
    a, r = _vec(answer), _vec(ref)
    same = len(answer["losses"]) == len(ref["losses"])
    return {
        "coef_gap": float(np.linalg.norm(a - r) / np.linalg.norm(r)),
        "loss_gap": float(np.max(np.abs(answer["losses"] - ref["losses"])
                                 / np.abs(ref["losses"])))
        if same else float("inf"),
    }
