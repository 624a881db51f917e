"""The plain reference ``glm_sgd_over_chips``: ``glm_sgd`` on a table that
one chip cannot hold, its rows laid over the chips of one host.

The same mathematics, from the same lines: ``glm_sgd``'s jitted ``fit`` (its
``step``, the scan over steps and epochs, the bfloat16 control and the
``half_batch`` / ``unchanged`` faults), its ``gaps``, ``NUMBERS``,
``CONTROLS`` and ``precision_of`` are imported, not copied.  Straightforward
``jax.numpy``, float32, every matrix product at precision ``highest``; it
imports nothing of ``flink_ml_tpu`` and takes nothing the program has made.
A configuration names it with ``"reference": "glm_sgd_over_chips"`` and says
over how many chips with ``"chips"``.

Departures from ``glm_sgd``, each for the table's size alone:

* ``Table`` lays a chunk of steps ``(steps, batch, d)`` on the host (a view of
  the table's rows; only the last, ragged chunk is padded, on the host) and
  puts it with ``jax.device_put`` under a ``NamedSharding`` that divides the
  BATCH axis over the first ``chips`` devices: every chip holds a ``1/chips``
  of the rows of every step, 6.35 GB of 25.4 GB.  ``glm_sgd`` puts a chunk on
  one device and pads and reshapes it there.
* The bfloat16 control's table is cast on the devices, chunk by chunk, after
  it is put (``glm_sgd`` casts inside the same jitted layout).
* The same jitted ``fit`` runs over the sharded chunks: the compiler's
  partitioner divides the products and places the sums over the chips (the
  gradient's contraction over the batch axis, the loss and the count become
  all-reduces).  No ``shard_map``, no ``psum`` written by hand, no kernel.
  What may differ from ``glm_sgd`` on one device is the float32 order of a
  sum over the batch: ``chips`` partial sums, then their sum
  (``tests/test_dp_fit.py`` states the gap read and its limit).

The ``half_batch`` fault leaves out the second half of every GLOBAL batch:
the rows of the last ``chips / 2`` chips.
"""

from __future__ import annotations

import numpy as np

from chipbench.references.glm_sgd import (  # noqa: F401 - the reference's names
    CHUNK_STEPS,
    CONTROLS,
    NUMBERS,
    PRECISIONS,
    _fit_fn,
    gaps,
    precision_of,
)


def batch_sharding(chips: int):
    """(rows, labels) shardings of a chunk: the batch axis over the first
    ``chips`` devices.  Fewer devices than the configuration states is an
    error, never a smaller mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"chipbench: reference glm_sgd_over_chips lays its "
                         f"table over {chips} devices; JAX found "
                         f"{len(devices)}")
    mesh = Mesh(np.array(devices[:chips]), ("chips",))
    return (NamedSharding(mesh, P(None, "chips", None)),
            NamedSharding(mesh, P(None, "chips")))


class Table:
    """One table laid out in SGD steps, in row order, every step's rows
    divided over ``chips`` devices, resident for any number of fits."""

    def __init__(self, X, y, batch, precision="f32", chips=1):
        import jax
        import jax.numpy as jnp

        n, d = X.shape
        self.batch, self.precision = int(batch), precision
        if self.batch % chips:
            raise SystemExit(f"chipbench: a batch of {self.batch} rows does "
                             f"not divide over {chips} chips")
        rows_on, labels_on = batch_sharding(int(chips))
        rows = CHUNK_STEPS * self.batch
        chunks = []
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            steps = -(-(hi - lo) // self.batch)
            pad = steps * self.batch - (hi - lo)
            x = np.asarray(X[lo:hi], np.float32)
            yc = np.asarray(y[lo:hi], np.float32)
            mask = np.ones(hi - lo, np.float32)
            if pad:  # the table's last steps: zero rows of zero weight
                x = np.pad(x, ((0, pad), (0, 0)))
                yc, mask = np.pad(yc, (0, pad)), np.pad(mask, (0, pad))
            xs = jax.device_put(x.reshape(steps, self.batch, d), rows_on)
            if precision == "bf16":
                xs = xs.astype(jnp.bfloat16)
            chunks.append((
                xs, jax.device_put(yc.reshape(steps, self.batch), labels_on),
                jax.device_put(mask.reshape(steps, self.batch), labels_on)))
        self.chunks = tuple(chunks)

    def fit(self, learning_rate, reg, epochs, fault=None) -> dict:
        """One fit's answer (coefficients, intercept, loss per epoch) as host
        float64: the same keys as the program's answer."""
        w, b, hist = _fit_fn(int(epochs), self.precision, fault)(
            self.chunks, np.float32(learning_rate), np.float32(reg))
        return {"coef": np.asarray(w, np.float64), "intercept": float(b),
                "losses": np.asarray(hist, np.float64)}
