"""Perf diagnosis: where does the wall time of the fused logreg fit go?

Measures, on the real device:
  1. host->device transfer bandwidth
  2. fused program time with the batch ALREADY resident in HBM
  3. device->host readback latency
  4. per-minibatch-step device time as a function of batch size
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from flink_ml_tpu.parallel.mesh import default_mesh as build_mesh, replicate, shard_batch
from flink_ml_tpu.lib.classification import _log_loss_grads
from flink_ml_tpu.lib.common import (
    make_glm_train_fn, pack_minibatches, _combined_view, fetch_flat,
)


def t(f, n=3):
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    mesh = build_mesh()
    print("devices:", jax.devices())

    # 1. transfer bandwidth
    for mb_size in (1, 8, 64):
        a = np.random.randn(mb_size * 1024 * 256).astype(np.float32)  # mb_size MB
        dt = t(lambda: jax.device_put(a).block_until_ready())
        print(f"h2d {mb_size:3d}MB: {dt*1e3:8.1f}ms  {mb_size/dt:8.1f} MB/s")

    # readback
    d = jax.device_put(np.random.randn(1024 * 256).astype(np.float32))
    dt = t(lambda: np.asarray(d))
    print(f"d2h   1MB: {dt*1e3:8.1f}ms  {1/dt:8.1f} MB/s")
    s = jax.device_put(np.float32(1.0))
    dt = t(lambda: float(s))
    print(f"d2h scalar: {dt*1e3:7.1f}ms (round-trip latency)")

    # tiny dispatch latency
    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.float32(0.0))
    f(x).block_until_ready()
    dt = t(lambda: f(x).block_until_ready())
    print(f"jit noop dispatch+sync: {dt*1e3:7.2f}ms")

    # 2/3. fused program on resident data, HIGGS shape
    n, dfeat, epochs = 160_000, 28, 50
    rng = np.random.RandomState(0)
    X = rng.randn(n, dfeat).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    grad_fn = _log_loss_grads(True)
    for batch in (8192, 65536, n):
        stack = pack_minibatches(X, y, 1, batch)
        train_fn = make_glm_train_fn(grad_fn, mesh, 0.5, 0.0, epochs, 0.0)
        combined = _combined_view(stack)
        dev_batch = shard_batch(mesh, combined)
        jax.block_until_ready(dev_batch)
        params0 = replicate(mesh, (jnp.zeros(dfeat), jnp.zeros(())))

        # placement (transfer) time
        dt_place = t(lambda: jax.block_until_ready(shard_batch(mesh, combined)))

        # program time on resident data (donation: re-place params each run,
        # but params are tiny)
        def run():
            p = jax.tree_util.tree_map(jnp.copy, params0)
            out = train_fn(p, dev_batch)
            jax.block_until_ready(out)

        run()  # compile
        dt_run = t(run)
        steps = stack.steps * epochs
        print(
            f"batch={batch:6d} steps/epoch={stack.steps:3d}: "
            f"place {dt_place*1e3:7.1f}ms ({combined.nbytes/1e6:.1f}MB), "
            f"program {dt_run*1e3:7.1f}ms "
            f"({dt_run/steps*1e6:7.1f}us/mb-step, "
            f"{n*epochs/dt_run/1e6:8.1f}M samples/s resident)"
        )

        # full fetch cost
        p = jax.tree_util.tree_map(jnp.copy, params0)
        out = train_fn(p, dev_batch)
        jax.block_until_ready(out)
        leaves = jax.tree_util.tree_leaves(out)
        dt_fetch = t(lambda: fetch_flat(*leaves))
        print(f"          fetch results: {dt_fetch*1e3:7.1f}ms")


if __name__ == "__main__":
    main()
