"""What the ``refit_dp`` kind asks of ``flink_ml_tpu`` beyond ``program.py``'s
calls: the default ML environment's mesh, set the public way
(``MLEnvironment.set_mesh`` with ``parallel/mesh.py:create_mesh``) to the
configuration's ``{"data": chips}`` over the first ``chips`` devices, and put
back afterwards.  On a host of exactly ``chips`` chips that is the mesh
``default_mesh()`` gives anyway; in the tests it is 4 of the CPU's 8 virtual
devices.  No knob, no environment variable.  And one measurement of the
program from outside, :func:`packed_forms`: how many times over its set-up
of a fit holds a table on the host.
"""

from __future__ import annotations


def require_devices(chips: int):
    """The first ``chips`` devices, or exit: a table sized for ``chips``
    chips is never laid over fewer."""
    import jax

    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(
            f"chipbench: kind refit_dp lays its table over {chips} devices; "
            f"JAX found {len(devices)} x {devices[0].platform}; refusing to "
            f"run")
    return devices[:chips]


def set_mesh(axes: dict, devices):
    """Set the default environment's mesh to ``axes`` over ``devices``;
    returns what :func:`restore_mesh` puts back."""
    from flink_ml_tpu.parallel.mesh import create_mesh
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    env = MLEnvironmentFactory.get_default()
    previous = env.get_mesh()
    env.set_mesh(create_mesh({k: int(v) for k, v in axes.items()}, devices))
    return previous


def restore_mesh(previous) -> None:
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    MLEnvironmentFactory.get_default().set_mesh(previous)


def packed_forms(axes: dict, devices, features: int, dtype: str) -> float:
    """How many times over the program holds a dense table on the HOST at
    the peak of a fit's set-up, the caller's own table apart: measured, by
    ``tracemalloc`` (NumPy reports its arrays to it) around ONE public
    ``LogisticRegression.fit`` of a small table (four steps of 4,096 rows a
    device, ``features`` wide: 205 MB at 784 over four) laid over ``devices``
    as ``axes`` says.  The fit is made twice and the second is measured, its
    program compiled already and its table placed anew, so that what Python
    allocates while it compiles (23 MB on the CPU) is not read as table.
    Nothing of the program is named here but ``program.py``'s calls.  Read
    on the CPU's virtual devices at 784 features (PR 37): 1.00 for a pack
    that lays the table once, straight into the slab the devices hold; 2.00
    for PR 36's, which padded the table and then copied it device-major."""
    import tracemalloc

    import numpy as np

    from chipbench import program

    a_device = 4096
    rows = len(devices) * 4 * a_device
    X = np.ones((rows, int(features)), dtype)
    y = (np.arange(rows) % 2).astype(np.float64)
    config = {"globalBatchSize": len(devices) * a_device, "maxIter": 1,
              "tol": 0.0, "withIntercept": True}

    def fit():
        program.logreg(config, 0.1, 0.0).fit(program.table(X, y))
        program.release()  # the next fit packs and places again

    previous = set_mesh(axes, devices)
    try:
        fit()
        tracemalloc.start()
        try:
            fit()
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
    finally:
        restore_mesh(previous)
    return peak / X.nbytes
