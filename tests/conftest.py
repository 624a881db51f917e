"""Test harness config.

Runs the whole suite on a virtual 8-device CPU mesh so psum/shard_map tests
exercise real collectives without TPU hardware — the analog of the reference
running parallel subtasks in Flink's in-JVM mini-cluster (SURVEY.md §4).

Everything here is plain environment + jax config set BEFORE the first
backend use.  The suite never touches a TPU: the Pallas tests pass
``interpret=True``; ``test_pallas_aot.py`` compiles with Mosaic for a chip
that is described, not attached; what only a run can show is covered on the
chip by ``chip_smoke.py``.
"""

import os
import tempfile

# the persistent compilation cache is a production warm-start feature; in
# tests it only adds disk churn and cross-process atime races (and the
# suite's programs are tiny), so keep it off unless a test opts in
os.environ.setdefault("FMT_COMPILE_CACHE", "off")

# RunReports, the compile ledger, flight-recorder dumps (breaker-open tests
# fire them) and trace sinks go to throwaway dirs, not the committed
# reports/ — a test run must leave the repo clean
os.environ.setdefault("FMT_OBS_REPORTS",
                      tempfile.mkdtemp(prefix="fmt_test_reports_"))
os.environ.setdefault("FMT_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="fmt_test_flight_"))
os.environ.setdefault("FMT_TRACE_DIR",
                      tempfile.mkdtemp(prefix="fmt_test_traces_"))

os.environ.setdefault("JAX_ENABLE_X64", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update(
    "jax_enable_x64",
    os.environ["JAX_ENABLE_X64"].lower() not in ("0", "false", "f", "no", "off"),
)

assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.device_count()} on "
    f"{jax.default_backend()}; backend was initialized before conftest"
)


import sys  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _no_warm_store_leaks_into_the_next_file():
    """A path deploy activates the process-wide warm-artifact store and
    nothing deactivates it: the next file on the same worker then got its
    fused dispatches off that store (no compile span, no jitted function),
    which failed whichever test expected a first compile, by worker order."""
    yield
    warmstart = sys.modules.get("flink_ml_tpu.serving.warmstart")
    if warmstart is not None:
        warmstart.configure(None)


@pytest.fixture(autouse=True, scope="module")
def _no_obs_switch_leaks_into_the_next_file():
    """The same, for the registry's switch: a file that turns telemetry on
    and leaves it on (the benchmark's rehearsals do: ``chipbench/program.py``
    ``prepare``) made ``test_trace.py``'s "off by default" fail whenever it
    ran next on the same worker, by worker order."""
    yield
    obs = sys.modules.get("flink_ml_tpu.obs")
    if obs is not None:
        obs.disable()
