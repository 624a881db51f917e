"""fmtlint — AST-based static analysis of this repo's own invariants.

The reference framework leans on Java's type system plus checkstyle to
keep its API contracts honest; a Python/JAX reproduction gets neither
for free.  Eleven PRs layered contracts onto this codebase that nothing
enforced mechanically until now:

* ``fused_kernel`` device closures and jit-traced functions must be
  pure jnp (no host calls, no clock, no RNG, no environment reads, no
  metric mutation) — :mod:`~flink_ml_tpu.analysis.checkers.jit_purity`;
* state mutated under ``self._lock`` in one method must not be touched
  bare in another (dispatcher/prefetch/monitor threads share these
  objects) — :mod:`~flink_ml_tpu.analysis.checkers.lock_discipline`;
* every ``FMT_*`` environment knob is declared exactly once in
  :mod:`flink_ml_tpu.utils.knobs` and documented in README.md
  — :mod:`~flink_ml_tpu.analysis.checkers.knob_registry`;
* thread-ambient scopes (``trace.use``, ``quarantine.capture``, drift
  taps) are used only as context managers, and metric names stay
  dotted-lowercase and kind-collision-free —
  :mod:`~flink_ml_tpu.analysis.checkers.hygiene`.

``python -m flink_ml_tpu.analysis --check`` mirrors ``obs --check``:
exit 0 when the repo is clean modulo the committed suppression baseline
(``analysis/baseline.json`` — every entry carries a written reason),
nonzero otherwise.  Pure stdlib, no JAX import: the CI job runs it on a
bare Python in a few seconds.
"""

from flink_ml_tpu.analysis.core import (  # noqa: F401
    Finding,
    Project,
    apply_baseline,
    load_baseline,
    load_project,
    run_checkers,
)
