"""``python -m flink_ml_tpu.obs`` — the report summary CLI (+ ``trace``).

The package ``__init__`` imports :mod:`flink_ml_tpu.obs.report`, so running
``python -m flink_ml_tpu.obs.report`` makes runpy re-execute an
already-imported module (a RuntimeWarning plus a duplicate copy of its
globals).  This entry point runs the SAME ``main`` without re-execution;
the longer spelling keeps working for compatibility.

Subcommands: ``python -m flink_ml_tpu.obs trace [TRACE_ID] [--list]``
renders one process's span waterfall from its ``traces-<pid>.jsonl``
sink (:mod:`flink_ml_tpu.obs.trace`); ``python -m flink_ml_tpu.obs
fleet [TRACE_ID] [--list]`` stitches EVERY per-pid sink in the trace
dir into one clock-corrected multi-process waterfall with a per-phase
cost rollup; ``python -m flink_ml_tpu.obs drift`` renders the
per-column reference-vs-live drift comparison
(:mod:`flink_ml_tpu.obs.drift`); everything else goes to the report
summary (``--check`` / ``--json`` / ``--reports`` / ``--last``).
"""

import sys

from flink_ml_tpu.obs.drift import drift_main
from flink_ml_tpu.obs.report import main
from flink_ml_tpu.obs.trace import fleet_main
from flink_ml_tpu.obs.trace import main as trace_main

if len(sys.argv) > 1 and sys.argv[1] == "trace":
    sys.exit(trace_main(sys.argv[2:]))
if len(sys.argv) > 1 and sys.argv[1] == "fleet":
    sys.exit(fleet_main(sys.argv[2:]))
if len(sys.argv) > 1 and sys.argv[1] == "drift":
    sys.exit(drift_main(sys.argv[2:]))
sys.exit(main())
