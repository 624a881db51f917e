"""Plain references, one file each: ``chipbench/references/<name>.py``.

A configuration names its reference (``"reference": "<name>"``); a job kind
loads it with ``load``.  A reference imports nothing of ``flink_ml_tpu`` and
exports ``NUMBERS`` (the names ``gaps`` returns), ``gaps(answer, ref)`` and
``CONTROLS`` (the variants of itself that have to come out as not correct:
``{label: keyword arguments}``).  What else it offers is between it and the
job kinds that use it.
"""

import importlib


def load(name: str):
    try:
        return importlib.import_module(f"chipbench.references.{name}")
    except ModuleNotFoundError as exc:
        if exc.name != f"chipbench.references.{name}":
            raise
        raise SystemExit(f"chipbench: no reference "
                         f"chipbench/references/{name}.py")
