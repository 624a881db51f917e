"""Job kinds, one file each: ``chipbench/kinds/<kind>.py``.

A traffic mix (``chipbench/traffic/<mix>.json``, data) names its kind with
``"job": "<kind>"``; ``chipbench.jobs.make`` finds the file by that name.  A
kind is the code that turns one entry of the mix into one call of the system
under test, and that knows which plain reference answers for it.  It exports

``make(config, mix, seed, spans)``
    the generator over the configuration's data, made from the seed:
    ``setup()``, ``job(i) -> (key, rows, answer)``, ``release()``,
    ``references(keys, **variant) -> {key: answer}``, ``gaps(answer, ref)``,
    ``work() -> {"bytes", "flops"}`` of one job, and ``keys`` (every key the
    mix cycles);
``numbers(config)``
    the names ``gaps`` returns: the cell's limits file has a limit for each;
``controls(config)``
    ``{label: variant}``: the ``references`` variants that have to fail;
``planted_faults(config)``
    ``{fault: [(object, attribute, replacement), ...]}``: ways to break the
    timed path underneath a run, for the tests.

A new kind of job (serving, another estimator) is a new file here, and no
edit to a file that is there.  See ``chipbench/README.md``.
"""
