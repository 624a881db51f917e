"""One process, one cell, once.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration's file, its traffic
mix (``chipbench/traffic/<mix>.json``), the mix's job kind
(``chipbench/kinds/<kind>.py``), its limits (``chipbench/limits/<cell>.json``)
and one reader per metric (``chipbench/end_to_end/<name>.py``,
``chipbench/layers/<name>.py``) by name: a new cell, configuration, mix, job
kind, reference or metric is new files and new entries, and no edit here.
See ``chipbench/README.md``.

Refuses anything but a TPU with the chips the cell asks for (exit 3, nothing
on standard output).  Set-up (data from the seed, pack, placement, every
program the window will use) is counted as ``setup_s``; then the window runs
for ``--seconds``; then ``memory_peak_bytes`` is read, the program's slabs are
freed and every answer of the window is held against the plain reference.
The last line of standard output is the result; the numbers compared, each
beside its limit, are its last key and the last lines of standard error.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: what the run leaves behind (RunReports, the trace while it is read); inside
#: the checkout, listed in .gitignore
OUT = os.path.join(ROOT, ".chipbench_out")
#: with --trace 1 the window, all of it traced, is at most this long (jobs
#: start until then, the last one finishes): a trace of some hundred fits is
#: tens of MB, and the per-layer metrics all read this one window
TRACE_SECONDS = 10.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in bench["workloads"]]
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                     f"(known: {known})")


def load_config(bench: dict, cell: dict) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == cell["config"]:
            return load_json(ROOT, entry["file"])
    raise SystemExit(f"chipbench: no config {cell['config']!r}")


def metrics_of(bench: dict, cell: dict, kind: str) -> list:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``): those
    that list the cell, or list nothing and move a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in reported]


def reader(kind_dir: str, name: str):
    """The reader of a metric: ``<kind_dir>/<name>.py``, else the family's
    (``<name>`` without its last dotted part, and so on)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(HERE, kind_dir, ".".join(parts[:n]) + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench.{kind_dir}.{'_'.join(parts[:n])}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read
    raise SystemExit(f"chipbench: no reader {kind_dir}/{name}.py")


class Context:
    """What a reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def phase(self, metric: dict) -> str:
        """A metric that moves ``setup_s`` is read over set-up, any other over
        the window."""
        return "setup" if metric.get("moves") == "setup_s" else "window"

    def counter(self, name: str, phase: str = "window") -> float:
        a, b = self.snapshots[phase]
        return b["counters"].get(name, 0) - a["counters"].get(name, 0)

    def timing(self, name: str, phase: str = "window"):
        """(seconds, count) observed under ``name`` in the phase."""
        a, b = self.snapshots[phase]
        zero = {"count": 0, "total_s": 0.0}
        ta, tb = a["timings"].get(name, zero), b["timings"].get(name, zero)
        return tb["total_s"] - ta["total_s"], tb["count"] - ta["count"]

    @property
    def peak(self) -> dict:
        return self.work.peak(self.device_kind)

    @property
    def job_work(self) -> dict:
        """Bytes and operations one job needs, as the job kind counts them."""
        return self.generator.work()

    @property
    def done(self) -> list:
        return [j for j in self.jobs if "answer" in j]


class CompileCounter:
    """Counts, through ``jax.monitoring``, every program lowered (built from
    scratch or read from the persistent cache) and every one compiled."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILED = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.lowered = self.compiled = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == self.LOWERED:
            self.lowered += 1
        elif event == self.COMPILED:
            self.compiled += 1


def require_chips(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"chipbench: JAX found no accelerator: {exc}")
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}); refusing to run\n")
        raise SystemExit(3)
    return devices


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _median_and_max(seconds):
    ordered = sorted(seconds)
    return ([ordered[len(ordered) // 2], ordered[-1]] if ordered else None)


def run_cell(args, bench, cell, config, mix, limits, devices):
    """Set-up, window, check.  Returns the result dict (see module doc)."""
    import jax

    from chipbench import check, jobs, program, trace_reduce, work

    clients = jobs.clients_of(mix)

    out_dir = os.path.join(OUT, cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    program.prepare(out_dir)
    compiles = CompileCounter()
    spans = jobs.Spans()
    snap_start = program.snapshot()

    generator = jobs.make(config, mix, args.seed, spans)
    generator.setup()
    snap_setup = program.snapshot()
    setup_compiled = compiles.compiled
    lowered0 = compiles.lowered
    setup_s = time.perf_counter() - _T0

    trace_dir = os.path.join(out_dir, "trace")
    seconds = args.seconds
    if args.trace:
        # the traced run's window is the traced window, and short
        seconds = min(seconds, TRACE_SECONDS)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            window_jobs, start, end = jobs.run_window(generator, seconds,
                                                      clients)
        jax.profiler.stop_trace()
    else:
        window_jobs, start, end = jobs.run_window(generator, seconds, clients)
    snap_end = program.snapshot()
    lowered_in_window = compiles.lowered - lowered0
    peak_bytes = memory_peak(devices)

    ctx = Context(
        cell=cell, config=config, mix=mix, seconds=seconds,
        generator=generator, jobs=window_jobs, window_start=start, window_end=end,
        setup_s=setup_s, spans=spans, work=work,
        device_kind=devices[0].device_kind,
        snapshots={"setup": (snap_start, snap_setup),
                   "window": (snap_setup, snap_end)},
        trace=None)
    if args.trace:
        ctx.trace = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # only now the reference: the peak is read, the program's slabs go
    generator.release()
    program.release()
    t_check = time.perf_counter()
    hidden = {k: v for k, v in snap_end["counters"].items()
              if k in program.MUST_BE_ZERO and v}
    failed = [j for j in window_jobs if "answer" not in j]
    values = check.compare(generator, window_jobs, {
        "failed_jobs": len(failed),
        "compiles_in_window": lowered_in_window
        + ctx.counter("train.compile_runs"),
        "hidden_failures": sum(hidden.values()),
    })
    correct, compared = check.verdict(values, limits)
    check_s = time.perf_counter() - t_check

    kind, readers = (("per_layer", "layers") if args.trace
                     else ("end_to_end", "end_to_end"))
    metrics = {}
    for metric in metrics_of(bench, cell, kind):
        value = reader(readers, metric["name"])(ctx, metric)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(window_jobs),
              "failed": len(failed), "metrics": metrics, "device": device}
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    record = {
        "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "setup_s": setup_s,
        "window_s": end - start, "check_s": check_s,
        "compiled_in_setup": setup_compiled,
        "jobs_done": len(ctx.done),
        # a stall shows as a longest job far over the median one
        "job_s": _median_and_max([j["end"] - j["start"] for j in ctx.done]),
        "errors": [j["error"] for j in failed][:3],
        "hidden": hidden, "values": values,
        "spans": {n: spans.total(n) for n in
                  sorted({r[0] for r in spans.records})},
        "programs": ctx.trace["programs"] if ctx.trace else None,
    }
    result["compared"] = compared  # last key of the line
    return result, record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m chipbench.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_config(bench, cell)
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")
    # the configuration's documented settings, before the program is imported
    os.environ.update({k: str(v) for k, v in config.get("env", {}).items()})

    # without the program the import fails here: non-zero, nothing printed
    import flink_ml_tpu  # noqa: F401

    devices = require_chips(int(cell["chips"]))
    result, record = run_cell(args, bench, cell, config, mix, limits, devices)
    sys.stdout.write("chipbench: record " + json.dumps(record) + "\n")
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    for name, pair in result["compared"].items():
        sys.stderr.write(f"chipbench: compared {name} = {pair['value']} "
                         f"(limit {pair['limit']})\n")
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
