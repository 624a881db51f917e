"""Where the host's time between two warm fits goes, read from a profile.

    python scripts/fit_gaps.py --shape epsilon --shape mnist8m [--fits 50]
    python scripts/fit_gaps.py --shape mnist8m_dp4    # on a host of four chips

The benchmark (``chipbench/``) says THAT the chip waits for the host between
two fits (``breakdown.idle_gaps``: ``job.fit``); its trace reader knows only
its own ``chipbench.*`` spans and deletes the trace.  This script holds its own
profiler session around warm fits of the program's public
``LogisticRegression.fit`` at a cell's shape and reads the program's own
``fmt.*`` spans (``obs.span``) and ``fmt.train*`` scopes from it:

* ``idle``      the device's idle time by the innermost ``fmt.*`` span that
                covers it (each fit sits in a ``chipbench.job.fit`` span, as
                in the harness, so the sum compares with the ledger's);
* ``spans``     host milliseconds a fit under each span (the registry);
* ``programs``  device programs a fit launches, by name;
* ``scopes``    the fused train program's device time by ``fmt.train*`` scope,
                and the xplane stat that carries the scope;
* ``placement`` the cold first fit: ``place.host_view`` / ``place.h2d`` and
                when the device saw the last slice (the ``jit_concat``
                program cannot start before it), against the span's end;
* ``cost``      warm fits a second with obs off, obs on, and obs on under
                the profiler, in rounds on one machine (``--no-cost`` leaves
                the off/on rounds out).

The scopes are metadata and no part of the persistent compile cache's key: a
machine whose cache holds the programs from before they carried scopes serves
them without.  To see the scopes, run with ``JAX_COMPILATION_CACHE_DIR`` set
to an empty directory (on the command line; the script sets none).

Data are made from ``--seed`` (standard normal columns, so already
standardised; over 2,097,152 rows one block of them repeated).  Tables go to
standard output, all numbers to ``chiprun_out/fit_gaps/<shape>.json``.  Runs
on whatever JAX finds; times mean something only on the chip.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: rows, features of the benchmark's configurations (chipbench/configs/);
#: ``mnist8m_dp4`` is the four-chip cell's whole table, for a host of four
#: chips (the default mesh lays it data-parallel over them)
SHAPES = {"epsilon": (400_000, 2_000), "mnist8m": (2_025_000, 784),
          "mnist8m_dp4": (8_100_000, 784), "tiny": (8_192, 32)}
BATCH, EPOCHS = 32_768, 10
#: a global step of the four-chip cell: BATCH a chip
BATCHES = {"mnist8m_dp4": 4 * BATCH}
#: rows drawn from the seed; a larger table repeats them
BLOCK = 1 << 21
GRID = [(lr, reg) for lr in (0.05, 0.1, 0.2, 0.5) for reg in (0.0, 1e-4)]
JOB_SPAN, FMT = "chipbench.job.fit", "fmt."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TRAIN_PROGRAM = "jit_bundled"


# -- the program's public surface ---------------------------------------------


def make_table(rows, features, seed):
    import numpy as np

    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((min(rows, BLOCK), features), dtype=np.float32)
    if rows > BLOCK:
        # the four-chip table repeats one seeded block: the gaps read no
        # value, and 25 GB drawn on one thread would take a minute
        X = np.resize(X, (rows, features))
    w = rng.standard_normal(features, dtype=np.float32) / np.sqrt(features)
    y = (X @ w + 0.5 * rng.standard_normal(rows, dtype=np.float32) > 0)
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y.astype(np.float64)})


def fit(table, point, batch):
    from flink_ml_tpu import obs
    from flink_ml_tpu.lib import LogisticRegression

    lr, reg = point
    with obs.profiler_annotation(JOB_SPAN):
        model = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("pred")
                 .set_learning_rate(lr).set_reg(reg)
                 .set_global_batch_size(batch).set_max_iter(EPOCHS)
                 .set_tol(0.0).fit(table))
        return model.coefficients(), model.train_losses_


def fits(table, n, batch, start=0):
    """n warm fits over the grid; seconds they took."""
    t0 = time.perf_counter()
    for i in range(start, start + n):
        fit(table, GRID[i % len(GRID)], batch)
    return time.perf_counter() - t0


def traced(trace_dir, body):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return out, path


def timings():
    from flink_ml_tpu import obs

    return {k: (v["total_s"], v["count"])
            for k, v in obs.registry().snapshot()["timings"].items()}


# -- reading the profile --------------------------------------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """[(number, value)] of one protobuf message (wire format)."""
    i, out = 0, []
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        out.append((key >> 3, value))
    return out


def op_metadata(path):
    """{operation name: {stat name: value}} of the device planes' event
    metadata, which ``jax.profiler.ProfileData`` does not show: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
    5, .ref_value = 7 (tsl/profiler/protobuf/xplane.proto)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        plane = _fields(plane)
        name = next((bytes(v).decode() for n, v in plane if n == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for n, entry in plane:
            if n == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        for n, entry in plane:
            if n != 4:
                continue
            stats, op = {}, ""
            for m, value in _fields(dict(_fields(entry))[2]):
                if m == 2:
                    op = bytes(value).decode()
                elif m == 5:
                    stat = dict(_fields(value))
                    if 5 in stat:
                        stats[stat_names.get(stat[1])] = \
                            bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        stats[stat_names.get(stat[1])] = \
                            stat_names.get(stat[7], "")
            out[op] = stats
    return out


def _union(intervals):
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _self_times(events):
    """{name: self ns} of nested (name, lo, hi) events on one line."""
    out, stack = {}, []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _hi, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        close(lo)
        if stack:
            stack[-1][2] -= hi - lo
        stack.append([name, hi, hi - lo])
    close(float("inf"))
    return out


def read_profile(path):
    """Host spans (``fmt.*`` and the job span), device operations and
    programs of one profile, times in ns on the profile's clock.  The
    device's are the first chip's: on a host of four every chip runs the
    same programs (data-parallel), and a sum over them would count each
    four times."""
    from jax.profiler import ProfileData

    host, ops, modules = [], [], []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name == "/device:TPU:0"
        for line in plane.lines:
            for e in line.events:
                span = (e.name, float(e.start_ns),
                        float(e.start_ns + e.duration_ns))
                if device and line.name == OPS_LINE:
                    ops.append(span)
                elif device and line.name == MODULES_LINE:
                    modules.append(span)
                elif plane.name == "/host:CPU" and (
                        e.name.startswith(FMT) or e.name == JOB_SPAN):
                    host.append(span)
    return host, ops, modules


def idle_by_span(host, ops):
    """The device's idle ns between the first job span's start and the last
    one's end, by the innermost (shortest) ``fmt.*`` span covering it; what
    only the job span covers is ``(job.fit, outside fmt.*)``."""
    jobs = [s for s in host if s[0] == JOB_SPAN]
    lo, hi = min(s[1] for s in jobs), max(s[2] for s in jobs)
    busy = _union([(max(a, lo), min(b, hi)) for _n, a, b in ops
                   if min(b, hi) > max(a, lo)])
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    cuts = sorted({t for _n, a, b in host for t in (a, b)})
    out = {}
    for g_lo, g_hi in zip(edges[0::2], edges[1::2]):
        points = [g_lo] + [c for c in cuts if g_lo < c < g_hi] + [g_hi]
        for a, b in zip(points, points[1:]):
            mid, best, best_len = (a + b) / 2, "(outside any job)", None
            for name, s_lo, s_hi in host:
                if s_lo <= mid < s_hi and (best_len is None
                                           or s_hi - s_lo < best_len):
                    best, best_len = name, s_hi - s_lo
            if best == JOB_SPAN:
                best = "(job.fit, outside fmt.*)"
            out[best] = out.get(best, 0.0) + (b - a)
    return out, hi - lo, sum(b - a for a, b in busy), len(jobs)


def nesting_faults(host):
    """fmt.* events that do not lie inside a job span on the trace's clock."""
    jobs = [s for s in host if s[0] == JOB_SPAN]
    return [s[0] for s in host if s[0] != JOB_SPAN
            and not any(j[1] <= s[1] and s[2] <= j[2] for j in jobs)]


def scope_of(tf_op):
    """The innermost ``fmt.*`` part of an operation's name."""
    parts = [p for p in str(tf_op).split("/") if p.startswith(FMT)]
    return parts[-1] if parts else "(no fmt scope)"


def scopes_table(ops, modules, metadata):
    """Device self seconds of the train program's operations by scope, and
    the stats whose value holds a scope."""
    runs = _union([(a, b) for n, a, b in modules
                   if n.startswith(TRAIN_PROGRAM)])
    inside = [e for e in ops if any(a <= e[1] and e[2] <= b for a, b in runs)]
    by_scope, carriers = {}, set()
    for name, ns in _self_times(inside).items():
        stats = metadata.get(name, {})
        carriers |= {k for k, v in stats.items() if FMT + "train" in str(v)}
        scope = scope_of(stats.get("tf_op", ""))
        by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
    largest = sorted(_self_times(inside).items(), key=lambda kv: -kv[1])[:4]
    sample = [{"op": name[:100], "self_s": ns / 1e9,
               "stats": {k: str(v)[:200]
                         for k, v in metadata.get(name, {}).items()}}
              for name, ns in largest]
    return by_scope, sorted(carriers), sample


def programs_by_span(host, modules):
    """{(program, innermost fmt.* span in which it started on the device):
    calls} of every program but the train program."""
    spans = [s for s in host if s[0] != JOB_SPAN]
    out = {}
    for name, start, _end in modules:
        if name.startswith(TRAIN_PROGRAM):
            continue
        owner, owner_len = "(no fmt span)", None
        for span, lo, hi in spans:
            if lo <= start < hi and (owner_len is None or hi - lo < owner_len):
                owner, owner_len = span, hi - lo
        key = f"{name.split('(')[0]} in {owner}"
        out[key] = out.get(key, 0) + 1
    return out


def placement(host, modules):
    """The cold fit: the place spans, and when the device had the table."""
    spans = {n: (a, b) for n, a, b in host if n in (
        FMT + "place.host_view", FMT + "place.h2d", FMT + "slab_pool.build")}
    concat = [(a, b) for n, a, b in modules if n.startswith("jit_concat")]
    out = {n[len(FMT):] + "_s": (b - a) / 1e9 for n, (a, b) in spans.items()}
    h2d = spans.get(FMT + "place.h2d")
    if h2d and concat:
        out["concat_starts_after_h2d_span_ends_s"] = \
            (concat[0][0] - h2d[1]) / 1e9
        out["concat_ends_after_h2d_span_ends_s"] = \
            (concat[-1][1] - h2d[1]) / 1e9
        out["h2d_span_start_to_concat_start_s"] = \
            (concat[0][0] - h2d[0]) / 1e9
    return out


# -- one shape ------------------------------------------------------------------


def table_lines(title, rows, header):
    print(f"\n{title}")
    print("| " + " | ".join(header) + " |")
    print("|" + "---|" * len(header))
    for row in rows:
        print("| " + " | ".join(
            f"{c:.4g}" if isinstance(c, float) else str(c) for c in row) + " |")


def run_shape(shape, n_fits, seed, out_dir, cost=True):
    import jax

    from flink_ml_tpu import obs
    from flink_ml_tpu.table import slab_pool

    rows, features = SHAPES[shape]
    batch = BATCHES.get(shape, min(BATCH, rows // 4))
    result = {"shape": shape, "rows": rows, "features": features,
              "fits": n_fits, "seed": seed,
              "device": [jax.devices()[0].platform,
                         jax.devices()[0].device_kind],
              "chips": len(jax.devices())}
    print(f"\n== {shape}: {rows} x {features}, batch {batch}, {EPOCHS} epochs, "
          f"{n_fits} fits a round, on {result['device']}")
    table = make_table(rows, features, seed)
    obs.enable()
    obs.reset()

    # the cold fit: pack, place, compile
    _, cold = traced(os.path.join(out_dir, "trace_cold"),
                     lambda: fit(table, GRID[0], batch))
    host, _ops, modules = read_profile(cold)
    result["placement"] = placement(host, modules)
    placed = obs.registry().snapshot()["counters"].get(
        "slab_pool.bytes_placed", 0)
    result["placement"]["bytes_placed"] = placed
    table_lines("placement (the cold first fit)",
                sorted(result["placement"].items()), ["what", "value"])
    fits(table, len(GRID), batch, start=1)  # every grid point's program

    # rounds on one machine: off, on, on under the profiler, on, off
    rate = {}
    if cost:
        obs.disable()
        rate["obs_off.1"] = n_fits / fits(table, n_fits, batch)
        obs.enable()
        rate["obs_on.1"] = n_fits / fits(table, n_fits, batch)
    before = timings()
    seconds, warm = traced(os.path.join(out_dir, "trace_warm"),
                           lambda: fits(table, n_fits, batch))
    after = timings()
    rate["obs_on_profiler_on"] = n_fits / seconds
    if cost:
        rate["obs_on.2"] = n_fits / fits(table, n_fits, batch)
        obs.disable()
        rate["obs_off.2"] = n_fits / fits(table, n_fits, batch)
        obs.enable()
    result["fits_per_s"] = rate
    table_lines("cost: warm fits a second", sorted(rate.items()),
                ["mode", "fits/s"])

    spans = {k: 1e3 * (after[k][0] - before.get(k, (0.0, 0))[0]) / n_fits
             for k in after if after[k][1] > before.get(k, (0.0, 0))[1]}
    result["span_ms_per_fit"] = spans
    table_lines("spans: host ms a fit (registry, the traced round)",
                sorted(spans.items(), key=lambda kv: -kv[1]), ["span", "ms"])

    host, ops, modules = read_profile(warm)
    result["fmt_events_outside_a_job_span"] = nesting_faults(host)
    result["fmt_events"] = sorted({n for n, _a, _b in host})
    programs = {}
    for name, _a, _b in modules:
        programs[name.split("(")[0]] = programs.get(name.split("(")[0], 0) + 1
    result["programs_per_fit"] = {k: v / n_fits for k, v in programs.items()}
    table_lines("programs: device programs a fit",
                sorted(result["programs_per_fit"].items()),
                ["program", "calls a fit"])
    result["extra_programs_per_fit_by_span"] = {
        k: v / n_fits for k, v in programs_by_span(host, modules).items()}
    table_lines("programs beside the train program, by the span they ran in",
                sorted(result["extra_programs_per_fit_by_span"].items()),
                ["program in span", "calls a fit"])
    if ops:
        idle, window, busy, jobs = idle_by_span(host, ops)
        result["idle_s"] = {k: v / 1e9 for k, v in idle.items()}
        result["window_s"], result["busy_s"] = window / 1e9, busy / 1e9
        table_lines(
            f"idle: device idle by innermost fmt.* span ({jobs} fits, window "
            f"{window / 1e9:.3f} s, busy {busy / 1e9:.3f} s, idle "
            f"{(window - busy) / 1e9:.4f} s = "
            f"{100 * (window - busy) / window:.2f}%)",
            [(k, v / 1e9, 1e3 * v / 1e9 / jobs)
             for k, v in sorted(idle.items(), key=lambda kv: -kv[1])],
            ["span", "idle s", "ms a fit"])
        by_scope, carriers, result["largest_ops"] = scopes_table(
            ops, modules, op_metadata(warm))
        result["train_scope_s"], result["scope_stats"] = by_scope, carriers
        table_lines(f"scopes: {TRAIN_PROGRAM} device self seconds by scope "
                    f"(stat carrying the scope: {carriers})",
                    sorted(by_scope.items(), key=lambda kv: -kv[1]),
                    ["scope", "device s"])
    else:
        print("\n(no /device:TPU plane in the profile: no idle and no scope "
              "table off the chip)")

    with open(os.path.join(out_dir, f"{shape}.json"), "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    for name in ("trace_cold", "trace_warm"):
        shutil.rmtree(os.path.join(out_dir, name), ignore_errors=True)
    del table
    slab_pool.pool().clear()
    gc.collect()
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python scripts/fit_gaps.py")
    parser.add_argument("--shape", action="append", choices=sorted(SHAPES),
                        required=True)
    parser.add_argument("--fits", type=int, default=50)
    parser.add_argument("--seed", type=int, default=24)
    parser.add_argument("--no-cost", action="store_true",
                        help="the traced round only, no obs on/off rounds")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "fit_gaps"))
    args = parser.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault("FMT_OBS_REPORTS", os.path.join(args.out, "reports"))
    for shape in args.shape:
        run_shape(shape, args.fits, args.seed, args.out,
                  cost=not args.no_cost)
    return 0


if __name__ == "__main__":
    sys.exit(main())
