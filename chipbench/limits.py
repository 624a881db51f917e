"""Readings for the limits of ``chipbench/limits/<cell>.json``.  Run by hand
on the chip (never by a benchmark run):

    python -m chipbench.limits --workload <cell> --seeds 12 --control-seeds 3 --seconds <s>

In ONE process, for each seed: the cell's data, set-up and a short window at
the cell's own size and load, then the same comparison a run makes
(``check.compare``): the program's reading of every number — the LOWER
readings.  For the first ``--control-seeds`` seeds also the UPPER readings:
the reference put in the program's place for the same jobs, in each variant
that the job kind's ``controls`` names (for ``glm_sgd``: computed in bfloat16,
the precision below the float32 the configurations state; with half of every
minibatch left out, the mean taken over the rest; with every step returning
its state unchanged), each read by the same numbers against the reference.  One JSON line
per seed on standard output, and a summary (largest lower, smallest upper per
number) as the last line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from chipbench import check, jobs, program, run


def readings(generator, keys, **variant):
    """The worst gaps of a variant of the reference against the reference."""
    refs = generator.references(keys)
    bad = generator.references(keys, **variant)
    return check.worst([generator.gaps(bad[k], refs[k]) for k in keys])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m chipbench.limits")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_200_000_011)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    config = run.load_config(bench, cell)
    mix = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    controls = jobs.kind(mix["job"]).controls(config)
    clients = jobs.clients_of(mix)
    import flink_ml_tpu  # noqa: F401
    import jax

    program.prepare(os.path.join(run.OUT, "limits"))
    print("chipbench.limits:", jax.devices()[0].device_kind, flush=True)
    lower, upper = {}, {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        generator = jobs.make(config, mix, seed, jobs.Spans())
        generator.setup()
        window, _start, _end = jobs.run_window(generator, args.seconds,
                                               clients)
        generator.release()
        program.release()
        failed = sum("answer" not in j for j in window)
        values = check.compare(generator, window, {"failed_jobs": failed})
        line = {"seed": seed, "jobs": len(window), "program": values}
        for name, v in values.items():
            lower[name] = max(lower.get(name, 0.0), v)
        if i < args.control_seeds:
            keys = sorted({j["key"] for j in window if "answer" in j})
            for label, variant in controls.items():
                line[label] = readings(generator, keys, **variant)
                for name, v in line[label].items():
                    slot = upper.setdefault(label, {})
                    slot[name] = min(slot.get(name, float("inf")), v)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del generator, window
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "largest_lower": lower, "smallest_upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
