"""Tests of the benchmark's own yardstick (``chipbench/``).  CPU only, tiny
sizes; collected by tier-1 (``pytest tests/``) because this directory is one
of ``BENCHMARK.json``'s ``paths``.

No JAX device call and no topology description at import time.  Everything
that is said of a cell is said of every cell ``BENCHMARK.json`` lists, from
what the cell's own job kind and reference declare: a later PR that adds a
cell, a configuration, a mix, a kind or a metric adds files and entries, and
these tests hold them to the same rules with no edit here.
"""

import argparse
import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, data, jobs, references, run  # noqa: E402
from chipbench import trace_reduce, work  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2**31 + 77


def _files(cell):
    """(entry, config, mix, limits, kind module) of a cell, found by name."""
    entry = run.find_cell(BENCH, cell)
    config = run.load_config(BENCH, entry)
    mix = run.load_json(BENCH_DIR, "traffic", entry["traffic"] + ".json")
    limits = run.load_json(BENCH_DIR, "limits", cell + ".json")
    return entry, config, mix, limits, jobs.kind(mix["job"])


def _small(config):
    """The configuration at the size its file gives for a rehearsal."""
    return dict(config, **config["rehearsal"])


def _limit_names(limits):
    return {n for n in limits if not n.startswith("_")}


def _cell_faults():
    return [(cell, fault) for cell in CELLS
            for fault in sorted(_files(cell)[4].planted_faults(
                _small(_files(cell)[1])))]


# -- BENCHMARK.json ------------------------------------------------------------


def test_benchmark_json_has_exactly_the_contract_keys():
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "chipbench" in BENCH["paths"]
    assert os.path.relpath(HERE, ROOT) in BENCH["paths"]
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_names_units_and_whys_are_well_formed():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in BENCH["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("config_name", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_file_says_what_its_entry_says(config_name):
    listed = next(c for c in BENCH["configs"] if c["name"] == config_name)
    config = run.load_json(ROOT, listed["file"])
    assert config["name"] == config_name
    assert config["reduced"] == listed["reduced"]
    assert config["source"] == listed["source"]
    # a size that differs from the published one is a cut, and is listed
    for key, published in config["published"].items():
        if key in config and config[key] != published:
            assert key in config["reduced"], key
    for key in config["reduced"]:
        assert config[key] < config["published"][key]
    reference = references.load(config["reference"])
    assert reference.NUMBERS and callable(reference.gaps)
    assert reference.CONTROLS


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_what_its_metrics_move(cell):
    entry, config, mix, limits, kind = _files(cell)
    assert jobs.clients_of(mix) >= 1
    # a limit for every number the kind compares and every one the harness
    # does, and for nothing else
    assert _limit_names(limits) == \
        set(kind.numbers(config)) | set(check.HARNESS_NUMBERS)
    for name in kind.numbers(config):
        lim = limits[name]
        if lim["limit"] != 0:  # set from two readings, room on both sides
            assert lim["lower"] < lim["limit"] < lim["upper"], name
            assert lim["upper"] >= 3 * lim["lower"], name
    assert kind.controls(config)
    e2e = run.metrics_of(BENCH, entry, "end_to_end")
    layers = run.metrics_of(BENCH, entry, "per_layer")
    reported = {m["name"] for m in e2e}
    assert "setup_s" in reported and len(reported) >= 2 and layers
    for m in e2e:
        assert callable(run.reader("end_to_end", m["name"]))
    for m in layers:
        assert m["moves"] in reported, (m["name"], m["moves"])
        assert callable(run.reader("layers", m["name"]))


def test_per_layer_metrics_move_a_metric_each_of_their_cells_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m.get("workloads", CELLS)) <= set(moved), m["name"]
    kernels = [m for m in BENCH["per_layer"] if m["name"].endswith("_roofline")]
    assert kernels and all(m["unit"] == "%" for m in kernels)
    for k in kernels:  # the whole step's share stands beside each roofline
        assert any("mfu" in re.split(r"[._]", m["name"])
                   and m["moves"] == k["moves"] for m in BENCH["per_layer"])


# -- work and peaks ------------------------------------------------------------


def test_work_matches_the_sizing_arithmetic():
    eps = work.fit_work(run.load_json(BENCH_DIR, "configs", "epsilon_lr.json"))
    mnist = work.fit_work(run.load_json(BENCH_DIR, "configs", "mnist8m_lr.json"))
    assert (eps["steps_per_epoch"], mnist["steps_per_epoch"]) == (13, 62)
    assert eps["resident_bytes"] == 13 * 32768 * 2002 * 4  # 3.41 GB
    assert mnist["resident_bytes"] == 62 * 32768 * 786 * 4  # 6.39 GB
    assert round(eps["resident_bytes"] / 1e9, 2) == 3.41
    assert round(mnist["resident_bytes"] / 1e9, 2) == 6.39
    assert eps["bytes_per_epoch"] == 400_000 * 2001 * 4
    assert mnist["bytes"] == 10 * 2_025_000 * 785 * 4
    assert eps["flops"] == 10 * 4 * 400_000 * 2000
    assert work.steps_per_epoch(320_000, 32768) == 10


def test_peaks_know_the_v5e_and_refuse_any_other_kind():
    peak = work.peak("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peak("cpu")
    with pytest.raises(KeyError):
        work.peak("_source")
    least, bound = work.least_seconds({"bytes": 819e9, "flops": 1e12}, peak)
    assert bound == "hbm" and least == pytest.approx(1.0)


# -- data and traffic ----------------------------------------------------------


def test_data_is_a_function_of_the_seed_alone():
    cfg = run.load_json(BENCH_DIR, "configs", "mnist8m_lr.json")["data"]
    a = data.make_rows(cfg, 3000, 16, 2**31 + 12345)
    b = data.make_rows(cfg, 3000, 16, 2**31 + 12345)
    c = data.make_rows(cfg, 3000, 16, 2**31 + 12346)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and a[0].min() >= 0 and a[0].max() <= 255
    assert 0.02 < a[1].mean() < 0.25  # one against the rest
    X = a[0].copy()
    data.standardise(X)
    assert np.allclose(X.mean(axis=0), 0, atol=1e-4)
    assert np.allclose(X.std(axis=0, ddof=1), 1, atol=1e-3)
    with pytest.raises(SystemExit):
        data.make_rows(cfg, 30, 4, 1, dtype="bfloat16")


def test_a_mix_says_how_it_is_sent_and_what_is_not_implemented_is_refused():
    assert jobs.clients_of({"clients": 8, "loop": "closed"}) == 8
    with pytest.raises(SystemExit):
        jobs.clients_of({"clients": 1, "loop": "open"})
    with pytest.raises(SystemExit):
        jobs.clients_of({"clients": 0, "loop": "closed"})
    with pytest.raises(KeyError):
        jobs.clients_of({"loop": "closed"})
    with pytest.raises(SystemExit):
        jobs.kind("no_such_kind")
    with pytest.raises(SystemExit):
        references.load("no_such_reference")
    assert sorted(jobs.order(8, SEED)) == list(range(8))
    assert jobs.order(8, SEED) != jobs.order(8, SEED + 1)


@pytest.mark.parametrize("key,value", [("dtype", "bfloat16"),
                                       ("withIntercept", False)])
def test_the_glm_reference_refuses_what_it_does_not_compute(key, value):
    config = run.load_json(BENCH_DIR, "configs", "epsilon_lr.json")
    reference = references.load(config["reference"])
    assert reference.precision_of(config) == "f32"
    with pytest.raises(SystemExit):
        reference.precision_of(dict(config, **{key: value}))


# -- trace reduction -----------------------------------------------------------


def test_trace_reduce_on_a_recorded_chip_trace():
    recorded = os.path.join(BENCH_DIR, "testdata", "sweep_small.xplane.pb")
    expected = run.load_json(BENCH_DIR, "testdata", "sweep_small.expected.json")
    got = trace_reduce.reduce(trace_reduce.load(recorded))
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    seconds, calls = trace_reduce.program_seconds(got, "jit_bundled")
    assert calls == expected["fit_calls"] and seconds <= got["busy_s"] * 1.001
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in expected["device_ops"]]
    idle = sum(s for _n, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    assert any(n == "job.fit" for n, _ in got["idle_gaps"])


def test_self_times_charge_a_container_only_what_its_body_leaves():
    events = [("while", 0.0, 100.0), ("a", 10.0, 40.0), ("b", 40.0, 90.0),
              ("c", 120.0, 130.0)]
    assert trace_reduce._self_times(events) == {
        "while": 20.0, "a": 30.0, "b": 50.0, "c": 10.0}
    owners = trace_reduce._Owners([("chipbench.job.fit", 10.0, 50.0),
                                   ("chipbench.window", 0.0, 100.0)])
    assert owners.split(0.0, 60.0) == [
        ("outside_any_span", 10.0), ("job.fit", 40.0),
        ("outside_any_span", 10.0)]


# -- a whole run on the CPU, the look for a chip skipped -----------------------


def _run(cell, seconds=0.6):
    import jax

    entry, config, mix, limits, _kind = _files(cell)
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds,
                              trace=0)
    return run.run_cell(args, BENCH, entry, _small(config), mix, limits,
                        jax.devices())


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal_prints_the_contract_line(cell):
    result, record = _run(cell)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    entry = run.find_cell(BENCH, cell)
    wanted = {m["name"] for m in run.metrics_of(BENCH, entry, "end_to_end")}
    assert set(result["metrics"]) == wanted
    for m in result["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert record["values"]["answers_checked"] >= 1
    assert set(result["compared"]) == _limit_names(_files(cell)[3])
    json.dumps(result)


@pytest.mark.parametrize("cell,fault", _cell_faults())
def test_a_broken_timed_path_comes_out_not_correct(cell, fault, monkeypatch):
    _entry, config, _mix, _limits, kind = _files(cell)
    for target, name, replacement in \
            kind.planted_faults(_small(config))[fault]:
        monkeypatch.setattr(target, name, replacement)
    result, _record = _run(cell)
    assert result["correct"] is False
    over = [n for n, p in result["compared"].items()
            if p["value"] is None or p["value"] > p["limit"]]
    assert set(over) & set(kind.numbers(config)), over


@pytest.mark.parametrize("cell", CELLS)
def test_a_failed_job_is_counted_and_not_correct(cell, monkeypatch):
    entry, config, mix, limits, _kind = _files(cell)
    sound = jobs.make

    def make(*a, **kw):
        generator = sound(*a, **kw)
        sound_job, calls = generator.job, {"n": 0}

        def job(i):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("planted")
            return sound_job(i)

        generator.job = job
        return generator

    monkeypatch.setattr(jobs, "make", make)
    result, record = _run(cell)
    assert result["failed"] == 1 and result["correct"] is False
    assert "planted" in record["errors"][0]


# -- the control: the reference in the program's place, a precision lower ------


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_and_the_faults_fail_the_cells_limits(cell):
    _entry, config, mix, limits, kind = _files(cell)
    generator = jobs.make(_small(config), mix, 2**31 + 5, jobs.Spans())
    keys = generator.keys[:2]
    refs = generator.references(keys)
    variants = [(v, True) for v in kind.controls(config).values()]
    for variant, must_fail in variants + [({}, False)]:
        got = generator.references(keys, **variant)
        values = check.worst([generator.gaps(got[k], refs[k]) for k in keys])
        assert set(values) == set(kind.numbers(config))
        values.update({n: 0.0 for n in check.HARNESS_NUMBERS},
                      answers_checked=float(len(keys)))
        correct, _compared = check.verdict(values, limits)
        assert correct is not must_fail, (variant, values)


def test_reference_matches_plain_numpy_in_float64():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((3000, 12)).astype(np.float32) * 3 + 1
    y = (rng.random(3000) < 0.4).astype(np.float32)
    got = references.load("glm_sgd").Table(X, y, 512).fit(0.02, 1e-3, 2)
    Xd = X.astype(np.float64)
    ww, bb, losses = np.zeros(12), 0.0, []
    for _ in range(2):
        tot = 0.0
        for lo in range(0, 3000, 512):
            xb, yb = Xd[lo:lo + 512], y[lo:lo + 512]
            z = xb @ ww + bb
            tot += np.sum(np.logaddexp(0, z) - yb * z)
            err = 1 / (1 + np.exp(-z)) - yb
            ww = ww - 0.02 * (xb.T @ err / len(yb) + 1e-3 * ww)
            bb = bb - 0.02 * err.mean()
        losses.append(tot / 3000)
    assert np.linalg.norm(got["coef"] - ww) / np.linalg.norm(ww) < 1e-5
    assert abs(got["intercept"] - bb) < 1e-6
    assert np.allclose(got["losses"], losses, rtol=1e-5)


# -- a new kind of job is new files only ---------------------------------------


def test_a_new_kind_of_job_plugs_in_as_files(monkeypatch):
    import jax

    plug = os.path.join(BENCH_DIR, "testdata", "plug")
    spec = run.load_json(plug, "cell.json")
    monkeypatch.setattr(jobs, "KINDS_DIR", os.path.join(plug, "kinds"))
    cell, name = spec["cell"], spec["cell"]["name"]
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:  # the entries a later PR would add
        if m["name"] in ("fit_rows_per_s", "fit_p95_ms"):
            m["workloads"].append(name)
    args = argparse.Namespace(workload=name, seed=SEED, seconds=0.3, trace=0)
    result, record = run.run_cell(args, bench, cell, spec["config"],
                                  spec["mix"], spec["limits"], jax.devices())
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_rows_per_s", "fit_p95_ms",
                                      "setup_s"}
    assert record["values"]["answers_checked"] == 3
    kind = jobs.kind("echo")
    generator = kind.make(spec["config"], spec["mix"], SEED, jobs.Spans())
    refs = generator.references(generator.keys)
    for variant in kind.controls(spec["config"]).values():
        bad = generator.references(generator.keys, **variant)
        values = check.worst([generator.gaps(bad[k], refs[k])
                              for k in generator.keys])
        values.update({n: 0.0 for n in check.HARNESS_NUMBERS},
                      answers_checked=3.0)
        assert check.verdict(values, spec["limits"])[0] is False


def test_callers_of_a_closed_loop_overlap_and_every_job_is_kept():
    import time

    class Slow:
        def job(self, i):
            time.sleep(0.01)
            return i % 2, 1, {"i": i}

    one, start, end = jobs.run_window(Slow(), 0.1, 1)
    three, _s, _e = jobs.run_window(Slow(), 0.1, 3)
    assert end - start >= 0.1 and len(three) >= 2 * len(one) >= 10
    assert [j["start"] for j in three] == sorted(j["start"] for j in three)
    assert sorted(j["answer"]["i"] for j in three) == list(range(len(three)))
    assert all(a["end"] <= b["start"] for a, b in zip(one, one[1:]))
    assert any(a["end"] > b["start"] for a, b in zip(three, three[1:]))


# -- the command ---------------------------------------------------------------


def test_no_tpu_means_a_non_zero_exit_and_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "refusing to run" in done.stderr
