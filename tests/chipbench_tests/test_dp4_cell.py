"""Tests of what PR 37 adds to the yardstick for the first four-chip cell
(``mnist8m_lr_dp4.sweep``): the rehearsal over 4 of the CPU's 8 virtual
devices with what the fit counts of its mesh, the control and the planted
faults, the reference laid over chips against ``glm_sgd`` on one device, the
work counted a chip, the configuration's and the cell's entries in
``BENCHMARK.json``, and the two collective readers on a ``Context`` built by
hand.  CPU only, small sizes.  The parametrised tests of ``test_chipbench.py``
pick the cell itself up from ``BENCHMARK.json`` as well.

One standing assertion does not hold beside this cell and is NOT edited here:
``test_ragged_cell.py:456``, ``assert all(c["chips"] == 1 ...)``, written when
no cell asked for four chips.  That file is a ``benchmark`` PR's to edit, so
``test_the_cell_is_listed_where_no_standing_test_pins_the_list`` FAILS since
PR 37, at that line and no other (``PERF.md`` section 7, row 3).

The two per-layer entries WAIT outside ``BENCHMARK.json`` (``WAITING``, below)
for another pinned list; ``scripts/waiting.py`` appends them in memory for a
run on the chip.
"""

import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import check, jobs, references, run, work, work_dp  # noqa: E402

CELL = "mnist8m_lr_dp4.sweep"
# the eight lists PR 33 appended its cell to, and no other
LISTS = ("fit_rows_per_s", "fit_p95_ms", "pool.hit_share",
         "train.dispatch_ms", "train_program_roofline", "mfu.fit",
         "fetch.sync_ms", "device.idle_share.sweep")

BENCH = run.load_json(ROOT, "BENCHMARK.json")
ENTRY = run.find_cell(BENCH, CELL)
CONFIG = run.load_json(BENCH_DIR, "configs", "mnist8m_lr_dp4.json")
MIX = run.load_json(BENCH_DIR, "traffic", "sweep_dp.json")
LIMITS = run.load_json(BENCH_DIR, "limits", CELL + ".json")
SMALL = dict(CONFIG, **CONFIG["rehearsal"])
SEED = 2**31 + 3737


# -- the cell on 4 of the 8 virtual devices ------------------------------------


def _rehearse(seconds=0.5):
    import jax

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=seconds,
                              trace=0)
    return run.run_cell(args, BENCH, ENTRY, SMALL, MIX, LIMITS,
                        jax.devices())


def test_the_rehearsal_runs_over_four_devices_and_puts_the_mesh_back():
    import jax

    from flink_ml_tpu import obs
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    env = MLEnvironmentFactory.get_default()
    before = env.get_mesh()
    assert len(jax.devices()) == 8 and before.devices.size == 8
    result, record = _rehearse()
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the contract's line
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"fit_rows_per_s", "fit_p95_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["compared"]) == set(LIMITS) - {"_readings"}
    assert record["values"]["answers_checked"] == min(8, result["attempted"])
    assert env.get_mesh() is before  # release() put it back
    counters = obs.registry().snapshot()["counters"]
    fits = counters["train.fused_runs"]
    assert fits == 9 + result["attempted"]  # set-up's nine, then the window's
    assert counters["train.data_shards"] == 4 * fits
    steps = -(-SMALL["rows"] // SMALL["globalBatchSize"])
    assert counters["train.psum_calls"] == 4 * steps * SMALL["maxIter"] * fits
    assert counters["train.psum_bytes"] == \
        (SMALL["features"] + 3) * 4 * steps * SMALL["maxIter"] * fits
    assert counters["place.devices"] == 4 and counters["slab_pool.misses"] == 1
    assert counters["slab_pool.hits"] == fits - 1


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged"])
def test_a_broken_timed_path_comes_out_not_correct(fault, monkeypatch):
    kind = jobs.kind("refit_dp")
    for target, name, replacement in kind.planted_faults(SMALL)[fault]:
        monkeypatch.setattr(target, name, replacement)
    result, _record = _rehearse()
    assert result["correct"] is False
    over = [n for n, p in result["compared"].items()
            if p["value"] is None or p["value"] > p["limit"]]
    assert set(over) & set(kind.numbers(SMALL)), over


def test_a_failed_job_is_counted_and_not_correct(monkeypatch):
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
    result, record = _rehearse()
    assert result["failed"] == 1 and result["correct"] is False
    assert "planted" in record["errors"][0]


def test_the_kind_refuses_fewer_devices_and_another_mesh(monkeypatch):
    import jax

    from chipbench import data

    monkeypatch.setattr(data, "make_rows", None)  # refused before any data
    with pytest.raises(SystemExit) as refused:
        jobs.make(dict(SMALL, chips=16, mesh={"data": 16}), MIX, SEED,
                  jobs.Spans())
    assert "refusing to run" in str(refused.value)
    assert f"found {len(jax.devices())}" in str(refused.value)
    with pytest.raises(SystemExit):
        jobs.make(dict(SMALL, mesh={"data": 2, "model": 2}), MIX, SEED,
                  jobs.Spans())


def test_the_kind_is_refit_over_chips():
    kind, refit = jobs.kind("refit_dp"), jobs.kind("refit")
    generator = kind.make(SMALL, MIX, SEED, jobs.Spans())
    plain = refit.make(SMALL, run.load_json(BENCH_DIR, "traffic",
                                            "sweep.json"), SEED, jobs.Spans())
    # the same jobs from the same seed, the same rows
    assert generator.points == plain.points and generator.order == plain.order
    assert np.array_equal(generator.X, plain.X)
    assert generator.chips == 4 and len(generator.devices) == 4
    assert sorted(kind.planted_faults(SMALL)) == sorted(
        refit.planted_faults(SMALL)) == ["answer_altered", "half_batch",
                                         "state_unchanged"]
    assert kind.numbers(SMALL) == ("coef_gap", "loss_gap")
    assert sorted(kind.controls(SMALL)) == [
        "control_bf16", "fault_half_batch", "fault_unchanged"]
    # the mix is sweep.json's, the kind apart
    sweep = run.load_json(BENCH_DIR, "traffic", "sweep.json")
    for key in ("input", "clients", "loop", "grid"):
        assert MIX[key] == sweep[key], key
    assert MIX["job"] == "refit_dp" and jobs.clients_of(MIX) == 1
    # the reference's table stays up for every variant of its precision,
    # and one table at a time
    keys = generator.keys[:2]
    refs = generator.references(keys)
    laid = generator._laid
    generator.references(keys, fault="half_batch")
    assert generator._laid is laid and laid[0] == "f32"
    generator.references(keys, precision="bf16")
    assert generator._laid[0] == "bf16"
    assert set(refs) == set(keys)


def test_the_control_and_the_faults_fail_by_the_limits_files_own_numbers():
    generator = jobs.make(SMALL, MIX, SEED + 1, jobs.Spans())
    keys = generator.keys[:3]
    refs = generator.references(keys)
    for label, variant in jobs.kind("refit_dp").controls(SMALL).items():
        bad = generator.references(keys, **variant)
        values = check.worst([generator.gaps(bad[k], refs[k]) for k in keys])
        values.update({n: 0.0 for n in check.HARNESS_NUMBERS},
                      answers_checked=float(len(keys)))
        correct, compared = check.verdict(values, LIMITS)
        assert correct is False, (label, compared)


def test_the_reference_over_chips_is_glm_sgd_on_one_device():
    """``glm_sgd_over_chips`` with ``chips=1`` runs ``glm_sgd``'s own jitted
    fit over the same rows on one device: the same bytes.  Over four devices
    the float32 order of a sum over the batch differs (four partial sums,
    then their sum): read 5.6e-9 to 1.1e-7 of the coefficients' norm and 0
    to 1.4e-7 on a loss over three seeds at this size; held to 1e-6, a
    twentieth of the cell's ``coef_gap`` limit and a fifth of its
    ``loss_gap`` limit."""
    over, plain = references.load("glm_sgd_over_chips"), \
        references.load("glm_sgd")
    generator = jobs.make(SMALL, MIX, SEED, jobs.Spans())
    X, y, batch = generator.X, generator.y, SMALL["globalBatchSize"]
    one = plain.Table(X, y, batch).fit(0.2, 1e-4, 3)
    same = over.Table(X, y, batch, chips=1).fit(0.2, 1e-4, 3)
    for key in ("coef", "losses"):
        assert np.array_equal(one[key], same[key]), key
    assert one["intercept"] == same["intercept"]
    four = over.Table(X, y, batch, chips=4).fit(0.2, 1e-4, 3)
    gaps = over.gaps(four, one)
    assert gaps["coef_gap"] < 1e-6 and gaps["loss_gap"] < 1e-6, gaps
    assert over.precision_of(CONFIG) == "f32"
    with pytest.raises(SystemExit):
        over.precision_of(dict(CONFIG, dtype="bfloat16"))


# -- the work, a chip ----------------------------------------------------------


def test_work_dp_is_fit_work_over_the_chips():
    whole, share = work.fit_work(CONFIG), work_dp.fit_work(CONFIG)
    assert share["chips"] == CONFIG["chips"] == ENTRY["chips"] == 4
    assert share["bytes"] * 4 == whole["bytes"] == 10 * 8_100_000 * 785 * 4
    assert share["flops"] * 4 == whole["flops"] == 10 * 4 * 8_100_000 * 784
    for key in whole:
        if key not in ("bytes", "flops"):
            assert share[key] == whole[key], key
    assert whole["steps_per_epoch"] == 62
    # a chip's share is, shape for shape, what mnist8m_lr counts for its fit
    quarter = work.fit_work(run.load_json(BENCH_DIR, "configs",
                                          "mnist8m_lr.json"))
    assert share["bytes"] == quarter["bytes"]
    assert share["flops"] == quarter["flops"]
    assert whole["resident_bytes"] == 4 * quarter["resident_bytes"]
    assert round(whole["resident_bytes"] / 4 / 1e9, 2) == 6.39  # a chip
    # one chip's peak bounds a chip's share: 77.6 ms a fit, by the bytes
    least, bound = work.least_seconds(share, work.peak("TPU v5 lite"))
    assert bound == "hbm" and least == pytest.approx(0.07764, rel=1e-3)
    generator = jobs.make(SMALL, MIX, SEED, jobs.Spans())
    assert generator.work() == work_dp.fit_work(SMALL)
    assert generator.rows_per_job == SMALL["rows"] * SMALL["maxIter"]


# -- the configuration and the cell's entries ----------------------------------


def test_the_configuration_states_the_deployment():
    quarter = run.load_json(BENCH_DIR, "configs", "mnist8m_lr.json")
    assert CONFIG["rows"] == CONFIG["published"]["rows"] == 8_100_000
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    assert CONFIG["published"] == quarter["published"]
    for key in ("features", "dtype", "maxIter", "tol", "withIntercept",
                "data", "env", "rehearsal"):
        assert CONFIG[key] == quarter[key], key
    assert CONFIG["mesh"] == {"data": 4} and CONFIG["chips"] == 4
    # the batch a chip is mnist8m_lr's: 32,768, so 131,072 a global step
    assert CONFIG["globalBatchSize"] == 4 * quarter["globalBatchSize"]
    assert CONFIG["rows"] == 4 * quarter["rows"]
    assert CONFIG["guarantees"][:3] == quarter["guarantees"]
    assert "sum over the four chips" in CONFIG["guarantees"][3]
    assert CONFIG["reference"] == "glm_sgd_over_chips"
    entry = next(c for c in BENCH["configs"] if c["name"] == "mnist8m_lr_dp4")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200
    assert entry["source"] not in {c["source"] for c in BENCH["configs"]
                                   if c is not entry}
    assert entry["source"].startswith(quarter["source"] + " whole")


def test_the_entries_are_the_contracts_and_the_last_of_their_lists():
    config, cell = BENCH["configs"][-1], BENCH["workloads"][-1]
    assert config["name"] == "mnist8m_lr_dp4" and cell is ENTRY
    assert sorted(config) == ["file", "name", "reduced", "source", "why"]
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    for entry in (config, cell):
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert config["file"] == "chipbench/configs/mnist8m_lr_dp4.json"
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    # what stood keeps its place: five configurations and five cells ahead
    assert [c["name"] for c in BENCH["configs"]][:5] == [
        "epsilon_lr", "mnist8m_lr", "criteo_sparse_lr", "mnist8m_kmeans",
        "url_ragged_lr"]
    assert [c["name"] for c in BENCH["workloads"]][:5] == [
        "epsilon_lr.sweep", "mnist8m_lr.sweep", "criteo_sparse_lr.sweep",
        "mnist8m_kmeans.restarts", "url_ragged_lr.sweep"]
    # with six cells one may ask for four chips (a quarter, rounded down,
    # and one always may); the five that stood ask for one
    assert len(BENCH["workloads"]) == 6
    assert [c["chips"] for c in BENCH["workloads"]] == [1, 1, 1, 1, 1, 4]
    assert BENCH["run_seconds"] == 30 and BENCH["paths"] == [
        "chipbench", "tests/chipbench_tests"]


def test_a_chip_run_reads_the_benchmark_with_what_waits_here(monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_waiting_script", os.path.join(ROOT, "scripts", "waiting.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    monkeypatch.setattr(run, "load_json", run.load_json)  # put back after
    script.overlay([os.path.basename(__file__)])
    seen = run.load_json(ROOT, "BENCHMARK.json")
    assert run.find_cell(seen, CELL) == ENTRY
    assert [m["name"] for m in seen["per_layer"]][-2:] == sorted(WAITING)
    assert seen["per_layer"][:-2] == BENCH["per_layer"]
    assert {m["name"] for m in run.metrics_of(seen, ENTRY, "per_layer")} == \
        set(LISTS[2:]) | set(WAITING)
    # any other file is read as it is, and the file on disk is untouched
    assert run.load_json(BENCH_DIR, "traffic", "sweep_dp.json") == MIX
    monkeypatch.undo()
    assert run.load_json(ROOT, "BENCHMARK.json") == BENCH


def test_the_cell_stands_after_the_ragged_cell_and_in_no_pinned_list():
    assert ENTRY["chips"] == 4 and ENTRY["traffic"] == "sweep_dp"
    assert ENTRY["config"] == "mnist8m_lr_dp4" and len(ENTRY["why"]) <= 200
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells[cells.index(CELL) - 1] == "url_ragged_lr.sweep"
    # the one cell of the benchmark that asks for four chips: with six
    # cells one may
    assert [c["name"] for c in BENCH["workloads"] if c["chips"] == 4] == [CELL]
    listed = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(LISTS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in LISTS:
            at = m["workloads"].index(CELL)
            assert m["workloads"][at - 1] == "url_ragged_lr.sweep"
    reported = {m["name"] for m in run.metrics_of(BENCH, ENTRY, "end_to_end")}
    assert reported == {"fit_rows_per_s", "fit_p95_ms", "setup_s"}
    for m in run.metrics_of(BENCH, ENTRY, "per_layer"):
        assert m["moves"] in reported, m["name"]
        assert callable(run.reader("layers", m["name"]))


def test_the_limits_say_where_they_came_from():
    assert set(LIMITS) - {"_readings"} == \
        {"coef_gap", "loss_gap"} | set(check.HARNESS_NUMBERS)
    assert "PR 37" in LIMITS["_readings"]
    reference = references.load(CONFIG["reference"])
    for name in reference.NUMBERS:
        entry = LIMITS[name]
        assert entry["lower"] < entry["limit"] < entry["upper"]
        assert entry["upper"] == min(entry[label]
                                     for label in reference.CONTROLS)
    for label in reference.CONTROLS:  # each fails one limit with room
        assert max(LIMITS[n][label] / LIMITS[n]["limit"]
                   for n in reference.NUMBERS) > 3, label
    for name in check.HARNESS_NUMBERS:
        assert LIMITS[name]["limit"] == 0


# -- the two collective readers, on a Context built by hand --------------------

# The per-layer entries these readers are for.  They WAIT outside
# BENCHMARK.json as eleven before them do: test_onepass_reader.py holds
# train.onepass_share to be the last entry of per_layer, the driver takes an
# entry put ahead of it for a change to it, and both files are a `benchmark`
# PR's to edit.  That PR loosens the assertion and appends these two as they
# stand here (PERF.md section 7: the twelfth and the thirteenth).
_ENTRY = {"workloads": [CELL], "moves": "fit_rows_per_s", "better": "lower",
          "source": "device_trace",
          "layer": "kernels (XLA programs on the chip)"}
WAITING = {
    "collective.share": dict(_ENTRY, name="collective.share", unit="%"),
    "collective.us_per_step": dict(_ENTRY, name="collective.us_per_step",
                                   unit="us"),
}

EMPTY = {"counters": {}, "timings": {}}
#: a traced window of 90 fits over four chips, seconds a chip
OPS = [["glm_grad.3", 8.30], ["all-reduce.2", 0.93], ["while.29", 0.07],
       ["broadcast_in_dim.16", 0.03]]
SPLIT = [["glm_grad.3", 8.30], ["all-reduce-start.2", 0.10],
         ["all-reduce-done.2", 0.83], ["while.29", 0.07]]
ONE_CHIP = [["glm_grad.3", 9.07], ["while.29", 0.07]]
CALLS = 90 * 4 * 62 * 10


def _trace(ops, busy=9.40):
    return {"device_ops": ops, "busy_s": busy, "window_s": 10.0}


def _ctx(calls=None, trace=None):
    window = {"counters": {} if calls is None else
              {"train.psum_calls": calls}, "timings": {}}
    return run.Context(snapshots={"setup": (EMPTY, EMPTY),
                                  "window": (EMPTY, window)}, trace=trace)


CASES = [
    ("collective.share", _ctx(CALLS, _trace(OPS)), 100 * 0.93 / 9.40),
    ("collective.share", _ctx(CALLS, _trace(SPLIT)), 100 * 0.93 / 9.40),
    ("collective.share", _ctx(None, _trace(OPS)), 100 * 0.93 / 9.40),
    ("collective.share", _ctx(CALLS, _trace(ONE_CHIP)), None),  # no such op
    ("collective.share", _ctx(CALLS), None),  # no trace
    ("collective.share", _ctx(CALLS, _trace(OPS, busy=0.0)), None),
    ("collective.us_per_step", _ctx(CALLS, _trace(OPS)),
     0.93e6 / (90 * 620)),
    ("collective.us_per_step", _ctx(CALLS, _trace(SPLIT)),
     0.93e6 / (90 * 620)),
    ("collective.us_per_step", _ctx(CALLS, _trace(ONE_CHIP)), None),
    ("collective.us_per_step", _ctx(None, _trace(OPS)), None),  # the parent
    ("collective.us_per_step", _ctx(0, _trace(OPS)), None),  # no fit
    ("collective.us_per_step", _ctx(CALLS), None),
]


@pytest.mark.parametrize(
    "name,ctx,expected", CASES,
    ids=[f"{name}-{i}" for i, (name, _c, _e) in enumerate(CASES)])
def test_a_collective_reader_gives_the_value_reckoned_by_hand_or_nothing(
        name, ctx, expected):
    got = run.reader("layers", name)(ctx, WAITING[name])
    if expected is None:
        assert got is None  # never 0
    else:
        assert got == pytest.approx(expected, rel=1e-12) and got > 0
    assert 0.93e6 / (90 * 620) == pytest.approx(16.67, rel=1e-3)  # us a step


@pytest.mark.parametrize("name", sorted(WAITING))
def test_an_entry_that_waits_is_ready_to_move_over(name):
    metric = WAITING[name]
    assert sorted(metric) == ["better", "layer", "moves", "name", "source",
                              "unit", "workloads"]
    assert metric["name"] == name and metric["workloads"] == [CELL]
    assert name not in {m["name"] for m in BENCH["per_layer"]}  # not yet
    assert metric["layer"] in {m["layer"] for m in BENCH["per_layer"]}
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1 and CELL in moved[0]["workloads"]
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


# -- the host's memory ---------------------------------------------------------


def test_the_set_up_holds_one_packed_form_of_the_table_on_the_host():
    """PR 37's pack lays the table once, straight into the slab: 1.003 packed
    forms at the peak of a fit's set-up at 784 features over 4 of the CPU's
    virtual devices (the slab's two more columns); PR 36's read 2.005 there,
    and at the cell's size ran the four-chip host out of memory."""
    import jax

    from chipbench import program_dp
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    before = MLEnvironmentFactory.get_default().get_mesh()
    forms = program_dp.packed_forms(CONFIG["mesh"], jax.devices()[:4],
                                    CONFIG["features"], CONFIG["dtype"])
    assert 1.0 < forms < 1.1, forms
    assert MLEnvironmentFactory.get_default().get_mesh() is before


@pytest.mark.parametrize("free_gb,forms,refused", [
    (152.0, 1.00, False),  # the four-chip host, this program
    (152.0, 2.01, True),   # the four-chip host, PR 36's pack
    (210.0, 2.01, False),  # eight tables of room: not asked
    (None, 2.01, False),   # no /proc/meminfo: not asked
])
def test_the_kind_refuses_a_host_the_set_up_would_run_out_of_memory(
        free_gb, forms, refused, monkeypatch):
    from chipbench import program_dp
    from chipbench.kinds import refit_dp

    asked = []
    monkeypatch.setattr(refit_dp, "mem_available",
                        lambda: None if free_gb is None else free_gb * 1e9)
    monkeypatch.setattr(program_dp, "packed_forms",
                        lambda *a: asked.append(a) or forms)
    if refused:
        with pytest.raises(SystemExit) as said:
            refit_dp.refuse_a_host_too_small(CONFIG, ["d"] * 4)
        assert "2.01 packed forms" in str(said.value)
        assert "25.4 GB" in str(said.value)
    else:
        refit_dp.refuse_a_host_too_small(CONFIG, ["d"] * 4)
    assert len(asked) == (free_gb == 152.0)
    if asked:
        assert asked[0] == ({"data": 4}, ["d"] * 4, 784, "float32")
    # the rehearsal's table is 1.9 MB: no host is asked about it
    monkeypatch.setattr(refit_dp, "mem_available", lambda: 1e9)
    refit_dp.refuse_a_host_too_small(SMALL, ["d"] * 4)
    assert len(asked) == (free_gb == 152.0)


def test_a_run_that_asks_the_question_is_a_run_like_any_other(monkeypatch):
    # on the four-chip host every run of the cell measures the program
    # first: the two small fits leave the run correct, nothing in the window
    from chipbench import program_dp

    loaded, asked, sound = jobs.kind, [], program_dp.packed_forms

    def kind(name):
        module = loaded(name)  # a module of its own every call
        if name == "refit_dp":
            module.ROOM_IN_TABLES = 10**9  # a rehearsal's host is asked too
        return module

    monkeypatch.setattr(jobs, "kind", kind)
    monkeypatch.setattr(program_dp, "packed_forms",
                        lambda *a: asked.append(sound(*a)) or asked[-1])
    result, _record = _rehearse()
    # 24 features: the slab's two more columns are a twelfth
    assert len(asked) == 1 and 1.0 < asked[0] < 1.2, asked
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["hidden_failures"]["value"] == 0
    assert result["compared"]["compiles_in_window"]["value"] == 0
