"""Compiles on the program's clock (PR 35).

While obs is on, ``obs/registry.py`` listens to ``jax.monitoring``: every
program JAX traces, lowers and compiles (or reads from the persistent cache)
is timed under ``compile.trace`` / ``.lower`` / ``.backend`` /
``.cache_read``, counted under ``compile.cache_hits`` / ``.cache_misses``,
booked under the span that was open on the compiling thread
(``compile.under/<span>``) and left as one ``compile`` event in the flight
recorder.  A warm fit compiles nothing and adds nothing; with obs off no
listener of the program's stands.
"""

import importlib
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import compilation_cache
from jax._src import monitoring as jax_monitoring

from flink_ml_tpu import obs
from flink_ml_tpu.lib import LogisticRegression
from flink_ml_tpu.obs import flight
from flink_ml_tpu.obs.report import main as report_main
from flink_ml_tpu.table import slab_pool
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

#: the module (``obs.registry`` is the function of that name)
registry = importlib.import_module("flink_ml_tpu.obs.registry")
SCHEMA = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
STAGES = ("compile.trace", "compile.lower", "compile.backend")
#: the fused train program, as lowering and backend name it
PROGRAM = "jit(bundled)"
CALLBACKS = (registry._on_event, registry._on_duration,
             registry._on_time_span)


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import flink_ml_tpu.obs.report as report_mod

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    obs.disable()
    obs.reset()
    flight.reset()
    slab_pool.reset_pool()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    flight.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


def _table(rows=1536, dim=6, seed=35):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, dim).astype(np.float32)
    y = (X @ rng.randn(dim) > 0).astype(np.float64)
    return Table.from_columns(SCHEMA, {"features": X, "label": y})


def _logreg(max_iter):
    """Every test takes a ``maxIter`` of its own, at a learning rate of this
    file's own: its program is cold there whatever ran before it on the
    worker."""
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.0351)
            .set_global_batch_size(512).set_max_iter(max_iter))


def _compile_totals():
    """{name: (count, seconds)} of every ``compile.*`` timing, and the
    ``compile.*`` counters."""
    snap = obs.registry().snapshot()
    timings = {k: (v["count"], v["total_s"])
               for k, v in snap["timings"].items() if k.startswith("compile.")}
    counters = {k: v for k, v in snap["counters"].items()
                if k.startswith("compile.")}
    return timings, counters


def _compile_events():
    return [e for e in flight.events() if e["kind"] == "compile"]


def _assert_under_sums_to_the_stages(timings):
    stages = sum(timings[s][1] for s in STAGES if s in timings)
    under = sum(seconds for name, (_n, seconds) in timings.items()
                if name.startswith("compile.under/"))
    assert under == pytest.approx(stages, rel=1e-9)
    return stages


# -- along a fit ---------------------------------------------------------------


def test_a_cold_fit_times_every_stage_under_the_span_that_caused_it():
    obs.enable()
    table = _table()
    _logreg(11).fit(table)
    timings, _counters = _compile_totals()
    for stage in STAGES:
        assert timings[stage][0] >= 1 and timings[stage][1] > 0, stage
    # one observation a lowered program, whatever was traced inside it
    assert timings["compile.trace"][0] == timings["compile.lower"][0] \
        == timings["compile.backend"][0]
    assert timings["compile.under/train.dispatch"][1] > 0
    assert _assert_under_sums_to_the_stages(timings) > 0
    (event,) = [e for e in _compile_events() if e["program"] == PROGRAM]
    assert event["span"] == "train.dispatch"
    assert event["backend_s"] > 0 and event["lower_s"] > 0 \
        and event["trace_s"] > 0
    assert event["cache"] == "off" and event["cache_read_s"] == 0.0
    # the fused program's three stages are what was booked under its span
    assert timings["compile.under/train.dispatch"] == (3, pytest.approx(
        event["trace_s"] + event["lower_s"] + event["backend_s"], rel=1e-9))
    # the enqueue of a cold call: dispatch less what compiled under it
    dispatch = obs.registry().snapshot()["timings"]["train.dispatch"]
    assert dispatch["total_s"] > timings["compile.under/train.dispatch"][1]
    # the fit's own report carries the same, with no further code
    (report,) = obs.load_reports()
    assert report["metrics"]["timings"]["compile.backend"]["count"] \
        == timings["compile.backend"][0]
    assert "compile.under/train.dispatch" in report["metrics"]["timings"]


def test_a_second_fit_of_the_same_program_adds_nothing():
    obs.enable()
    table = _table()
    _logreg(12).fit(table)
    before, events = _compile_totals(), len(_compile_events())
    _logreg(12).fit(table)
    assert _compile_totals() == before
    assert len(_compile_events()) == events
    warm = obs.load_reports()[-1]["metrics"]
    assert not [k for k in warm["timings"] if k.startswith("compile.")]
    assert not [k for k in warm["counters"] if k.startswith("compile.")]


def test_a_rebuild_inside_a_warm_loop_names_itself(tmp_path, capsys):
    obs.enable()
    table = _table()
    for _ in range(3):
        _logreg(13).fit(table)
    events = len(_compile_events())
    counters = obs.registry().snapshot()["counters"]
    assert counters["train.compile_runs"] == 1
    _logreg(14).fit(table)  # the same table, another program
    new = _compile_events()[events:]
    assert [(e["program"], e["span"]) for e in new] == \
        [(PROGRAM, "train.dispatch")]
    assert new[0]["backend_s"] > 0 and new[0]["cache"] == "off"
    assert obs.registry().snapshot()["counters"]["train.compile_runs"] == 2
    # that fit's RunReport, and no other warm one, shows it ...
    reports = obs.load_reports()
    compiled = [i for i, r in enumerate(reports)
                if "compile.backend" in r["metrics"]["timings"]]
    assert compiled == [0, 3]
    own = reports[3]["metrics"]["timings"]
    assert own["compile.backend"]["count"] == 1
    assert own["compile.under/train.dispatch"]["total_s"] == pytest.approx(
        sum(own[s]["total_s"] for s in STAGES), rel=1e-9)
    # ... and the operator's one command lists both
    assert report_main(["--reports", str(tmp_path / "reports")]) == 0
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("COMPILED fit")]
    assert [line.split()[3] for line in lines] == ["#0", "#3"]
    assert "under train.dispatch=" in lines[1] and "backend=" in lines[1]
    assert report_main(["--reports", str(tmp_path / "reports"),
                        "--json"]) == 0
    listed = json.loads(capsys.readouterr().out)["compiled_fits"]
    assert [c["fit_index"] for c in listed] == [0, 3]
    assert listed[1]["programs"] == 1 and listed[1]["backend_s"] > 0
    assert list(listed[1]["under"]) == ["train.dispatch"]


@pytest.fixture
def persistent_cache(tmp_path, monkeypatch):
    """JAX's persistent cache at a directory of this test, as
    ``JAX_COMPILATION_CACHE_DIR`` places it (the suite runs with it off)."""
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {name: getattr(jax.config, name) for name in names}
    directory = str(tmp_path / "xla_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", directory)
    for name, value in zip(names, (directory, True, -1, 0)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield directory
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_a_cache_read_is_a_hit_with_its_seconds(persistent_cache):
    obs.enable()
    table = _table()
    _logreg(15).fit(table)
    (cold,) = [e for e in _compile_events() if e["program"] == PROGRAM]
    events = len(_compile_events())
    first, counted = _compile_totals()
    jax.clear_caches()  # the process forgets; the directory does not
    _logreg(15).fit(table)
    (again,) = [e for e in _compile_events()[events:]
                if e["program"] == PROGRAM]
    timings, counters = _compile_totals()
    if cold["cache"] == "off":
        # a platform whose cache takes no program says so, both times
        assert again["cache"] == "off" and not counters
        assert "compile.cache_read" not in timings
        return
    assert cold["cache"] == "miss" and cold["cache_read_s"] == 0.0
    assert counted["compile.cache_misses"] >= 1
    assert "compile.cache_hits" not in counted
    assert "compile.cache_read" not in first
    assert again["cache"] == "hit" and again["span"] == "train.dispatch"
    # JAX's backend event wraps the read: compiled = backend less cache_read
    assert 0 < again["cache_read_s"] <= again["backend_s"]
    assert counters["compile.cache_hits"] >= 1
    # (the eager programs an earlier test compiled before the directory
    # was there miss now, and are written: the fused program does not)
    assert counters["compile.cache_misses"] >= counted["compile.cache_misses"]
    assert timings["compile.cache_read"][0] == counters["compile.cache_hits"]
    assert timings["compile.cache_read"][1] >= again["cache_read_s"]
    _assert_under_sums_to_the_stages(timings)


# -- the listeners -------------------------------------------------------------


def _standing():
    lists = (jax_monitoring._event_listeners,
             jax_monitoring._event_duration_secs_listeners,
             jax_monitoring._event_time_span_listeners)
    return [sum(cb is mine for cb in listeners)
            for listeners, mine in zip(lists, CALLBACKS)]


def test_no_listener_stands_while_obs_is_off_and_one_set_while_on():
    assert not obs.enabled() and _standing() == [0, 0, 0]
    obs.enable()
    obs.enable()  # twice up is one set
    assert _standing() == [1, 1, 1]
    obs.disable()
    obs.disable()  # twice down is harmless
    assert _standing() == [0, 0, 0]
    obs.enable()
    assert _standing() == [1, 1, 1]
    # somebody else's clear takes ours down too: disable() lives with it
    mine = [list(getattr(jax_monitoring, name)) for name in (
        "_event_listeners", "_event_duration_secs_listeners",
        "_event_time_span_listeners")]
    try:
        jax_monitoring.clear_event_listeners()
        obs.disable()
    finally:
        for name, kept in zip(("_event_listeners",
                               "_event_duration_secs_listeners",
                               "_event_time_span_listeners"), mine):
            setattr(jax_monitoring, name,
                    [cb for cb in kept if cb not in CALLBACKS])
    assert _standing() == [0, 0, 0] and not obs.enabled()


def test_a_cold_fit_with_obs_off_leaves_the_registry_empty():
    assert _standing() == [0, 0, 0]
    _logreg(16).fit(_table())
    assert obs.registry().snapshot() == {"counters": {}, "gauges": {},
                                         "timings": {}}
    assert _compile_events() == []
    assert obs.span("train.dispatch") is obs.span("fit.wall")  # nullcontext


def test_the_trace_of_a_program_is_the_outer_interval_not_the_sum():
    @jax.jit
    def inner(x):
        return x @ x

    @jax.jit
    def reduce(x):
        return x.sum()

    @jax.jit
    def outer(x):
        return reduce(inner(x)) + reduce(inner(x + 1.0))

    x = jnp.ones((32, 32), jnp.float32)
    x.block_until_ready()
    seen = []

    def spans(event, start, end, fun_name="", **_kw):
        if event == registry._TRACE_EVENT:
            seen.append((fun_name, start, end))

    obs.enable()
    jax_monitoring.register_event_time_span_listener(spans)
    try:
        with obs.span("test.nested"):
            outer(x).block_until_ready()
    finally:
        jax_monitoring.unregister_event_time_span_listener(spans)
    names = [name for name, _s, _e in seen]
    assert names[-1] == "outer" and {"inner", "reduce"} <= set(names[:-1])
    _name, start, end = seen[-1]
    assert all(start <= s and e <= end for _n, s, e in seen)  # the trap
    timings, _counters = _compile_totals()
    assert timings["compile.trace"] == (1, pytest.approx(end - start,
                                                         rel=1e-9))
    assert sum(e - s for _n, s, e in seen) > end - start
    (event,) = _compile_events()
    assert event["program"] == "jit(outer)" and event["span"] == "test.nested"
    assert event["trace_s"] == pytest.approx(end - start, rel=1e-9)
    assert set(timings) == set(STAGES) | {"compile.under/test.nested"}
    _assert_under_sums_to_the_stages(timings)


def test_a_compile_outside_any_span_is_booked_under_none():
    x = jnp.arange(7.0)
    x.block_until_ready()
    obs.enable()
    jax.jit(lambda v: v * 3.0 + 35.0)(x).block_until_ready()
    timings, _counters = _compile_totals()
    assert set(timings) == set(STAGES) | {"compile.under/none"}
    (event,) = _compile_events()
    assert event["span"] is None
    _assert_under_sums_to_the_stages(timings)


# -- the innermost open span ---------------------------------------------------


def test_a_nested_span_restores_its_parent_on_two_threads_at_once():
    obs.enable()
    both_open = threading.Barrier(2, timeout=30)
    seen = {}

    def work(tag):
        names = seen.setdefault(tag, [])
        names.append(registry._open_span_name())
        with obs.span(f"outer.{tag}"):
            names.append(registry._open_span_name())
            with obs.phase(f"inner_{tag}"):
                both_open.wait()  # both threads hold two open spans here
                names.append(registry._open_span_name())
                both_open.wait()
            names.append(registry._open_span_name())
            try:
                with obs.span(f"failing.{tag}"):
                    raise KeyError(tag)
            except KeyError:
                pass
            names.append(registry._open_span_name())
        names.append(registry._open_span_name())

    threads = [threading.Thread(target=work, args=(tag,)) for tag in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    for tag in "ab":
        assert seen[tag] == [None, f"outer.{tag}", f"phase.inner_{tag}",
                             f"outer.{tag}", f"outer.{tag}", None], tag
    assert registry._open_span_name() is None  # and none leaked to this one
