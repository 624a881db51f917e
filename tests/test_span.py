"""The program's one span API (``obs.span``) and where it sits (PR 24).

* one call gives three records from one pair of clock readings: a registry
  timing, a ``fmt.<name>`` scope on the profiler's clock and a child span of
  the thread's request trace; it nests, survives an exception, and is the
  shared ``nullcontext`` when obs is off;
* one warm ``LogisticRegression.fit`` observes every span of the fit path
  exactly once, ``slab_pool.build`` / ``place.*`` only on the miss, and
  ``fit.wall`` covers its children;
* the fused train program and the fused serve program carry names the program
  owns: ``fmt.train*`` / ``fmt.serve`` scopes on their operations, which do
  not change the program, and the module name ``jit_bundled`` that the
  benchmark's trace reader matches on.
"""

import glob
import importlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.api.pipeline import Pipeline
from flink_ml_tpu.common import fused
from flink_ml_tpu.lib import LinearRegression, LogisticRegression, common
from flink_ml_tpu.lib.feature import StandardScaler
from flink_ml_tpu.obs import trace
from flink_ml_tpu.table import slab_pool
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

#: the module (``obs.registry`` is the function of that name)
registry = importlib.import_module("flink_ml_tpu.obs.registry")
SCHEMA = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
#: fit.wall's direct children on the dense fused path
CHILDREN = ("fit.prepare", "slab_pool.lookup", "train.place_params",
            "train.dispatch", "train.sync", "train.demux", "train.health",
            "fit.finish", "fit.report")
MISS_ONLY = ("slab_pool.build", "place.host_view", "place.h2d")
TRAIN_SCOPES = {"fmt.train", "fmt.train.scores", "fmt.train.grad", "fmt.train.psum",
                "fmt.train.update", "fmt.train.bundle"}


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import flink_ml_tpu.obs.report as report_mod

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    monkeypatch.setenv("FMT_TRACE_DIR", str(tmp_path / "traces"))
    obs.disable()
    obs.reset()
    trace.enable(False, sample=1.0)
    trace.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    trace.enable(False, sample=1.0)
    trace.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


@pytest.fixture
def annotations(monkeypatch):
    """The profiler's door, recorded: [(event, name)] in order."""
    seen = []

    class Recorded:
        def __init__(self, name, **attrs):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(registry, "_TRACE_ANNOTATION", Recorded)
    return seen


def _timings():
    return obs.registry().snapshot()["timings"]


def _table(rows=2048, dim=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, dim).astype(np.float32)
    y = (X @ rng.randn(dim) > 0).astype(np.float64)
    return Table.from_columns(SCHEMA, {"features": X, "label": y})


def _logreg():
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_global_batch_size(512).set_max_iter(2))


# -- the span ------------------------------------------------------------------


def test_off_is_the_one_shared_nullcontext_and_records_nothing(annotations):
    assert not obs.enabled()
    a = obs.span("fit.wall")
    assert a is obs.span("train.sync") and a is obs.phase("pack_dense")
    with obs.span("s.off") as s:
        assert s is None
    assert _timings() == {} and annotations == []


def test_one_call_gives_registry_annotation_and_trace_child(annotations):
    obs.enable()
    trace.enable(True, sample=1.0)
    before = time.time()
    with trace.root_span("fit"):
        root_id = trace.current()[0].span_id
        with obs.span("train.x") as s:
            time.sleep(0.002)
    after = time.time()
    stat = _timings()["train.x"]
    assert stat["count"] == 1 and stat["total_s"] == s.seconds >= 0.002
    # the request trace's own spans have no profiler scope: no reader yet
    assert annotations == [("enter", "fmt.train.x"), ("exit", "fmt.train.x")]
    child, root = trace.recent_spans()
    assert (child["name"], root["name"]) == ("train.x", "fit")
    assert child["parent_id"] == root_id == root["span_id"]
    assert child["dur_s"] == s.seconds and child["status"] == "ok"
    # its true start: inside the root, not reckoned back from the record
    assert before <= root["ts"] <= child["ts"]
    assert child["ts"] + child["dur_s"] <= after


def test_no_request_trace_means_no_trace_record(annotations):
    obs.enable()
    trace.enable(True, sample=1.0)
    with obs.span("orphan"):  # tracing on, no trace active on the thread
        pass
    assert trace.recent_spans() == [] and "orphan" in _timings()
    trace.enable(False)
    with obs.span("untraced"):
        pass
    assert trace.recent_spans() == [] and "untraced" in _timings()


def test_tracing_alone_keeps_the_span_live_and_the_registry_empty(annotations):
    assert not obs.enabled()
    trace.enable(True, sample=1.0)
    assert obs.phase("p") is registry._NULL_CTX
    with trace.root_span("fit"):
        with obs.span("train.x") as s:
            pass
    assert [r["name"] for r in trace.recent_spans()] == ["train.x", "fit"]
    assert s.seconds >= 0 and _timings() == {}
    trace.enable(False)
    assert obs.span("train.x") is obs.phase("p")


def test_spans_and_phases_nest(annotations):
    obs.enable()
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            with obs.phase("a"):
                with obs.phase("b"):
                    pass
    t = _timings()
    # a span's key is its name; a phase's path nests among phases only
    assert set(t) == {"outer", "inner", "phase.a", "phase.a/b"}
    assert 0 <= inner.seconds <= outer.seconds
    assert [n for e, n in annotations if e == "enter"] == \
        ["fmt.outer", "fmt.inner", "fmt.phase.a", "fmt.phase.a/b"]
    assert [n for e, n in annotations if e == "exit"] == \
        ["fmt.phase.a/b", "fmt.phase.a", "fmt.inner", "fmt.outer"]


def test_a_span_survives_an_exception(annotations):
    obs.enable()
    trace.enable(True, sample=1.0)
    with pytest.raises(ValueError, match="boom"):
        with trace.root_span("fit"):
            with obs.span("train.x"), obs.phase("p"):
                raise ValueError("boom")
    t = _timings()
    assert t["train.x"]["count"] == 1 and t["phase.p"]["count"] == 1
    assert annotations.count(("exit", "fmt.train.x")) == 1
    child = [s for s in trace.recent_spans() if s["name"] == "train.x"]
    assert len(child) == 1 and child[0]["status"] == "error"
    with obs.phase("q"):  # the phase stack was popped
        pass
    assert "phase.q" in _timings()


def test_the_request_trace_reaches_the_span_through_one_hook(annotations):
    """``obs.trace`` registers its recorder with the span API while tracing
    is on; the registry module knows nothing of the trace module."""
    assert registry._TRACE_HOOK is None
    trace.enable(True, sample=1.0)
    assert registry._TRACE_HOOK is not None
    req = trace.start_request("serving.request")
    with trace.use((req.ctx,)), trace.span("queue_wait"):
        with obs.span("inside"):
            pass
    req.end()
    names = [r["name"] for r in trace.recent_spans()]
    assert names == ["inside", "queue_wait", "serving.request"]
    assert annotations == [("enter", "fmt.inside"), ("exit", "fmt.inside")]
    trace.enable(False)
    assert registry._TRACE_HOOK is None
    with open(registry.__file__, encoding="utf-8") as fh:
        assert "obs import trace" not in fh.read()


def test_one_place_imports_the_profiler():
    import os

    root = os.path.dirname(os.path.abspath(obs.__file__))
    pkg = os.path.dirname(root)
    hits = []
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        if re.search(r"import jax\.profiler|from jax\.profiler import|"
                     r"from jax import profiler|jax\.profiler\.\w+\(", text):
            hits.append(os.path.relpath(path, pkg))
    assert hits == [os.path.join("obs", "registry.py")]
    assert not os.path.exists(os.path.join(pkg, "utils", "tracing.py"))


# -- along one fit -------------------------------------------------------------


def test_a_warm_fit_observes_every_span_once_and_the_miss_spans_on_the_miss():
    obs.enable()
    slab_pool.reset_pool()
    table = _table()
    _logreg().fit(table)  # packs, places, compiles: the miss
    first = _timings()
    for name in CHILDREN + MISS_ONLY + ("fit.wall", "phase.pack_dense"):
        assert first[name]["count"] == 1, name
    assert first["slab_pool.lookup"]["total_s"] >= \
        first["slab_pool.build"]["total_s"] >= \
        first["place.host_view"]["total_s"] + first["place.h2d"]["total_s"]
    # the RunReport's timings are this fit's own: one sample a key, and no
    # reservoir sorted inside the fit
    (report,) = obs.load_reports()
    stat = report["metrics"]["timings"]["train.sync"]
    assert stat["count"] == 1
    assert stat["p50_s"] == stat["p99_s"] == stat["total_s"]
    _logreg().fit(table)  # warm: a pool hit, nothing placed
    warm = _timings()
    delta = {k: (v["count"] - first.get(k, {"count": 0})["count"],
                 v["total_s"] - first.get(k, {"total_s": 0.0})["total_s"])
             for k, v in warm.items()}
    for name in CHILDREN + ("fit.wall",):
        assert delta[name][0] == 1, name
    for name in MISS_ONLY + ("phase.pack_dense",):
        assert delta[name][0] == 0, name
    wall, children = delta["fit.wall"][1], sum(delta[c][1] for c in CHILDREN)
    assert wall >= children > 0
    # the counters the standing readers use are written as before
    counters = obs.registry().snapshot()["counters"]
    assert counters["slab_pool.hits"] == counters["slab_pool.misses"] == 1
    assert counters["slab_pool.bytes_placed"] > 0
    assert counters["train.fused_runs"] == 2
    # the dispatch/sync split of a step is the registry's (the step's own
    # copies went with PR 35): one more observation each, inside the step
    step = _logreg().fit(table).train_metrics_.steps[-1]
    third = _timings()
    split = [third[n]["total_s"] - warm[n]["total_s"]
             for n in ("train.dispatch", "train.sync")]
    assert all(third[n]["count"] == 3
               for n in ("train.dispatch", "train.sync"))
    assert 0 < sum(split) <= step["seconds"]
    assert not {"dispatch_seconds", "sync_seconds", "place_seconds"} & set(step)
    assert step["call_latency_ms"] == pytest.approx(step["seconds"] * 1e3)


def test_a_fit_with_obs_off_opens_no_span(annotations):
    model = _logreg().fit(_table())
    assert _timings() == {} and annotations == []
    step = model.train_metrics_.steps[-1]
    assert step["call_latency_ms"] > 0 and "dispatch_seconds" not in step


def test_the_spans_of_a_fit_sit_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    obs.enable()
    table = _table()
    _logreg().fit(table)
    # the test may hold the profiler itself: it is the operator here
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    _logreg().fit(table)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fmt."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(events) == {"fmt." + n for n in CHILDREN + ("fit.wall",)}
    ((lo, hi),) = events["fmt.fit.wall"]
    for name in CHILDREN:
        ((a, b),) = events["fmt." + name]
        assert lo <= a <= b <= hi, name
    order = sorted(CHILDREN, key=lambda n: events["fmt." + n][0][0])
    assert tuple(order) == CHILDREN  # in the order the fit runs them


# -- names on the device -------------------------------------------------------


def _train_program(estimator):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    fn = common.make_glm_train_fn(estimator._grad_fn(), mesh, 0.125, 0.0, 3,
                                  0.0, bundle=True)
    n_dev = len(mesh.devices.flat)
    params = (jnp.zeros((8,), jnp.float32), jnp.zeros((), jnp.float32))
    batch = jnp.zeros((2 * n_dev, 64, 10), jnp.float32)
    # the jitted program is the closure's one cell that lowers
    (program,) = [c.cell_contents for c in fn.__closure__
                  if hasattr(c.cell_contents, "lower")]
    return program.lower(params, batch)


@pytest.mark.parametrize("estimator", [LogisticRegression, LinearRegression])
def test_the_fused_train_program_carries_its_scopes_and_its_name(estimator):
    lowered = _train_program(estimator())
    plain = lowered.as_text()
    assert plain.startswith("module @jit_bundled")
    # scopes are metadata: the program a cache key is made of has none
    assert "fmt." not in plain
    assert set(re.findall(r"fmt\.[a-z_.]+",
                          lowered.as_text(debug_info=True))) == TRAIN_SCOPES
    # and they reach the compiled operations' names
    compiled = lowered.compile().as_text()
    assert set(re.findall(r"fmt\.[a-z_.]+", compiled)) == TRAIN_SCOPES
    assert re.search(r'op_name="jit\(bundled\)/[^"]*fmt\.train/[^"]*'
                     r'fmt\.train\.update/', compiled)


def test_the_fused_serve_program_carries_its_scope(monkeypatch):
    table = _table(rows=256)
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        _logreg(),
    ]).fit(table)
    texts = []
    sound = fused.FusedRun._dispatch_fn

    def recording(self, mesh, variant, placed, margs, *rest):
        # the jitted program itself: with a warm-artifact store active (an
        # earlier test's) the dispatch gets a compiled executable instead
        lowered = self._apply_fn(mesh, variant).lower(*placed, *margs)
        texts.append((lowered.as_text(), lowered.as_text(debug_info=True)))
        return sound(self, mesh, variant, placed, margs, *rest)

    monkeypatch.setattr(fused.FusedRun, "_dispatch_fn", recording)
    fused.reset_family_fns()
    (out,) = model.transform(table.slice_rows(0, 64))
    assert out.num_rows() == 64 and texts
    for plain, debug in texts:
        assert plain.startswith("module @jit_fused")
        assert "fmt." not in plain and "fmt.serve" in debug
