"""fmtlint (flink_ml_tpu.analysis): checker fixtures, baseline semantics,
the repo self-check, and the lock-discipline race its LOCK rules caught.

The fixture corpus lives in ``tests/fixtures/analysis/``: one bad and one
good module per checker family.  Bad modules must produce exactly their
advertised rule ids; good modules must produce none — both directions,
so a checker that goes blind AND a checker that starts screaming are
each a red test.
"""

import ast
import json
import os
import subprocess
import sys
import threading

import pytest

from flink_ml_tpu.analysis import (
    apply_baseline,
    load_baseline,
    load_project,
    run_checkers,
)
from flink_ml_tpu.analysis.checkers import CHECKERS, RULES
from flink_ml_tpu.analysis.core import REPO_ROOT, Module, Project, Suppression
from flink_ml_tpu.utils import knobs

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def run_on(*fixture_names):
    """Analyzer findings restricted to the named fixture files."""
    paths = [os.path.join(FIXTURES, n) for n in fixture_names]
    project, parse_findings = load_project(extra_paths=paths)
    assert not parse_findings
    wanted = {f"tests/fixtures/analysis/{n}" for n in fixture_names}
    return [f for f in run_checkers(project, CHECKERS) if f.file in wanted]


def synth_project(sources, docs=None):
    """A Project built from {rel_path: source} strings (no filesystem)."""
    modules = [Module(path="/" + rel, rel=rel, tree=ast.parse(src),
                      source=src)
               for rel, src in sources.items()]
    return Project("/", modules, docs or {"README.md": ""})


class TestKnobsModule:
    def test_every_declaration_unique_and_typed(self):
        names = [k.name for k in knobs.DECLARATIONS]
        assert len(names) == len(set(names))
        assert all(k.type in ("bool", "int", "float", "str")
                   for k in knobs.DECLARATIONS)
        assert all(k.doc for k in knobs.DECLARATIONS)

    def test_bool_default_bias(self, monkeypatch):
        # default-off knobs turn on only for explicit truthy values
        monkeypatch.setenv("FMT_OBS", "garbage")
        assert knobs.knob_bool("FMT_OBS") is False
        monkeypatch.setenv("FMT_OBS", "on")
        assert knobs.knob_bool("FMT_OBS") is True
        # default-on knobs turn off only for explicit falsy values
        monkeypatch.setenv("FMT_GUARD", "garbage")
        assert knobs.knob_bool("FMT_GUARD") is True
        monkeypatch.setenv("FMT_GUARD", "off")
        assert knobs.knob_bool("FMT_GUARD") is False

    def test_numeric_knobs_degrade_to_default(self, monkeypatch):
        monkeypatch.setenv("FMT_RETRY_ATTEMPTS", "not-a-number")
        assert knobs.knob_int("FMT_RETRY_ATTEMPTS") == 3
        monkeypatch.setenv("FMT_SLO_WINDOW_S", "")
        assert knobs.knob_float("FMT_SLO_WINDOW_S") == 30.0
        monkeypatch.setenv("FMT_SERVING_MAX_BATCH", "64")
        assert knobs.knob_int("FMT_SERVING_MAX_BATCH") == 64

    def test_bool_knobs_strip_whitespace(self, monkeypatch):
        monkeypatch.setenv("FMT_DRIFT", "true ")
        assert knobs.knob_bool("FMT_DRIFT") is True
        monkeypatch.setenv("FMT_GUARD", " 0\n")
        assert knobs.knob_bool("FMT_GUARD") is False

    def test_int_knobs_accept_float_form(self, monkeypatch):
        # the serving sites historically parsed via int(_env_float(...))
        monkeypatch.setenv("FMT_SERVING_QUEUE_CAP", "8192.0")
        assert knobs.knob_int("FMT_SERVING_QUEUE_CAP") == 8192
        monkeypatch.setenv("FMT_SERVING_QUEUE_CAP", "1e4")
        assert knobs.knob_int("FMT_SERVING_QUEUE_CAP") == 10000

    def test_flight_events_default_matches_ring(self):
        from flink_ml_tpu.obs import flight

        assert knobs.knob_int("FMT_FLIGHT_EVENTS") == \
            flight._DEFAULT_CAPACITY == 512

    def test_undeclared_name_raises(self):
        with pytest.raises(KeyError, match="undeclared knob"):
            knobs.raw("FMT_DOES_NOT_EXIST")

    def test_str_knob_and_raw(self, monkeypatch):
        monkeypatch.delenv("FMT_TELEMETRY_HOST", raising=False)
        assert knobs.knob_str("FMT_TELEMETRY_HOST") == "127.0.0.1"
        assert knobs.raw("FMT_TELEMETRY_HOST") is None
        monkeypatch.setenv("FMT_TELEMETRY_HOST", "0.0.0.0")
        assert knobs.knob_str("FMT_TELEMETRY_HOST") == "0.0.0.0"


class TestJitPurity:
    def test_bad_fixture_fires_every_rule(self):
        findings = run_on("jit_bad.py")
        rules = {f.rule for f in findings}
        assert rules == {"JIT001", "JIT002", "JIT003"}
        messages = " | ".join(f.message for f in findings)
        assert "time.time()" in messages
        assert "print()" in messages
        assert "metric mutation obs.counter_add()" in messages
        assert "np.asarray()" in messages          # the fused closure
        assert "donate_argnames names 'missing'" in messages

    def test_good_fixture_is_clean(self):
        assert run_on("jit_good.py") == []

    def test_transitive_host_effect_attributed_to_root(self):
        findings = run_on("jit_bad.py")
        decorated = [f for f in findings
                     if "@jax.jit" in f.message and f.rule == "JIT001"]
        # the impure helper is one call deep from the decorated root
        assert decorated and all(f.symbol == "_impure_step"
                                 for f in decorated)


class TestLockDiscipline:
    def test_bad_fixture(self):
        findings = run_on("lock_bad.py")
        assert {(f.rule, f.symbol) for f in findings} == {
            ("LOCK002", "Racy.peek"), ("LOCK001", "Racy.reset")}

    def test_good_fixture_is_clean(self):
        assert run_on("lock_good.py") == []


class TestKnobChecker:
    def test_bad_fixture(self):
        findings = run_on("knob_bad.py")
        by_rule = {}
        for f in findings:
            by_rule.setdefault(f.rule, []).append(f.message)
        # .get + subscript + `from os import environ` + `from os import
        # getenv` — the aliased spellings must not evade the gate
        assert len(by_rule.pop("KNOB001")) == 4
        assert "FMT_NOT_A_REAL_KNOB" in by_rule.pop("KNOB002")[0]
        assert not by_rule

    def test_good_fixture_is_clean(self):
        assert run_on("knob_good.py") == []

    def test_dead_and_undocumented_knobs(self):
        knobs_src = (
            "def declare(*a): pass\n"
            "class Knob:\n"
            "    def __init__(self, *a): pass\n"
            'DECLARATIONS = (Knob("FMT_ALPHA", "1", "bool", "doc"),\n'
            '                Knob("FMT_BETA", "0", "bool", "doc"),\n'
            '                Knob("FMT_ALPHA", "1", "bool", "dup"))\n')
        reader = ("from flink_ml_tpu.utils import knobs\n"
                  'X = knobs.knob_bool("FMT_ALPHA")\n')
        project = synth_project(
            {"flink_ml_tpu/utils/knobs.py": knobs_src,
             "flink_ml_tpu/reader.py": reader},
            docs={"README.md": "`FMT_ALPHA` and `FMT_GONE`"})
        findings = run_checkers(project, CHECKERS)
        rules = {(f.rule, f.message.split("'")[1]) for f in findings
                 if f.rule.startswith("KNOB")}
        assert ("KNOB006", "FMT_ALPHA") in rules        # duplicate decl
        assert ("KNOB003", "FMT_BETA") in rules         # dead knob
        assert ("KNOB004", "FMT_BETA") in rules         # undocumented
        assert ("KNOB005", "FMT_GONE") in rules         # doc drift


class TestHygiene:
    def test_bad_fixture(self):
        findings = run_on("hygiene_bad.py")
        rules = sorted(f.rule for f in findings)
        assert rules == ["METRIC001", "METRIC002", "METRIC002",
                         "SCOPE001", "SCOPE001"]

    def test_good_fixture_is_clean(self):
        assert run_on("hygiene_good.py") == []


class TestBaseline:
    def test_missing_reason_is_meta_finding(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"suppressions": [
            {"rule": "LOCK002", "file": "x.py", "match": "y", "reason": " "},
        ]}))
        entries, findings = load_baseline(str(path))
        assert entries == []
        assert [f.rule for f in findings] == ["META001"]
        assert "written reason" in findings[0].message

    def test_non_object_entries_are_meta_findings_not_crashes(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"suppressions": [
            "oops",
            {"rule": "KNOB001", "file": "x.py", "match": "y",
             "reason": "a genuine reason that is long enough"},
        ]}))
        entries, findings = load_baseline(str(path))
        assert [e.rule for e in entries] == ["KNOB001"]
        assert [f.rule for f in findings] == ["META001"]
        path.write_text(json.dumps({"suppressions": "all of them"}))
        entries, findings = load_baseline(str(path))
        assert entries == [] and [f.rule for f in findings] == ["META001"]

    def test_match_suppresses_and_unused_reported(self):
        findings = run_on("lock_bad.py")
        entries = [
            Suppression("LOCK002", "tests/fixtures/analysis/lock_bad.py",
                        "'_count'", "fixture"),
            Suppression("LOCK001", "tests/fixtures/analysis/lock_bad.py",
                        "'_never_matches'", "stale"),
        ]
        kept, suppressed, unused = apply_baseline(findings, entries)
        assert [f.rule for f in suppressed] == ["LOCK002"]
        assert [f.rule for f in kept] == ["LOCK001"]
        assert [e.match for e in unused] == ["'_never_matches'"]

    def test_match_can_key_on_symbol(self):
        findings = run_on("lock_bad.py")
        entries = [Suppression(
            "LOCK002", "tests/fixtures/analysis/lock_bad.py",
            "(Racy.peek)", "symbol-keyed")]
        _kept, suppressed, _unused = apply_baseline(findings, entries)
        assert [f.symbol for f in suppressed] == ["Racy.peek"]

    def test_committed_baseline_reasons_are_substantive(self):
        entries, findings = load_baseline()
        assert not findings
        assert entries, "committed baseline should document its FPs"
        for entry in entries:
            assert len(entry.reason) > 40, (
                f"suppression {entry.rule}/{entry.match} needs a real "
                f"written reason, not a token")


class TestRepoSelfCheck:
    """The acceptance gate: clean at HEAD, red on a seeded violation."""

    def _kept(self, extra=()):
        project, parse_findings = load_project(extra_paths=extra)
        findings = parse_findings + run_checkers(project, CHECKERS)
        entries, meta = load_baseline()
        kept, _suppressed, _unused = apply_baseline(findings, entries)
        return kept + meta

    def test_repo_is_clean_at_head(self):
        kept = self._kept()
        assert kept == [], "\n".join(f.format() for f in kept)

    def test_seeded_violation_fails(self, tmp_path):
        bad = tmp_path / "seeded.py"
        bad.write_text(
            "import os\n"
            "import threading\n\n\n"
            "def read():\n"
            "    return os.environ.get('FMT_OBS')\n\n\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n\n"
            "    def inc(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n\n"
            "    def peek(self):\n"
            "        return self._n\n")
        kept = self._kept(extra=[str(bad)])
        assert {f.rule for f in kept} == {"KNOB001", "LOCK002"}

    def test_cli_check_exits_zero_on_repo(self):
        proc = subprocess.run(
            [sys.executable, "-m", "flink_ml_tpu.analysis", "--check",
             "--json", "--no-report"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["ok"] is True
        assert payload["findings"] == 0
        assert payload["files_scanned"] > 90
        assert payload["suppressed"] >= 1

    def test_cli_check_fails_on_seeded_package_violation(self):
        seeded = os.path.join(REPO_ROOT, "flink_ml_tpu",
                              "_fmtlint_seeded_violation.py")
        with open(seeded, "w") as fh:
            fh.write("import os\nBAD = os.environ.get('FMT_OBS')\n")
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "flink_ml_tpu.analysis", "--check",
                 "--json", "--no-report"],
                cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
            assert proc.returncode == 1, proc.stdout + proc.stderr
            payload = json.loads(proc.stdout)
            assert payload["rules"].get("KNOB001") == 1
        finally:
            os.remove(seeded)

    def test_rule_table_documents_every_rule(self):
        emitted = set()
        for f in run_on("jit_bad.py", "lock_bad.py", "knob_bad.py",
                        "hygiene_bad.py"):
            emitted.add(f.rule)
        assert emitted <= set(RULES)
        for rule in ("JIT001", "JIT002", "JIT003", "LOCK001", "LOCK002",
                     "KNOB001", "KNOB002", "KNOB003", "KNOB004", "KNOB005",
                     "KNOB006", "SCOPE001", "METRIC001", "METRIC002",
                     "META001", "META002"):
            assert rule in RULES


class TestAnalysisReportLine:
    def test_analysis_report_follows_fmt_obs_reports(self, tmp_path,
                                                  monkeypatch):
        # the analyzer's report must land where obs --check will look
        from flink_ml_tpu.analysis.__main__ import default_report_dir
        from flink_ml_tpu.obs.report import reports_dir

        monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path))
        assert default_report_dir() == str(tmp_path) == reports_dir()
        monkeypatch.delenv("FMT_OBS_REPORTS")
        assert default_report_dir() == os.path.join(REPO_ROOT, "reports")

    def test_obs_check_reads_analysis_report(self, tmp_path):
        from flink_ml_tpu.obs.report import analysis_summary

        payload = {"kind": "analysis", "ok": True, "findings": 0,
                   "suppressed": 4, "files_scanned": 98, "rules": {}}
        (tmp_path / "analysis.json").write_text(json.dumps(payload))
        got = analysis_summary(str(tmp_path))
        assert got == payload

    def test_absent_or_malformed_report_is_none(self, tmp_path):
        from flink_ml_tpu.obs.report import analysis_summary

        assert analysis_summary(str(tmp_path)) is None
        (tmp_path / "analysis.json").write_text("{not json")
        assert analysis_summary(str(tmp_path)) is None


class TestDriftRollRace:
    """The genuine LOCK finding fmtlint caught in DriftMonitor.roll():
    the persist decision was computed under the lock but *claimed*
    outside it, so two dispatcher threads rolling past the reference
    freeze together could both write the reference sidecar (and read
    ``_persist_path``/``_persisted`` bare while at it).  Red before the
    fix: ``save`` ran twice and the reference-complete flight event
    recorded twice."""

    def _frozen_monitor(self, monkeypatch, tmp_path):
        from flink_ml_tpu.obs import drift

        mon = drift.DriftMonitor(name="race", ref_target=1,
                                 persist_path=str(tmp_path / "ref.json"))
        mon._ref_in_rows = 1  # at target: the next roll freezes the ref

        entered = threading.Event()
        release = threading.Event()
        calls = []

        def slow_save(self, path):
            calls.append(path)
            entered.set()
            assert release.wait(5)

        monkeypatch.setattr(drift.DriftMonitor, "save", slow_save)
        return mon, entered, release, calls

    def test_concurrent_rolls_persist_once(self, monkeypatch, tmp_path):
        from flink_ml_tpu.obs import flight

        flight.reset()
        mon, entered, release, calls = self._frozen_monitor(
            monkeypatch, tmp_path)

        t = threading.Thread(target=mon.roll)
        t.start()
        assert entered.wait(5)   # thread A is mid-save, lock released
        mon.roll()               # thread B rolls through the same window
        # B must not have announced on A's behalf: A's save outcome is
        # still unknown, so an announce here would guess at `persisted`
        assert not [e for e in flight.events()
                    if e["kind"] == "drift.reference_complete"]
        release.set()
        t.join(5)
        assert not t.is_alive()

        assert len(calls) == 1, "double persist: the race fmtlint flagged"
        announces = [e for e in flight.events()
                     if e["kind"] == "drift.reference_complete"]
        assert len(announces) == 1
        assert announces[0]["persisted"] is True

    def test_failed_persist_announces_unpersisted(self, monkeypatch,
                                                  tmp_path):
        from flink_ml_tpu.obs import drift, flight

        flight.reset()
        mon = drift.DriftMonitor(name="race2", ref_target=1,
                                 persist_path=str(tmp_path / "ref.json"))
        mon._ref_in_rows = 1

        def failing_save(self, path):
            raise OSError("disk full")

        monkeypatch.setattr(drift.DriftMonitor, "save", failing_save)
        mon.roll()
        announces = [e for e in flight.events()
                     if e["kind"] == "drift.reference_complete"]
        assert len(announces) == 1
        assert announces[0]["persisted"] is False
        assert mon._persisted is False
