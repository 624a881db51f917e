"""chip_smoke.py on the CPU: it must refuse to run, and its phases (all but
the Mosaic ones) must pass at a tiny size — so chip time is never spent
debugging the script itself."""

import json
import os
import subprocess
import sys

import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.fault import pressure
from flink_ml_tpu.serve.breaker import reset_breakers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_the_cpu_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu", FMT_COMPILE_CACHE="off")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "refusing to run" in proc.stderr
    # no phase ran and no result line was printed
    assert proc.stdout.strip() == ""


def test_phases_pass_at_a_tiny_size(tmp_path, monkeypatch):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    out = str(tmp_path / "smoke")
    # run() points these at its output directory; pre-set them through
    # monkeypatch so the suite's own values come back afterwards
    for var, sub in (("FMT_OBS_REPORTS", "reports"),
                     ("FMT_TRACE_DIR", "traces"),
                     ("FMT_FLIGHT_DIR", "flight")):
        monkeypatch.setenv(var, os.path.join(out, sub))
    tiny = dict(chip_smoke.FULL, n_train=8192, n_test=2048, batch=1024,
                requests=6, max_request_rows=32, big_request_rows=512)
    phases = [p for p in chip_smoke.PHASES
              if p[0] in ("fit", "transform", "serve", "nothing_hid")]
    reset_breakers()
    pressure.reset_states()
    try:
        summary = chip_smoke.run(tiny, phases, out, platform="cpu")
    finally:
        obs.disable()
        obs.reset()
    assert summary["ok"] is True and summary["claim"] is None
    assert list(summary)[-1] == "claim"
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}
    assert [summary["phases"][name]["ok"] for name, _ in phases] == \
        [True] * 4
    assert summary["phases"]["fit"]["slab_devices"] == 8
    counters = summary["counters"]
    assert all(counters[k] == 0 for k in chip_smoke.MUST_BE_ZERO), counters
    assert counters["slab_pool.hits"] >= 1
    assert counters["warmstart.hits"] >= 1
    assert counters["fused.shard_map_dispatches"] >= 1
    # the six compile totals: seconds by stage, the cache's hits and misses
    assert counters["compile.backend"] > 0 and counters["compile.lower"] > 0
    assert counters["compile.trace"] > 0
    assert counters["compile.cache_read"] == 0  # the suite runs cache off
    assert counters["compile.cache_hits"] == 0 \
        and counters["compile.cache_misses"] == 0
    json.dumps(summary)  # the "chip_smoke: summary" line must serialize
    # the LAST stdout line: exactly the two keys the driver accepts
    last = json.loads(json.dumps(chip_smoke.verdict(summary)))
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 8}}
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    # it wrote where it was told, and only there
    assert sorted(os.listdir(out)) == ["model_xla", "reports"]
    assert os.path.isdir(os.path.join(out, "model_xla", "warm_aot"))


def test_a_hidden_failure_fails_the_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    obs.enable()
    obs.reset()
    try:
        obs.counter_add("serve.fallbacks")
        with pytest.raises(AssertionError, match="serve.fallbacks"):
            chip_smoke.phase_nothing_hid({})
    finally:
        obs.disable()
        obs.reset()
