"""The ``train.onepass_share`` reader (PR 26) against a ``Context`` built by
hand, as ``test_span_readers.py`` does for its nine: every fit on the kernel,
a part of them, and a program that has no such counter.  No JAX."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
NAME = "train.onepass_share"
(METRIC,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]


def _ctx(setup_counters, end_counters):
    """Counters at the window's start (after set-up) and at its end."""
    return run.Context(snapshots={
        "window": ({"counters": setup_counters, "timings": {}},
                   {"counters": end_counters, "timings": {}})}, trace=None)


CASES = [
    # 9 fits in set-up, 150 in the window, every one on the kernel
    ({"train.fused_runs": 9, "train.onepass_fits": 9},
     {"train.fused_runs": 159, "train.onepass_fits": 159}, 100.0),
    # a part: 30 of the window's 120
    ({"train.fused_runs": 9, "train.onepass_fits": 9},
     {"train.fused_runs": 129, "train.onepass_fits": 39}, 25.0),
    # a program with the kernel whose fits all kept the XLA step: the
    # counter is there, at 0
    ({"train.fused_runs": 9, "train.onepass_fits": 0},
     {"train.fused_runs": 59, "train.onepass_fits": 0,
      "train.onepass_declined": 59}, 0.0),
    # the parent: no such counter
    ({"train.fused_runs": 9}, {"train.fused_runs": 159}, None),
    # the counter, and no fit in the window
    ({"train.fused_runs": 9, "train.onepass_fits": 9},
     {"train.fused_runs": 9, "train.onepass_fits": 9}, None),
]


@pytest.mark.parametrize("before,after,expected", CASES,
                         ids=["all", "a-part", "none-of-them", "no-counter",
                              "no-fit"])
def test_the_share_reckoned_by_hand_or_nothing(before, after, expected):
    got = run.reader("layers", NAME)(_ctx(before, after), METRIC)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, rel=1e-12)


def test_the_entry_stands_beside_its_layers_other_metric():
    roofline = [m for m in BENCH["per_layer"]
                if m["name"] == "train_program_roofline"][0]
    assert METRIC["layer"] == roofline["layer"]  # letter for letter
    assert METRIC["moves"] == "fit_rows_per_s"
    assert METRIC["source"] == "program_counter"
    assert METRIC["workloads"] == ["epsilon_lr.sweep", "mnist8m_lr.sweep"]
    assert BENCH["per_layer"][-1] is METRIC  # appended, nothing moved
    assert os.path.exists(os.path.join(ROOT, "chipbench", "layers",
                                       NAME + ".py"))
