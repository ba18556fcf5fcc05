"""`correct` has been shown to fail.

The control: the reference computed in fp8 and put in the program's
place goes through the same `check()` against the same limits file as
the program's own numbers, and the run prints `correct` false (here at
a size a test run can hold and against the file's `toy` limits; the
runs at the cells' own sizes and committed limits are in PERF.md). The
faults: a run of the harness with the timed path broken underneath
prints `correct` false, once for each fault a cell can have. And a
number with no limit is never correct.
"""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_harness import last_line, run_cell  # noqa: E402

from benchmarks.common import held_checks  # noqa: E402

TRAIN_NUMBERS = {"grad_diff_median", "grad_norm_gap_max",
                 "change_norm_gap_max"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_training_step_is_not_correct(fault):
    line = last_line(run_cell("gpt2m-train-1k", "--fault", fault))
    assert line["correct"] is False
    failed = [k for k, v in line["checks"].items() if not v["ok"]]
    assert set(failed) & TRAIN_NUMBERS, line["checks"]


@pytest.mark.parametrize("cell", ["gpt2m-chat", "gpt2l-docs"])
def test_an_altered_token_is_not_correct(cell):
    line = last_line(run_cell(cell, "--fault", "token_altered"))
    assert line["correct"] is False
    assert not line["checks"]["token_gap_max"]["ok"]


@pytest.mark.parametrize("cell", ["gpt2m-chat", "gpt2l-docs"])
def test_the_fp8_control_is_not_correct_in_a_serving_cell(cell):
    line = last_line(run_cell(cell, "--control", "fp8"))
    assert line["correct"] is False
    assert not line["checks"]["token_gap_max"]["ok"]
    # nothing but the compared numbers failed: the run itself was sound
    assert all(v["ok"] for k, v in line["checks"].items()
               if not k.startswith("token_gap_"))


@pytest.mark.parametrize("control", ["fp8", "half_batch"])
def test_a_stand_in_for_the_training_step_is_not_correct(control):
    line = last_line(run_cell("gpt2m-train-1k", "--control", control))
    assert line["correct"] is False
    failed = {k for k, v in line["checks"].items() if not v["ok"]}
    assert failed and failed <= TRAIN_NUMBERS, line["checks"]


def test_a_number_with_no_limit_is_not_correct():
    # no limits file, or no entry: the limit is None and never passes
    assert [c["ok"] for c in held_checks({}, {"gap": 0.0})] == [False]
    assert [c["ok"] for c in held_checks({"gap": {"readings": "none yet"}},
                                         {"gap": 0.0})] == [False]
    # a reading that is missing fails a limit that is there
    assert [c["ok"] for c in held_checks({"gap": {"limit": 1.0}},
                                         {"gap": None})] == [False]
    # only an explicit entry takes a number out of the comparison
    assert held_checks({"gap": {"held": False, "readings": "why"}},
                       {"gap": 9.0}) == []
    got = held_checks({"gap": {"limit": 1.0}}, {"gap": 0.5})
    assert [(c["name"], c["ok"]) for c in got] == [("gap", True)]


LIMITS = sorted(glob.glob(os.path.join(ROOT, "benchmarks", "limits",
                                       "*.json")))


@pytest.mark.parametrize("path", LIMITS, ids=os.path.basename)
def test_committed_limits_lie_between_their_readings(path):
    """Each limit the check holds passes the largest reading of the
    sound program (`lower`) and fails the smallest of the control or
    fault it was set against (`upper`, three times the lower or more)."""
    limits = json.load(open(path))
    held = {k: v for k, v in limits.items()
            if k != "toy" and v.get("held") is not False}
    assert held, "a cell compares at least one number with the reference"
    for name, e in held.items():
        assert e["readings"]
        assert e["upper"] >= 3 * e["lower"], name
        assert [c["ok"] for c in held_checks(limits, {name: e["lower"]})] \
            == [True], name
        assert [c["ok"] for c in held_checks(limits, {name: e["upper"]})] \
            == [False], name
