"""What `lfm2-reason` adds to the benchmark: the operation and byte
counts against counts made by hand, the new per-layer metric on
hand-made counts, and the cell's own run and control at toy widths on
the CPU.

    pytest benchmarks/tests
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_harness import last_line, run_cell  # noqa: E402

from benchmarks import flops_glm  # noqa: E402
from benchmarks import flops_lfm2 as flops  # noqa: E402
from benchmarks import scope_reduce, weights_lfm2  # noqa: E402
from benchmarks.common import load_module  # noqa: E402

CELL = "lfm2-reason"
METRIC = "moe_pairs_per_expert"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    p = flops.params(cfg)
    # [B | C | x] and out; q, k, v, o
    assert p["conv"] == 2048 * 3 * 2048 + 2048 * 2048 == 16_777_216
    assert p["attention"] == 2048 * (2048 + 2 * 512) + 2048 * 2048 \
        == 10_485_760
    assert p["expert"] == 3 * 2048 * 1792
    assert p["head"] == 2048 * 65_536
    assert flops.layer_counts(cfg) == {"conv": 10, "attention": 3,
                                       "dense": 1, "routed": 12}
    active = (10 * p["conv"] + 3 * p["attention"] + 3 * 2048 * 7168
              + 12 * (2048 * 32 + 4 * p["expert"]))
    assert flops.active_params(cfg) == active == 772_538_368
    # what is resident: every expert, the table once (the head is the
    # table), the convolution's taps, the expert bias and the norm scales
    small = (10 * 3 * 2048 + 12 * 32 + 13 * 2 * 2048 + 3 * 2 * 64 + 2048)
    assert (active + 12 * 28 * p["expert"] + p["head"] + small
            == cfg["parameters"] == weights_lfm2.count_params(cfg)
            == 4_606_249_728)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b"][0]
    assert entry["reduced"] == cfg["reduced"]


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops.attention_flops_per_key(cfg) == 32 * 2 * 2 * 64
    assert flops.conv_flops_per_token(cfg) == 2048 * 2 * 4
    base = 2 * 772_538_368 + 10 * 2048 * 8
    # one generated token at context 100: the head, three attention
    # layers
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 100.0) \
        == base + 2 * 2048 * 65_536 + 3 * 8192 * 100
    # a prefill token at context 7: no head
    assert flops.serve_flops_active(cfg, 1, 0, 7.0, 0.0) \
        == base + 3 * 8192 * 7


def test_the_expert_need_is_glms_count(cfg):
    assert flops.moe_need is flops_glm.moe_need
    need = flops.moe_need(cfg, assignments=128, active_experts=12 * 32)
    assert need["flops"] == 6 * 2048 * 1792 * 128
    # every expert's three matrices once: 8.46 GB a step
    assert need["bytes"] == 384 * 3 * 2048 * 1792 * 2 + 128 * 2 * 2048 * 2


def _observed(monkeypatch, counts):
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: None if counts is None else
                        dict({"steps": 2}, **{f: counts[f] for f in fields}))
    return {"trace": [], "trace_window_s": 1.0, "window_s": 1.0}


def test_the_new_metric_is_pairs_over_active_experts(monkeypatch):
    read = load_module("layer_metrics", METRIC).read
    obs = _observed(monkeypatch, {"moe_assignments": 2 * 128.0,
                                  "moe_active_experts": 2 * 381.0})
    assert read(obs) == pytest.approx(128 / 381)
    # a program that does not count them, or a step of no expert layer
    assert read(_observed(monkeypatch, None)) is None
    assert read(_observed(monkeypatch, {"moe_assignments": 0.0,
                                        "moe_active_experts": 0.0})) is None


def test_the_toy_cell_is_correct():
    line = last_line(run_cell(CELL, trace=1, seconds=6))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["engine_compiles"]["value"] == 1
    assert line["checks"]["token_gap_max"]["value"] <= 1e-4
    assert line["metrics"][METRIC]["value"] > 1
    assert "decode_rows_per_step" in line["metrics"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False
