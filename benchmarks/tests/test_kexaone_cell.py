"""What `kexaone-reason` adds to the benchmark: the operation and byte
counts of one chip's share against counts made by hand, the new
per-layer metric on a hand-made trace and counts, and the cell's own
run and control at toy widths on the CPU.

    pytest benchmarks/tests
"""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_harness import last_line, run_cell  # noqa: E402

from benchmarks import flops_glm  # noqa: E402
from benchmarks import flops_kexaone as flops  # noqa: E402
from benchmarks import scope_reduce, trace_reduce  # noqa: E402
from benchmarks.common import load_module  # noqa: E402

CELL = "kexaone-reason"
METRIC = "gqa_attn_roofline_pct"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    p = flops.params(cfg)
    # q 64 x 128 = 8,192 wide, k and v 8 x 128 each, o back to 6,144
    assert p["attention"] == 6144 * (8192 + 2 * 1024) + 8192 * 6144 \
        == 113_246_208
    assert p["expert"] == p["shared"] == 3 * 6144 * 2048 == 37_748_736
    assert p["router"] == 6144 * 128
    assert p["dense_ffn"] == 3 * 6144 * 18432
    assert p["head"] == 6144 * 19_200
    assert flops.layer_counts(cfg) == {"window": 4, "full": 1, "dense": 1,
                                       "routed": 4}
    # a token's 8 chosen experts lie one on each of the 8 shards, on the
    # mean
    assert flops.held_experts_per_token(cfg) == 1.0
    active = (5 * p["attention"] + p["dense_ffn"]
              + 4 * (p["router"] + 2 * p["expert"]))
    assert flops.active_params(cfg) == active == 1_211_105_280
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "k-exaone-236b-a23b"][0]
    assert entry["reduced"] == cfg["reduced"]


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops.attention_flops_per_key(cfg) == 64 * 2 * 2 * 128
    base = 2 * 1_211_105_280
    # one generated token at context 100: the head, the full layer over
    # 100 keys, the four window layers over 128 at most
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 100.0) \
        == base + 2 * 6144 * 19_200 + 5 * 32_768 * 100
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 1000.0) \
        == base + 2 * 6144 * 19_200 + 32_768 * (1000 + 4 * 128)
    # a prefill token at context 7: no head, every layer 7 keys
    assert flops.serve_flops_active(cfg, 1, 0, 7.0, 0.0) \
        == base + 5 * 32_768 * 7


def test_the_expert_need_reads_the_held_experts_alone(cfg):
    assert flops.moe_need is flops_glm.moe_need
    # a decode step: 32 held pairs on 14 of the 16 held experts in each
    # of the 4 layers; the other 224 pairs a layer are another chip's
    need = flops.moe_need(cfg, assignments=4 * 32, active_experts=4 * 14)
    assert need["flops"] == 6 * 6144 * 2048 * 128
    assert need["bytes"] == 56 * 3 * 6144 * 2048 * 2 + 128 * 2 * 6144 * 2
    # 4.2 GB of the held experts' weights a step
    assert 4.2e9 < need["bytes"] < 4.3e9


def test_the_attention_need_clips_a_window_layer(cfg):
    # one cached row: 8 kv heads x [k | v] x 128 x 2 B
    assert flops.kv_row_bytes(cfg) == 4096
    need = flops.attn_need(cfg, keys_attended=32 * 128, kv_rows_read=32 * 128)
    assert need == {"flops": 32_768.0 * 4096, "bytes": 4096.0 * 4096}


def _observed(monkeypatch, cfg, counts, kernels):
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: None if counts is None else
                        dict({"steps": 2}, **{f: counts[f] for f in fields}))
    monkeypatch.setattr(scope_reduce, "device_steps", lambda obs: 2)
    monkeypatch.setattr(trace_reduce, "op_sums", lambda events: kernels)
    return {"trace": [SimpleNamespace()], "trace_window_s": 1.0,
            "window_s": 1.0, "config": cfg, "peaks": PEAKS}


def test_the_new_metric_is_need_over_both_named_calls(monkeypatch, cfg):
    read = load_module("layer_metrics", METRIC).read
    counts = {"attn_keys_window": 2 * 32 * 128.0,
              "kv_rows_window": 2 * 32 * 128.0,
              "attn_keys_full": 2 * 32 * 1500.0,
              "kv_rows_full": 2 * 32 * 1500.0}
    kernels = {"ragged_gqa_window.1 tpu_custom_call":
               {"total_s": 2 * 4 * 100e-6, "count": 8},
               "ragged_gqa_full.2 tpu_custom_call":
               {"total_s": 2 * 300e-6, "count": 2},
               "fusion.3": {"total_s": 1.0, "count": 9}}
    obs = _observed(monkeypatch, cfg, counts, kernels)
    # bound by bytes at a decode step's rows: 4 window layers of 32 x 128
    # rows and the full layer's 32 x 1,500, over 0.7 ms of the two calls
    least = (4 * 32 * 128 + 32 * 1500) * 4096 / PEAKS["hbm_bytes_per_s"]
    assert read(obs) == pytest.approx(100 * least / 700e-6)
    # a program that does not count them, or a step with neither call
    assert read(_observed(monkeypatch, cfg, None, kernels)) is None
    assert read(_observed(monkeypatch, cfg, counts,
                          {"fusion.3": kernels["fusion.3"]})) is None
    assert read(dict(obs, config={})) is None


def test_the_toy_cell_is_correct():
    line = last_line(run_cell(CELL, trace=1, seconds=6))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["engine_compiles"]["value"] == 1
    assert line["checks"]["token_gap_max"]["value"] <= 1e-4
    assert line["metrics"]["moe_pairs_per_expert"]["value"] >= 1
    assert "decode_rows_per_step" in line["metrics"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False
