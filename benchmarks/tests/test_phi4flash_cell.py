"""What `phi4mf-reason` adds to the benchmark: the operation and byte
counts against counts made by hand, the two kernel rooflines on
hand-made traces, and the cell's own run, control and fault at toy
widths on the CPU.

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

from benchmarks import flops_phi4flash as flops  # noqa: E402
from benchmarks import scope_reduce, trace_reduce  # noqa: E402
from benchmarks import weights_phi4flash  # noqa: E402
from benchmarks.common import load_module  # noqa: E402

CELL = "phi4mf-reason"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    assert flops.kinds(cfg) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                                "cross": 7}
    p = flops.params(cfg)
    assert p["ffn"] == 3 * 2560 * 10240 == 78_643_200
    # in_proj, x_proj, dt_proj, out_proj
    assert p["mamba"] == (2560 * 10240 + 5120 * 192 + 160 * 5120
                          + 5120 * 2560) == 41_123_840
    assert p["gmu"] == 2 * 2560 * 5120 == 26_214_400
    assert p["window"] == p["full"] == 2560 * 5120 + 2560 * 2560
    assert p["cross"] == 2 * 2560 * 2560
    assert p["head"] == 200_064 * 2560
    active = (32 * p["ffn"] + 9 * p["mamba"] + 7 * p["gmu"] + 9 * p["full"]
              + 7 * p["cross"])
    assert flops.active_params(cfg) == active == 3_338_895_360
    # the file's own count: what is resident, the table and the small
    # leaves included
    small = (32 * 4 * 2560 + 2 * 2560
             + 9 * (4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120)
             + 9 * (5120 + 2560) + 7 * 2 * 2560 + 16 * (4 * 64 + 128))
    assert active + p["head"] + small == cfg["parameters"] \
        == weights_phi4flash.count_params(cfg) == 3_852_562_944


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops.attention_flops_per_key(cfg) == 40 * 2 * (64 + 128)
    scan = 9 * 9 * 5120 * 16
    # one generated token at position 99 (100 keys, inside the window)
    want = (2 * 3_338_895_360 + 2 * 200_064 * 2560 + scan
            + 15_360 * 16 * 100)
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 100.0) == want
    # at position 999 the eight window layers see 512 keys
    far = (2 * 3_338_895_360 + 2 * 200_064 * 2560 + scan
           + 15_360 * (8 * 1000 + 8 * 512))
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 1000.0) == far
    # a prompt of 1,024 tokens prefilled: positions + 1 sum to
    # 1024 * 1025 / 2, clipped 512 * 513 / 2 + 512 * 512
    whole, clipped = 1024 * 1025 // 2, 512 * 513 // 2 + 512 * 512
    got = flops.serve_flops_active(cfg, 1024, 0, float(whole), 0.0)
    assert got == pytest.approx(
        1024 * (2 * 3_338_895_360 + scan)
        + 15_360 * (8 * whole + 8 * clipped), rel=1e-12)


def test_kernel_needs_are_the_hand_counts(cfg):
    ssm = flops.ssm_need(cfg, ssm_tokens=100, state_slots=32)
    assert ssm["flops"] == 100 * 5120 * 16 * 9
    assert ssm["bytes"] == (32 * 2 * (5120 * 16 * 4 + 5120 * 3 * 2)
                            + 100 * (4 * 5120 * 2 + 2 * 16 * 4))
    assert flops.kv_row_bytes(cfg) == 5120
    attn = flops.attn_need(cfg, keys_attended=1000, kv_rows_read=900)
    assert attn["flops"] == 40 * 2 * (64 + 128) * 1000
    assert attn["bytes"] == 5120 * 900


def _observed(cfg, events, monkeypatch, counts):
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: dict(
                            {"steps": 2}, **{f: counts[f] for f in fields}))
    return {"trace": events, "config": cfg, "trace_window_s": 1.0,
            "window_s": 1.0,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def _op(name, dur_ns):
    return trace_reduce.Event("/device:TPU:0", trace_reduce.OPS_LINE, name,
                              0.0, dur_ns)


STEP = trace_reduce.Event("/device:TPU:0", trace_reduce.MODULES_LINE,
                          "jit__step_fn(1)", 0.0, 32e6)


def test_both_rooflines_read_a_toy_trace(cfg, monkeypatch):
    """Two executions of the step: the kernels' events by the names
    their Pallas calls carry, the need from the span fields' sums."""
    counts = {"ssm_tokens": 2 * 288.0, "state_slots": 2 * 32.0,
              "attn_keys_full": 2 * 40_000.0, "kv_rows_full": 2 * 37_000.0,
              "attn_keys_window": 2 * 16_000.0, "kv_rows_window": 2 * 16_500.0}
    events = [STEP, STEP,
              _op("ragged_selective_scan.3 tpu_custom_call", 2e6),
              _op("ragged_selective_scan.4 tpu_custom_call", 2e6),
              _op("ragged_diff_attention.9 tpu_custom_call", 6e6),
              _op("ragged_diff_attention.11 tpu_custom_call", 6e6),
              _op("fusion.2", 16e6)]
    obs = _observed(cfg, events, monkeypatch, counts)
    ssm = load_module("layer_metrics", "ssm_roofline_pct").read(obs)
    need = flops.ssm_need(cfg, 288, 32)
    least = 9 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert ssm == pytest.approx(100 * least / 2e-3)
    assert 0 < ssm < 100
    attn = load_module("layer_metrics", "hybrid_attn_roofline_pct").read(obs)
    full = flops.attn_need(cfg, 40_000, 37_000)
    win = flops.attn_need(cfg, 16_000, 16_500)
    least = (8 * max(full["flops"] / 197e12, full["bytes"] / 819e9)
             + 8 * max(win["flops"] / 197e12, win["bytes"] / 819e9))
    assert attn == pytest.approx(100 * least / 6e-3)
    assert 0 < attn < 100


def test_rooflines_say_nothing_where_there_is_nothing(cfg, monkeypatch):
    """No trace, a program without the kernels or without the span
    fields (the parent's), another configuration: None, nothing
    raised."""
    counts = dict.fromkeys(("ssm_tokens", "state_slots", "attn_keys_full",
                            "kv_rows_full", "attn_keys_window",
                            "kv_rows_window"), 1.0)
    for name in ("ssm_roofline_pct", "hybrid_attn_roofline_pct"):
        read = load_module("layer_metrics", name).read
        assert read(_observed(cfg, [], monkeypatch, counts)) is None
        no_kernel = _observed(cfg, [STEP, _op("fusion.2", 1e6)], monkeypatch,
                              counts)
        assert read(no_kernel) is None
        other = dict(no_kernel, config={"flops": "benchmarks.flops_glm"})
        assert read(other) is None
        monkeypatch.setattr(scope_reduce, "slice_counts",
                            lambda obs, fields: None)
        both = [STEP, _op("ragged_selective_scan.3 tpu_custom_call", 1e6),
                _op("ragged_diff_attention.9 tpu_custom_call", 1e6)]
        assert read(dict(no_kernel, trace=both)) is None


def test_the_toy_cell_is_correct():
    line = last_line(run_cell(CELL, trace=1))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["engine_compiles"]["value"] == 1
    assert line["checks"]["token_gap_max"]["value"] <= 1e-4
    # a CPU trace has no device plane: both rooflines stay out, and say
    # nothing on the way
    assert "ssm_roofline_pct" not in line["metrics"]
    assert "hybrid_attn_roofline_pct" not in line["metrics"]
    assert "decode_rows_per_step" in line["metrics"]


def test_an_altered_token_is_not_correct():
    line = last_line(run_cell(CELL, "--fault", "token_altered"))
    assert line["correct"] is False
    assert not line["checks"]["token_gap_max"]["ok"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False
