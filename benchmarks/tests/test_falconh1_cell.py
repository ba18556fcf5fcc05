"""What `falconh1-reason` adds to the benchmark: the operation and byte
counts against counts made by hand, the SSD kernel's roofline on a
hand-made trace and counts, and the cell's own run and control at toy
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

from benchmarks import flops_falconh1 as flops  # noqa: E402
from benchmarks import scope_reduce, trace_reduce  # noqa: E402
from benchmarks import weights_falconh1  # noqa: E402
from benchmarks.common import build_model, load_module  # noqa: E402

CELL = "falconh1-reason"
METRIC = "ssd_roofline_pct"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    p = flops.params(cfg)
    # q, k, v, o
    assert p["attention"] == 5120 * (2560 + 2 * 512) + 2560 * 5120 \
        == 31_457_280
    # [z | x | B | C | dt], out
    assert p["mamba"] == 5120 * (2 * 4096 + 2 * 512 + 32) + 4096 * 5120 \
        == 68_321_280
    assert p["mlp"] == 3 * 5120 * 21_504 == 330_301_440
    assert p["head"] == 5120 * 261_120
    active = 6 * (p["attention"] + p["mamba"] + p["mlp"])
    assert flops.active_params(cfg) == active == 2_580_480_000
    # what is resident: the table, the convolution, the scan's leaves and
    # the norm scales too
    small = 6 * (4 * 5120 + 5120 + 3 * 32 + 4096 + 2 * 5120) + 5120
    assert active + 2 * p["head"] + small == cfg["parameters"] \
        == weights_falconh1.count_params(cfg) == 5_254_594_112
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "falcon-h1-34b"][0]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    import jax
    import jax.numpy as jnp
    toy = {**cfg, **cfg["toy"]}
    tree = jax.eval_shape(build_model(toy).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree.leaves(tree)) \
        == weights_falconh1.count_params(toy)


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops.attention_flops_per_key(cfg) == 20 * 2 * 2 * 128
    assert flops.ssd_flops_per_token(cfg) == 32 * 4 * 256 * 128
    base = 2 * 2_580_480_000 + 6 * 32 * 4 * 256 * 128
    # one generated token at context 100
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 100.0) \
        == base + 2 * 5120 * 261_120 + 6 * 10_240 * 100
    # a prefill token at context 7: no head
    assert flops.serve_flops_active(cfg, 1, 0, 7.0, 0.0) \
        == base + 6 * 10_240 * 7


def test_ssd_need_is_the_hand_count(cfg):
    # a slot: the state [32, 256, 128] float32 and the tail [3, 5120]
    assert flops.ssd_state_bytes(cfg) == 32 * 256 * 128 * 4 + 3 * 5120 * 2
    need = flops.ssd_need(cfg, tokens=100, slots=32)
    assert need["flops"] == 100 * 32 * 4 * 256 * 128
    # a token's x, B, C, delta in and y out, float32
    assert need["bytes"] == 32 * 2 * (4_194_304 + 30_720) \
        + 100 * (2 * 4096 + 2 * 512 + 32) * 4


def _op(name, dur_ns):
    return trace_reduce.Event("/device:TPU:0", trace_reduce.OPS_LINE, name,
                              0.0, dur_ns)


STEP = trace_reduce.Event("/device:TPU:0", trace_reduce.MODULES_LINE,
                          "jit__step_fn(1)", 0.0, 30e6)
COUNTS = {"ssm_tokens": 2 * 76.0, "state_slots": 2 * 32.0}


def _observed(cfg, events, monkeypatch, counts):
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: dict(
                            {"steps": 2}, **{f: counts[f] for f in fields}))
    return {"trace": events, "config": cfg, "trace_window_s": 1.0,
            "window_s": 1.0,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_new_metric_reads_a_toy_trace(cfg, monkeypatch):
    """Two executions of the step: the kernel's events by the name its
    Pallas call carries (and not the other recurrences'), the need from
    the span fields' sums."""
    events = [STEP, STEP,
              _op("ragged_ssd.3 tpu_custom_call", 2.8e6),
              _op("ragged_ssd.4 tpu_custom_call", 2.8e6),
              _op("ragged_lightning_attention.9 tpu_custom_call", 9e6),
              _op("ragged_selective_scan.2 tpu_custom_call", 9e6),
              _op("fusion.2", 30e6)]
    obs = _observed(cfg, events, monkeypatch, COUNTS)
    got = load_module("layer_metrics", METRIC).read(obs)
    need = flops.ssd_need(cfg, 76, 32)
    least = 6 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert got == pytest.approx(100 * least / 2.8e-3) and 0 < got < 100


def test_the_new_metric_says_nothing_where_there_is_nothing(cfg,
                                                            monkeypatch):
    """No trace, a program without the kernel or without the span
    fields (the parent's), another configuration: None, nothing
    raised."""
    read = load_module("layer_metrics", METRIC).read
    assert read(_observed(cfg, [], monkeypatch, COUNTS)) is None
    no_kernel = _observed(cfg, [STEP, _op("fusion.2", 1e6)], monkeypatch,
                          COUNTS)
    assert read(no_kernel) is None
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        other = dict(no_kernel, config=json.load(f))
    other["trace"] = [STEP, _op("ragged_selective_scan.2 tpu_custom_call",
                                1e6)]
    assert read(other) is None
    obs = _observed(cfg, [STEP, _op("ragged_ssd.3 tpu_custom_call", 1e6)],
                    monkeypatch, COUNTS)
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: None)
    assert read(obs) is None


def test_the_toy_cell_is_correct():
    line = last_line(run_cell(CELL, trace=1, seconds=6))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["engine_compiles"]["value"] == 1
    assert line["checks"]["token_gap_max"]["value"] <= 1e-4
    # a CPU trace has no device plane: the roofline stays out, and says
    # nothing on the way
    assert METRIC not in line["metrics"]
    assert "decode_rows_per_step" in line["metrics"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False
