"""What `sala-docs32k` adds to the benchmark: the operation and byte
counts against counts made by hand, the two kernel rooflines and the
snapshot share on hand-made traces and counts, and the cell's own run
and control at toy widths on the CPU.

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

from benchmarks import flops_sala as flops  # noqa: E402
from benchmarks import scope_reduce, trace_reduce  # noqa: E402
from benchmarks import weights_sala  # noqa: E402
from benchmarks.common import build_model, load_module  # noqa: E402

CELL = "sala-docs32k"
NEW = ("sparse_attn_roofline_pct", "lightning_roofline_pct",
       "snapshot_restore_pct")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    assert flops.kinds(cfg) == {"minicpm4": 4, "lightning-attn": 12}
    p = flops.params(cfg)
    assert p["ffn"] == 3 * 4096 * 16384 == 201_326_592
    # q, [k | v], the gate, o
    assert p["minicpm4"] == 3 * 4096 * 4096 + 4096 * 512 == 52_428_800
    # [q | k | v], the gate, o
    assert p["lightning-attn"] == 5 * 4096 * 4096 == 83_886_080
    assert p["head"] == 4096 * 73_448
    active = 16 * p["ffn"] + 4 * p["minicpm4"] + 12 * p["lightning-attn"]
    assert flops.active_params(cfg) == active == 4_437_573_632
    # what is resident: the table and the norm scales too
    small = (16 * 2 * 4096 + 4096 + 4 * 2 * 128
             + 12 * (2 * 128 + 4096))
    assert active + 2 * p["head"] + small == cfg["parameters"] \
        == weights_sala.count_params(cfg) == 5_039_448_064


def test_the_configuration_is_the_published_slice(cfg):
    assert cfg["mixer_types"] == cfg["published"]["mixer_types"][8:24]
    assert cfg["mixer_types"].count("minicpm4") * 3 \
        == cfg["mixer_types"].count("lightning-attn")
    assert sorted(cfg["reduced"]) == ["mixer_types", "num_hidden_layers"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "minicpm-sala"][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # the tree the program builds from the file holds as many
    import jax
    import jax.numpy as jnp
    toy = {**cfg, **cfg["toy"]}
    tree = jax.eval_shape(build_model(toy).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree.leaves(tree)) \
        == weights_sala.count_params(toy)


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops.attention_flops_per_key(cfg) == 32 * 2 * 2 * 128
    assert flops.index_flops_per_row(cfg) == 32 * 2 * 128
    assert flops.lightning_flops_per_token(cfg) == 32 * 4 * 128 * 128
    base = 2 * 4_437_573_632 + 2 * 4096 * 73_448 + 12 * 32 * 4 * 128 * 128
    # one generated token at context 100: dense, 100 keys in 4 layers
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 100.0) \
        == base + 4 * 16_384 * 100
    # at context 33,000: 65 blocks of 64, the 2,048 newest and half a
    # block; 33,000 / 16 compressed rows
    kept = 65 * 64 + 2048 + 32
    assert flops.kept_keys(cfg, 33_000) == kept
    assert flops.serve_flops_active(cfg, 0, 1, 0.0, 33_000.0) \
        == base + 4 * (16_384 * kept + 8192 * 33_000 / 16)


def test_kernel_needs_are_the_hand_counts(cfg):
    assert flops.kv_row_bytes(cfg) == 1024 and flops.index_row_bytes(cfg) \
        == 512
    need = flops.sparse_need(cfg, keys=1000, rows_read=900, index_rows=50,
                             queries=2)
    assert need["flops"] == 16_384 * 1000 + 8192 * 50 * 2
    assert need["bytes"] == 1024 * 900 + 512 * 50
    la = flops.lightning_need(cfg, tokens=100, state_slots=32)
    assert la["flops"] == 100 * 32 * 4 * 128 * 128
    assert la["bytes"] == 32 * 2 * 32 * 128 * 128 * 4 + 100 * 4 * 4096 * 4


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
                          "jit__step_fn(1)", 0.0, 60e6)
COUNTS = {"sparse_keys": 2 * 600_000.0, "sparse_rows_read": 2 * 200_000.0,
          "la_tokens": 2 * 100.0, "state_slots": 2 * 31.0,
          "snapshot_tokens_skipped": 32_768.0, "chunk_tokens": 232.0}


def test_the_new_metrics_read_a_toy_trace(cfg, monkeypatch):
    """Two executions of the step: the kernels' events by the names
    their Pallas calls carry, the need from the span fields' sums."""
    events = [STEP, STEP,
              _op("ragged_sparse_attention.3 tpu_custom_call", 20e6),
              _op("ragged_sparse_attention.4 tpu_custom_call", 20e6),
              _op("ragged_lightning_attention.9 tpu_custom_call", 7e6),
              _op("ragged_lightning_attention.11 tpu_custom_call", 7e6),
              _op("fusion.2", 30e6)]
    obs = _observed(cfg, events, monkeypatch, COUNTS)
    got = load_module("layer_metrics", "sparse_attn_roofline_pct").read(obs)
    need = flops.sparse_need(cfg, 600_000, 200_000, 0, 0)
    least = 4 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert got == pytest.approx(100 * least / 20e-3) and 0 < got < 100
    got = load_module("layer_metrics", "lightning_roofline_pct").read(obs)
    need = flops.lightning_need(cfg, 100, 31)
    least = 12 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert got == pytest.approx(100 * least / 7e-3) and 0 < got < 100
    got = load_module("layer_metrics", "snapshot_restore_pct").read(obs)
    assert got == pytest.approx(100 * 32_768 / 33_000)


def test_the_new_metrics_say_nothing_where_there_is_nothing(cfg,
                                                            monkeypatch):
    """No trace, a program without the kernels or without the span
    fields (the parent's), another configuration: None, nothing
    raised."""
    for name in NEW[:2]:
        read = load_module("layer_metrics", name).read
        assert read(_observed(cfg, [], monkeypatch, COUNTS)) is None
        no_kernel = _observed(cfg, [STEP, _op("fusion.2", 1e6)], monkeypatch,
                              COUNTS)
        assert read(no_kernel) is None
        other = dict(no_kernel, config={"flops": "benchmarks.flops_glm"})
        assert read(other) is None
    both = [STEP, _op("ragged_sparse_attention.3 tpu_custom_call", 1e6),
            _op("ragged_lightning_attention.9 tpu_custom_call", 1e6)]
    obs = _observed(cfg, both, monkeypatch, COUNTS)
    monkeypatch.setattr(scope_reduce, "slice_counts",
                        lambda obs, fields: None)
    for name in NEW:
        assert load_module("layer_metrics", name).read(obs) is None


def test_the_toy_cell_is_correct():
    line = last_line(run_cell(CELL, trace=1, seconds=6))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["engine_compiles"]["value"] == 1
    assert line["checks"]["token_gap_max"]["value"] <= 1e-4
    # a CPU trace has no device plane: both rooflines stay out, and say
    # nothing on the way; the snapshot share reads the ring
    assert "sparse_attn_roofline_pct" not in line["metrics"]
    assert "lightning_roofline_pct" not in line["metrics"]
    assert line["metrics"]["snapshot_restore_pct"]["value"] > 50
    assert line["metrics"]["kv_hit_pct.tok_s"]["value"] > 50
    assert "decode_rows_per_step" in line["metrics"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False


def test_the_step_span_carries_every_new_field():
    """What the three readers sum is on `engine.step` in a toy serve
    (the traced toy line above reads one of them end to end)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from paddle_tpu.engine.engine import ServeEngine
    prof = importlib.import_module("paddle_tpu.profiler.profiler")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        full = json.load(f)
    toy = {**full, **full["toy"]}
    model = build_model(toy)
    eng = ServeEngine(model, {"params": weights_sala.make_params(toy, 5)},
                      **{k: v for k, v in toy["serve"].items()})
    prof.reset_profiler()
    eng.generate([list(range(1, 60))], max_new_tokens=3)
    steps = [e for e in prof.get_events() if e["name"] == "engine.step"]
    for field in ("sparse_rows_read", "sparse_keys", "index_rows_read",
                  "blocks_selected", "la_tokens", "state_slots",
                  "snapshots_taken", "snapshots_restored",
                  "snapshot_tokens_skipped"):
        assert all(field in s["args"] for s in steps), field
