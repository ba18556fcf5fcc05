"""What `glm47f-docs8k` adds to the benchmark: the operation and byte
counts against counts made by hand, the reduction of a trace by the
compiled program's scopes on hand-made events, and the cell's own
control and fault at toy widths (`test_control.py` names its cells).

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

from benchmarks import flops_glm, scope_reduce, trace_reduce  # noqa: E402

CELL = "glm47f-docs8k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_hand_counts(cfg):
    p = flops_glm.params(cfg)
    # q_a, q_b, kv_a, kv_b, o
    assert p["mla"] == (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                        + 5120 * 2048) == 21_757_952
    assert p["expert"] == p["shared"] == 3 * 2048 * 1536 == 9_437_184
    assert p["router"] == 131_072 and p["dense_ffn"] == 62_914_560
    assert p["head"] == 154_880 * 2048
    assert flops_glm.layer_counts(cfg) == (1, 6)
    # seven attentions, one dense FFN, six times router + shared + 4
    assert flops_glm.active_params(cfg) == (
        7 * 21_757_952 + 62_914_560
        + 6 * (131_072 + 5 * 9_437_184)) == 499_122_176
    # the file's own count: what is resident, norms and bias included
    resident = (7 * p["mla"] + p["dense_ffn"]
                + 6 * (p["router"] + 65 * p["expert"]) + 2 * p["head"]
                + 7 * (2 * 2048 + 768 + 512) + 2048 + 6 * 64)
    assert resident == cfg["parameters"]


def test_serving_operations_are_the_hand_counts(cfg):
    assert flops_glm.attention_flops_per_key(cfg) == 20 * 4 * 256
    # one generated token at position 99 (100 keys), nothing prefilled
    want = (2 * 499_122_176 + 2 * 154_880 * 2048 + 20_480 * 7 * 100)
    assert flops_glm.serve_flops_active(cfg, 0, 1, 0.0, 100.0) == want
    # a prefilled token passes no head
    assert (flops_glm.serve_flops_active(cfg, 1, 0, 100.0, 0.0)
            == want - 2 * 154_880 * 2048)


def test_kernel_needs_are_the_hand_counts(cfg):
    moe = flops_glm.moe_need(cfg, assignments=64 * 6, active_experts=300)
    assert moe["flops"] == 6 * 2048 * 1536 * 384
    assert moe["bytes"] == 300 * 3 * 2048 * 1536 * 2 + 384 * 2 * 2048 * 2
    assert flops_glm.latent_row_bytes(cfg) == 1280
    mla = flops_glm.mla_need(cfg, keys_attended=1000, kv_tokens_read=900)
    assert mla["flops"] == 20 * 2 * (576 + 512) * 1000
    assert mla["bytes"] == 1280 * 900


PROGRAM = """
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(_step_fn)/blocks_1/moe/moe_experts/sort" stack_frame_id=3}
  %ragged-dot-none.3 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged_latent_attention.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_fn)/blocks_1/attn/mla_attention/pallas_call" stack_frame_id=9}
  ROOT %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(_step_fn)/blocks_1/moe/shared/down/dot_general"}
  %fusion.9 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f9, metadata={op_name="jit(_step_fn)/blocks_1/not_moe_experts_at_all/x"}
}
"""


def test_scopes_are_read_from_the_compiled_programs_text():
    where = scope_reduce.scopes_of(PROGRAM, ("moe_experts", "mla_attention"))
    assert where == {"fusion.1": "moe_experts",
                     "ragged-dot-none.3": "moe_experts",
                     "ragged_latent_attention.7": "mla_attention"}

    def op(name, dur):
        return trace_reduce.Event("/device:TPU:0", trace_reduce.OPS_LINE,
                                  name, 0.0, dur)
    events = [op("fusion.1", 1e6), op("fusion.1", 2e6),
              op("ragged-dot-none.3 tpu_custom_call", 4e6),
              op("ragged_latent_attention.7 tpu_custom_call", 8e6),
              op("fusion.2", 16e6),
              trace_reduce.Event("/device:TPU:0", trace_reduce.MODULES_LINE,
                                 "jit__step_fn(1)", 0.0, 32e6)]
    got = scope_reduce.scope_seconds(events, PROGRAM,
                                     ("moe_experts", "mla_attention"))
    assert got["moe_experts"]["count"] == 3
    assert got["moe_experts"]["total_s"] == pytest.approx(7e-3)
    assert got["mla_attention"]["total_s"] == pytest.approx(8e-3)
    assert scope_reduce.device_steps({"trace": events}) == 1
    # no trace, or no program text: nothing to read, nothing raised
    assert scope_reduce.scope_seconds([], PROGRAM, ("moe_experts",)) is None
    assert scope_reduce.scope_seconds(events, None, ("moe_experts",)) is None
    assert scope_reduce.slice_counts({"trace_window_s": None}, ("x",)) is None


def test_an_altered_token_is_not_correct():
    line = last_line(run_cell(CELL, "--fault", "token_altered"))
    assert line["correct"] is False
    assert not line["checks"]["token_gap_max"]["ok"]


def test_the_fp8_control_is_not_correct():
    line = last_line(run_cell(CELL, "--control", "fp8"))
    assert line["correct"] is False
    assert not line["checks"]["token_gap_max"]["ok"]
    # nothing but the compared numbers failed: the run itself was sound
    assert all(v["ok"] for k, v in line["checks"].items()
               if not k.startswith("token_gap_"))
