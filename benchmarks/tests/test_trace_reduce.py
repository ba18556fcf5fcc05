"""The reduction from a trace to numbers, on a hand-built event list with
overlapping intervals, and the operation counts against hand-worked
numbers for GPT-2 medium."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, trace_reduce as tr  # noqa: E402
from benchmarks.trace_reduce import Event  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
TRACE = [
    # device 0: two steps; ops overlap inside the first
    Event(D0, MODS, "jit__step_fn(1)", 0, 100e6),
    Event(D0, MODS, "jit__step_fn(1)", 150e6, 80e6),
    Event(D0, OPS, "fusion.1", 0, 40e6),
    Event(D0, OPS, "attn.3 tpu_custom_call", 30e6, 50e6),      # overlaps
    Event(D0, OPS, "fusion.1", 90e6, 10e6),
    Event(D0, OPS, "attn.3 tpu_custom_call", 150e6, 60e6),
    Event(D0, OPS, "copy.2", 160e6, 10e6),                     # inside
    Event(D0, OPS, "fusion.1", 220e6, 10e6),
    # device 1: half as busy
    Event(D1, OPS, "fusion.1", 0, 85e6),
]


def test_union_counts_an_overlap_once():
    assert tr.union_ns([(0, 40), (30, 80), (90, 100)]) == 90
    assert tr.union_ns([(0, 10), (2, 3)]) == 10
    assert tr.union_ns([]) == 0


def test_busy_is_the_union_averaged_over_the_chips():
    # device 0: [0, 80) + [90, 100) + [150, 210) + [220, 230) = 160 ms
    assert tr.busy_seconds(TRACE) == pytest.approx((0.160 + 0.085) / 2)
    assert tr.busy_seconds([e for e in TRACE if e.plane == D0]) == \
        pytest.approx(0.160)
    assert tr.busy_seconds([]) == 0.0


def test_the_window_is_the_trace_s_own_extent():
    # first operation's start to the last one's end, over both devices
    assert tr.extent_seconds(TRACE) == pytest.approx(0.230)
    assert tr.extent_seconds([e for e in TRACE if e.plane == D1]) == \
        pytest.approx(0.085)
    assert tr.extent_seconds([e for e in TRACE if e.line == MODS]) == 0.0
    # busy is a union inside the extent: the share needs no clamp
    assert tr.busy_seconds(TRACE) <= tr.extent_seconds(TRACE)


def _slice(monkeypatch, events, asked, started, stopped, returned):
    from benchmarks import tracing
    monkeypatch.setattr(tr, "find_xplane", lambda d: "x")
    monkeypatch.setattr(tr, "load_xplane", lambda path, prefix: events)
    s = tracing.TraceSlice("/nonexistent/trace", tr.DEVICE_PREFIX)
    s.asked, s.started, s.stopped, s.returned = (asked, started, stopped,
                                                 returned)
    return s


def test_operations_past_the_stop_stamp_widen_the_window(monkeypatch):
    # the host stamped 0.20 s between the two calls, but operations went
    # on while stop_trace collected: 0.23 s of trace, 0.16 s busy. The
    # share is of the trace's extent and is reported as it is
    d0 = [e for e in TRACE if e.plane == D0]
    got = _slice(monkeypatch, d0, 10.0, 10.01, 10.21, 10.26).reduce()
    assert got["trace_window_s"] == pytest.approx(0.230)
    assert got["busy_s"] == pytest.approx(0.160)
    assert got["busy_s"] > 0.75 * (10.21 - 10.01)   # what a clamp hid
    from benchmarks import tracing
    assert tracing.idle_pct(got) == pytest.approx(100 * (1 - 0.160 / 0.230))


@pytest.mark.parametrize("stamps", [
    (10.0, 10.01, 10.11, 10.12),    # the trace outlasts the profiler
    (10.0, 10.01, 11.01, 11.02),    # the trace covers a fifth of it
])
def test_a_trace_that_does_not_fit_the_host_s_stamps_is_refused(
        monkeypatch, stamps):
    d0 = [e for e in TRACE if e.plane == D0]
    with pytest.raises(SystemExit):
        _slice(monkeypatch, d0, *stamps).reduce()


def test_a_device_trace_with_no_operation_is_refused(monkeypatch):
    monkeypatch.setattr(tr, "describe_xplane", lambda path: [])
    with pytest.raises(SystemExit):
        _slice(monkeypatch, [], 10.0, 10.01, 10.21, 10.26).reduce()


def test_per_program_and_per_kernel_sums():
    progs = tr.matching(tr.program_sums(TRACE), "step_fn")
    assert progs == {"jit__step_fn(1)": {
        "count": 2, "total_s": pytest.approx(0.180),
        "median_s": pytest.approx(0.090)}}
    kernels = tr.matching(tr.op_sums(TRACE), "tpu_custom_call")
    assert kernels["attn.3 tpu_custom_call"]["count"] == 2
    assert kernels["attn.3 tpu_custom_call"]["total_s"] == \
        pytest.approx(0.110)
    assert tr.top_ops(TRACE, 1, merge=str) == [["fusion.1",
                                                 pytest.approx(0.145)]]
    # an operation's calls from every layer come under one name
    more = TRACE + [Event(D0, OPS, "attn.4 tpu_custom_call", 300e6, 50e6)]
    assert tr.top_ops(more, 2) == [
        ["attn.* tpu_custom_call", pytest.approx(0.160)],
        ["fusion.*", pytest.approx(0.145)]]


def test_idle_gaps_are_named_by_their_neighbours():
    gaps = dict(tr.idle_gaps(TRACE))
    assert gaps["after fusion.1 before attn.3 tpu_custom_call"] == \
        pytest.approx(0.050)
    assert gaps["after attn.3 tpu_custom_call before fusion.1"] == \
        pytest.approx(0.020)


def test_an_op_event_is_named_by_its_result_and_its_kernel():
    hlo = ('%attn.100 = bf16[128,1024,64]{2,1,0} custom-call(bf16[128] %x), '
           'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_name(hlo) == "attn.100 tpu_custom_call"
    assert tr.short_name("%fusion.3 = bf16[8] fusion(%a), kind=kLoop") == \
        "fusion.3"
    assert tr.short_name("jit__step_fn(123)") == "jit__step_fn(123)"


MEDIUM = {"n_embd": 1024, "n_inner": 4096, "vocab_size": 50257,
          "n_layer": 24, "n_head": 16}


def test_parameter_count_of_gpt2_medium():
    p = flops.causal_lm_params(MEDIUM)
    assert p["embed"] == 50257 * 1024 == 51_463_168
    # a layer: 4 x (1024^2 + 1024) + 2 x 1024 x 4096 + 4096 + 1024 + 4 x 1024
    assert p["layers"] == 24 * 12_596_224
    assert p["total"] == 353_774_592


def test_training_step_of_gpt2_medium_is_18_6_teraflop():
    # 6 x 8192 tokens x 353,774,592 = 17.389e12
    # 6 x 8 x 1024^2 x 1024 x 24 = 1.237e12
    got = flops.train_step_flops(MEDIUM, 8, 1024)
    assert got == pytest.approx(17.389e12 + 1.237e12, rel=1e-3)


def test_flash_attention_counts_the_causal_half():
    f = flops.flash_flops(8, 1024, 16, 64)
    assert f["fwd"] == 2 * 8 * 1024 * 1024 * 64 * 16 == 17_179_869_184
    assert f["bwd"] == 2 * f["fwd"]
    b = flops.flash_bytes(8, 1024, 16, 64)
    assert b["fwd"] == 4 * 8 * 1024 * 16 * 64 * 2 == 67_108_864
    assert b["bwd"] == 2 * b["fwd"]


def test_serving_counts_the_head_once_a_generated_token():
    p = flops.causal_lm_params(MEDIUM)
    body, head = 2.0 * (p["layers"] + p["final_ln"]), 2.0 * p["embed"]
    # one generated token at position 99 (100 keys), nothing prefilled
    got = flops.serve_flops(MEDIUM, 0, 1, 0.0, 100.0)
    assert got == pytest.approx(body + head + 4 * 1024 * 24 * 100)
    # ten prompt tokens computed at positions 0..9 (55 keys): no head
    got = flops.serve_flops(MEDIUM, 10, 0, 55.0, 0.0)
    assert got == pytest.approx(10 * body + 4 * 1024 * 24 * 55)
