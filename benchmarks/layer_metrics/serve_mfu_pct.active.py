"""Operations the model needs for the tokens the window computed, over
the window times the chip's bf16 peak, for a model of which a token
passes only a part: the active parameters (attention, the shared and the
chosen routed experts, the router; the head once a generated token) and
attention in its published form. The counting functions are the
configuration's own (`flops` in its file)."""

import importlib


def read(obs):
    if obs.get("peaks") is None or "flops" not in obs["config"]:
        return None
    counts = importlib.import_module(obs["config"]["flops"])
    c = obs["counters"]
    need = counts.serve_flops_active(
        obs["config"], c["prefill"], c["generated"],
        obs["prefill_context_sum"], obs["generated_context_sum"])
    return 100.0 * need / (obs["window_s"] * obs["peaks"]["bf16_flops"])
