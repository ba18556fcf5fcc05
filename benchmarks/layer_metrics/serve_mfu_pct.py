"""Operations the model requires for the tokens the window computed,
over the window times the chip's bf16 peak."""

from benchmarks import flops


def read(obs):
    if obs.get("peaks") is None:
        return None
    c = obs["counters"]
    need = flops.serve_flops(
        obs["config"], c["prefill"], c["generated"],
        obs["prefill_context_sum"], obs["generated_context_sum"])
    return 100.0 * need / (obs["window_s"] * obs["peaks"]["bf16_flops"])
