"""Operations the steps of the window require (forward and backward,
recomputation not counted) over the window times the chip's bf16 peak."""

from benchmarks import flops


def read(obs):
    if obs.get("peaks") is None:
        return None
    mix = obs["traffic"]
    need = obs["steps"] * flops.train_step_flops(
        obs["config"], mix["batch"], mix["seq"])
    return 100.0 * need / (obs["window_s"] * obs["peaks"]["bf16_flops"])
