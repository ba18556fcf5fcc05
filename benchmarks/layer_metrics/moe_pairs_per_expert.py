"""Rows an active expert's grouped product gets a step: the (token,
expert) pairs of the traced slice's steps over the (layer, expert) pairs
that had a token (the program's `engine.step` fields `moe_assignments`
and `moe_active_experts`, summed over the slice's steps). On a v5e an
expert's three products stay bound by reading its weights below about
240 rows, so this says how far the step's batch is from paying for
them. None where the program does not count them."""

from benchmarks import scope_reduce


def read(obs):
    counts = scope_reduce.slice_counts(
        obs, ("moe_assignments", "moe_active_experts"))
    if not counts or not counts["moe_active_experts"]:
        return None
    return counts["moe_assignments"] / counts["moe_active_experts"]
