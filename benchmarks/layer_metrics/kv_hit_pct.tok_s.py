"""`kv_hit_pct` in a cell that holds no time to first token end to end:
the same share of prompt tokens served by the prefix cache, there moving
the tokens a second that the prefill it saves buys."""

from benchmarks.common import load_module

read = load_module("layer_metrics", "kv_hit_pct").read
