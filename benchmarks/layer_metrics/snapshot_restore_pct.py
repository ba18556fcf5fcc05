"""Share of the traced slice's prompt tokens that a restored state
snapshot let the engine skip: `engine.step`'s `snapshot_tokens_skipped`
(the depth of each admission's hit over recurrent state, which is the
boundary its slot was restored to) over that plus `chunk_tokens` (the
prompt tokens the slice's steps computed), summed over the slice's
steps. Over a layout with state every prefix hit is such a restore, so
over a whole window it agrees with `kv_hit_pct.tok_s`; this one reads
the cache's own count of what the snapshots saved."""

from benchmarks import scope_reduce


def read(obs):
    counts = scope_reduce.slice_counts(
        obs, ("snapshot_tokens_skipped", "chunk_tokens"))
    if not counts:
        return None
    total = counts["snapshot_tokens_skipped"] + counts["chunk_tokens"]
    return 100.0 * counts["snapshot_tokens_skipped"] / total if total else None
