"""Share of prompt tokens the prefix cache served over the window."""


def read(obs):
    c = obs["counters"]
    if not c.get("kv_prompt"):
        return None
    return 100.0 * c["kv_hit"] / c["kv_prompt"]
