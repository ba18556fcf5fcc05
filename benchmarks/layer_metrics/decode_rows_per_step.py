"""Tokens generated per engine step over the window: exact counts."""


def read(obs):
    c = obs["counters"]
    return c["generated"] / c["steps"] if c.get("steps") else None
