"""A program counter's increase over the window."""


def read(obs, key):
    c = obs.get("counters") or {}
    return c.get(key)
