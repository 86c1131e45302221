"""A number the driver observed directly (e.g. compile requests inside the
window)."""


def read(obs, key):
    return obs.get(key)
