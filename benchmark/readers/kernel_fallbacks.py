"""Dispatcher gates that declined a shape while the cell's programs were
traced (``ops.pallas.record_dispatch``): each is a kernel replaced by its jnp
body."""


def read(obs):
    return len(obs.get("fallbacks", ()))
