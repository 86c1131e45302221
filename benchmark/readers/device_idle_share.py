"""100 x (1 - union of device-op intervals / traced window), from the trace."""


def read(obs):
    tr = obs.get("trace")
    if tr is None or not tr.devices:
        return None
    return 100.0 * tr.idle_share()
