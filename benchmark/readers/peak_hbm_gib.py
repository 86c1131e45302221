"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, in GiB."""


def read(obs):
    peak = obs["device"].get("memory_peak_bytes")
    return peak / 2**30 if peak else None
