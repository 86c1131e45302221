"""Process start to window start: imports, weights, compile or cache load,
warm-up, the correctness sample, the traffic's ramp."""


def read(obs):
    return obs["window"][0] - obs["t_process"]
