"""Process start to the window's start: cluster, spawn, backend, weights,
compile or cache load, warm-up, and the load's lead-in."""


def read(obs: dict) -> float:
    return obs["setup_s"]
