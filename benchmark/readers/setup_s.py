"""Process start to the measured window's first instant."""


def read(obs):
    return obs.get("window_start_s")
