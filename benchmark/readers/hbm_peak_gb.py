"""Peak device memory on the fullest chip, ``memory_stats()
["peak_bytes_in_use"]`` after the window."""


def read(obs):
    b = obs.get("memory_peak_bytes")
    return None if not b else b / 1e9
