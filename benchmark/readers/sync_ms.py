"""The outer step alone (pseudo-gradient all-reduce, Nesterov update,
workers reset): median of a few host-clock timings to
``block_until_ready``, taken after the window."""

from benchmark.stats import median


def read(obs):
    m = median(obs.get("sync_s") or ())
    return None if m is None else 1e3 * m
