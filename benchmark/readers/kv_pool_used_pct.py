"""Share of the paged pool's blocks that requests hold (the engine
takes a request's blocks for its prompt and all the tokens it asked
for when it admits it): mean of the driver's readings of the block
pool, two a second, inside the window."""

from benchmark.stats import mean


def read(obs):
    m = mean(obs.get("pool_used_share") or ())
    return None if m is None else 100.0 * m
