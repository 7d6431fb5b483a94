"""99th percentile of the gaps between consecutive tokens (see
``itl_p50_ms``): of some ten thousand gaps a window, over a hundred lie
beyond it."""

from benchmark.readers.itl_p50_ms import token_gaps
from benchmark.stats import pct


def read(obs):
    p = pct(token_gaps(obs), 0.99)
    return None if p is None else 1e3 * p
