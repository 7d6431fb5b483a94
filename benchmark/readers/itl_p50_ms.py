"""Median gap between consecutive tokens of one answer: differences of
the server's ``timing.token_s`` (the scheduler's clock at each token's
delivery) over the requests sent and answered inside the window. None
where the server stamps no token."""

from benchmark.stats import pct


def token_gaps(obs):
    return [b - a for r in obs.get("requests") or ()
            for a, b in zip(r["timing"].get("token_s") or (),
                            (r["timing"].get("token_s") or ())[1:])]


def read(obs):
    p = pct(token_gaps(obs), 0.5)
    return None if p is None else 1e3 * p
