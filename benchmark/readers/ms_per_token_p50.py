"""Median over the requests sent and answered inside the window of the
caller's whole wait for the answer (the load generator's own clock)
over the tokens answered. The server streams nothing, so the whole wait
is what a caller feels; queueing and the first token's wait are in it."""

from benchmark.stats import pct


def read(obs):
    p = pct([(r["t_end"] - r["t_send"]) / r["n_tokens"]
             for r in obs.get("requests") or ()], 0.5)
    return None if p is None else 1e3 * p
