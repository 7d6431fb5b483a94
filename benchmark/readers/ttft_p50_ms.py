"""Median of the server's own ``timing.ttft_s`` (submission to first
token, the server's clock) over the requests sent and answered inside
the window."""

from benchmark.stats import pct


def read(obs):
    vals = [r["timing"]["ttft_s"] for r in obs.get("requests") or ()
            if r["timing"].get("ttft_s") is not None]
    p = pct(vals, 0.5)
    return None if p is None else 1e3 * p
