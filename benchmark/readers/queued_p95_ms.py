"""95th percentile of the server's ``timing.queued_s`` (submission to
a slot) over the requests sent and answered inside the window."""

from benchmark.stats import pct


def read(obs):
    p = pct([r["timing"]["queued_s"] for r in obs.get("requests") or ()], 0.95)
    return None if p is None else 1e3 * p
