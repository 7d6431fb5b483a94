"""95th percentile over the requests sent and answered inside the
window of (client-side total - ttft_s) / (tokens - 1): a per-request
mean gap between tokens, not a gap's tail (the server streams nothing
and stamps no token)."""

from benchmark.stats import pct


def read(obs):
    vals = [(r["t_end"] - r["t_send"] - r["timing"]["ttft_s"]) / (r["n_tokens"] - 1)
            for r in obs.get("requests") or ()
            if r["n_tokens"] > 1 and r["timing"].get("ttft_s") is not None]
    p = pct(vals, 0.95)
    return None if p is None else 1e3 * p
