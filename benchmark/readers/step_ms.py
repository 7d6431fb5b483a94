"""One inner step of the model: the window's median round time, less
the outer step's, over the round's inner steps."""

from benchmark.stats import median


def read(obs):
    rnd, sync = median(obs.get("round_s") or ()), median(obs.get("sync_s") or ())
    if rnd is None or sync is None:
        return None
    return 1e3 * (rnd - sync) / obs["inner_steps"]
