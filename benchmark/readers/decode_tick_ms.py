"""The engine's own per-program clock (``devtime_stats()``): seconds in
decode dispatches over their count, inside the window. A host clock
around a fenced dispatch: it includes the dispatch and the fetch of the
tokens, and is no device busy time."""


def decode_sum(obs, key):
    return sum(v for k, v in (obs.get("devtime") or {}).get(key, {}).items()
               if k.startswith("decode:"))


def read(obs):
    n = decode_sum(obs, "dispatches")
    return 1e3 * decode_sum(obs, "device_seconds") / n if n else None
