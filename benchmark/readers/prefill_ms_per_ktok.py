"""The engine's own clock over prefill chunks: seconds in prefill-chunk
dispatches inside the window per thousand prompt tokens they computed
(each dispatch counted at its bucket's width, pads included)."""


def read(obs):
    dev = obs.get("devtime") or {}
    secs = tokens = 0.0
    for key, n in dev.get("dispatches", {}).items():
        kind, bucket, _ = key.split(":", 2)
        if kind == "prefill_chunk":
            tokens += n * int(bucket)
            secs += dev["device_seconds"].get(key, 0.0)
    return 1e6 * secs / tokens if tokens else None
