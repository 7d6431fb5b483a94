"""Output tokens emitted inside the window, over its seconds: the
scheduler's cumulative ``tokens_out`` counter read at the window's two
ends, so every token of every stream counts where it is emitted and no
request has to end inside the window. The serving driver holds the
counter, in set-up, to the tokens its warm-up requests were answered
with."""


def read(obs):
    if not obs.get("tokens") or not obs.get("window_s"):
        return None
    return obs["tokens"] / obs["window_s"]
