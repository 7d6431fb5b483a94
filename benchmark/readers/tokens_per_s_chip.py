"""Tokens of whole rounds completed in the window, over its seconds
and the chips used."""


def read(obs):
    if not obs.get("tokens") or not obs.get("window_s"):
        return None
    return obs["tokens"] / obs["window_s"] / obs["chips"]
