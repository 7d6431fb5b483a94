"""Slots that hold a decoding stream (busy and past their prefill), as
the scheduler's ``stats()`` counts them: mean of the driver's readings,
two a second, inside the window."""

from benchmark.stats import mean


def read(obs):
    return mean(obs.get("slots_decoding") or ())
