"""Idle milliseconds a decode tick between programs while the serving
thread waits for the chip or copies its outputs back (``engine.wait``,
``engine.fetch_tokens``, ``engine.fetch_counts``), from the program's
own spans in the traced window (``host_idle.py``). None where it holds
no decode tick."""

from benchmark import host_idle


def read(obs):
    return host_idle.read(obs, "engine.fetch_idle_ms_per_tick")
