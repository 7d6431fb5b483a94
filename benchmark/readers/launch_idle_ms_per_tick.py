"""Idle milliseconds a decode tick between programs while the serving
thread launches a program (the own time of ``engine.decode_dispatch``
and ``engine.prefill_chunk``), from the program's own spans in the
traced window (``host_idle.py``). None where it holds no decode tick."""

from benchmark import host_idle


def read(obs):
    return host_idle.read(obs, "engine.launch_idle_ms_per_tick")
