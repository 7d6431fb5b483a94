"""Idle milliseconds a decode tick between programs while the serving
thread uploads a tick's or a chunk's inputs or makes a key schedule
(``engine.stage``, ``engine.stage_chunk``, ``engine.keys``), from the
program's own spans in the traced window (``host_idle.py``). None where
it holds no decode tick."""

from benchmark import host_idle


def read(obs):
    return host_idle.read(obs, "engine.stage_idle_ms_per_tick")
