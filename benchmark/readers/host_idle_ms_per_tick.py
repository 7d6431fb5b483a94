"""Idle milliseconds a decode tick between programs under every other
span of the serving thread (the scheduler's steps, ``sched.tick``'s own
time) or under no span, the loop's sleep ``sched.idle`` excepted, from
the program's own spans in the traced window (``host_idle.py``). None
where it holds no decode tick."""

from benchmark import host_idle


def read(obs):
    return host_idle.read(obs, "sched.host_idle_ms_per_tick")
