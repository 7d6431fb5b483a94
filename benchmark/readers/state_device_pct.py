"""Share of the device's leaf-operation time under ``linear_state``
(the linear layers' recurrence: the read-modify-write of the state and
its read-out; ``scope_times_state.py``). None for a program without
it."""

from benchmark import scope_times_state


def read(obs):
    secs = scope_times_state.seconds(obs, ("linear_state",))
    return None if secs is None else 100.0 * secs / scope_times_state.of_run(obs)["leaf_s"]
