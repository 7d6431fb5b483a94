"""Device idle share from the profiler trace: 1 minus the union of the
leaf device operations' intervals over the traced window, on the worst
chip (``trace_reduce.py``)."""


def read(obs):
    tr = obs.get("trace")
    return None if not tr else 100.0 * tr["idle_share_worst"]
