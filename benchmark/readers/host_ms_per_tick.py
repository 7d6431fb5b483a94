"""What the host adds to a decode tick's cycle, from the program's own
spans in the traced window: over the ``sched.tick``s that hold a decode
dispatch, start to next start, less the time inside the device calls,
the token fetch and the idle sleep; a tick's mean (``span_reduce.py``).
None where the trace holds no such span (a program without them)."""

from benchmark import span_reduce


def read(obs):
    ticks = (span_reduce.of_run(obs) or {}).get("ticks")
    return ticks["host_ms"] if ticks else None
