"""The chip's idle time between programs in a serving trace, put down to
what the host was doing, a decode tick's mean.

``span_reduce.reduce_spans`` gives chip 0's gaps between programs by the
innermost span of the program's thread over each instant (``idle``,
``by_span``: a span's own time, its children's excluded). This module
sorts those seconds into four groups of the serving thread's spans
(``nanodiloco_tpu/serve/engine.py``, ``scheduler.py``, ``server.py``):

- ``engine.launch_idle_ms_per_tick``: the own time of
  ``engine.decode_dispatch`` and ``engine.prefill_chunk``, the jitted
  calls' host time before the chip starts;
- ``engine.fetch_idle_ms_per_tick``: ``engine.wait`` (the chip's finish
  seen by the host), ``engine.fetch_tokens`` and ``engine.fetch_counts``
  (the copies back);
- ``engine.stage_idle_ms_per_tick``: ``engine.stage``,
  ``engine.stage_chunk``, ``engine.keys`` (the uploads and the key
  schedule's small programs);
- ``sched.host_idle_ms_per_tick``: every other span of the thread and
  no span (``span_reduce.NO_SPAN``), ``sched.idle`` (the loop asleep
  with no slot live) excepted.

Each is milliseconds of idle over the decode ticks of the traced window
(its ``engine.decode_dispatch`` spans), None where it holds none. The
four, the idle under ``sched.idle`` and the idle inside running
programs add up to the window's idle.

    python3 -m benchmark.host_idle [trace dir or file]

prints the four, the seconds of each part, and the share of the idle
between programs that no finer span than ``sched.tick`` covers.
Checked on hand-made intervals and a recorded trace:
``benchmark/tests/test_bench_host_idle.py``.
"""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":  # run as a file: the checkout, not this directory
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import span_reduce  # noqa: E402

IDLE = "sched.idle"
GROUPS = {
    "engine.launch_idle_ms_per_tick": ("engine.decode_dispatch", "engine.prefill_chunk"),
    "engine.fetch_idle_ms_per_tick": ("engine.wait", "engine.fetch_tokens",
                                      "engine.fetch_counts"),
    "engine.stage_idle_ms_per_tick": ("engine.stage", "engine.stage_chunk", "engine.keys"),
}
HOST = "sched.host_idle_ms_per_tick"
METRICS = (*GROUPS, HOST)
_GROUPED = {name for names in GROUPS.values() for name in names}


def split(reduced: dict) -> dict | None:
    """The four metrics and what they are made of, from one
    ``reduce_spans`` result; None where the window holds no decode
    dispatch."""
    ticks = reduced["spans"].get(span_reduce.DISPATCH, {}).get("count")
    if not ticks:
        return None
    idle = reduced["idle"]
    by_span = idle["by_span"]
    seconds = {metric: sum(by_span.get(n, 0.0) for n in names)
               for metric, names in GROUPS.items()}
    host = {n: s for n, s in by_span.items() if n not in _GROUPED and n != IDLE}
    seconds[HOST] = sum(host.values())
    unsplit = by_span.get(span_reduce.TICK, 0.0) + by_span.get(span_reduce.NO_SPAN, 0.0)
    return {
        "ticks": ticks,
        "ms_per_tick": {m: 1e3 * seconds[m] / ticks for m in METRICS},
        "seconds": {**seconds, IDLE: by_span.get(IDLE, 0.0),
                    "in_program": idle["in_program_s"]},
        "idle_s": idle["idle_s"],
        "between_s": idle["between_s"],
        "host_by_span": dict(sorted(host.items(), key=lambda kv: -kv[1])),
        "unsplit_share": unsplit / idle["between_s"] if idle["between_s"] else 0.0,
    }


def of_run(obs) -> dict | None:
    """``split`` of the trace this run recorded; None without one."""
    reduced = span_reduce.of_run(obs)
    return None if reduced is None else split(reduced)


def read(obs, metric: str) -> float | None:
    got = of_run(obs)
    return None if got is None else got["ms_per_tick"][metric]


def main(argv) -> int:
    where = argv[0] if argv else span_reduce.TRACE_ROOT
    path = where if os.path.isfile(where) else span_reduce.tr.find_xplane(where)
    if path is None:
        print(f"no *.xplane.pb under {where}", file=sys.stderr)
        return 1
    reduced = span_reduce.reduce_spans(path)
    got = None if reduced is None else split(reduced)
    if got is None:
        print(f"{path}: no decode tick on a device plane", file=sys.stderr)
        return 1
    print(json.dumps(got, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
