"""From a profiler trace to numbers: the one reduction every PR shares.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else. What it returns:

- ``window_s``: the traced window, the span of the harness's own
  ``WINDOW`` annotation on the host (the device events' span where
  that annotation is missing);
- per device plane, ``busy_s``: the union of the intervals in which a
  *leaf* operation ran, clipped to the window. A TPU's ``XLA Ops`` line
  nests: a ``while`` or a fusion's parent event spans its body, so a
  union over every event would call a whole scanned round busy. An
  event that holds another is a container and is left out, here and in
  the operations' ranking;
- ``device_ops``: the ten leaf operations with most summed time, over
  all chips. A TPU names an event by its whole HLO instruction; the
  name is cut to the instruction's own name and result type
  (``short_name``);
- ``idle_gaps``: the idle seconds inside the window (chip 0) in groups,
  the ten largest. A gap that lies inside an event of the ``XLA
  Modules`` line falls while a program runs (operations waiting on an
  asynchronous copy, a loop's own bookkeeping) and reads
  ``in_program:<module>``: nothing the host could have filled. A gap
  between programs is the host's: it reads as the harness annotation
  that overlaps it most (``labels``), else as the host event that does,
  else ``unattributed``.

The ``Async XLA Ops`` line (copies that run beside the compute) is not
counted as busy: the share is of the compute units' time.

Checked on a small recorded trace: ``benchmark/tests/test_trace_reduce.py``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench_window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
# host events that say nothing about what the host was doing
_DULL_HOST = ("$", "ThreadpoolListener", "ThunkExecutor")
_LONG_NS = 20_000_000     # host events of 20 ms and more


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``*.xplane.pb`` under ``trace_dir``, or None."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _events(line):
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def short_name(name: str, limit: int = 96) -> str:
    """``%fusion.5 = bf16[8,64]{1,0:T(8,128)} fusion(...)`` as
    ``fusion.5 bf16[8,64]``: the instruction's name and result type,
    layouts dropped. A name that is no HLO instruction stays whole."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:limit]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):  # the type ends at the first space outside brackets
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    kind = re.sub(r"\{[^{}]*\}", "", rest[:end])
    return f"{head.lstrip('%')} {kind}"[:limit]


def module_name(name: str) -> str:
    """``jit__round_step(6479687804092931308)`` as ``jit__round_step``."""
    return name.partition("(")[0]


def leaf_events(events):
    """Events that contain no other event of the same line. ``events``
    are (start, end, name); a child lies wholly inside its parent."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    is_leaf = [True] * len(events)
    stack: list[int] = []
    for i in order:
        start, end, _ = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1]:
            is_leaf[stack[-1]] = False
            stack.append(i)
        elif not stack:
            stack.append(i)
        # else: overlaps its neighbour without lying inside it (an
        # asynchronous copy or collective): a leaf of its own
    return [events[i] for i in order if is_leaf[i]]


def union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi], sorted."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def describe(path: str, limit: int = 6) -> list[str]:
    """Planes, lines and first events of a trace, for a human to read
    before trusting the reduction on a new device:
    ``print("\\n".join(describe(find_xplane("benchmark/out/trace/<cell>"))))``."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = [e.name for e in evs[:limit]]
            out.append(f"  line {line.name!r}: {len(evs)} events, first {names}")
    return out


def reduce_trace(path: str, labels: tuple[str, ...] = ()) -> dict | None:
    """The reduction described at the top; None where the trace holds no
    device plane with an operation in it (a CPU trace)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_lines: dict[str, list] = {}
    modules: dict[str, list] = {}
    host_events: list = []  # (start, end, name) of every host thread
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if _OPS_LINE in lines:
                evs = _events(lines[_OPS_LINE])
                if evs:
                    device_lines[plane.name] = leaf_events(evs)
                    modules[plane.name] = sorted(
                        _events(lines[_MODULES_LINE])) if _MODULES_LINE in lines else []
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_events.extend(_events(ln))
    if not device_lines:
        return None

    marks = [e for e in host_events if e[2] == WINDOW]
    if marks:
        lo, hi = min(m[0] for m in marks), max(m[1] for m in marks)
    else:
        lo = min(e[0] for evs in device_lines.values() for e in evs)
        hi = max(e[1] for evs in device_lines.values() for e in evs)
    window_ns = hi - lo

    busy_ns, op_ns, merged = {}, {}, {}
    for name, evs in device_lines.items():
        merged[name] = union(((s, e) for s, e, _ in evs), lo, hi)
        busy_ns[name] = sum(e - s for s, e in merged[name])
        for s, e, op in evs:
            d = _overlap(s, e, lo, hi)
            if d:
                op_ns[op] = op_ns.get(op, 0) + d

    # idle gaps of the first chip, by what the host was doing
    first = sorted(device_lines)[0]
    edges = [lo] + [t for seg in merged[first] for t in seg] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    mine = _Cover(e for e in host_events if e[2] in labels)
    others = _Cover(e for e in host_events
                    if e[2] not in labels and e[2] != WINDOW
                    and not e[2].startswith(_DULL_HOST))
    running = _Cover(modules[first])
    by_label: dict[str, int] = {}
    for g0, g1 in gaps:
        inside = running.covering(g0, g1)
        if inside:
            label = "in_program:" + module_name(inside)
        else:
            label = mine.best(g0, g1) or others.best(g0, g1) or "unattributed"
        by_label[label] = by_label.get(label, 0) + (g1 - g0)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window_ns / 1e9,
        "busy_s_by_device": {k: v / 1e9 for k, v in sorted(busy_ns.items())},
        "busy_s": sum(busy_ns.values()) / len(busy_ns) / 1e9,
        "idle_share_worst": 1.0 - min(busy_ns.values()) / window_ns,
        "device_ops": [[short_name(k), v] for k, v in top(op_ns)],
        "idle_gaps": top(by_label),
    }


class _Cover:
    """Which of a set of (start, end, name) events overlaps a gap most.
    Events shorter than ``_LONG_NS`` are found by bisection on their
    starts; the few longer ones are walked every time."""

    def __init__(self, events):
        evs = sorted(events)
        self.long = [e for e in evs if e[1] - e[0] >= _LONG_NS]
        self.short = [e for e in evs if e[1] - e[0] < _LONG_NS]
        self.starts = [e[0] for e in self.short]

    def covering(self, g0, g1):
        """Name of an event that holds all of [g0, g1], else None."""
        i = bisect.bisect_left(self.starts, g0 - _LONG_NS)
        candidates = list(self.long)
        while i < len(self.short) and self.short[i][0] <= g0:
            candidates.append(self.short[i])
            i += 1
        for s, e, name in candidates:
            if s <= g0 and g1 <= e:
                return name
        return None

    def best(self, g0, g1):
        """Name of the event that overlaps [g0, g1] most, None where
        none does. Of two that cover the gap alike the shorter wins:
        the innermost says more."""
        best, best_key = None, (0, 0)
        i = bisect.bisect_left(self.starts, g0 - _LONG_NS)
        candidates = list(self.long)
        while i < len(self.short) and self.short[i][0] < g1:
            candidates.append(self.short[i])
            i += 1
        for s, e, name in candidates:
            ov = _overlap(s, e, g0, g1)
            if ov and (ov, -(e - s)) > best_key:
                best, best_key = name, (ov, -(e - s))
        return best
