"""From a profiler trace to the PROGRAM's own spans and scopes.

``trace_reduce.py`` reduces a trace to what the device did (busy union,
idle share, top operations, idle gaps by the harness's annotations).
This module reads the same file for what the program says about itself
(PERF.md, section 3), with that module's window rule, leaf rule and
union, by import:

- **spans**: a host event with a ``layer`` stat is one of the program's
  ``trace_span``s (``nanodiloco_tpu/obs/tracer.py``); no list of names
  is needed to tell them from the runtime's own events. Per name: count,
  total and self time inside the window. A span's self time is its
  duration less its children's; spans nest by containment on a thread.
- **ticks**: over the ``sched.tick``s of the window that hold an
  ``engine.decode_dispatch``, the time from a tick's start to the next
  tick's start less the time inside ``NOT_HOST`` spans (the two device
  calls, the token fetch that waits for the device, the idle sleep):
  what the host adds to a tick's cycle.
- **idle**: chip 0's idle seconds inside the window, split into gaps
  inside a running program (``XLA Modules``) and gaps between programs;
  the latter by the innermost program span over them, on the thread that
  carries most of the program's span time.
- **scopes**: per ``jax.named_scope`` of the program (``SCOPES``), the
  summed time of leaf operations on ``XLA Ops``. The share's base is the
  sum of all leaf operations' time, so the scopes and ``unscoped`` add
  up to it (it is the busy union where no two leaves overlap).

Where the scope is: JAX writes the scope stack into every operation's
``op_name``; the TPU runtime keeps it as the ``tf_op`` stat of the
event's *metadata* (beside ``flops``, ``bytes_accessed``, ``source``),
not on the event and not in its name. ``jax.profiler.ProfileData`` shows
an event's own stats only, so ``op_names`` reads those few fields of the
file itself, in protobuf wire format (XSpace.planes.event_metadata).
A CPU trace carries no ``tf_op``: only the chip shows scopes.

    python3 -m benchmark.span_reduce [--describe] [trace dir or file]

prints the reduction (or what the file holds, to read by hand before
trusting the reduction on a new runtime). Checked on recorded traces:
``benchmark/tests/test_bench_span_reduce.py``.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys

if __name__ == "__main__":  # run as a file: the checkout, not this directory
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import trace_reduce as tr  # noqa: E402

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out", "trace")
# the program's named scopes (models/llama.py, models/generate.py,
# ops/fused_ce.py, parallel/diloco.py)
SCOPES = ("embed", "norm", "attn_proj", "attention", "mlp", "head", "loss",
          "inner_opt", "outer", "kv_write", "kv_gather", "sample", "layer_scan")
TICK, DISPATCH = "sched.tick", "engine.decode_dispatch"
NOT_HOST = (DISPATCH, "engine.fetch_tokens", "engine.prefill_chunk", "sched.idle")
NO_SPAN = "(no span)"

_JIT = re.compile(r"p?jit\([^()]*\)")  # jit(norm) is a function, not a scope
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def scope_of(op_name: str) -> str | None:
    """The innermost of the program's scopes in an ``op_name`` such as
    ``jit(f)/while/body/transpose(jvp(attention))/dot_general``."""
    for word in reversed(_WORD.findall(_JIT.sub("", op_name))):
        if word in SCOPES:
            return word
    return None


# -- the event metadata's stats, from the file ---------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a slice of ``buf`` for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_names(path: str) -> dict[str, str]:
    """Device event name (the whole HLO instruction) -> its ``tf_op``
    stat, JAX's ``op_name``. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value = 2);
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5; XStatMetadata.id = 1, .name = 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, tf_op = "", [], None
        for pf, value in _fields(plane):
            if pf == 2:
                name = str(value, "utf8")
            elif pf == 4:
                events.append(value)
            elif pf == 5:
                meta = dict(_fields(dict(_fields(value))[2]))
                if str(meta.get(2, b""), "utf8") == "tf_op":
                    tf_op = meta.get(1, 0)
        if tf_op is None or not tr._DEVICE_PLANE.match(name):
            continue
        for entry in events:
            ev_name = op = None
            for mf, value in _fields(dict(_fields(entry))[2]):
                if mf == 2:
                    ev_name = str(value, "utf8")
                elif mf == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) == tf_op and 5 in stat:
                        op = str(stat[5], "utf8")
            if ev_name and op:
                out.setdefault(ev_name, op)
    return out


# -- spans ---------------------------------------------------------------


def self_segments(spans):
    """``spans`` are (start, end, name) of ONE thread, nested by
    containment. Returns disjoint (start, end, name) pieces, sorted:
    every instant under a span belongs to the innermost span over it."""
    out, stack = [], []  # stack of [end, name, cursor]

    def close(item):
        end, name, cursor = item
        if end > cursor:
            out.append((cursor, end, name))

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[2], start, top[1]))
            top[2] = max(top[2], min(end, top[0]))
        stack.append([end, name, start])
    while stack:
        close(stack.pop())
    return sorted(out)


def tick_host(spans):
    """(ticks counted, mean host seconds a tick) over the ticks that
    hold a decode dispatch and have a successor; None where there is
    none. ``spans`` are (start, end, name) of the program's thread."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    ticks = [s for s in spans if s[2] == TICK]
    host = []
    for (t0, t1, _), (n0, _, _) in zip(ticks, ticks[1:]):
        inside = spans[bisect.bisect_left(starts, t0):bisect.bisect_left(starts, n0)]
        if any(s[2] == DISPATCH and s[0] < t1 for s in inside):
            host.append((n0 - t0) - sum(e - s for s, e, nm in inside if nm in NOT_HOST))
    return (len(host), sum(host) / len(host) / 1e9) if host else None


def attribute(gaps, segments):
    """Seconds of ``gaps`` [(start, end)] by the name of the segment of
    ``segments`` (disjoint, sorted) over them; the rest is ``NO_SPAN``."""
    ends = [s[1] for s in segments]
    out: dict[str, int] = {}
    for g0, g1 in gaps:
        left = g1 - g0
        i = bisect.bisect_right(ends, g0)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            ov = min(e, g1) - max(s, g0)
            out[name] = out.get(name, 0) + ov
            left -= ov
            i += 1
        if left:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + left
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def _clip(s, e, lo, hi):
    return max(0, min(e, hi) - max(s, lo))


def scope_times(device, names, lo, hi) -> dict | None:
    """Leaf operations' seconds inside [lo, hi] by the program's named
    scopes. ``device`` maps a plane to its leaf events, ``names`` an
    event's name to its ``op_name``. None where no operation names a
    scope: a program without them, or a CPU trace."""
    by_scope: dict[str, int] = {}
    loose: dict[str, int] = {}
    for evs in device.values():
        for s, e, op in evs:
            d = _clip(s, e, lo, hi)
            if d:
                scope = scope_of(names.get(op, ""))
                into, key = (loose, op) if scope is None else (by_scope, scope)
                into[key] = into.get(key, 0) + d
    if not by_scope:
        return None
    unscoped = sum(loose.values())
    return {
        "leaf_s": (sum(by_scope.values()) + unscoped) / 1e9,
        "by_scope": {k: v / 1e9 for k, v in
                     sorted(by_scope.items(), key=lambda kv: -kv[1])},
        "unscoped_s": unscoped / 1e9,
        "unscoped_ops": [
            [tr.short_name(op), d / 1e9, names.get(op, "")[-80:]]
            for op, d in sorted(loose.items(), key=lambda kv: -kv[1])[:10]],
    }


@functools.lru_cache(maxsize=4)
def reduce_spans(path: str) -> dict | None:
    """The reduction described at the top; None where the trace holds
    no device plane with an operation in it (a CPU trace)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    modules: dict[str, list] = {}
    threads: list[list] = []  # per host line, its program spans
    layers: dict[str, str] = {}
    marks = []
    for plane in data.planes:
        if tr._DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            evs = tr._events(lines[tr._OPS_LINE]) if tr._OPS_LINE in lines else []
            if evs:
                device[plane.name] = tr.leaf_events(evs)
                modules[plane.name] = sorted(
                    tr._events(lines[tr._MODULES_LINE])
                ) if tr._MODULES_LINE in lines else []
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                mine = []
                for ev in ln.events:
                    if ev.name == tr.WINDOW:
                        marks.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        continue
                    layer = next((v for k, v in ev.stats if k == "layer"), None)
                    if layer is not None:
                        mine.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                        layers[ev.name] = str(layer)
                if mine:
                    threads.append(mine)
    if not device:
        return None
    if marks:
        lo, hi = min(m[0] for m in marks), max(m[1] for m in marks)
    else:
        lo = min(e[0] for evs in device.values() for e in evs)
        hi = max(e[1] for evs in device.values() for e in evs)

    # spans: count, total and self time inside the window
    spans: dict[str, dict] = {}
    segments = [self_segments(t) for t in threads]
    for mine, segs in zip(threads, segments):
        for s, e, name in mine:
            d = _clip(s, e, lo, hi)
            if d:
                row = spans.setdefault(name, {"layer": layers[name], "count": 0,
                                              "total_s": 0.0, "self_s": 0.0})
                row["count"] += 1
                row["total_s"] += d / 1e9
        for s, e, name in segs:
            if name in spans:
                spans[name]["self_s"] += _clip(s, e, lo, hi) / 1e9
    # the program's thread: the one with most span time in the window
    main = max(range(len(threads)), default=None, key=lambda i: sum(
        _clip(s, e, lo, hi) for s, e, _ in segments[i]))
    ticks = tick_host([s for s in threads[main] if lo <= s[0] and s[1] <= hi]
                      ) if main is not None else None

    # chip 0's idle gaps: inside a program, or between programs by span
    first = sorted(device)[0]
    merged = tr.union(((s, e) for s, e, _ in device[first]), lo, hi)
    edges = [lo] + [t for seg in merged for t in seg] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    mods = modules[first]
    mod_starts = [m[0] for m in mods]
    between = []
    for g0, g1 in gaps:
        i = bisect.bisect_right(mod_starts, g0) - 1
        if not (i >= 0 and g1 <= mods[i][1]):
            between.append((g0, g1))
    idle_s = sum(g1 - g0 for g0, g1 in gaps) / 1e9
    between_s = sum(g1 - g0 for g0, g1 in between) / 1e9

    return {
        "window_s": (hi - lo) / 1e9,
        "spans": dict(sorted(spans.items(), key=lambda kv: -kv[1]["self_s"])),
        "ticks": None if ticks is None else
        {"count": ticks[0], "host_ms": 1e3 * ticks[1]},
        "idle": {"idle_s": idle_s, "in_program_s": idle_s - between_s,
                 "between_s": between_s,
                 "by_span": attribute(
                     between, segments[main] if main is not None else [])},
        "scopes": scope_times(device, op_names(path), lo, hi),
    }


def of_run(obs) -> dict | None:
    """The reduction of the trace this run recorded (the newest under
    ``benchmark/out/trace``), None where the harness's own reduction
    found no device plane in it."""
    if not obs.get("trace"):
        return None
    path = tr.find_xplane(TRACE_ROOT)
    return None if path is None else reduce_spans(path)


def scope_pct(obs, scopes) -> float | None:
    """Share, in percent, of the leaf operations' time under ``scopes``
    (``None``: under none of the program's); None where the trace names
    no scope at all (a program without them)."""
    got = (of_run(obs) or {}).get("scopes")
    if not got:
        return None
    secs = (got["unscoped_s"] if scopes is None
            else sum(got["by_scope"].get(s, 0.0) for s in scopes))
    return 100.0 * secs / got["leaf_s"]


def describe(path: str, limit: int = 6) -> list[str]:
    """Planes, lines and each line's longest events with each event's
    stats beside its name, and for a device operation its metadata's
    ``tf_op``: where a span's ``layer`` and an operation's scope are, to
    be read by hand before trusting the reduction on a new runtime."""
    import jax

    names = op_names(path)
    out = [f"{len(names)} device operations carry a tf_op stat on their metadata"]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events, the longest:")
            for ev in sorted(evs, key=lambda e: -e.duration_ns)[:limit]:
                stats = {k: v for k, v in ev.stats}
                if ev.name in names:
                    stats["metadata.tf_op"] = names[ev.name]
                out.append(f"    {tr.short_name(ev.name)}  {stats}")
    return out


def main(argv) -> int:
    args = [a for a in argv if a != "--describe"]
    where = args[0] if args else TRACE_ROOT
    path = where if os.path.isfile(where) else tr.find_xplane(where)
    if path is None:
        print(f"no *.xplane.pb under {where}", file=sys.stderr)
        return 1
    if len(args) != len(argv):
        print("\n".join(describe(path)))
    else:
        print(json.dumps(reduce_spans(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
