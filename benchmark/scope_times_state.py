"""Device time by the program's named scopes, for the scopes of the
sparse and linear attention layers: ``sparse_select``, ``sparse_attend``,
``kv_compress`` and ``linear_state`` stand INSIDE ``attention``, so the
accepted readers (``span_reduce.SCOPES``, which stays as it is) see
``attention`` and this one sees the finer name. The same leaf operations,
window and ``tf_op`` names as ``scope_times.by_scope``, whose procedure
this repeats with its own table (that file's table is the accepted
yardstick's and may not be edited).
"""

from __future__ import annotations

import functools

from benchmark import span_reduce
from benchmark import trace_reduce as tr

STATE_SCOPES = ("sparse_select", "sparse_attend", "kv_compress", "linear_state")
SCOPES = span_reduce.SCOPES + STATE_SCOPES


def scope_of(op_name: str) -> str | None:
    for word in reversed(span_reduce._WORD.findall(span_reduce._JIT.sub("", op_name))):
        if word in SCOPES:
            return word
    return None


@functools.lru_cache(maxsize=4)
def by_scope(path: str) -> dict | None:
    """{"leaf_s": seconds of all leaf operations in the window,
    "by_scope": {innermost scope: seconds}}; None where the trace holds
    no device operation that names a scope (a CPU trace)."""
    import jax

    names = span_reduce.op_names(path)
    marks, device = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if tr._DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == tr._OPS_LINE:
                    device.extend(tr.leaf_events(tr._events(ln)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                marks.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in ln.events if ev.name == tr.WINDOW)
    if not device:
        return None
    lo = min(m[0] for m in marks) if marks else min(e[0] for e in device)
    hi = max(m[1] for m in marks) if marks else max(e[1] for e in device)
    out: dict[str, float] = {}
    leaf = 0
    for s, e, op in device:
        d = max(0, min(e, hi) - max(s, lo))
        if d:
            leaf += d
            scope = scope_of(names.get(op, "")) or "(none)"
            out[scope] = out.get(scope, 0) + d
    if set(out) == {"(none)"}:
        return None
    return {"leaf_s": leaf / 1e9,
            "by_scope": {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}}


def of_run(obs) -> dict | None:
    if not obs.get("trace"):
        return None
    path = tr.find_xplane(span_reduce.TRACE_ROOT)
    return None if path is None else by_scope(path)


def seconds(obs, scopes) -> float | None:
    """Device seconds under ``scopes`` in the traced window; None where
    the trace names none of them (a program without these layers)."""
    got = of_run(obs)
    if not got or not any(s in got["by_scope"] for s in scopes):
        return None
    return sum(got["by_scope"].get(s, 0.0) for s in scopes)
