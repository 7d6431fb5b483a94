"""Device time by the program's named scopes for a traced TRAINING round
of a sparse model: ``scope_times.py``'s reduction (the same leaf
operations, window and ``tf_op`` names) over a table that also knows the
two scopes PR 32 added to the program, ``moe_aux`` (the balance term,
inside ``mlp``) and ``rope`` (building the rotary tables, inside
``attn_proj``). That file's table is the accepted yardstick's and stays
as it is; a later ``benchmark`` PR folds the two.

    python3 -m benchmark.scope_times_train [trace dir or file]

prints the scope table and how often each grouped-product kernel ran.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import sys

if __name__ == "__main__":  # run as a file: the checkout, not this directory
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import scope_times, span_reduce  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

SCOPES = scope_times.SCOPES + ("moe_aux", "rope")
KERNELS = scope_times.KERNELS


def scope_of(op_name: str) -> str | None:
    for prefix, scope in KERNELS.items():
        if op_name.startswith(prefix):
            return scope
    for word in reversed(span_reduce._WORD.findall(span_reduce._JIT.sub("", op_name))):
        if word in SCOPES:
            return word
    return None


@functools.lru_cache(maxsize=4)
def by_scope(path: str) -> dict | None:
    """{"leaf_s", "by_scope": {innermost scope: seconds}, "kernels":
    {grouped-product kernel name: executions in the window}}; None where
    the trace holds no device operation that names a scope."""
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
    out: dict[str, float] = collections.defaultdict(float)
    kernels: dict[str, int] = collections.Counter()
    leaf = 0
    for s, e, op in device:
        d = max(0, min(e, hi) - max(s, lo))
        if d:
            leaf += d
            name = names.get(op, "")
            out[scope_of(name) or "(none)"] += d
            if any(name.startswith(prefix) for prefix in KERNELS):
                kernels[name.split(".")[0]] += 1
    if set(out) == {"(none)"}:
        return None
    return {"leaf_s": leaf / 1e9, "kernels": dict(kernels),
            "by_scope": {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}}


def of_run(obs) -> dict | None:
    if not obs.get("trace"):
        return None
    path = tr.find_xplane(span_reduce.TRACE_ROOT)
    return None if path is None else by_scope(path)


if __name__ == "__main__":
    where = sys.argv[1] if len(sys.argv) > 1 else span_reduce.TRACE_ROOT
    found = where if os.path.isfile(where) else tr.find_xplane(where)
    print(json.dumps(by_scope(found) if found else None, indent=1))
