"""Shared plumbing for the evidence-capture scripts (elastic_cost,
moe_evidence, longctx_demo, streaming_overlap, wire_quality).

Extracted round 5 (review finding: the preamble had been copy-pasted
verbatim four times): the sys.path bootstrap, the CPU pin, and the
append-a-JSON-line recorder live HERE so a fix to any of them cannot
silently diverge across scripts.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pin_cpu_unless(env_var: str, n_devices: int = 8) -> None:
    """Bootstrap imports and pin ``n_devices`` virtual CPU devices
    before any backend query: these scripts record CPU evidence (counts,
    losses, relative structure) by design. Setting ``<env_var>=1`` opts
    into the machine's accelerator instead."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import jax

    if os.environ.get(env_var) != "1":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_devices)


def make_recorder(out_path: str):
    """Returns ``record(dict)`` that timestamps, appends one JSON line
    to ``out_path``, and echoes it to stdout — the shared evidence
    artifact shape."""

    def record(rec: dict) -> None:
        rec = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), **rec}
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)

    return record
