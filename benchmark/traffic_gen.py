"""The one general traffic generator: a data file of parameters in,
a list of requests and their arrival plan out.

A mix is ``benchmark/traffic/<name>.json``. For a serving cell:

    {"arrival": {"kind": "closed", "clients": 32},
     "ramp_s": 5, "trace_s": 3,
     "cycle": 64, "shape_seed": 0,
     "prompt_tokens": {"median": 512, "sigma": 0.7, "min": 64, "max": 1536, "multiple": 64},
     "output_tokens": {"median": 128, "sigma": 0.7, "min": 16, "max": 512, "multiple": 16},
     "temperature": 0.0}

Every seed gets the same work in another order. The lengths are no
sample: a *cycle* of ``cycle`` (prompt, output) shapes is laid out at
the quantiles (i + 0.5) / cycle of the two log-normals (clipped and
rounded as the file says), outputs paired to prompts by a permutation
fixed by ``shape_seed``. The request list is cycle after cycle, each in
an order drawn from the run's ``--seed``; clients take requests from
the head of that one list. So any few hundred consecutive requests
hold nearly whole cycles, whatever the seed: the seed changes the
order and the token ids, never the mix. Token ids are uniform over the
vocabulary, from the seed, so no two prompts share a prefix.

The only arrival kind is the closed loop: ``clients`` callers, each
sending its next request when the last is answered. Open-loop arrivals
and shared prefixes come with the cells that need them and a chip run
that proves them (PERF.md, section 7).

No JAX here: the parent builds the plan, a child without JAX sends it.
"""

from __future__ import annotations

import random
import statistics

import numpy as np


def _quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles of a clipped, rounded log-normal."""
    nd = statistics.NormalDist()
    mult = int(spec.get("multiple", 1))
    out = []
    for i in range(n):
        x = spec["median"] * np.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        x = int(round(x / mult)) * mult
        out.append(int(min(max(x, spec["min"]), spec["max"])))
    return out


def cycle_shapes(mix: dict) -> list[tuple[int, int]]:
    """The cycle of (prompt, output) lengths: the same for every seed."""
    n = int(mix["cycle"])
    prompts = _quantile_lengths(mix["prompt_tokens"], n)
    outputs = _quantile_lengths(mix["output_tokens"], n)
    random.Random(int(mix.get("shape_seed", 0))).shuffle(outputs)
    return list(zip(prompts, outputs))


def build_requests(mix: dict, vocab_size: int, seed: int, count: int) -> list[dict]:
    """``count`` requests: whole cycles in seeded order, seeded token ids."""
    shapes = cycle_shapes(mix)
    order_rng = random.Random(seed)
    ids_rng = np.random.default_rng(seed)
    out: list[dict] = []
    while len(out) < count:
        cyc = list(shapes)
        order_rng.shuffle(cyc)
        for p, o in cyc:
            out.append({
                "token_ids": ids_rng.integers(0, vocab_size, p).tolist(),
                "max_new_tokens": int(o),
                "temperature": float(mix.get("temperature", 0.0)),
                "stop": False,
            })
    return out[:count]


def clients(mix: dict) -> int:
    """The closed loop's fixed concurrency."""
    arrival = mix["arrival"]
    if arrival["kind"] != "closed":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    return int(arrival["clients"])


def request_budget(mix: dict, seconds: float, est_per_s: float) -> int:
    """How many requests to make for a run: twice what the cell's file
    expects to complete in ramp + window, in whole cycles."""
    n = 2.0 * est_per_s * (float(mix.get("ramp_s", 0)) + seconds)
    cyc = int(mix["cycle"])
    return max(cyc, int(-(-n // cyc)) * cyc)
