"""Share of the device's leaf-operation time under the expert layer's
scopes ``moe_route`` (router, top-k, sort), ``moe_experts`` (the
grouped products with their gather and combine) and ``moe_shared``
(``scope_times.py``). None for a program without them."""

from benchmark import scope_times

SCOPES = ("moe_route", "moe_experts", "moe_shared")


def read(obs):
    got = scope_times.of_run(obs)
    if not got or not any(s in got["by_scope"] for s in SCOPES):
        return None
    return 100.0 * sum(got["by_scope"].get(s, 0.0) for s in SCOPES) / got["leaf_s"]
