"""Share of the device's leaf-operation time in a traced training round
under the expert layer's scopes ``moe_route`` (router, top-k, sort),
``moe_experts`` (the grouped products with their gather and scatter-add)
and ``moe_aux`` (the balance term), forward, recomputed and backward
(``scope_times_train.py``). None for a run that trained no sparse
layer."""

from benchmark import scope_times_train

SCOPES = ("moe_route", "moe_experts", "moe_aux")


def read(obs):
    got = scope_times_train.of_run(obs) if obs.get("moe_train") else None
    if not got or not any(s in got["by_scope"] for s in SCOPES):
        return None
    return 100.0 * sum(got["by_scope"].get(s, 0.0) for s in SCOPES) / got["leaf_s"]
