"""The grouped products' share of the HBM roofline: the bytes of the
held experts hit in the traced programs (``costs_sparse.experts_bytes``
over the program's ``moe_experts_hit`` counter between the trace's two
ends) over the chip's peak bandwidth, over the device time under
``moe_experts`` in the same trace. Bound by bytes: at a few rows an
expert the products read each hit expert's weights once and do little
arithmetic on them."""

from types import SimpleNamespace

from benchmark import costs, costs_sparse, scope_times


def read(obs):
    got = scope_times.of_run(obs)
    hit = (obs.get("moe_traced") or {}).get("moe_experts_hit")
    secs = (got or {}).get("by_scope", {}).get("moe_experts")
    if not hit or not secs:
        return None
    need = costs_sparse.experts_bytes(SimpleNamespace(**obs["model"]), hit,
                                      obs["weight_itemsize"])
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / secs
