"""The grouped products' share of their roofline in a traced training
round: what the held pairs of the traced rounds (the program's
``moe_held_pairs`` between the trace's two ends) need at the least,
forward, recomputed forward and backward, as the larger of FLOPs over
the chip's bf16 peak and bytes over its HBM peak
(``costs_sparse_train.py``), over the device time under ``moe_experts``
in the same trace (the products with their gather and scatter-add)."""

from types import SimpleNamespace

from benchmark import costs, costs_sparse_train, scope_times_train


def read(obs):
    traced = obs.get("moe_traced") or {}
    got = scope_times_train.of_run(obs) if obs.get("moe_train") else None
    secs = (got or {}).get("by_scope", {}).get("moe_experts")
    if not traced.get("moe_held_pairs") or not secs:
        return None
    m, peaks = SimpleNamespace(**obs["model"]), costs.peaks_for(obs["device_kind"])
    flops = costs_sparse_train.experts_flops(m, traced["moe_held_pairs"])
    moved = costs_sparse_train.experts_bytes(
        m, traced["moe_held_pairs"], traced["layer_calls"], obs["experts_held"],
        obs["compute_itemsize"])
    need = max(flops / (peaks["bf16_tflops"] * 1e12), moved / (peaks["hbm_gb_per_s"] * 1e9))
    return 100.0 * need / secs
