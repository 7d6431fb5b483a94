"""The sparse read's share of the HBM roofline: the bytes of the chosen
rows and of the compressed keys scored in the traced programs
(``costs_state.sparse_read_bytes`` over the program's counters between
the trace's two ends) over the chip's peak bandwidth, over the device
time under ``sparse_select`` + ``sparse_attend`` in the same trace. The
count is of the work (rows up to the query in the chosen blocks, each
once), whatever implements the read."""

from types import SimpleNamespace

from benchmark import costs, costs_state, scope_times_state


def read(obs):
    c = obs.get("attn_traced") or {}
    secs = scope_times_state.seconds(obs, ("sparse_select", "sparse_attend"))
    if not secs or not c.get("sparse_rows_read"):
        return None
    need = costs_state.sparse_read_bytes(
        SimpleNamespace(**obs["model"]), c["sparse_rows_read"],
        c["sparse_compressed_rows"], obs["kv_itemsize"])
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / secs
