"""``decode_hbm_pct`` for a stack of sparse and linear attention
layers: ``costs_state.decode_tick_bytes`` (the weights a token meets,
the chosen rows and compressed keys by the program's counters over the
window's ticks, the states read and written) over the chip's peak
bandwidth, over the engine's host-fenced tick time."""

from types import SimpleNamespace

from benchmark import costs, costs_state
from benchmark.readers import decode_tick_ms


def read(obs):
    tick_ms = decode_tick_ms.read(obs)
    ticks = decode_tick_ms.decode_sum(obs, "dispatches")
    c = (obs.get("attn") or {}).get("decode")
    if not tick_ms or not ticks or not c:
        return None
    need = costs_state.decode_tick_bytes(
        SimpleNamespace(**obs["model"]), c["sparse_rows_read"] / ticks,
        c["sparse_compressed_rows"] / ticks, c["state_updates"] / ticks,
        obs["weight_itemsize"], obs["kv_itemsize"])
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / (tick_ms / 1e3)
