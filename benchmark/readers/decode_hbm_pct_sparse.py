"""``decode_hbm_pct`` for a sparse model with window and full layers:
``costs_sparse.decode_tick_bytes`` (the held experts HIT a tick, from
the program's counter over the window's ticks; a sliding layer's rows
capped at its window) over the chip's peak bandwidth, over the engine's
host-fenced tick time."""

from types import SimpleNamespace

from benchmark import costs, costs_sparse
from benchmark.readers import decode_tick_ms, slots_decoding


def read(obs):
    tick_ms, streams = decode_tick_ms.read(obs), slots_decoding.read(obs)
    ticks = decode_tick_ms.decode_sum(obs, "dispatches")
    hit = ((obs.get("moe") or {}).get("decode") or {}).get("moe_experts_hit")
    if not tick_ms or not streams or not ticks or hit is None:
        return None
    m = SimpleNamespace(**obs["model"])
    sparse = costs_sparse.layer_counts(m)[1]
    need = costs_sparse.decode_tick_bytes(
        m, streams, obs["kv_rows_per_stream"], hit / ticks / sparse,
        obs["weight_itemsize"], obs["kv_itemsize"])
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / (tick_ms / 1e3)
