"""Share of the HBM roofline a decode tick reaches: the bytes a tick
must read (``costs.decode_tick_bytes``: layer weights and head once,
plus the K and V rows of the live tokens) over the chip's peak
bandwidth, over the engine's tick time. The tick time is the engine's
host-fenced clock (see ``decode_tick_ms``), so this under-reads the
kernels' own share by the dispatch and the fetch it includes. Live rows
are the window's mean count of decoding slots (``slots_decoding``)
times the rows a stream of the mix holds, averaged over its life and
over the mix's cycle (the driver's ``kv_rows_per_stream``)."""

from types import SimpleNamespace

from benchmark import costs
from benchmark.readers import decode_tick_ms, slots_decoding


def read(obs):
    tick_ms = decode_tick_ms.read(obs)
    streams = slots_decoding.read(obs)
    if not tick_ms or not streams:
        return None
    need = costs.decode_tick_bytes(
        SimpleNamespace(**obs["model"]), streams * obs["kv_rows_per_stream"],
        obs["weight_itemsize"], obs["kv_itemsize"])
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / (tick_ms / 1e3)
