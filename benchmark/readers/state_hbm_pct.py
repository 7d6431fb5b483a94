"""The state update's share of the HBM roofline: every update of the
traced programs reads one row's float32 state and writes it back
(``costs_state.state_rw_bytes`` over the program's ``state_updates``
between the trace's two ends), over the chip's peak bandwidth, over the
device time under ``linear_state`` in the same trace."""

from types import SimpleNamespace

from benchmark import costs, costs_state, scope_times_state


def read(obs):
    updates = (obs.get("attn_traced") or {}).get("state_updates")
    secs = scope_times_state.seconds(obs, ("linear_state",))
    if not secs or not updates:
        return None
    need = costs_state.state_rw_bytes(SimpleNamespace(**obs["model"]), updates)
    peak = costs.peaks_for(obs["device_kind"])["hbm_gb_per_s"] * 1e9
    return 100.0 * (need / peak) / secs
