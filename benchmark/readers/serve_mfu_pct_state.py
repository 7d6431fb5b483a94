"""Share of the chip's bf16 peak that the window's decoded tokens need:
``costs_state.flops`` (projections, SwiGLU and head a token; the chosen
rows' scores and values, the compressed keys' scores and the
recurrence by the program's counters over the window's ticks) over the
peak and the window's seconds. The whole step's share: it bounds any
later claim in the cell. A decode-only window: a few percent."""

from types import SimpleNamespace

from benchmark import costs, costs_state


def read(obs):
    c = (obs.get("attn") or {}).get("decode")
    if not c or not obs.get("window_s") or not obs.get("tokens"):
        return None
    need = costs_state.flops(SimpleNamespace(**obs["model"]), obs["tokens"],
                             c["sparse_rows_read"], c["sparse_compressed_rows"],
                             c["state_updates"])
    peak = costs.peaks_for(obs["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * need / obs["window_s"] / peak
