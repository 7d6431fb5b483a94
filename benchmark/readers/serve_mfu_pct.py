"""Share of the chip's bf16 peak that the window's served tokens need:
matmul FLOPs of the prompt tokens prefilled and the output tokens
decoded inside the window (``costs_sparse.flops_per_token``: counted
from the program's ``moe_pairs`` and ``moe_held_pairs``, a prompt token
at the mix's mean causal context, an output token at a stream's mean
rows) over the peak and the window's seconds. The whole step's share:
it bounds any later claim in the cell."""

from types import SimpleNamespace

from benchmark import costs, costs_sparse


def read(obs):
    moe = obs.get("moe") or {}
    if not moe or not obs.get("window_s"):
        return None
    m = SimpleNamespace(**obs["model"])
    sparse = costs_sparse.layer_counts(m)[1]
    flops = 0.0
    for kind, rows in (("prefill_chunk", obs["prompt_context_rows"]),
                       ("decode", obs["kv_rows_per_stream"])):
        c = moe.get(kind) or {}
        tokens = c.get("moe_pairs", 0) / (m.num_experts_per_tok * sparse)
        if tokens:
            flops += tokens * costs_sparse.flops_per_token(
                m, c["moe_held_pairs"] / (tokens * sparse), rows)
    peak = costs.peaks_for(obs["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * flops / obs["window_s"] / peak
