"""Model FLOP/s utilization: tokens/s/chip of the untraced rounds times
the FLOPs a token needs (``costs.train_flops_per_token``: causal
attention, the head counted once, recomputation not counted) over the
chip's bf16 peak for its exact ``device_kind``."""

from benchmark import costs
from benchmark.stats import median


def read(obs):
    rnd = median(obs.get("round_s") or ())
    if rnd is None or not obs.get("flops_per_token"):
        return None
    tokens_per_s_chip = obs["tokens_per_round"] / rnd / obs["chips"]
    peak = costs.peaks_for(obs["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * tokens_per_s_chip * obs["flops_per_token"] / peak
