"""Token-expert pairs routed to held experts over the held experts hit,
in the decode ticks inside the window (both summed over layers and
ticks, the program's counters): the rows a held expert's grouped product
sees a tick. The prefill chunks' counters are left out: a chunk of 512
tokens gives an expert some thirty rows, and a blend of the two would
move with the chunks' share of the programs, not with the load."""


def read(obs):
    ticks = (obs.get("moe") or {}).get("decode") or {}
    held, hit = ticks.get("moe_held_pairs", 0), ticks.get("moe_experts_hit", 0)
    return held / hit if hit else None
