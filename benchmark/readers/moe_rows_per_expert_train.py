"""Token-expert pairs routed to held experts over the held experts, a
sparse-layer call, in the window's rounds (the program's counters
summed over layers, inner steps and rounds): the rows a held expert's
grouped product sees a step. None for a run that trained no sparse
layer."""


def read(obs):
    got = obs.get("moe_train") or {}
    calls = got.get("layer_calls", 0) * obs.get("experts_held", 0)
    return got["moe_held_pairs"] / calls if calls else None
