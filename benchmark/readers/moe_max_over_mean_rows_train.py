"""Rows of the largest held group over the mean group's, over the
window's sparse-layer calls (the program's ``moe_max_group_rows`` and
``moe_held_pairs``, each summed over layers, inner steps and rounds):
the imbalance the grouped products saw. None for a run that trained no
sparse layer."""


def read(obs):
    got = obs.get("moe_train") or {}
    held = got.get("moe_held_pairs", 0)
    return got["moe_max_group_rows"] * obs["experts_held"] / held if held else None
