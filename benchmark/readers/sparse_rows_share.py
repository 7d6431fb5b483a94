"""K/V rows the sparse layers' queries attended to over the rows their
streams held (what full attention would have read), in the decode ticks
inside the window: the program's ``sparse_rows_read`` over
``sparse_rows_held``, both summed over layers and ticks."""


def read(obs):
    ticks = (obs.get("attn") or {}).get("decode") or {}
    held = ticks.get("sparse_rows_held", 0)
    return ticks.get("sparse_rows_read", 0) / held if held else None
