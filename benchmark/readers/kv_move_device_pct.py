"""Share of the device's leaf-operation time that moves K and V rows:
under ``kv_write`` (new rows into the cache or pool), ``kv_gather`` (a
slot's rows or block table out of it) or ``layer_scan`` (what the layer
scan does to its per-layer operands: in a serve program nine tenths of
it is the pool, sliced out of its stack a layer at a time and stacked
back; the rest the layer's weights) (``span_reduce.py``). Copies the
compiler inserts carry no scope and stand in ``device.unscoped_pct``."""

from benchmark import span_reduce


def read(obs):
    return span_reduce.scope_pct(obs, ("kv_write", "kv_gather", "layer_scan"))
