"""Share of the device's leaf-operation time under the sparse layers'
scopes ``sparse_select`` (compressed scores, pooling, top-k),
``sparse_attend`` (the chosen blocks' gather and the attention over
them) and ``kv_compress`` (``scope_times_state.py``). None for a
program without them."""

from benchmark import scope_times_state

SCOPES = ("sparse_select", "sparse_attend", "kv_compress")


def read(obs):
    secs = scope_times_state.seconds(obs, SCOPES)
    return None if secs is None else 100.0 * secs / scope_times_state.of_run(obs)["leaf_s"]
