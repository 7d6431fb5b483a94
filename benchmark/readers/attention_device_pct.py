"""Share of the device's leaf-operation time under the model's
``attention`` scope (scores, mask, softmax, values: what a fused kernel
replaces), forward, recomputed and backward (``span_reduce.py``)."""

from benchmark import span_reduce


def read(obs):
    return span_reduce.scope_pct(obs, ("attention",))
