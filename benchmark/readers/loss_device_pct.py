"""Share of the device's leaf-operation time under ``loss`` (the
cross-entropy; the chunked one holds the vocabulary head's matmul) and
``head`` (the head where it runs apart) (``span_reduce.py``)."""

from benchmark import span_reduce


def read(obs):
    return span_reduce.scope_pct(obs, ("loss", "head"))
