"""Operations and bytes from shapes and counters for a stack of
block-sparse and linear attention layers served through three kinds of
cache: what ``costs.decode_tick_bytes`` is to a dense decoder
(``costs.py``, whose peaks table this reads).

``m`` is any object with the PROGRAM's names as attributes:
``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``vocab_size`` and ``layer_types``
(one entry a layer run: ``sparse_attention`` or ``linear_attention``).
What the selection reads is no assumption: it is handed in from the
program's counters (``sparse_rows_read``, ``sparse_compressed_rows``,
``state_updates``: serve/engine.py ``attn_stats()``), each counted
once, whatever implements the read.
"""

from __future__ import annotations

SPARSE, LINEAR = "sparse_attention", "linear_attention"


def attention_params(m, kind: str) -> int:
    """A layer's attention matrices (norm scales excluded). Sparse: q,
    o and the output gate at H heads, k and v at Hkv. Linear: q, k, v,
    o and the gate, every one at H heads."""
    wide = m.hidden_size * m.num_attention_heads * m.head_dim
    if kind == LINEAR:
        return 5 * wide
    return 3 * wide + 2 * m.hidden_size * m.num_key_value_heads * m.head_dim


def fixed_params(m) -> int:
    """Weights every token is multiplied by: each layer's attention
    matrices and SwiGLU, and the head (the embedding is a gather of a
    few rows, not counted)."""
    return (sum(attention_params(m, k) for k in m.layer_types)
            + len(m.layer_types) * 3 * m.hidden_size * m.intermediate_size
            + m.hidden_size * m.vocab_size)


def kv_row_bytes(m, kv_itemsize: int) -> int:
    """One cached token's K and V in one sparse layer, all KV heads."""
    return 2 * m.num_key_value_heads * m.head_dim * kv_itemsize


def compressed_row_bytes(m, kv_itemsize: int) -> int:
    """One compressed key, all KV heads."""
    return m.num_key_value_heads * m.head_dim * kv_itemsize


def state_bytes(m) -> int:
    """One row's float32 state in one linear layer: [H, hd, hd]."""
    return m.num_attention_heads * m.head_dim * m.head_dim * 4


def sparse_read_bytes(m, rows_read: float, compressed_rows: float, kv_itemsize: int) -> float:
    """What the sparse layers' selection and attention must read: the
    chosen blocks' K and V rows up to the query and the compressed keys
    scored (both summed over layers and queries by the program)."""
    return (rows_read * kv_row_bytes(m, kv_itemsize)
            + compressed_rows * compressed_row_bytes(m, kv_itemsize))


def state_rw_bytes(m, state_updates: float) -> float:
    """A state update reads a row's state and writes it back."""
    return 2.0 * state_updates * state_bytes(m)


def decode_tick_bytes(m, rows_read: float, compressed_rows: float, state_updates: float,
                      weight_itemsize: int, kv_itemsize: int) -> float:
    """Bytes one decode tick must move through HBM: ``fixed_params``
    once, the chosen rows and the compressed keys its queries read, and
    its states read and written (all three a tick's own counts).
    Activations, norm scales, block tables and the rows written are left
    out: megabytes against gigabytes."""
    return (fixed_params(m) * weight_itemsize
            + sparse_read_bytes(m, rows_read, compressed_rows, kv_itemsize)
            + state_rw_bytes(m, state_updates))


def flops(m, tokens: float, rows_read: float, compressed_rows: float,
          state_updates: float) -> float:
    """Matmul FLOPs of ``tokens`` decoded tokens, forward (2 a
    multiply-add): ``fixed_params`` a token; every chosen row scored
    and weighted by all H query heads (4 hd a head a row); every
    compressed key scored by all H heads (2 hd); a state update's outer
    product and read-out (4 hd^2 a head)."""
    heads = m.num_attention_heads * m.head_dim
    return (2.0 * fixed_params(m) * tokens + 4.0 * heads * rows_read
            + 2.0 * heads * compressed_rows + 4.0 * heads * m.head_dim * state_updates)
