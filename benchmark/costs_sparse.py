"""Operations and bytes from shapes for a sparse model with window and
full attention layers, served as one chip's share: what
``costs.decode_tick_bytes`` and ``costs.train_flops_per_token`` are to
a dense decoder (``costs.py``, whose peaks table this reads).

``m`` is any object with the configuration file's names as attributes
as the PROGRAM runs them: ``hidden_size``, ``intermediate_size`` (the
dense layer's width), ``moe_intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``layer_types`` (one entry a
layer run), ``first_k_dense_replace``, ``num_experts`` (the ROUTER's
width), ``num_experts_per_tok``, ``num_shared_experts``,
``sliding_window``, ``vocab_size`` (the rows HELD). How many held
experts a tick or a token meets is no assumption of uniform routing: it
is handed in from the program's counters (``moe_experts_hit``,
``moe_held_pairs``).
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def attention_params(m) -> int:
    """q, k, v, o of one layer (norm scales excluded)."""
    d, nh, nkv, hd = (m.hidden_size, m.num_attention_heads, m.num_key_value_heads,
                      m.head_dim)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def expert_params(m) -> int:
    """One expert's SwiGLU (routed or shared): three matrices."""
    return 3 * m.hidden_size * m.moe_intermediate_size


def layer_counts(m) -> tuple[int, int, int, int]:
    """(dense layers, sparse layers, full layers, sliding layers)."""
    n = len(m.layer_types)
    dense = min(m.first_k_dense_replace, n)
    sliding = sum(k == SLIDING for k in m.layer_types)
    return dense, n - dense, n - sliding, sliding


def fixed_params(m) -> int:
    """Weights every token is multiplied by, whatever it routes to:
    attention in every layer, the dense layers' SwiGLU, each sparse
    layer's router and shared experts, the head's held rows (the
    embedding is a gather of a few rows, not counted)."""
    dense, sparse, _, _ = layer_counts(m)
    return ((dense + sparse) * attention_params(m)
            + dense * 3 * m.hidden_size * m.intermediate_size
            + sparse * (m.hidden_size * m.num_experts
                        + m.num_shared_experts * expert_params(m))
            + m.hidden_size * m.vocab_size)


def kv_row_bytes(m, kv_itemsize: int) -> int:
    """Bytes of one layer's K and V rows for one cached token."""
    return 2 * m.num_key_value_heads * m.head_dim * kv_itemsize


def decode_tick_bytes(m, streams: float, rows_per_stream: float,
                      experts_hit_per_layer: float, weight_itemsize: int,
                      kv_itemsize: int) -> float:
    """Bytes one decode tick must read from HBM: ``fixed_params`` once,
    the held experts HIT (``experts_hit_per_layer``: held experts that
    at least one of the tick's tokens chose, a sparse layer's mean, from
    the program's counter), and K and V: all of a stream's live rows on
    a full layer, ``min(rows, window)`` on a sliding layer. Activations,
    norm scales, block tables and the rows written are left out."""
    _, sparse, full, sliding = layer_counts(m)
    weights = fixed_params(m) + sparse * experts_hit_per_layer * expert_params(m)
    rows = full * rows_per_stream + sliding * min(rows_per_stream, m.sliding_window)
    return weights * weight_itemsize + streams * rows * kv_row_bytes(m, kv_itemsize)


def experts_bytes(m, experts_hit: float, weight_itemsize: int) -> float:
    """Bytes of the held experts hit (a count summed over layers and
    programs): what the grouped products must read."""
    return experts_hit * expert_params(m) * weight_itemsize


def flops_per_token(m, held_pairs_per_token_layer: float, context_rows: float) -> float:
    """Matmul FLOPs one token needs on THIS chip, forward only (2 FLOPs a
    multiply-add): ``fixed_params``, the held experts it is routed to
    (``held_pairs_per_token_layer``: the program's ``moe_held_pairs``
    over its tokens and sparse layers; 1.0 where 8 of 128 meet 16 held
    evenly), and attention's scores and values over ``context_rows``
    keys on a full layer and at most the window on a sliding one."""
    _, sparse, full, sliding = layer_counts(m)
    weights = fixed_params(m) + sparse * held_pairs_per_token_layer * expert_params(m)
    keys = full * context_rows + sliding * min(context_rows, m.sliding_window)
    return 2.0 * weights + 4.0 * keys * m.num_attention_heads * m.head_dim
