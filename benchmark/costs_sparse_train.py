"""Operations and bytes from shapes for a sparse model with window and
full attention layers TRAINED as one chip's share: what
``costs.train_flops_per_token`` is to a dense decoder, over
``costs_sparse.py``'s counts of parameters (whose ``m`` this takes: the
configuration's names as the PROGRAM runs them, ``layer_types`` one
entry a layer run, ``num_experts`` the router's width, ``vocab_size``
the rows held).

Counted at the level of the mask, not of the blocks: a window layer's
row is charged the keys it may see, ``min(i + 1, window)``, and a full
layer's row ``i + 1``; what a block computes and masks away is no work,
so the share of the peak cannot pass 100% by counting it. The held
pairs a token meets are the program's own counter (``moe_held_pairs``
over tokens and sparse layers), not an assumption of uniform routing.
Recomputation is never counted in ``flops_per_token``.
"""

from __future__ import annotations

from benchmark import costs_sparse

SLIDING = costs_sparse.SLIDING


def mean_keys(seq: int, window: int | None) -> float:
    """Keys a row of a causal layer sees, the mean over ``seq`` rows."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def flops_per_token(m, seq: int, held_pairs_per_token_layer: float) -> float:
    """Matmul FLOPs one trained token needs on THIS chip, forward and
    backward (3 x forward, 2 FLOPs a multiply-add): 6 x (attention, the
    router and the head's held rows, and the held experts the token is
    routed to) plus scores and values over the keys each row sees."""
    _, sparse, full, sliding = costs_sparse.layer_counts(m)
    weights = costs_sparse.fixed_params(m) \
        + sparse * held_pairs_per_token_layer * costs_sparse.expert_params(m)
    keys = full * mean_keys(seq, None) + sliding * mean_keys(seq, m.sliding_window)
    return 6.0 * weights + 12.0 * keys * m.num_attention_heads * m.head_dim


# the grouped products of one sparse layer, as the traced round executes
# them: forward, the layer's recomputed forward, and a backward of two
# products (one for the rows, one for the weights) to each forward one
PASSES = 4


def experts_flops(m, held_pairs: float) -> float:
    """FLOPs of the three grouped products over ``held_pairs`` rows (a
    count summed over layers and steps), all ``PASSES`` of them."""
    return PASSES * 2.0 * costs_sparse.expert_params(m) * held_pairs


def experts_bytes(m, held_pairs: float, layer_calls: float, held: int,
                  itemsize: int) -> float:
    """Bytes the same passes must move at the least: each call reads its
    ``held`` experts' weights once a pass, and a row is read at the
    model's width, written and read at the expert's width twice (gate
    and up, then their product) and written at the model's width."""
    weights = layer_calls * held * costs_sparse.expert_params(m)
    rows = held_pairs * (2 * m.hidden_size + 3 * m.moe_intermediate_size)
    return PASSES * (weights + rows) * itemsize
