"""Chunked softmax cross-entropy: the vocabulary projection and the loss
computed blockwise so the full [B, S, V] logits tensor never exists.

For small-hidden/large-vocab models (the reference's default is hidden 128
with a 32000-token vocab, ref configs/llama_default.json + huggyllama
tokenizer) the logits are the single largest tensor in the step —
[8, 1024, 32000] fp32 is ~1 GB — and the loss is HBM-bandwidth-bound on
writing + re-reading them. Here rows are processed in chunks under a
``lax.scan`` with ``jax.checkpoint``: forward computes each chunk's logits
on the fly (bf16 matmul on the MXU, logsumexp in f32) and keeps only the
scalar partials; backward rematerializes the chunk instead of loading it.
HBM high-water drops from O(B*S*V) to O(chunk*V); FLOPs go up by one extra
head matmul in the backward — the classic TPU trade.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("loss")
def chunked_softmax_xent(
    hidden: jax.Array,     # [N, d] compute-dtype rows (already label-aligned)
    head: jax.Array,       # [d, V]
    targets: jax.Array,    # [N] int
    weights: jax.Array,    # [N] float (0 = ignore row)
    chunk: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """Returns (sum_loss, sum_weights): the weighted NLL summed over rows
    and the total weight, both f32 — callers normalize. Rows are padded up
    to a chunk multiple with zero weight (static shapes for one compile).
    """
    n, d = hidden.shape
    n_pad = (-n) % chunk
    if n_pad:
        hidden = jnp.concatenate(
            [hidden, jnp.zeros((n_pad, d), hidden.dtype)], axis=0
        )
        targets = jnp.concatenate([targets, jnp.zeros((n_pad,), targets.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros((n_pad,), weights.dtype)])
    n_chunks = hidden.shape[0] // chunk

    hidden = hidden.reshape(n_chunks, chunk, d)
    targets = targets.reshape(n_chunks, chunk)
    weights = weights.reshape(n_chunks, chunk).astype(jnp.float32)

    @jax.checkpoint
    def chunk_loss(head, hx, tg, w):
        logits = (hx @ head).astype(jnp.float32)           # [C, V]
        lse = jax.nn.logsumexp(logits, axis=-1)            # [C]
        gold = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (lse - gold))

    def body(carry, xs):
        hx, tg, w = xs
        return carry + chunk_loss(head, hx, tg, w), None

    # derive the init from the data so it carries the correct varying-axes
    # type when this runs inside a shard_map manual region (a plain
    # jnp.zeros would be unvarying and fail scan's carry typing); both
    # inputs contribute — under pipeline parallelism the hidden states
    # are pp-varying while the weights are not
    zero = 0.0 * weights[0, 0] + 0.0 * hidden[0, 0, 0].astype(jnp.float32)
    sum_loss, _ = jax.lax.scan(body, zero, (hidden, targets, weights))
    return sum_loss, jnp.sum(weights)
