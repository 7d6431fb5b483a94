"""Fused causal and sliding-window attention for the training path.

The scores, the mask and the softmax of ``models/llama.py:dense_attention``
stay in VMEM: this is the installed jax's splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``: forward, dq and
dk/dv Mosaic kernels behind a custom VJP) under the repo's layout and
semantics. Tiles of keys wholly outside the mask are never visited, K
and V stay at their own head count, and a ``[B, S]`` validity array
reaches the kernel as the segment ids of rows and keys. ``models/llama.py:_attention``
decides which calls come here (``fused_attention_applies``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# Rows of a query tile and of a key tile in all three kernels, and the
# keys a tile's inner step computes at once: one chip sweep on a v5e at
# 16,384 tokens of 32 heads over 4 of 128, S 1,024 to 8,192, full and
# window 1,024 (PERF.md section 6, PR 33). Tiles of 1,024 run a full
# layer 17% faster than tiles of 512 and a window layer 10% slower (two
# tiles of keys a row block where three of 512 hold a quarter fewer);
# tiles of 2,048 pass the VMEM a kernel may take. The backward pass is
# the library's two kernels and not its fused one, which is 12% faster
# on a full layer and rounds dq to the activations' dtype once a key
# tile before summing, where these accumulate in float32.
TILE = 1024
COMPUTE_TILE = 512


def whole_tiles(s: int) -> bool:
    """Whether a sequence of ``s`` is a whole number of the kernels' tiles."""
    return s % TILE == 0


def block_sizes(s: int) -> splash.BlockSizes:
    """Tiles of the forward, dq and dk/dv kernels: a function of S alone
    (the same at every S the sweep measured)."""
    if not whole_tiles(s):
        raise ValueError(f"a sequence of {s} is no whole number of tiles of {TILE}")
    return splash.BlockSizes(
        block_q=TILE, block_kv=TILE, block_kv_compute=COMPUTE_TILE,
        block_q_dkv=TILE, block_kv_dkv=TILE, block_kv_dkv_compute=COMPUTE_TILE,
        block_q_dq=TILE, block_kv_dq=TILE,
    )


@functools.lru_cache(maxsize=None)
def _kernel(s: int, heads: int, window: int | None, interpret: bool,
            sizes: splash.BlockSizes):
    """The library's kernel for one static (S, heads, mask, tiles): its
    tables of which tiles to visit are built once a process."""
    one = (splash.CausalMask((s, s)) if window is None
           else splash.LocalMask((s, s), (window - 1, 0), 0))
    # the tables are made as arrays: concrete ones, also where the first
    # call comes from inside a trace
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash.MultiHeadMask([one] * heads), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def splash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, valid: jax.Array | None = None,
    *, window: int | None = None, interpret: bool = False,
    sizes: splash.BlockSizes | None = None,
) -> jax.Array:
    """q [B, S, H, hd], k and v [B, S, Hkv, hd] (GQA, not expanded), ``valid``
    None or a [B, S] 0/1 array of the real tokens. Returns [B, S, H, hd]:
    ``dense_attention``'s result on every real row. Causal; with ``window``
    a row i sees keys i - window < j <= i; a real row sees no key with
    ``valid == 0``. A PADDING row sees the padding keys its mask allows,
    itself among them (``valid`` is the segment id of rows and keys
    alike), where ``dense_attention`` gives it the real keys before it:
    its output is loss-masked and no real row reads it either way, and
    so no row of the kernel is ever empty. That matters: the library's
    backward pass takes a row's probabilities from its saved
    log-sum-exp, which for a row of masked scores alone is the mask
    value itself in float32 (-2.4e38 + log n rounds back), so every
    masked key reads probability 1 and not 1/n; on the chip one such
    row in the loss (left padding) put the model's gradient 2.9e6 times
    off (PERF.md section 6, PR 33). Softmax statistics in float32,
    products in q's dtype accumulated in float32; the scores stay in
    float32 (the dense blocks round them to q's dtype first).
    ``interpret`` and ``sizes`` are for tests and sweeps: the program's
    tiles follow from S."""
    s, h, hd = q.shape[1:]
    kernel = _kernel(s, h, window, interpret, sizes or block_sizes(s))
    # the library applies no scale: on q, one more rounding of q in bf16
    q = q * jnp.asarray(1.0 / math.sqrt(hd), q.dtype)
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    ids = None
    if valid is not None:
        real = (valid > 0).astype(jnp.int32)
        ids = splash.SegmentIds(q=real, kv=real)
    out = jax.vmap(kernel)(q, k, v, ids)
    return jnp.swapaxes(out, 1, 2)
