"""Decayed linear attention (lightning attention, arXiv:2401.04658): a
layer whose cache is a state and no rows.

Per head, in float32, with a fixed decay ``lam = exp(log_decay)`` < 1:

    S_t = lam * S_{t-1} + k_t^T v_t          S is [hd, hd], S_{-1} = 0
    o_t = q_t S_t / sqrt(hd)

``step`` is that recurrence for one token a row (a decode tick);
``chunk`` is the same over T tokens at once (a prefill chunk, the full
forward pass): with S_in the state before the chunk's first token,

    o_t   = (lam^(t+1) q_t S_in + sum_{j<=t} lam^(t-j) (q_t . k_j) v_j) / sqrt(hd)
    S_out = lam^n S_in + sum_{j<n} lam^(n-1-j) k_j^T v_j

for the row's ``n`` real tokens (a right-padded final chunk leaves the
state where its last real token put it). Every power of ``lam`` is
``exp(e * log_decay)`` with ``e >= 0``, formed in log space, so nothing
is ever divided by a power of ``lam`` and nothing leaves float32
whatever the chunk's length (``lam`` is as low as 0.43: lam^-256 would).
The [T, T] table of relative decays is 33 MB for 32 heads at T = 512.

q and k arrive normed and rotated, in the compute dtype; the products
accumulate in float32 and whatever multiplies the float32 state runs at
``Precision.HIGHEST`` (the TPU's default would round the state to bf16
inside the matmul, which is the fault the benchmark's control injects).
Both run under the scope ``linear_state`` inside ``attention``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def step(q, k, v, state, log_decay, live=None):
    """One token a row. q, k, v [B, H, hd]; ``state`` [B, H, hd, hd]
    float32; ``log_decay`` [H] float32; ``live`` [B] (0: the row's state
    stays as it is). Returns (o [B, H, hd] float32, state)."""
    with jax.named_scope("attention"), jax.named_scope("linear_state"):
        lam = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        new = lam * state + kf[..., :, None] * vf[..., None, :]
        o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), new, precision=HIGHEST)
        if live is not None:
            new = jnp.where(live[:, None, None, None] > 0, new, state)
        return o * (1.0 / math.sqrt(q.shape[-1])), new


def chunk(q, k, v, state, log_decay, n_real=None):
    """T tokens a row. q, k, v [B, T, H, hd]; ``state`` [B, H, hd, hd]
    float32 (the state before the first token); ``n_real`` [B] real
    tokens of each row, a prefix (None: all T). Returns (o [B, T, H, hd]
    float32, the state after each row's last real token)."""
    b, t, h, hd = q.shape
    with jax.named_scope("attention"), jax.named_scope("linear_state"):
        ld = log_decay.astype(jnp.float32)                       # [H]
        i = jnp.arange(t, dtype=jnp.float32)
        n = jnp.full((b,), t, jnp.float32) if n_real is None else n_real.astype(jnp.float32)
        # within the chunk: lam^(t-j) for j <= t, an exact zero above
        gap = i[:, None] - i[None, :]                            # [T, T]
        rel = jnp.where(gap >= 0, jnp.exp(gap[None] * ld[:, None, None]), 0.0)  # [H, T, T]
        scores = jnp.einsum("bthd,bjhd->bhtj", q, k,
                            preferred_element_type=jnp.float32) * rel[None]
        vf = v.astype(jnp.float32)
        o = jnp.einsum("bhtj,bjhe->bthe", scores, vf, precision=HIGHEST)
        # the carried state, seen through t + 1 steps of decay
        seen = jnp.exp((i + 1.0)[:, None] * ld[None, :])         # [T, H]
        o = o + jnp.einsum("bthd,bhde->bthe", q.astype(jnp.float32) * seen[None, :, :, None],
                           state, precision=HIGHEST)
        # the state out: each real key decayed to the last real token
        left = n[:, None] - 1.0 - i[None, :]                     # [B, T]
        w = jnp.where(left[:, :, None] >= 0, jnp.exp(left[:, :, None] * ld[None, None, :]), 0.0)
        kw = k.astype(jnp.float32) * w[..., None]                # [B, T, H, hd]
        carried = jnp.exp(n[:, None] * ld[None, :])[:, :, None, None] * state
        new = carried + jnp.einsum("bjhd,bjhe->bhde", kw, vf, precision=HIGHEST)
        return o * (1.0 / math.sqrt(hd)), new
