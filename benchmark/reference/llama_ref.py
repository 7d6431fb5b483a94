"""Plain reference of the dense pre-norm decoder both configurations use.

Written from the published description (Touvron et al. 2023 and the
HF ``LlamaForCausalLM`` / ``MistralForCausalLM`` modelling code, which
share these equations when no sliding window is set), in straight
``jax.numpy``: no kernel, no cache, no batching tricks, nothing
imported from ``nanodiloco_tpu``. It is the yardstick ``correct`` is
decided against, so it lives with the benchmark.

    h_0   = E[tokens]
    a     = RMSNorm(h; g1) ;  q, k, v = a Wq, a Wk, a Wv  (heads of hd)
    q, k  = RoPE(q), RoPE(k)            rotate-half, theta^(-2i/hd)
    o     = softmax(q k^T / sqrt(hd) + causal) v   (each KV head serves
            n_heads / n_kv_heads query heads: grouped-query attention)
    h     = h + o Wo
    m     = RMSNorm(h; g2) ;  h = h + (silu(m Wg) * (m Wu)) Wd
    logits = RMSNorm(h_L; g) W_head     (W_head = E^T when tied)

Weights are stored [in, out] (``x @ W``). ``weights`` is this module's
own layout:

    {"embed": [V, d], "final_norm": [d], "lm_head": [d, V] (absent when
     tied), "layers": {name: [L, ...]}} with the layer names
     input_layernorm, q_proj, k_proj, v_proj, o_proj,
     post_attention_layernorm, gate_proj, up_proj, down_proj.

Departures from the description: the layers are walked with
``lax.scan`` over the stacked arrays (one layer is compiled, whatever
the depth); ``dtype`` may be bfloat16, in which case matmuls take bf16
inputs and norms and softmax stay in float32 — the plain bf16 pass
whose distance from the float32 pass is the rounding floor that served
logits are judged against; ``remat`` recomputes each layer in the
backward pass (the same arithmetic, less memory); ``kv_fault`` is a
function applied to K (after RoPE) and V of every layer, with which the
correctness check makes its negative controls (a pool stored in fewer
bits, a wrong block). In float32 every matmul runs at
``jax.default_matmul_precision("highest")``: a TPU otherwise multiplies
float32 in bf16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [B, S, H, hd]: rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]  # [1,S,1,hd]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * jnp.cos(ang).astype(x.dtype) + rot * jnp.sin(ang).astype(x.dtype))


def _layer(h, w, hp, dtype, kv_fault=None):
    b, s, d = h.shape
    nh, nkv = hp["num_attention_heads"], hp["num_key_value_heads"]
    hd = d // nh
    a = _rms_norm(h, w["input_layernorm"], hp["rms_norm_eps"])
    q = (a @ w["q_proj"].astype(dtype)).reshape(b, s, nh, hd)
    k = (a @ w["k_proj"].astype(dtype)).reshape(b, s, nkv, hd)
    v = (a @ w["v_proj"].astype(dtype)).reshape(b, s, nkv, hd)
    q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    if kv_fault is not None:
        k, v = kv_fault(k), kv_fault(v)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * hd)
    h = h + o @ w["o_proj"].astype(dtype)
    m = _rms_norm(h, w["post_attention_layernorm"], hp["rms_norm_eps"])
    gate = jax.nn.silu(m @ w["gate_proj"].astype(dtype))
    up = m @ w["up_proj"].astype(dtype)
    return h + (gate * up) @ w["down_proj"].astype(dtype)


def _forward(weights, tokens, hp, dtype, remat, kv_fault):
    h = weights["embed"].astype(dtype)[tokens]

    def body(h, w):
        return _layer(h, w, hp, dtype, kv_fault), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, weights["layers"])
    h = _rms_norm(h, weights["final_norm"], hp["rms_norm_eps"])
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"].T
    return (h @ head.astype(dtype)).astype(jnp.float32)


def forward(weights, tokens, hp: dict, dtype=jnp.float32, remat=False,
            kv_fault=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        with jax.default_matmul_precision("highest"):
            return _forward(weights, tokens, hp, dtype, remat, kv_fault)
    return _forward(weights, tokens, hp, dtype, remat, kv_fault)


def loss(weights, tokens, hp: dict, dtype=jnp.float32, remat=False):
    """Mean cross-entropy of token t+1 under the logits at t."""
    logits = forward(weights, tokens, hp, dtype, remat)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
