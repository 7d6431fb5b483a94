"""Plain reference of the ``exaone_moe`` decoder (K-EXAONE-236B-A23B).

Written from the catalog row's ``config`` and ``described_as`` (source:
huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``) in
straight ``jax.numpy``: no kernel, no cache, no batching tricks, nothing
imported from ``nanodiloco_tpu``. It is the yardstick ``correct`` is
decided against, so it lives with the benchmark. For layer ``l`` with
attention kind ``layer_types[l]`` (sliding: a window; full) and a dense
feed-forward for ``l < first_k_dense_replace``, else a sparse one:

    a = RMSNorm(h; g1)
    q = a Wq [H x hd]   k = a Wk [Hkv x hd]   v = a Wv [Hkv x hd]
    q = RMSNorm_hd(q; gq)   k = RMSNorm_hd(k; gk)      per head  (assumed)
    sliding layer: q, k = RoPE(theta, rotate-half);  full layer: none  (assumed)
    o = softmax(q k^T / sqrt(hd) + M) v     each KV head serves H / Hkv query heads
        M: key j is seen from row i iff j <= i, and on a sliding layer i - j < window
    h = h + o Wo
    m = RMSNorm(h; g2)
    dense:  h = h + (silu(m Wg) * (m Wu)) Wd
    sparse: s = sigmoid(m Wr) in R^E, float32
            C = the k experts with the largest s + b      (n_group 1: no grouping)
            w_e = scale * s_e / sum_{c in C} s_c  for e in C
            h = h + sum_{e in C} w_e FFN_e(m) + FFN_shared(m)
    logits = RMSNorm(h_L; g) W_head                        untied

Left out on purpose: the multi-token-prediction block (the row does not
say how it joins the embedding to the hidden state); the model is run
without self-drafting.

**The chip's share.** ``held = (first, count)``: ``w_e`` is formed over
all k chosen experts, whether or not they are held; the sum runs over
the chosen experts with ``first <= e < first + count`` alone, plus the
shared expert, and that partial ``h`` goes on to the next layer. The
weights hold those ``count`` experts only. No code stands in for the
absent experts.

Weights are stored [in, out] (``x @ W``), in this module's own layout:

    {"embed": [V, d], "final_norm": [d], "lm_head": [d, V],
     "layers": [one dict a layer]} with input_layernorm, q_proj, k_proj,
     v_proj, o_proj, q_norm [hd], k_norm [hd], post_attention_layernorm
     and, dense: gate_proj, up_proj, down_proj; sparse: router [d, E],
     router_bias [E] float32, experts_gate / experts_up [count, d, f],
     experts_down [count, f, d], shared_gate / shared_up [d, fs],
     shared_down [fs, d] (the shared experts as one SwiGLU of their
     summed width).

Departures from the description, each for the check's sake: every HELD
expert is computed for every token and masked by the choice (no sort, no
grouped product; a ``lax.scan`` walks the held experts, so that one
expert is compiled); attention's scores are made a block of query rows
at a time, each row against all of its keys at once (so that the
published widths fit beside the weights); ``dtype`` may be bfloat16 (matmuls take bf16 inputs,
norms, softmax and the gate stay in float32: the plain bf16 pass whose
distance from the float32 pass is the rounding floor); ``choice`` hands
in the experts another program chose ([L_sparse, B, S, k] int32, -1
rows: this pass's own top-k), so that a comparison of logits is not
decided by a near-tie in a top-8 of 128; ``kv_fault`` is applied to K
(after RoPE) and V of every layer and ``fault`` switches one mechanism
off, with which the check makes its negative controls; ``inputs_in``
rounds every matmul's inputs to fewer bits (the pass in the precision
below the stated one). In float32 every matmul runs at
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FAULTS = ("window_ignored", "gate_over_held_only", "rope_on_full_layers")
# query rows a block of attention scores, where the sequence is a whole
# number of them: [B, H, rows, S] float32 at a time and not [B, H, S, S]
Q_BLOCKS = (256, 128, 64)


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


def _swiglu(mm, m, wg, wu, wd):
    return mm(jax.nn.silu(mm(m, wg)) * mm(m, wu), wd)


def gate(m, w, hp, mm, fault=None, held=None, choice=None):
    """The sparse layer's gate. m [B, S, d] -> (weights [B, S, E] float32,
    zero off the chosen experts; selection scores s + b [B, S, E])."""
    k = hp["num_experts_per_tok"]
    logits = mm(m, w["router"]).astype(jnp.float32)
    s = jax.nn.sigmoid(logits) if hp["scoring_func"] == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    sel = s + w["router_bias"].astype(jnp.float32) if "router_bias" in w else s
    _, own = jax.lax.top_k(sel, k)                              # [B, S, k]
    if choice is not None:
        own = jnp.where(choice[..., :1] >= 0, choice, own)
    chosen = jnp.sum(jax.nn.one_hot(own, s.shape[-1], dtype=jnp.float32), axis=-2)
    picked = s * chosen
    if fault == "gate_over_held_only" and held is not None:
        e = jnp.arange(s.shape[-1])
        picked = picked * ((e >= held[0]) & (e < held[0] + held[1]))
    if hp["norm_topk_prob"]:  # the floor: a faulted pass may leave a token no expert
        picked = picked / jnp.maximum(jnp.sum(picked, axis=-1, keepdims=True), 1e-20)
    return picked * hp["routed_scaling_factor"], sel


def _layer(h, w, kind, hp, dtype, mm, kv_fault, fault, held, choice):
    b, s, d = h.shape
    nh, nkv, hd = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    a = _rms_norm(h, w["input_layernorm"], hp["rms_norm_eps"])
    q = mm(a, w["q_proj"]).reshape(b, s, nh, hd)
    k = mm(a, w["k_proj"]).reshape(b, s, nkv, hd)
    v = mm(a, w["v_proj"]).reshape(b, s, nkv, hd)
    q = _rms_norm(q, w["q_norm"], hp["rms_norm_eps"])
    k = _rms_norm(k, w["k_norm"], hp["rms_norm_eps"])
    sliding = kind == "sliding_attention"
    if sliding or fault == "rope_on_full_layers":
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    if kv_fault is not None:
        k, v = kv_fault(k), kv_fault(v)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    window = hp["sliding_window"] if sliding and fault != "window_ignored" else None

    def rows(args):  # a block of query rows against every key
        qb, i = args                                          # [B, blk, H, hd], [blk]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) / math.sqrt(hd)
        j = jnp.arange(s)[None, :]
        seen = j <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - j < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blk = next((n for n in Q_BLOCKS if s % n == 0 and s > n), s)
    o = jax.lax.map(rows, (jnp.moveaxis(q.reshape(b, s // blk, blk, nh, hd), 1, 0),
                           jnp.arange(s).reshape(s // blk, blk)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, nh * hd)
    h = h + mm(o, w["o_proj"])
    m = _rms_norm(h, w["post_attention_layernorm"], hp["rms_norm_eps"])
    if "router" not in w:
        return h + _swiglu(mm, m, w["gate_proj"], w["up_proj"], w["down_proj"]), None
    weights, sel = gate(m, w, hp, mm, fault, held, choice)
    first, count = held
    out = _swiglu(mm, m, w["shared_gate"], w["shared_up"], w["shared_down"]) \
        if "shared_gate" in w else 0
    mine = jnp.moveaxis(weights[..., first:first + count], -1, 0).astype(dtype)

    def one(out, e):  # every held expert for every token, masked by the choice
        wg, wu, wd, w_e = e
        return out + w_e[..., None] * _swiglu(mm, m, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m) + out,
                          (w["experts_gate"], w["experts_up"], w["experts_down"], mine))
    return h + out, sel


def _matmul(dtype, inputs_in):
    def mm(x, w):
        x, w = x.astype(dtype), w.astype(dtype)
        if inputs_in is not None:  # not a cast there and back, which a compiler may drop
            x = jax.lax.reduce_precision(x, *inputs_in)
            w = jax.lax.reduce_precision(w, *inputs_in)
        return x @ w

    return mm


def _forward(weights, tokens, hp, dtype, kv_fault, fault, held, choice, inputs_in,
             program=lambda f: f):
    """``program`` wraps the embedding, every kind of layer and the head
    before they are called: the identity for one traced pass, ``jax.jit``
    for a program a layer (``by_layer``)."""
    mm = _matmul(dtype, inputs_in)
    h = program(lambda e, t: e.astype(dtype)[t])(weights["embed"], tokens)
    layers: dict = {}
    scores, n_sparse = [], 0
    for l, w in enumerate(weights["layers"]):
        sparse, kind = "router" in w, hp["layer_types"][l]
        held_l = (0, w["experts_gate"].shape[0]) if held is None and sparse else held
        c = choice[n_sparse] if (choice is not None and sparse) else None
        if (kind, sparse) not in layers:  # layers of one kind share a program
            layers[kind, sparse] = program(
                lambda h, w, c, kind=kind, held_l=held_l: _layer(
                    h, w, kind, hp, dtype, mm, kv_fault, fault, held_l, c))
        h, sel = layers[kind, sparse](h, w, c)
        if sparse:
            scores.append(sel)
            n_sparse += 1
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"].T
    logits = program(lambda h, g, head: mm(
        _rms_norm(h, g, hp["rms_norm_eps"]), head).astype(jnp.float32))(
            h, weights["final_norm"], head)
    return logits, scores


def forward(weights, tokens, hp: dict, dtype=jnp.float32, held=None, kv_fault=None,
            fault=None, choice=None, inputs_in=None, with_scores=False, by_layer=False):
    """tokens [B, S] int32 -> logits [B, S, V] float32 (and, with
    ``with_scores``, each sparse layer's selection scores s + b
    [B, S, E]). ``held`` (first, count) of the experts the weights hold;
    None: all of the router's, from 0. ``by_layer`` (not under a trace of
    the caller's): the embedding, each layer and the head run as compiled
    programs of their own, so that one layer's weights at a time stand
    cast to ``dtype`` beside the stored ones and not the whole model's."""
    dtype = jnp.dtype(dtype)
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    args = (weights, tokens, hp, dtype, kv_fault, fault, held, choice, inputs_in)
    program = jax.jit if by_layer else (lambda f: f)
    if dtype == jnp.float32:
        with jax.default_matmul_precision("highest"):
            logits, scores = _forward(*args, program)
    else:
        logits, scores = _forward(*args, program)
    return (logits, scores) if with_scores else logits


def loss(weights, tokens, hp: dict, dtype=jnp.float32, held=None):
    """Mean cross-entropy of token t+1 under the logits at t."""
    logits = forward(weights, tokens, hp, dtype, held)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
