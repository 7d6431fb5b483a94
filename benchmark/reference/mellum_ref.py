"""Plain reference of the ``mellum`` decoder (Mellum2-12B-A2.5B), with
its training loss and, by ``jax.grad``, its gradients.

Written from the catalog row's ``config`` (source:
huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct ``config.json``) in
straight ``jax.numpy``: no kernel, no sort, no grouped product, nothing
imported from ``nanodiloco_tpu``. It is the yardstick ``correct`` is
decided against, so it lives with the benchmark. Layer ``l`` has the
attention kind ``layer_types[l]`` and a sparse feed-forward:

    a = RMSNorm(h; g1)                                   eps 1e-6, pre-norm (assumed)
    q = a Wq [H x hd]   k = a Wk [Hkv x hd]   v = a Wv [Hkv x hd]     no bias, no q/k norm
    q, k = RoPE_kind(q), RoPE_kind(k)                    rotate-half, the kind's own table
    o = softmax(q k^T / sqrt(hd) + M) v                  each KV head serves H / Hkv query heads
        M: key j is seen from row i iff j <= i, and on a sliding layer i - j < window
    h = h + o Wo
    m = RMSNorm(h; g2)
    p = softmax(m Wr) in R^E, float32
    C = the k experts with the largest p;   w_e = p_e / sum_{c in C} p_c  for e in C
    h = h + sum_{e in C} w_e W_down,e (silu(W_gate,e m) * W_up,e m)
    logits = RMSNorm(h_L; g) W_head                      untied

    loss = mean_t CE(logits_t, token_{t+1}) + coef * sum_l E sum_e f_e P_e
        f_e: the share of the layer's k T (token, expert) pairs that chose e (no gradient)
        P_e: the mean over tokens of p_e            (the Switch balance term; coef assumed)

The tables (``rope_parameters`` by kind). default: inv_n = theta^(-2n/hd),
n = 0..hd/2-1. yarn: d(beta) = hd ln(L0 / (2 pi beta)) / (2 ln theta),
low = floor(d(beta_fast)), high = ceil(d(beta_slow)), both clipped to
[0, hd - 1]; ramp_n = clip((n - low) / (high - low), 0, 1);
inv_n = (1 - ramp_n) base_n + ramp_n base_n / factor; cos and sin times
``attention_factor``.

Left out on purpose: the "MTP head" the row's ``described_as`` names (the
config has no key for it and the row no equations).

**The chip's share.** ``held = (first, count)``: ``p``, the choice, the
weights ``w_e`` and the balance term are formed over all E experts; the
sum over the chosen runs over those with ``first <= e < first + count``
alone, and that partial ``h`` goes on to the next layer. The weights
hold those ``count`` experts only. No code stands in for the absent ones.

Weights are stored [in, out] (``x @ W``), in this module's own layout:

    {"embed": [V, d], "final_norm": [d], "lm_head": [d, V],
     "layers": [one dict a layer]} with input_layernorm, q_proj, k_proj,
     v_proj, o_proj, post_attention_layernorm, router [d, E],
     experts_gate / experts_up [count, d, f], experts_down [count, f, d].

Departures from the description, each for the check's sake: every HELD
expert is computed for every token and masked by the choice (a
``lax.scan`` walks the held experts); attention's scores are made a block
of query rows at a time, each row against all keys at once, and with
``remat`` a block and a layer are made again in the backward pass (so
that the published widths fit beside the weights); ``dtype`` may be
bfloat16 (matmuls take bf16 inputs; norms, softmax, the gate and the loss
stay in float32); ``choice`` hands in the experts another program chose
([L, B, S, k] int32; a row of -1: this pass's own top-k), so that a
comparison is not decided by a near-tie in a top-8 of 64; ``fault`` switches one mechanism off (the check's
negative controls, ``FAULTS``: ``matmul_inputs_in_fp8`` rounds every
matmul's inputs to an 8-bit float, the pass in the precision below the
stated one; ``half_the_batch_left_out`` takes ``loss``'s cross-entropy
over the first half of the sequences alone), by name or, as an array, at
run time (``_on``). In float32 every
matmul runs at ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FAULTS = ("window_ignored", "yarn_left_out", "gate_over_held_only", "balance_left_out",
          "matmul_inputs_in_fp8", "half_the_batch_left_out")
FP8 = (4, 3)  # an 8-bit float's exponent and mantissa bits
SLIDING = "sliding_attention"
# query rows a block of attention scores, where the sequence is a whole
# number of them: [B, H, rows, S] float32 at a time and not [B, H, S, S]
Q_BLOCKS = (256, 128, 64, 8)


def _rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _on(fault, name: str):
    """Whether ``fault`` switches ``name`` off. ``fault`` is None, one of
    FAULTS (a Python bool comes back: one pass traced with that
    mechanism off), or an int32 scalar ARRAY, 0 for none and 1 + the
    index in FAULTS (a traced bool: one compiled program serves the
    clean pass and every control)."""
    if fault is None or isinstance(fault, str):
        return fault == name
    return fault == FAULTS.index(name) + 1


def _pick(flag, off, on):
    """``off()`` where the mechanism is switched off, else ``on``."""
    if isinstance(flag, bool):
        return off() if flag else on
    return jnp.where(flag, off(), on)


def yarn_range(rope: dict, hd: int) -> tuple[int, int]:
    """(low, high) of the ramp over the hd / 2 rotary dimensions."""
    def d(beta):
        return hd * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * beta)) \
            / (2 * math.log(rope["rope_theta"]))

    return max(math.floor(d(rope["beta_fast"])), 0), min(math.ceil(d(rope["beta_slow"])), hd - 1)


def inv_freq(rope: dict, hd: int):
    """(the hd / 2 rotary frequencies, the factor on cos and sin)."""
    n = jnp.arange(0, hd, 2, dtype=jnp.float32)
    base = 1.0 / (rope["rope_theta"] ** (n / hd))
    if rope.get("rope_type", "default") != "yarn":
        return base, 1.0
    low, high = yarn_range(rope, hd)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / max(high - low, 0.001),
                    0.0, 1.0)
    return (1.0 - ramp) * base + ramp * base / rope["factor"], rope["attention_factor"]


def _rope(x, inv, factor):
    """x [B, S, H, hd]: rotate-half rotary embedding at positions 0..S-1
    with the frequencies ``inv`` [hd / 2], cos and sin times ``factor``."""
    s, hd = x.shape[1], x.shape[3]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]  # [1, S, 1, hd]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * (jnp.cos(ang) * factor).astype(x.dtype)
            + rot * (jnp.sin(ang) * factor).astype(x.dtype))


def _swiglu(mm, m, wg, wu, wd):
    return mm(jax.nn.silu(mm(m, wg)) * mm(m, wu), wd)


def gate(m, w, hp, mm, fault=None, held=None, choice=None):
    """m [B, S, d] -> (weights [B, S, E] float32, zero off the chosen
    experts; the probabilities p [B, S, E]; the layer's balance term)."""
    e, k = hp["num_experts"], hp["num_experts_per_tok"]
    p = jax.nn.softmax(mm(m, w["router"]).astype(jnp.float32), axis=-1)
    own = jax.lax.top_k(p, k)[1]                                        # [B, S, k]
    if choice is not None:  # a row of -1: this pass's own choice
        own = jnp.where(choice[..., :1] >= 0, choice, own)
    chosen = jnp.sum(jax.nn.one_hot(own, e, dtype=jnp.float32), axis=-2)
    picked = p * chosen
    at = jnp.arange(e)
    picked = _pick(_on(fault, "gate_over_held_only"),
                   lambda: picked * ((at >= held[0]) & (at < held[0] + held[1])), picked)
    if hp["norm_topk_prob"]:
        total = jnp.sum(picked, axis=-1, keepdims=True)
        # a faulted pass may leave a token no expert: weights of 0 then
        picked = picked / jnp.where(total > 0, total, 1.0)
    f = jax.lax.stop_gradient(jnp.mean(chosen, axis=(0, 1)) / k)
    balance = e * jnp.sum(f * jnp.mean(p, axis=(0, 1)))
    return picked, p, balance


def _layer(h, w, kind, hp, dtype, mm, fault, held, choice, remat):
    b, s, d = h.shape
    nh, nkv, hd = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    a = _rms_norm(h, w["input_layernorm"], hp["rms_norm_eps"])
    q = mm(a, w["q_proj"]).reshape(b, s, nh, hd)
    k = mm(a, w["k_proj"]).reshape(b, s, nkv, hd)
    v = mm(a, w["v_proj"]).reshape(b, s, nkv, hd)
    inv, factor = inv_freq(hp["rope_parameters"][kind], hd)
    plain = inv_freq(hp["rope_parameters"][SLIDING], hd)  # the window layers' table
    inv = _pick(_on(fault, "yarn_left_out"), lambda: plain[0], inv)
    factor = _pick(_on(fault, "yarn_left_out"), lambda: plain[1], factor)
    q, k = _rope(q, inv, factor), _rope(k, inv, factor)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    window = hp["sliding_window"] if kind == SLIDING else None

    def rows(args):  # a block of query rows against every key
        qb, i = args                                          # [B, blk, H, hd], [blk]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k).astype(jnp.float32) / math.sqrt(hd)
        j = jnp.arange(s)[None, :]
        seen = j <= i[:, None]
        if window is not None:
            seen = _pick(_on(fault, "window_ignored"), lambda: seen,
                         seen & (i[:, None] - j < window))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blk = next((n for n in Q_BLOCKS if s % n == 0 and s > n), s)
    o = jax.lax.map(jax.checkpoint(rows) if remat else rows,
                    (jnp.moveaxis(q.reshape(b, s // blk, blk, nh, hd), 1, 0),
                     jnp.arange(s).reshape(s // blk, blk)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, nh * hd)
    h = h + mm(o, w["o_proj"])
    m = _rms_norm(h, w["post_attention_layernorm"], hp["rms_norm_eps"])
    weights, p, balance = gate(m, w, hp, mm, fault, held, choice)
    first, count = held
    mine = jnp.moveaxis(weights[..., first:first + count], -1, 0).astype(dtype)

    def one(out, e):  # every held expert for every token, masked by the choice
        wg, wu, wd, w_e = e
        return out + w_e[..., None] * _swiglu(mm, m, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (w["experts_gate"], w["experts_up"], w["experts_down"], mine))
    return h + out, p, balance


def _matmul(dtype, fault):
    def mm(x, w):
        x, w = x.astype(dtype), w.astype(dtype)
        fp8 = _on(fault, "matmul_inputs_in_fp8")
        # not a cast there and back, which a compiler may drop
        x = _pick(fp8, lambda: jax.lax.reduce_precision(x, *FP8), x)
        w = _pick(fp8, lambda: jax.lax.reduce_precision(w, *FP8), w)
        return x @ w

    return mm


def _hidden(weights, tokens, hp, dtype, held, fault, choice, remat):
    """The final normed hidden states [B, S, d], each layer's
    probabilities [L][B, S, E] and the summed balance term."""
    mm = _matmul(dtype, fault)
    h = weights["embed"].astype(dtype)[tokens]
    probs, balance = [], jnp.zeros((), jnp.float32)
    for l, w in enumerate(weights["layers"]):
        held_l = (0, w["experts_gate"].shape[0]) if held is None else held
        layer = lambda h, w, c, kind=hp["layer_types"][l], held_l=held_l: _layer(
            h, w, kind, hp, dtype, mm, fault, held_l, c, remat)
        h, p, b = (jax.checkpoint(layer) if remat else layer)(
            h, w, None if choice is None else choice[l])
        probs.append(p)
        balance = balance + b
    return _rms_norm(h, weights["final_norm"], hp["rms_norm_eps"]), probs, balance, mm


def _in_precision(dtype, fn):
    if jnp.dtype(dtype) == jnp.float32:
        with jax.default_matmul_precision("highest"):
            return fn()
    return fn()


def forward(weights, tokens, hp: dict, dtype=jnp.float32, held=None, fault=None,
            choice=None, with_probs=False):
    """tokens [B, S] int32 -> logits [B, S, V] float32 (and, with
    ``with_probs``, each layer's router probabilities [B, S, E]).
    ``held`` (first, count) of the experts the weights hold; None: all
    of the router's, from 0."""
    if isinstance(fault, str) and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    dtype = jnp.dtype(dtype)

    def run():
        x, probs, _, mm = _hidden(weights, tokens, hp, dtype, held, fault, choice, False)
        return mm(x, weights["lm_head"]).astype(jnp.float32), probs

    logits, probs = _in_precision(dtype, run)
    return (logits, probs) if with_probs else logits


def token_losses(weights, tokens, hp: dict, dtype=jnp.float32, held=None, fault=None,
                 choice=None, remat=False, with_probs=False):
    """(the cross-entropy of token t+1 under the logits at t [B, S - 1],
    the layers' summed balance term) and, with ``with_probs``, each
    layer's router probabilities [B, S, E]. ``remat``: a layer, a block
    of attention and a sequence's head are made again in the backward
    pass."""
    if isinstance(fault, str) and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    dtype = jnp.dtype(dtype)

    def run():
        x, probs, balance, mm = _hidden(weights, tokens, hp, dtype, held, fault, choice, remat)

        def nll(x, t):  # one sequence
            logp = jax.nn.log_softmax(mm(x[:-1], weights["lm_head"]).astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, t[1:, None], axis=-1)[:, 0]

        each = jax.lax.map(lambda a: (jax.checkpoint(nll) if remat else nll)(*a), (x, tokens))
        balance = _pick(_on(fault, "balance_left_out"), lambda: jnp.zeros((), jnp.float32),
                        balance)
        return (each, balance, probs) if with_probs else (each, balance)

    return _in_precision(dtype, run)


def loss(weights, tokens, hp: dict, with_parts=False, **how):
    """Mean cross-entropy of token t+1 under the logits at t, plus
    ``router_aux_coef`` times the layers' summed balance term (and with
    ``with_parts`` the pair (cross-entropy, balance term) beside it).
    ``how``: ``token_losses``'s arguments."""
    each, balance = token_losses(weights, tokens, hp, **how)
    ce = _pick(_on(how.get("fault"), "half_the_batch_left_out"),
               lambda: jnp.mean(each[:max(1, each.shape[0] // 2)]), jnp.mean(each))
    total = ce + hp["router_aux_coef"] * balance
    return (total, (ce, balance)) if with_parts else total
