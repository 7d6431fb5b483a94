"""Plain reference of the ``minicpm_sala`` decoder (MiniCPM-SALA).

Written from the catalog row's ``config`` and ``described_as`` (source:
huggingface.co/openbmb/MiniCPM-SALA ``config.json``) and the papers the
two layer kinds come from, in straight ``jax.numpy``: no kernel, no
cache, no chunks, nothing imported from ``nanodiloco_tpu``. It is the
yardstick ``correct`` is decided against, so it lives with the
benchmark. With s = scale_depth / sqrt(PUBLISHED layers):

    x = scale_emb * E[token]
    each layer:  x = x + s * Mixer(RMSNorm(x; g1))
                 x = x + s * (silu(m Wg) * (m Wu)) Wd,   m = RMSNorm(x; g2)
    logits = W_head (RMSNorm(x; g) / (hidden_size / dim_model_base))      untied

``lightning-attn`` mixer (Lightning Attention, arXiv:2401.04658), a = RMSNorm(x):
    q, k, v = a Wq, a Wk, a Wv          H heads of hd each, every head its own k and v
    q = RMSNorm_hd(q; gq)   k = RMSNorm_hd(k; gk)   then RoPE(theta, rotate-half) on both
    per head h, float32, token by token:
        S_t = lam_h S_{t-1} + k_t^T v_t        S_{-1} = 0
        o_t = q_t S_t / sqrt(hd)
    lam_h = exp(-2^(-e (h + 1) / H) * f_l),  f_l = 1 - l / (N - 1) + 1e-5
            for the layer's PUBLISHED index l of N (fixed, not learned)
    y = (RMSNorm(o; go) * sigmoid(a Wg)) Wo                 the norm over the H * hd joined values

``minicpm4`` mixer (InfLLM-V2 block-sparse attention, arXiv:2509.24663, sizes as
MiniCPM4's ``sparse_config``, arXiv:2506.07900): q H heads, k and v Hkv heads, per-head
RMSNorm on q and k, NO RoPE, scale 1 / sqrt(hd). The query at position t sees n = t + 1 keys:
    n <= dense_len: causal softmax attention over all n keys
    else, per KV group:
        c_j = mean(k[stride j : stride j + kernel])     every j with stride j + kernel <= n
        p^h = softmax_j(q^h . c_j / sqrt(hd));  r_j = sum over the group's heads of p^h_j
        b_m = max r_j over the j whose keys touch block m = [blk m, blk m + blk); 0 where none
        forced: blocks [0, init_blocks) and every block with one of keys [n - window, n)
        chosen: the forced and the topk highest b_m of the other blocks, ties to the lower
        softmax attention over the keys <= t of the chosen blocks
    y = (o * sigmoid(a Wg)) Wo

Departures from the descriptions, each noted where it is made: (1) the
family's code applies ``dense_len`` to a whole call; here it is applied
to every query position, so that the result does not depend on how a
sequence is cut into calls; (2) attention and the SwiGLU run a block of
rows at a time (``ROWS``), each row still against all of its keys at
once, so that the published widths fit; only the logits at the
positions ``at`` are made; (3) ``dtype`` may be bfloat16 (matmul inputs
in bf16; norms, softmax, the selection's sums and the state in
float32): the plain bf16 pass whose distance from the float32 pass is
the rounding floor; (4) ``choice`` hands in the blocks another program
chose, so that a comparison of logits is not decided by a near-tie in a
top-64; (5) ``fault`` switches one mechanism off or wrong, with which
the check makes its negative controls. In float32 every matmul runs at
``jax.default_matmul_precision("highest")``.

Weights are stored [in, out] (``x @ W``), in this module's own layout:

    {"embed": [V, d], "final_norm": [d], "lm_head": [d, V], "layers": [one dict a layer]}
    with input_layernorm, q_proj, k_proj, v_proj, o_proj, o_gate, q_norm [hd], k_norm [hd],
    post_attention_layernorm, gate_proj, up_proj, down_proj, and for a lightning layer
    o_norm [H * hd].

``hp``: num_attention_heads, num_key_value_heads, head_dim, hidden_size,
rms_norm_eps, rope_theta, mixer_types (one a layer RUN), layer_indices
(the published index of each), published_layers, scale_emb, scale_depth,
dim_model_base, decay_exponent, block_size, topk, kernel_size,
kernel_stride, init_blocks, window_size, dense_len.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

FAULTS = ("full_attention_for_choice", "decay_one", "state_in_bf16",
          "rope_on_sparse_layers", "scale_by_cut_depth")
# rows a block of attention scores or of the SwiGLU, where the sequence
# is a whole number of them
ROWS = 128


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


def _by_rows(fn, s: int, *arrays):
    """``fn`` over blocks of ROWS rows (axis 1 of every array) where
    ``s`` is a whole number of them, one after the other; else at once."""
    if s <= ROWS or s % ROWS:
        return fn(*arrays)
    cut = lambda a: jnp.moveaxis(a.reshape(a.shape[0], s // ROWS, ROWS, *a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in arrays))
    return jax.tree.map(
        lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], s, *a.shape[3:]), out)


def log_decay(hp: dict, l: int):
    """log lam_h of the layer with PUBLISHED index ``l``: [H] float32."""
    nh = hp["num_attention_heads"]
    f = 1.0 - l / (hp["published_layers"] - 1) + 1e-5
    h = jnp.arange(1, nh + 1, dtype=jnp.float32)
    return -(2.0 ** (-hp["decay_exponent"] * h / nh)) * f


def _lightning(q, k, v, ld, fault):
    """The recurrence, token by token. q, k, v [B, S, H, hd] -> [B, S, H, hd] float32."""
    b, s, nh, hd = q.shape
    lam = jnp.ones((nh,), jnp.float32) if fault == "decay_one" else jnp.exp(ld)

    def token(state, qkv):
        qt, kt, vt = (x.astype(jnp.float32) for x in qkv)       # [B, H, hd]
        state = lam[None, :, None, None] * state + kt[..., :, None] * vt[..., None, :]
        if fault == "state_in_bf16":  # not a cast there and back, which a compiler may drop
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("bhd,bhde->bhe", qt, state,
                                 precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)

    _, o = jax.lax.scan(token, jnp.zeros((b, nh, hd, hd), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 1)


def _sparse(q, k, v, hp, dtype, choice, fault):
    """q [B, S, H, hd], k, v [B, S, Hkv, hd] -> (o [B, S, H, hd], this
    pass's own top-k [B, S, Hkv, topk] (-1 where fewer blocks exist),
    the shortfall [B, S, Hkv] of the blocks attended to: how far the
    least of them lies under this pass's own k-th best score)."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    blk, topk, kern, stride = (hp["block_size"], hp["topk"], hp["kernel_size"],
                               hp["kernel_stride"])
    nj = max((s - kern) // stride + 1, 0)
    nm = max(-(-s // blk), topk)
    # every compressed key of the sequence, in float32 from the keys as held
    if nj:
        span = jnp.arange(nj)[:, None] * stride + jnp.arange(kern)[None, :]    # [J, kernel]
        c = jnp.mean(k[:, span].astype(jnp.float32), axis=2).astype(dtype)
    else:  # shorter than one compressed key: nobody chooses
        c, nj = jnp.zeros((b, 1, nkv, hd), dtype), 1
    key_block = jnp.arange(s) // blk
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def rows(qb, t, cb):
        # qb [B, R, H, hd] at positions t [B, R]; cb the choice handed in or None
        r_ = qb.shape[1]
        n = t + 1
        qg = qb.reshape(b, r_, nkv, nh // nkv, hd)
        sc = jnp.einsum("brkgd,bjkd->brkgj", qg, c).astype(jnp.float32) / math.sqrt(hd)
        done = (jnp.arange(nj)[None, None, :] * stride + kern <= n[:, :, None])
        done = done[:, :, None, None, :]
        p = jax.nn.softmax(jnp.where(done, sc, -1e30), axis=-1)
        r = jnp.sum(jnp.where(done, p, 0.0), axis=3)                    # [B, R, Hkv, J]
        # b_m: the rows j with stride j < blk (m + 1) and stride j + kernel > blk m
        m = jnp.arange(nm)
        score = jnp.zeros((b, r_, nkv, nm), jnp.float32)
        first = -(-(-kern + 1) // stride)  # the least d with stride d + kernel > 0
        for d in range(first, blk // stride):
            j = m * (blk // stride) + d
            ok = (j >= 0) & (j < nj) & (j * stride < (m + 1) * blk) & (j * stride + kern > m * blk)
            score = jnp.maximum(score, jnp.where(ok, r[..., jnp.clip(j, 0, nj - 1)], 0.0))
        forced = (m[None, None, :] < hp["init_blocks"]) | (
            m[None, None, :] >= ((n - hp["window_size"]) // blk)[:, :, None])
        exists = m[None, None, :] <= (t // blk)[:, :, None]
        cand = (exists & ~forced)[:, :, None, :]                        # [B, R, 1, M]
        vals, own = jax.lax.top_k(jnp.where(cand, score, -1.0), topk)
        own = jnp.where(vals >= 0.0, own, -1)
        use = own if cb is None else cb
        took = jnp.any(use[..., :, None] == m[None, None, None, None, :], axis=-2)
        allowed = forced[:, :, None, :] | took                          # [B, R, Hkv, M]
        dense = (n <= hp["dense_len"])[:, :, None, None]  # departure (1): by query position
        if fault == "full_attention_for_choice":
            dense = jnp.ones_like(dense)
        allowed = allowed | dense
        seen = allowed[..., key_block] & (jnp.arange(s)[None, None, None, :] <= t[:, :, None, None])
        att = jnp.einsum("brkgd,bskd->brkgs", qg, k).astype(jnp.float32) / math.sqrt(hd)
        att = jnp.where(seen[:, :, :, None, :], att, -jnp.inf)
        probs = jax.nn.softmax(att, axis=-1).astype(dtype)
        o = jnp.einsum("brkgs,bskd->brkgd", probs, v).reshape(b, r_, nh, hd)
        # how far under this pass's own k-th best the least block taken lies
        kth = jnp.maximum(vals[..., -1], 0.0)
        mine = jnp.take_along_axis(score, jnp.maximum(use, 0), axis=-1)
        short = jnp.max(jnp.where(use >= 0, kth[..., None] - mine, 0.0), axis=-1)
        short = jnp.where(dense[..., 0], 0.0, jnp.maximum(short, 0.0))
        return o, own, short

    if choice is None:
        return _by_rows(lambda qb, t: rows(qb, t, None), s, q, pos)
    return _by_rows(rows, s, q, pos, choice)


def _matmul(dtype):
    return lambda x, w: x.astype(dtype) @ w.astype(dtype)


def _layer(h, w, ld, choice, mixer, hp, dtype, fault):
    """One layer. ``ld``: a lightning layer's log decays [H] (an
    argument, so that the nine of them are one compiled program)."""
    b, s, d = h.shape
    mm = _matmul(dtype)
    nh, nkv, hd = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    depth = len(hp["mixer_types"]) if fault == "scale_by_cut_depth" else hp["published_layers"]
    scale = hp["scale_depth"] / math.sqrt(depth)
    a = _rms_norm(h, w["input_layernorm"], hp["rms_norm_eps"])
    lightning = mixer == "lightning-attn"
    if lightning:
        nkv = nh
    q = mm(a, w["q_proj"]).reshape(b, s, nh, hd)
    k = mm(a, w["k_proj"]).reshape(b, s, nkv, hd)
    v = mm(a, w["v_proj"]).reshape(b, s, nkv, hd)
    q = _rms_norm(q, w["q_norm"], hp["rms_norm_eps"])
    k = _rms_norm(k, w["k_norm"], hp["rms_norm_eps"])
    if lightning or fault == "rope_on_sparse_layers":
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    own = short = None
    if lightning:
        o = _lightning(q, k, v, ld, fault).reshape(b, s, nh * hd)
        o = _rms_norm(o, w["o_norm"], hp["rms_norm_eps"])
    else:
        o, own, short = _sparse(q, k, v, hp, dtype, choice, fault)
        o = o.reshape(b, s, nh * hd)
    gate = jax.nn.sigmoid(mm(a, w["o_gate"]).astype(jnp.float32))
    h = h + (scale * mm((o.astype(jnp.float32) * gate).astype(dtype), w["o_proj"])).astype(dtype)

    def swiglu(hb):
        m = _rms_norm(hb, w["post_attention_layernorm"], hp["rms_norm_eps"])
        out = mm(jax.nn.silu(mm(m, w["gate_proj"])) * mm(m, w["up_proj"]), w["down_proj"])
        return hb + (scale * out).astype(dtype)

    return _by_rows(swiglu, s, h), own, short


@functools.lru_cache(maxsize=64)
def _program(part: str, frozen_hp: tuple, dtype, fault, jitted: bool):
    """The embedding, a layer of one mixer or the head as a function of
    arrays alone, compiled (``by_layer``) or not; kept, so that a second
    pass of the same kind compiles nothing."""
    hp = dict(frozen_hp)
    if part == "embed":
        fn = lambda e, t: (hp["scale_emb"] * e[t].astype(dtype)).astype(dtype)
    elif part == "head":
        def fn(h, g, head, at):
            if at is not None:
                h = jnp.take_along_axis(h, at[:, :, None], axis=1)
            x = _rms_norm(h, g, hp["rms_norm_eps"]).astype(jnp.float32)
            x = (x / (hp["hidden_size"] / hp["dim_model_base"])).astype(dtype)
            return _matmul(dtype)(x, head).astype(jnp.float32)
    else:
        fn = lambda h, w, ld, c: _layer(h, w, ld, c, part, hp, dtype, fault)
    return jax.jit(fn) if jitted else fn


def _forward(weights, tokens, hp, dtype, at, choice, fault, jitted):
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in hp.items()))
    program = lambda part: _program(part, frozen, dtype, fault, jitted)
    h = program("embed")(weights["embed"], tokens)
    owns, shorts, n_sparse = [], [], 0
    for w, mixer, l in zip(weights["layers"], hp["mixer_types"], hp["layer_indices"]):
        sparse = mixer == "minicpm4"
        c = choice[n_sparse] if (choice is not None and sparse) else None
        h, own, short = program(mixer)(h, w, None if sparse else log_decay(hp, l), c)
        if sparse:
            owns.append(own)
            shorts.append(short)
            n_sparse += 1
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"].T
    return program("head")(h, weights["final_norm"], head, at), owns, shorts


def forward(weights, tokens, hp: dict, dtype=jnp.float32, at=None, choice=None,
            fault=None, with_choice=False, by_layer=False):
    """tokens [B, S] int32 -> logits [B, S, V] float32, or [B, n, V] at
    the positions ``at`` [B, n]. ``choice`` [sparse layers, B, S, Hkv,
    topk] int32 (-1: no block) is followed by the queries past
    ``dense_len``; None: this pass's own. ``with_choice`` adds {"own":
    this pass's own top-k for every sparse layer, "shortfall": [sparse
    layers, B, S, Hkv]}. ``by_layer`` (not under a trace of the
    caller's): the embedding, each layer and the head run as compiled
    programs of their own, so that one layer's weights at a time stand
    cast to ``dtype`` beside the stored ones."""
    dtype = jnp.dtype(dtype)
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    args = (weights, tokens, hp, dtype, at, choice, fault, bool(by_layer))
    if dtype == jnp.float32:
        with jax.default_matmul_precision("highest"):
            logits, owns, shorts = _forward(*args)
    else:
        logits, owns, shorts = _forward(*args)
    if with_choice:
        return logits, {"own": jnp.stack(owns), "shortfall": jnp.stack(shorts)}
    return logits
