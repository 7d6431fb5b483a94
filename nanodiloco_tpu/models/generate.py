"""Autoregressive generation with a static-shape KV cache.

No reference analog (the reference is training-only; its model would rely
on HF ``generate``, ref nanodiloco/main.py:97-99) — but a framework whose
users train language models needs to sample from them. The design is
TPU-native throughout:

- ONE jitted program per (config, shape) pair: prefill + the whole decode
  loop compile together; the decode loop is a ``lax.scan`` over steps, so
  there are no per-token dispatches (the usual host-bound decode loop
  costs one dispatch per token).
- The KV cache is preallocated at ``[L, B, S_max, Hkv, hd]`` and written
  with ``lax.dynamic_update_slice`` — static shapes, no growing arrays.
  It rides the layer ``lax.scan`` as per-layer carry slices, mirroring
  the training forward's scan-over-layers layout (models/llama.py), so
  the same stacked parameter pytree works unchanged.
- Decode attention is GQA-native: query heads are grouped against the
  Hkv cache heads with einsums — cached K/V are never expanded to the
  full query-head count in HBM (decode is K/V-bandwidth-bound; this is
  the entire point of GQA).
- Long contexts tile the cache: from 1024 total context the scores use
  the shared online-softmax recurrence (ops/online_softmax.py) over
  512-key blocks, bounded by the live prefix — O(block) score memory
  and no reads of the untouched cache tail (``decode_block``).

Variable-length prompts are handled with a right-aligned convention:
``prompt_len`` marks each row's true length; shorter prompts are padded
on the LEFT by the caller (or via ``pad_prompts``) so the last prompt
token always sits at the same static position. Pad positions are masked
out of attention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from nanodiloco_tpu.models import linear_attention, sparse_attention
from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.models.llama import (
    MASK_VALUE,
    Params,
    apply_rope,
    attn_output,
    layer_plan,
    mixed_mlp_block,
    mlp_block,
    qkv_proj,
    residual,
    rms_norm,
    rope_tables,
    run_layers,
)
from nanodiloco_tpu.ops.online_softmax import block_update, finalize_grouped


def init_kv_cache(cfg: LlamaConfig, batch: int, max_length: int) -> dict:
    """Preallocated cache: k/v [L, B, S_max, Hkv, hd] in compute dtype.
    A mixed configuration's cache follows its ``layer_plan``
    (``_plan_cache``): one k/v [B, S_max, Hkv, hd] a layer, every layer
    at full length here (the window is in the mask; the serve engine's
    rings are ``init_mixed_serve_cache``)."""
    cdt = jnp.dtype(cfg.dtype)
    if cfg.mixed:
        shape = (batch, max_length, cfg.kv_heads, cfg.head_dim)
        return _plan_cache(cfg, lambda kind: {"k": jnp.zeros(shape, cdt),
                                              "v": jnp.zeros(shape, cdt)})
    shape = (
        cfg.num_hidden_layers, batch, max_length, cfg.kv_heads, cfg.head_dim,
    )
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


def _plan_cache(cfg: LlamaConfig, entry) -> dict:
    """The cache ``run_layers`` takes: ``entry(attention kind)`` (zeros)
    for each leading layer, and for each layer of the period the same
    zeros stacked over the periods."""
    plan = layer_plan(cfg)
    lead, period = _plan_kinds(cfg)

    def stacked(kind):
        # made at its stacked shape: a broadcast of the unstacked zeros
        # would hold both at once, and a serving cache is gigabytes
        shapes = jax.eval_shape(lambda: entry(kind))
        return jax.tree.map(lambda a: jnp.zeros((plan.periods,) + a.shape, a.dtype), shapes)

    return {"lead": tuple(entry(kind) for kind in lead),
            "period": tuple(stacked(kind) for kind in period)}


def _plan_kinds(cfg: LlamaConfig) -> tuple[list, list]:
    """Attention kinds of the leading layers and of one period's layers:
    the order of a plan cache's entries."""
    plan = layer_plan(cfg)
    kinds = [kind for kind, _ in plan.kinds]
    n = plan.period if plan.periods else 0
    return kinds[:plan.lead], kinds[plan.lead:plan.lead + n]


def _mixed_layer(cfg: LlamaConfig, x, layer, kind, rope, attend, token_valid):
    """One cached layer of a mixed configuration: the projections
    (``qkv_proj``), ``attend(q, k, v) -> (attention [B, T, H * hd], the
    layer's updated cache entry)``, the output projection and the dense
    or sparse feed-forward. Returns ``run_layers``' (x, entry, counters,
    chosen experts)."""
    cdt = x.dtype
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn_proj"):
        q, k, v = qkv_proj(cfg, h, layer, rope, kind[0])
    attn, entry = attend(q, k, v)
    if cfg.state_layers:
        # a sparse or linear layer hands out its own counters and choice
        # beside the entry; neither kind stands beside expert layers
        entry, counters, chosen = entry
        attn = attn_output(cfg, attn, h, layer, kind[0])
    with jax.named_scope("attn_proj"):
        x = residual(cfg, x, attn @ layer["wo"].astype(cdt))
    if cfg.state_layers:
        return mixed_mlp_block(cfg, x, layer, token_valid)[0], entry, counters, chosen
    x, counters, chosen = mixed_mlp_block(cfg, x, layer, token_valid)
    return x, entry, counters, chosen


def _cached_block_mixed(params, cfg: LlamaConfig, tokens, cache, pos, key_valid,
                        token_valid, last_index):
    """``_cached_block`` for a mixed configuration: the same contract
    over ``init_kv_cache``'s per-layer cache, dense scores over the
    whole cache with the sliding layers' window in their mask."""
    cdt = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    with jax.named_scope("attn_proj"):
        cos, sin = rope_tables(cfg, t, offset=pos)
    s_max = key_valid.shape[1]
    qi = pos + jnp.arange(t)
    with jax.named_scope("attention"):
        ki = jnp.arange(s_max)[None, None, :]
        ok = (ki <= qi[None, :, None]) & (key_valid[:, None, :] > 0)
        near = ok & (qi[None, :, None] - ki < (cfg.sliding_window or 0))
        masks = {"full_attention": jnp.where(ok, 0.0, MASK_VALUE)[:, None],
                 "sliding_attention": jnp.where(near, 0.0, MASK_VALUE)[:, None]}

    def body(x, layer, kind, c):
        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                ck = jax.lax.dynamic_update_slice(c["k"], k, (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(c["v"], v, (0, pos, 0, 0))
            return _slot_attention(q, ck, cv, masks[kind[0]]), {"k": ck, "v": cv}

        return _mixed_layer(cfg, x, layer, kind, lambda a: apply_rope(a, cos, sin),
                            attend, token_valid)

    x, cache, _, _ = run_layers(cfg, params, x, body, cache)
    xl = x[:, -1] if last_index is None else \
        jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)[:, 0]
    x = rms_norm(xl, params["final_norm"], cfg.rms_norm_eps)
    return _head_logits(params, x, cdt), cache


def _cached_block(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,        # [B, T] — T = prompt length (prefill) or 1
    cache: dict,              # k/v [L, B, S_alloc, Hkv, hd]
    pos: jax.Array,           # scalar int32: write offset into the cache
    key_valid: jax.Array,     # [B, S_alloc] 1 = cache position holds a real token
    token_valid: jax.Array,   # [B, T] 1 = input token is real (left-pad = 0);
                              # MoE routing must not spend capacity on pads
    block: int = 0,           # 0 = dense scores over the full cache;
                              # >0 = online-softmax over cache blocks
                              # (S_alloc must be a multiple of block)
    last_index=None,          # traced scalar: position within [0, T) whose
                              # logits to return (None = the static last row;
                              # chunked prefill's final chunk may carry
                              # right-padding after its last real token)
):
    """Run the decoder over ``tokens``, reading/writing the KV cache at
    ``pos``. Returns (last-position logits [B, V] float32, updated
    cache) — only the final position is ever sampled, so the vocabulary
    head is applied to it alone (at Llama-3-8B scale, full-prompt prefill
    logits would be a multi-GB [B, P, V] tensor computed to be thrown
    away).

    With ``block > 0`` attention uses the shared flash recurrence
    (ops/online_softmax.py): scores exist one ``[*, block]`` tile at a
    time instead of ``[B, nkv, G, T, S_alloc]`` — O(block) score memory
    at the long contexts the training side supports (VERDICT r2 weak #5)
    — and the block loop's upper bound is the live prefix ``pos + T``,
    so early decode steps never touch the untouched cache tail."""
    if cfg.state_layers:
        raise ValueError(
            "generate()'s one-program cache holds K and V rows alone: a "
            "linear_attention layer's per-row state and a sparse_attention "
            "layer's compressed keys are kept by the serving engine only "
            "(serve/engine.py: InferenceEngine), and left-padded prompts "
            "would need a mask neither carries")
    if cfg.mixed:
        if block:
            raise ValueError(
                "blockwise (online-softmax) cached attention does not carry a "
                "mixed layer stack's sliding-window layers; use decode_block=0")
        return _cached_block_mixed(params, cfg, tokens, cache, pos, key_valid,
                                   token_valid, last_index)
    cdt = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    s_max = cache["k"].shape[2]
    nh, nkv, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    g = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    if block and s_max % block:
        raise ValueError(f"cache length {s_max} not a multiple of block {block}")

    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    with jax.named_scope("attn_proj"):
        cos, sin = rope_tables(cfg, t, offset=pos)

    qi = pos + jnp.arange(t)  # [T] global query positions
    if not block:
        # Additive mask [B, T, S]: query at global position pos+qi may see
        # cache key ki when ki <= pos+qi AND the slot holds a real token.
        with jax.named_scope("attention"):
            ki = jnp.arange(s_max)[None, None, :]
            ok = (ki <= qi[None, :, None]) & (key_valid[:, None, :] > 0)
            mask = jnp.where(ok, 0.0, MASK_VALUE)[:, None]  # [B, 1, T, S]

    def attn_dense(qg, ck, cv):
        # grouped GQA attention against the full cache (softmax in fp32)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck).astype(jnp.float32)
        scores = scores * scale + mask[:, :, None]  # [B, nkv, G, T, S]
        probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
        attn = jnp.einsum("bkgts,bskd->btkgd", probs, cv)
        return attn.reshape(b, t, nh * hd)

    def attn_blockwise(qg, ck, cv):
        # Query rows fold (G, T) position-fastest so finalize_grouped
        # restores the HF head order h = hkv * G + g.
        qr = jnp.transpose(qg, (0, 2, 3, 1, 4)).reshape(b, nkv, g * t, hd)
        o = jnp.zeros((b, nkv, g * t, hd), jnp.float32)
        l = jnp.zeros((b, nkv, g * t), jnp.float32)
        m = jnp.full((b, nkv, g * t), -jnp.inf, jnp.float32)

        def body(j, carry):
            o, l, m = carry
            off = j * block
            ckj = jax.lax.dynamic_slice(ck, (0, off, 0, 0), (b, block, nkv, hd))
            cvj = jax.lax.dynamic_slice(cv, (0, off, 0, 0), (b, block, nkv, hd))
            kvj = jax.lax.dynamic_slice(key_valid, (0, off), (b, block))
            ki = off + jnp.arange(block)
            ok = (ki[None, None, :] <= qi[None, :, None]) & (kvj[:, None, :] > 0)
            s = jnp.einsum("bhqd,bkhd->bhqk", qr, ckj).astype(jnp.float32)
            okr = jnp.broadcast_to(
                ok[:, None, None], (b, 1, g, t, block)
            ).reshape(b, 1, g * t, block)
            s = jnp.where(okr, s * scale, -jnp.inf)
            return block_update(o, l, m, s, jnp.transpose(cvj, (0, 2, 1, 3)))

        # traced upper bound: only blocks intersecting [0, pos+T) exist
        n_live = (pos + t + block - 1) // block
        o, l, m = jax.lax.fori_loop(0, n_live, body, (o, l, m))
        return finalize_grouped(o, l, g, cdt).reshape(b, t, nh * hd)

    def layer_body(x, scanned):
        layer, ck, cv = scanned  # layer params + this layer's cache slices
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn_proj"):
            q = (h @ layer["wq"].astype(cdt)).reshape(b, t, nh, hd)
            k = (h @ layer["wk"].astype(cdt)).reshape(b, t, nkv, hd)
            v = (h @ layer["wv"].astype(cdt)).reshape(b, t, nkv, hd)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        with jax.named_scope("kv_write"):
            ck = jax.lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))

        with jax.named_scope("attention"):
            qg = q.reshape(b, t, nkv, g, hd)
            attn = (attn_blockwise if block else attn_dense)(qg, ck, cv)
        with jax.named_scope("attn_proj"):
            x = x + attn @ layer["wo"].astype(cdt)

        x, _aux = mlp_block(cfg, x, layer, valid=token_valid)
        return x, (ck, cv)

    # layer_scan: what the scan itself does to its per-layer operands
    # (a layer's weights and cache sliced out, the cache stacked back)
    with jax.named_scope("layer_scan"):
        x, (ck, cv) = jax.lax.scan(
            layer_body, x, (params["layers"], cache["k"], cache["v"])
        )
    if last_index is None:
        xl = x[:, -1]  # [B, d]
    else:
        # same gather the static slice performs, at a traced index —
        # op-for-op identical math, so a chunked prefill whose last real
        # token is not the chunk's last row stays on the generate() path
        xl = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)[:, 0]
    x = rms_norm(xl, params["final_norm"], cfg.rms_norm_eps)  # [B, d]
    return _head_logits(params, x, cdt), {"k": ck, "v": cv}


@jax.named_scope("head")
def _head_logits(params: Params, x, cdt):
    """Final-normed hidden states -> float32 logits through the
    vocabulary head (the tied embedding's transpose where there is no
    ``lm_head``): the one head of every cached and serve program."""
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    return (x @ head.astype(cdt)).astype(jnp.float32)


def _auto_decode_block(context_len: int) -> int:
    """Default attention tiling for a given total context: dense scores
    below 1024 (one fused XLA attention beats a short block loop), 512-key
    online-softmax tiles from 1024 up (score memory stays O(block) no
    matter how long the cache grows)."""
    return 512 if context_len >= 1024 else 0


@jax.named_scope("sample")
def _sample(logits, key, temperature: float, top_k: int, top_p: float = 1.0):
    """[B, V] logits -> [B] int32. temperature 0 = greedy (key unused);
    ``top_k`` keeps the k best logits; ``top_p`` < 1 keeps the smallest
    set of tokens whose probability mass reaches p (nucleus sampling;
    applied after top_k, both post-temperature)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, MASK_VALUE, logits)
    if 0.0 < top_p < 1.0:
        # a sorted token is IN the nucleus iff the mass strictly before
        # it is < p (so the best token always survives, and when float
        # rounding keeps the cumsum below p — top_p ~ 1.0 on a big
        # vocab — the filter gracefully removes nothing instead of
        # collapsing to greedy)
        sl = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sl, axis=-1)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        thresh = jnp.min(
            jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < thresh, MASK_VALUE, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.lru_cache(maxsize=8)
def _build_generate(
    cfg: LlamaConfig, batch: int, prompt_len: int, max_new_tokens: int,
    temperature: float, top_k: int, mesh=None, stop_token: int | None = None,
    decode_block: int = 0, top_p: float = 1.0,
):
    s_max = prompt_len + max_new_tokens
    # blockwise attention needs a block-aligned cache; the extra slots are
    # causally unreachable (their index exceeds every query position)
    s_alloc = (
        ((s_max + decode_block - 1) // decode_block) * decode_block
        if decode_block else s_max
    )

    def run(params, prompt, prompt_valid, key):
        if mesh is not None:
            # sharded decode (e.g. a tp/fsdp-sharded 8B): constrain the
            # params to the training sharding rules and let GSPMD
            # partition the cache and einsums around them. Lazy import:
            # parallel imports models, so the reverse edge must not be
            # at module top.
            from nanodiloco_tpu.parallel.sharding import constrain, param_specs

            params = constrain(params, mesh, param_specs(cfg))
        cache = init_kv_cache(cfg, batch, s_alloc)
        # prefill: the whole (left-padded) prompt in one block
        key_valid = jnp.concatenate(
            [
                prompt_valid,
                jnp.ones((batch, max_new_tokens), jnp.int32),
                jnp.zeros((batch, s_alloc - s_max), jnp.int32),
            ],
            axis=1,
        )
        logits, cache = _cached_block(
            params, cfg, prompt, cache, jnp.int32(0), key_valid, prompt_valid,
            block=decode_block,
        )
        key, k0 = jax.random.split(key)
        tok0 = _sample(logits, k0, temperature, top_k, top_p)
        if max_new_tokens == 1:
            return tok0[:, None]

        dec_valid = jnp.ones((batch, 1), jnp.int32)  # generated tokens are real
        # rows that emitted stop_token keep emitting it (static shapes:
        # the scan always runs max_new_tokens steps; finished rows are
        # pinned, not exited — the caller truncates at the stop token)
        done0 = (
            tok0 == stop_token if stop_token is not None
            else jnp.zeros((batch,), bool)
        )

        def step(carry, step_key):
            cache, pos, tok, done = carry
            logits, cache = _cached_block(
                params, cfg, tok[:, None], cache, pos, key_valid, dec_valid,
                block=decode_block,
            )
            nxt = _sample(logits, step_key, temperature, top_k, top_p)
            if stop_token is not None:
                nxt = jnp.where(done, jnp.int32(stop_token), nxt)
                done = done | (nxt == stop_token)
            return (cache, pos + 1, nxt, done), nxt

        # max_new_tokens - 1 steps: the first new token came from prefill,
        # and each step emits the token it just sampled (no trailing
        # forward pass whose sample would be discarded)
        keys = jax.random.split(key, max_new_tokens - 1)
        _, rest = jax.lax.scan(
            step, (cache, jnp.int32(prompt_len), tok0, done0), keys
        )
        return jnp.concatenate([tok0[None], rest], axis=0).T  # [B, N]

    return jax.jit(run)


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: LlamaConfig,
    max_new_tokens: int,
    *,
    prompt_valid: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    key: jax.Array | None = None,
    mesh=None,
    stop_token: int | None = None,
    decode_block: int | None = None,
) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` [B, P].

    Returns the new tokens [B, max_new_tokens] (int32). ``temperature=0``
    is greedy decoding; otherwise pass ``key`` (and optionally ``top_k``
    and/or nucleus ``top_p``) for stochastic sampling. ``prompt_valid`` [B, P] marks real prompt
    tokens for left-padded variable-length prompts (default: all real).
    ``mesh`` shards the decode over its ``tp``/``fsdp`` axes (the
    training sharding rules, parallel/sharding.py) — for models too big
    for one device. ``stop_token`` pins a row to that token once emitted
    (shapes stay static; truncate at the first stop token). The whole
    prefill+decode runs as one compiled program, cached per
    (config, shape, sampling, mesh) signature.

    ``decode_block``: attention tile size over the KV cache. ``None``
    (default) auto-selects — dense scores for short contexts, the
    online-softmax block recurrence at 512-key tiles once the context
    reaches 1024 so score memory stays O(block) however long the cache
    is. Pass an explicit block size, or 0 to force the dense path.

    Known divergence from the training forward (token-choice MoE,
    ADVICE r2): expert capacity is sized from the tokens in the CURRENT
    call — B×P real tokens at prefill, B at each decode step — while
    training routes over the full B×S batch. When the capacity factor is
    ample (default 4.0) routing is identical; when capacity BINDS, which
    tokens overflow to the residual path differs between a training
    forward over the same text and prefill/decode, so logits can diverge.
    Keep capacity_factor generous for sampling, or treat bound-capacity
    sampling as approximate. ``moe_dispatch="ragged"`` has no capacity
    at all, so this divergence does not exist there: cached decode
    routes exactly as the training forward at ANY capacity factor
    (tested: tests/test_generate.py ragged greedy parity).
    """
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be [batch, prompt_len]; got {prompt.shape}")
    if cfg.num_experts and cfg.router_type == "experts_choose":
        raise ValueError(
            "expert-choice routing is training-only: expert top-C token "
            "selection sees the whole token set, so prefill and per-step "
            "decode route differently (arXiv:2202.09368's known "
            "acausality); use router_type='tokens_choose' for sampling"
        )
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1; got {max_new_tokens}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0; got {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0; got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
    top_k = min(int(top_k), cfg.vocab_size)  # top-k over everything == no cut
    if temperature > 0.0 and key is None:
        raise ValueError("stochastic sampling (temperature > 0) requires a PRNG key")
    if key is None:
        key = jax.random.key(0)  # unused by greedy sampling
    b, p = prompt.shape
    if prompt_valid is None:
        prompt_valid = jnp.ones((b, p), jnp.int32)
    if decode_block is None:
        # a mixed layer stack's window layers run dense scores only
        decode_block = 0 if cfg.mixed else _auto_decode_block(p + max_new_tokens)
    elif decode_block < 0:
        raise ValueError(f"decode_block must be >= 0; got {decode_block}")
    fn = _build_generate(
        cfg, b, p, int(max_new_tokens), float(temperature), int(top_k), mesh,
        None if stop_token is None else int(stop_token), int(decode_block),
        float(top_p),
    )
    if mesh is not None:
        with jax.set_mesh(mesh):
            return fn(params, prompt.astype(jnp.int32), prompt_valid, key)
    return fn(params, prompt.astype(jnp.int32), prompt_valid, key)


def pad_prompts(prompts: list[list[int]], pad_id: int = 0):
    """Left-pad variable-length prompts to a common length; returns
    (tokens [B, P], valid [B, P]) ready for ``generate``. An empty ROW is
    allowed (all-pad, valid all zero — the caller decides whether an
    empty prompt is meaningful); an empty LIST is not."""
    if not prompts:
        raise ValueError("pad_prompts needs at least one prompt")
    p = max(len(x) for x in prompts)
    toks = np.full((len(prompts), p), pad_id, np.int32)
    valid = np.zeros((len(prompts), p), np.int32)
    for i, x in enumerate(prompts):
        if len(x):
            toks[i, p - len(x):] = x
            valid[i, p - len(x):] = 1
    return jnp.asarray(toks), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# Slot-addressed serving programs (nanodiloco_tpu/serve)
#
# The continuous-batching engine owns ONE block arena
# [L, num_blocks, block_size, Hkv, hd] addressed through per-slot block
# tables — a slot holds only the blocks its sequence actually occupies,
# so HBM caps concurrency by TOKENS RESIDENT, not slots x worst-case
# S_max. The programs covering a request's whole life:
#   - prefill_chunk_paged_fn: write one CHUNK of a request's prompt K/V
#     into its slot at a traced offset (the same ``_cached_block`` the
#     one-shot ``generate`` prefill uses, so the two paths can never
#     drift), return the chunk's last-real-position logits AND the
#     token sampled from them — sampling is fused into the chunk
#     program, so a final chunk is ONE dispatch, not
#     attention-then-sample. Chunk lengths are BUCKETED to powers of
#     two up to the engine's chunk size, so the compile count is
#     bounded by log2(chunk_size)+1 — NOT one executable per prompt
#     length, the PR-4 recompile trap. It gathers the slot's contiguous
#     view through its block table and scatters only the touched blocks
#     back (out-of-range table entries drop, so a bucketed pad tail
#     past the slot's allocation is a no-op write).
#   - decode_slots_paged_fn: advance ALL slots one token with PER-SLOT
#     positions, PRNG keys, and sampling params, sampling fused in —
#     one executable per tick does attention+sampling with zero extra
#     dispatch; compiled once per (config, B, table width) — admitting
#     or retiring a request never recompiles anything. It gathers each
#     layer's K/V through the block tables INSIDE the layer scan, so
#     the contiguous working view exists one layer at a time, and
#     writes each slot's new row by physical (block, offset) scatter
#     (inactive slots are redirected out of range and dropped). The
#     view is NOT the table's whole width: the tick reads the first
#     ``w`` blocks of every table, ``w`` the narrowest of a fixed ladder
#     of widths that holds the longest live slot's rows, picked inside
#     the program from the positions (``view_ladder``, below).
# A table is one chunk of sentinel entries wider than any allocation
# (the engine's ``table_blocks``): a right-padded final chunk's rows
# past the allocation then still fall inside the table, where their
# writes drop, and the ladder's top width is that whole table, so a
# call at the very top of an allocation fits its view too.
# A shared prefix is shared BLOCKS, by reference: no program copies K/V
# rows. Sampling params ride as traced arrays so a new request with new
# temperature/top_k/top_p reuses the same executable.
#
# int8 KV: the arena stores int8 K/V plus one float32
# scale per (layer, block, row) — quantize on write (scale =
# amax(|row|)/127 over the row's [Hkv, hd] values), dequantize in the
# attention read. Per-ROW scales mean appending a token never
# requantizes earlier rows, so there is no accumulation of repeated
# quantization error; rewriting an untouched row round-trips to the
# same int8 bits (the scale reproduces to within 2^-23 relative, and
# |q| <= 127 keeps round() exact). ~4x serve slots per HBM byte vs a
# float32 cache at the cost of a bounded logit perturbation — the float
# pool stays bit-identical to solo ``generate()``.
# ---------------------------------------------------------------------------


def init_kv_pool(cfg: LlamaConfig, num_blocks: int, block_size: int,
                 kv_dtype: str | None = None) -> dict:
    """Preallocated block arena: k/v ``[L, num_blocks, block_size, Hkv,
    hd]``. ``kv_dtype="int8"`` stores int8 values plus per-(layer,
    block, row) float32 scales ``ks``/``vs`` ``[L, num_blocks,
    block_size]``; otherwise the compute dtype (paged-fp)."""
    shape = (
        cfg.num_hidden_layers, num_blocks, block_size, cfg.kv_heads,
        cfg.head_dim,
    )
    if kv_dtype == "int8":
        sshape = shape[:3]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sshape, jnp.float32),
            "vs": jnp.zeros(sshape, jnp.float32),
        }
    cdt = jnp.dtype(kv_dtype or cfg.dtype)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


def kv_bytes_per_token(cfg: LlamaConfig, kv_dtype: str | None = None) -> int:
    """HBM bytes one cached token position costs: K+V rows across all
    layers, plus the per-row scales in int8 mode — the accounting the
    capacity bench and the admission arithmetic share."""
    row = cfg.num_hidden_layers * cfg.kv_heads * cfg.head_dim
    if kv_dtype == "int8":
        return 2 * row + 2 * cfg.num_hidden_layers * 4  # int8 + f32 scales
    return 2 * row * jnp.dtype(kv_dtype or cfg.dtype).itemsize


def _quantize_rows(rows):
    """``[..., Hkv, hd]`` float rows -> (int8 rows, float32 scale
    ``[...]``): symmetric per-row quantization at amax/127. The amax
    floor keeps all-zero rows (never-written cache) at scale ~0 without
    a divide-by-zero."""
    f = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=(-2, -1))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(f / scale[..., None, None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale


def _dequantize_rows(q, scale, cdt):
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(cdt)


@jax.named_scope("sample")
def _sample_slots(logits, keys, temperature, top_k, top_p):
    """Per-slot ``_sample``: [B, V] logits with PER-ROW key / temperature /
    top_k / top_p arrays -> [B] int32. Same op sequence as ``_sample``
    (division, k-th-largest cut, nucleus threshold over the top_k
    survivors, categorical), with the static Python gates replaced by
    no-op thresholds (-inf) so every row shares one traced program:
    temperature 0 = greedy, top_k 0 = no cut, top_p >= 1 = no nucleus.

    The no-op gates are also SKIPPED at runtime (``lax.cond`` on the
    whole batch): an all-greedy tick runs argmax alone, and a sampled
    tick without top_k/top_p skips the two full-vocab sorts — measured
    at >80% of a decode/verify dispatch on CPU for a [B, 2048] vocab.
    Bit-exact by construction: a skipped filter is one whose thresholds
    were -inf (an identity ``where``), and a skipped categorical is one
    whose draw the final ``temperature > 0`` select would discard."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def stochastic(operands):
        logits, key_data, temperature, top_k, top_p = operands
        t = temperature[:, None]
        scaled = logits / jnp.where(t > 0.0, t, 1.0)

        def filtered(scaled):
            # k-th largest of the scaled logits == lax.top_k(...)[0][..., -1:]
            sl = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
            kth = jnp.take_along_axis(
                sl, jnp.clip(top_k[:, None] - 1, 0, v - 1), axis=-1
            )
            kth = jnp.where(top_k[:, None] > 0, kth, -jnp.inf)
            filt = jnp.where(scaled < kth, MASK_VALUE, scaled)
            # nucleus over the top_k-filtered logits (same composition
            # order and same keep rule as _sample: mass strictly BEFORE
            # a token < p)
            sl2 = jnp.flip(jnp.sort(filt, axis=-1), axis=-1)
            probs = jax.nn.softmax(sl2, axis=-1)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
            thresh = jnp.min(
                jnp.where(keep, sl2, jnp.inf), axis=-1, keepdims=True
            )
            thresh = jnp.where(top_p[:, None] < 1.0, thresh, -jnp.inf)
            return jnp.where(filt < thresh, MASK_VALUE, filt)

        filt = jax.lax.cond(
            jnp.any(top_k > 0) | jnp.any(top_p < 1.0),
            filtered, lambda s: s, scaled,
        )
        keys = jax.random.wrap_key_data(key_data)
        return jax.vmap(jax.random.categorical)(keys, filt).astype(jnp.int32)

    drawn = jax.lax.cond(
        jnp.any(temperature > 0.0),
        stochastic, lambda operands: greedy,
        (logits, jax.random.key_data(keys), temperature, top_k, top_p),
    )
    return jnp.where(temperature > 0.0, drawn, greedy)


def _serve_donate():
    # donating the cache makes each tick update in place on accelerators;
    # CPU has no donation and would warn on every call
    return () if jax.default_backend() == "cpu" else (1,)


# -- tensor-parallel serving (mesh != None on the serve programs) -----------
#
# Every serve program below takes an optional ``mesh``: params are
# constrained to the training partition rules (parallel/sharding.py
# ``param_specs`` — the same layout solo ``generate(mesh=...)`` uses, so
# a TP-served stream and a TP solo run shard every matmul identically
# and stay BIT-identical on the same layout), the KV arenas are
# constrained to ``kv_arena_leaf_spec`` (head-sharded: each shard owns its
# own KV heads' rows end to end — no K/V ever crosses a shard), and the
# final logits are constrained to REPLICATED before sampling, so the
# fused per-slot sampling — and with it the per-step PRNG key schedule —
# runs exactly as on one device. The only cross-shard reductions are the
# ones the param specs imply (the wo / w_down row-parallel psums), which
# GSPMD inserts; nothing here issues a collective.


def _tp_params(params, cfg: LlamaConfig, mesh):
    # lazy import: parallel imports models, so the reverse edge must not
    # be at module top (same note as generate()'s sharded path)
    from nanodiloco_tpu.parallel.sharding import constrain, param_specs

    return constrain(params, mesh, param_specs(cfg))


def _tp_kv(kv: dict, mesh) -> dict:
    """Constrain a KV arena pytree per ``kv_arena_leaf_spec`` (5-d k/v
    on the KV-head axis, the int8 per-row scales replicated)."""
    from jax.sharding import NamedSharding

    from nanodiloco_tpu.parallel.sharding import kv_arena_leaf_spec

    return {
        name: jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, kv_arena_leaf_spec(arr.ndim))
        )
        for name, arr in kv.items()
    }


def _tp_replicated(x, mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec())
    )


def _sample_one(logits, key_data, temperature, top_k, top_p):
    """Single-row ``_sample_slots`` over raw key data: the fused
    prefill-side sample (same op sequence the decode tick uses)."""
    key = jax.random.wrap_key_data(key_data)
    return _sample_slots(
        logits, key[None], temperature[None], top_k[None], top_p[None]
    )[0]


@functools.lru_cache(maxsize=8)
def prefill_chunk_paged_fn(cfg: LlamaConfig, kv_dtype: str | None = None,
                           mesh=None):
    """Jitted ``(params, pool, table [max_blocks] i32, chunk [1,C],
    chunk_valid [1,C], pos, last_idx, key_data [2]u32, temperature,
    top_k, top_p) -> (token scalar, logits [1,V] float32, pool)``: run
    ONE chunk of a prompt at positions ``[pos, pos+C)`` of the slot
    whose block table this is, and sample from its last-real-position
    logits in the same executable (an interior chunk's sample is
    discarded by the caller — a vocab sort, noise next to the decoder).
    Gathers the slot's contiguous K/V view through its WHOLE block
    table, every layer's at once (clamped out-of-range sentinel entries
    read causally-dead garbage; the ticks' ladder of view widths is not
    taken here: ``_cached_block`` wants all layers' views before its
    layer scan),
    runs the SAME ``_cached_block`` the one-shot ``generate`` prefill
    runs — so float-pool logits are bit-identical to solo
    ``generate()`` — and scatters only the touched blocks back. The
    engine guarantees ``pos`` is block-aligned (chunk starts are
    multiples of chunk_size and block_size divides chunk_size), so the
    touched window is ``[pos, pos + max(C, block_size))``; rows past
    the slot's allocation are pad positions whose writes drop at the
    out-of-range sentinel. ``chunk_valid`` zeroes pad tokens out of MoE
    routing. int8 mode dequantizes the gather and quantizes the
    scattered rows per-row (see module notes: rewriting an untouched
    row round-trips). ``pos`` and ``last_idx`` are traced: one
    executable per CHUNK LENGTH covers every slot, offset and amount
    of right-padding."""
    quant = kv_dtype == "int8"

    def run(params, pool, table, chunk, chunk_valid, pos, last_idx,
            key_data, temperature, top_k, top_p):
        if mesh is not None:
            params = _tp_params(params, cfg, mesh)
            pool = _tp_kv(pool, mesh)
        cdt = jnp.dtype(cfg.dtype)
        l, nb, bs, nkv, hd = pool["k"].shape
        mb = table.shape[0]

        @jax.named_scope("kv_gather")
        def gathered(name, sname):
            g = pool[name][:, table]  # [L, mb, bs, Hkv, hd]
            if quant:
                g = _dequantize_rows(g, pool[sname][:, table], cdt)
            return g.reshape(l, 1, mb * bs, nkv, hd).astype(cdt)

        sub = {"k": gathered("k", "ks"), "v": gathered("v", "vs")}
        key_valid = jnp.ones((1, mb * bs), jnp.int32)
        logits, sub = _cached_block(
            params, cfg, chunk, sub, pos, key_valid, chunk_valid,
            block=0, last_index=last_idx,
        )
        c = chunk.shape[1]
        # one block wider than the chunk itself: covers an unaligned
        # start (the engine sends none: see above) and costs one
        # identity rewrite of already-gathered rows in the aligned case
        n_touch = min(c // bs + 1, mb) if c >= bs else 1
        # both slices clamp to the same block boundary; the explicit
        # min keeps the table slice and the data slice in lockstep
        new = {}
        with jax.named_scope("kv_write"):
            b0 = jnp.minimum(pos // bs, mb - n_touch)
            phys = jax.lax.dynamic_slice(table, (b0,), (n_touch,))
            for name, sname in (("k", "ks"), ("v", "vs")):
                w = jax.lax.dynamic_slice(
                    sub[name], (0, 0, b0 * bs, 0, 0),
                    (l, 1, n_touch * bs, nkv, hd),
                ).reshape(l, n_touch, bs, nkv, hd)
                if quant:
                    q, sc = _quantize_rows(w)
                    new[name] = pool[name].at[:, phys].set(q, mode="drop")
                    new[sname] = pool[sname].at[:, phys].set(sc, mode="drop")
                else:
                    new[name] = pool[name].at[:, phys].set(
                        w.astype(pool[name].dtype), mode="drop"
                    )
        if mesh is not None:
            logits = _tp_replicated(logits, mesh)
            new = _tp_kv(new, mesh)
        tok = _sample_one(logits, key_data, temperature, top_k, top_p)
        return tok, logits, new

    return jax.jit(run, donate_argnums=_serve_donate())


def _decode_slots_paged_block(params, cfg: LlamaConfig, tokens, pool,
                              tables, pos, active, quant: bool):
    """One decode step for B independent slots: ``tokens`` [B] at
    PER-SLOT positions ``pos`` [B] — the T=1 special case of the
    speculative verify block (per-layer in-scan gather through the
    first blocks of the tables, as many as the longest live slot's rows
    need (``_table_attention``), physical (block, row) scatter BEFORE
    the gather, inactive slots redirected to the out-of-range sentinel
    and dropped),
    delegated so the per-slot-position transformer step has ONE
    implementation the tick and its verify widening can never drift
    between. Returns (logits [B, V] float32, updated pool)."""
    logits, pool = _verify_slots_paged_block(
        params, cfg, tokens[:, None], pool, tables, pos, active, quant
    )
    return logits[:, 0], pool


@functools.lru_cache(maxsize=8)
def decode_slots_paged_fn(cfg: LlamaConfig, kv_dtype: str | None = None,
                          mesh=None):
    """Jitted ``(params, pool, tables [B, max_blocks] i32, tokens [B],
    pos [B], key_data [B,2] u32, temperature [B], top_k [B], top_p [B],
    active [B]) -> (next_tokens [B], pool)`` — one tick advancing every
    slot through the block arena, sampling fused in. PRNG keys travel
    as raw key data so the host can stage each slot's precomputed key
    sequence in numpy."""
    quant = kv_dtype == "int8"

    def run(params, pool, tables, tokens, pos, key_data,
            temperature, top_k, top_p, active):
        if mesh is not None:
            params = _tp_params(params, cfg, mesh)
            pool = _tp_kv(pool, mesh)
        logits, pool = _decode_slots_paged_block(
            params, cfg, tokens, pool, tables, pos, active, quant
        )
        if mesh is not None:
            logits = _tp_replicated(logits, mesh)
            pool = _tp_kv(pool, mesh)
        keys = jax.random.wrap_key_data(key_data)
        nxt = _sample_slots(logits, keys, temperature, top_k, top_p)
        return nxt, pool

    return jax.jit(run, donate_argnums=_serve_donate())


# ---------------------------------------------------------------------------
# Speculative-decoding verification (serve/speculation.py proposes drafts)
#
# One compiled forward verifies up to k host-proposed draft tokens per
# slot per tick: the inputs are [cur_token, d_0..d_{k-1}] at per-slot
# positions pos..pos+k (the same shape as a prefill chunk — the paged
# gather/scatter machinery is already built), the program computes
# logits at ALL k+1 positions, samples each position with the SAME
# per-step PRNG key schedule the plain tick would have used, and
# accepts the longest draft prefix whose tokens equal the sampled
# targets. For a DETERMINISTIC proposal (prompt-lookup is a point mass)
# this exact-match rule IS rejection sampling: accept d with
# probability p(d), and on mismatch the emitted token is the target
# sample conditioned on != d — exactly the residual distribution — so
# sampled streams are not merely distributionally correct, they are
# BIT-IDENTICAL to the non-speculative stream (and greedy acceptance
# is its temperature-0 special case). A tick therefore always emits
# m+1 tokens per slot (m accepted drafts + the one verified target):
# all-reject still makes one token of forward progress, and there is
# no acceptance/parity trade anywhere.
#
# Rollback on rejection is cursor arithmetic, not block surgery: K/V
# rows written for rejected/pad positions land PAST the advanced
# cursor, inside the slot's own up-front block allocation (or drop at
# the out-of-range sentinel), and every future tick REWRITES its
# window [cursor, cursor+T) before any query can read it — a garbage
# row is overwritten before it is ever causally reachable, the same
# argument that makes retired-slot rows safe (PR-6 lesson). Blocks are
# never freed or reallocated mid-request, so rejection cannot leak.
# ---------------------------------------------------------------------------


def _sample_slots_multi(logits, key_data, temperature, top_k, top_p):
    """``_sample_slots`` over [B, T, V] logits with per-(slot, position)
    keys [B, T, 2]: rows flatten to B*T and run the IDENTICAL per-row op
    sequence (every row's sample depends only on its own logits and
    key), so position j of slot b samples exactly what the plain tick at
    that step would."""
    b, t, v = logits.shape
    keys = jax.random.wrap_key_data(key_data.reshape(b * t, 2))
    rep = lambda a: jnp.repeat(a, t, axis=0)  # [B] -> [B*T], b-major
    flat = _sample_slots(
        logits.reshape(b * t, v), keys, rep(temperature), rep(top_k),
        rep(top_p),
    )
    return flat.reshape(b, t)


@jax.named_scope("sample")
def _accept_prefix(tokens, sampled, draft_len):
    """Longest-accepted-prefix + emission count: drafts are
    ``tokens[:, 1:]`` (position j's draft), targets are
    ``sampled[:, :-1]`` (the verified token AT position j). ``m`` =
    leading positions where they agree (pad positions beyond
    ``draft_len`` never match); the tick emits ``m + 1`` tokens —
    ``sampled[:, :m]`` (== the accepted drafts) plus ``sampled[:, m]``,
    the bonus/correction target. Never zero: forward progress every
    tick."""
    k = tokens.shape[1] - 1
    match = (tokens[:, 1:] == sampled[:, :-1]) & (
        jnp.arange(k)[None, :] < draft_len[:, None]
    )
    m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    return m + 1


@jax.named_scope("attn_proj")
def _slot_rope_tables(cfg: LlamaConfig, qpos, cdt):
    """cos/sin ``[B, T, 1, hd]`` in the rotate-half convention for
    per-(slot, position) global positions ``qpos`` [B, T]: the serve
    programs' ``rope_tables``, one phase a row (and, as there, one
    table: refused where the configuration has one a layer kind)."""
    hd = cfg.head_dim
    rope_tables(cfg, 0)  # raises for rotary parameters by layer kind
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    )
    freqs = qpos.astype(jnp.float32)[..., None] * inv_freq  # [B, T, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [B, T, hd]
    return (jnp.cos(emb)[:, :, None, :].astype(cdt),
            jnp.sin(emb)[:, :, None, :].astype(cdt))


@jax.named_scope("attention")
def _slot_attention(q, ck, cv, mask):
    """Grouped GQA attention of each slot's queries ``q`` [B, T, H, hd]
    over that slot's keys and values ``ck``/``cv`` [B, S, Hkv, hd]
    under the additive ``mask`` [B, 1, T, S], softmax in float32:
    [B, T, H * hd]."""
    b, t, nh, hd = q.shape
    nkv = ck.shape[2]
    qg = q.reshape(b, t, nkv, nh // nkv, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd)) + mask[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", probs, cv).reshape(b, t, nh * hd)


# -- the view a full-attention read takes through the block tables ----------
#
# A block table is as wide as the longest request the engine admits, and
# a call's queries see rows ``[0, need)`` alone, ``need`` = the last
# query position of any live row + 1: every row at or past it is masked
# for every query of the call, and ``MASK_VALUE`` underflows to an exact
# zero in the float32 softmax, so a view of ANY width >= need gives the
# same bits. The read therefore takes the narrowest of a fixed ladder of
# widths that holds ``need``, chosen INSIDE the program (``lax.switch``
# on the positions it is handed): one executable whatever the streams
# hold, nothing for the host to stage. The new rows' write stays outside
# the branches and before them; the pool enters them read-only.

VIEW_STEPS = 8


def view_ladder(table_blocks: int) -> tuple[int, ...]:
    """The view widths, in blocks, a table of ``table_blocks`` entries
    is read at: the distinct ``ceil(table_blocks * i / VIEW_STEPS)``,
    ascending, the table's whole width last. A function of the table's
    width alone (a toy table collapses to fewer widths), shared by the
    programs and by the engine's host-side tally of the widths taken."""
    return tuple(sorted({-(-table_blocks * i // VIEW_STEPS)
                         for i in range(1, VIEW_STEPS + 1)}))


def view_rung(ladder: tuple[int, ...], need_rows, block_size: int):
    """Index of the narrowest width of ``ladder`` that holds
    ``need_rows`` rows (the count of widths too narrow; the top width
    where none holds them). ``need_rows`` is a host integer or a traced
    scalar: the program and the engine's tally run this one rule."""
    need_blocks = (need_rows + block_size - 1) // block_size
    return (np.asarray(ladder[:-1], np.int32) < need_blocks).sum()


def _table_attention(tables, block_size: int, qpos, active):
    """``attend(q, read) -> [B, T, H * hd]`` for queries at positions
    ``qpos`` [B, T] over each row's K/V behind ``tables`` [B, mb]:
    ``read(tables[:, :w])`` gathers (and dequantizes) a view of ``w``
    blocks, ``(ck, cv)`` [B, w * block_size, Hkv, hd], and runs inside
    the one branch whose width is taken, under a causal mask of that
    width. Dead rows (``active`` 0) count no rows; their queries read
    garbage, as they always did. A ladder of one width emits no branch
    and builds its mask here, outside any layer loop."""
    mb = tables.shape[1]
    ladder = view_ladder(mb)

    def mask_of(w):
        with jax.named_scope("attention"):
            ok = jnp.arange(w * block_size)[None, None, :] <= qpos[:, :, None]
            return jnp.where(ok, 0.0, MASK_VALUE)[:, None]   # [B, 1, T, S]

    if len(ladder) == 1:
        mask = mask_of(mb)
        return lambda q, read: _slot_attention(q, *read(tables), mask)
    need = jnp.max(jnp.where(active > 0, qpos[:, -1] + 1, 0))
    rung = view_rung(ladder, need, block_size)

    def attend(q, read):
        def at(w):
            return lambda q: _slot_attention(q, *read(tables[:, :w]), mask_of(w))

        return jax.lax.switch(rung, [at(w) for w in ladder], q)

    return attend


def _verify_slots_paged_block(params, cfg: LlamaConfig, tokens, pool,
                              tables, pos, active, quant: bool):
    """``_decode_slots_paged_block`` widened to T = k+1 positions per
    slot: ``tokens`` [B, T] write at per-slot positions ``pos..pos+T-1``
    and logits [B, T, V] come back for EVERY position (rows past a
    query's own position are causally masked, so a T-wide call is
    bit-identical per row to T single-token ticks over the same cache
    bits). Each of the T new rows scatters at its own physical (block,
    row) address — a verify window may CROSS a block boundary, so
    addresses are resolved per position — before the gather, all inside
    the layer scan. The gather reads each table's first ``w`` blocks,
    the narrowest width of ``view_ladder`` that holds ``pos + T`` rows
    of the longest live slot (``_table_attention``: one branch a width
    inside this one program; rows past it are masked for every query of
    the call, so any such width gives the same bits); a dead slot's
    writes drop (a tick lands MID-prefill of a neighbour slot, which
    must not be stamped with garbage K/V).
    Positions past a slot's allocation hit the sentinel table entry and
    drop; rejected/pad rows inside the allocation are overwritten by a
    later tick before the cursor can ever expose them (see the section
    note above)."""
    cdt = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    _l, nb, bs, nkv, hd = pool["k"].shape
    mb = tables.shape[1]
    nh = cfg.num_attention_heads

    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]  # [B, T, d]

    qpos = pos[:, None] + jnp.arange(t)[None, :]
    cos, sin = _slot_rope_tables(cfg, qpos, cdt)

    def rope(a):
        half = a.shape[-1] // 2
        a1, a2 = a[..., :half], a[..., half:]
        return a * cos + jnp.concatenate([-a2, a1], axis=-1) * sin

    view = _table_attention(tables, bs, qpos, active)
    # per-(slot, position) physical addresses; inactive slots redirect
    # past the arena and drop, exactly like the T=1 tick
    with jax.named_scope("kv_write"):
        bi = jnp.clip(qpos // bs, 0, mb - 1)                # [B, T]
        off = qpos % bs
        phys = jnp.take_along_axis(tables, bi, axis=1)      # [B, T]
        phys = jnp.where(active[:, None] > 0, phys, nb)
    token_valid = jnp.broadcast_to(active[:, None], (b, t))

    def layer_body(x, scanned):
        if quant:
            layer, pk, pv, pks, pvs = scanned
        else:
            layer, pk, pv = scanned
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn_proj"):
            q = (h @ layer["wq"].astype(cdt)).reshape(b, t, nh, hd)
            k = (h @ layer["wk"].astype(cdt)).reshape(b, t, nkv, hd)
            v = (h @ layer["wv"].astype(cdt)).reshape(b, t, nkv, hd)
            q = rope(q)
            k = rope(k)
        with jax.named_scope("kv_write"):
            if quant:
                qk, sk = _quantize_rows(k)                  # [B, T, ...]
                qv, sv = _quantize_rows(v)
                pk = pk.at[phys, off].set(qk, mode="drop")
                pv = pv.at[phys, off].set(qv, mode="drop")
                pks = pks.at[phys, off].set(sk, mode="drop")
                pvs = pvs.at[phys, off].set(sv, mode="drop")
            else:
                pk = pk.at[phys, off].set(k.astype(pk.dtype), mode="drop")
                pv = pv.at[phys, off].set(v.astype(pv.dtype), mode="drop")

        @jax.named_scope("kv_gather")
        def read(tw):
            if quant:
                ck = _dequantize_rows(pk[tw], pks[tw], cdt)
                cv = _dequantize_rows(pv[tw], pvs[tw], cdt)
            else:
                ck, cv = pk[tw], pv[tw]
            rows = tw.shape[1] * bs
            return (ck.reshape(b, rows, nkv, hd).astype(cdt),
                    cv.reshape(b, rows, nkv, hd).astype(cdt))

        attn = view(q, read)
        with jax.named_scope("attn_proj"):
            x = x + attn @ layer["wo"].astype(cdt)

        x, _aux = mlp_block(cfg, x, layer, valid=token_valid)
        if quant:
            return x, (pk, pv, pks, pvs)
        return x, (pk, pv)

    if quant:
        scanned = (params["layers"], pool["k"], pool["v"],
                   pool["ks"], pool["vs"])
    else:
        scanned = (params["layers"], pool["k"], pool["v"])
    # layer_scan: the scan's own slicing of a layer's weights and pool
    # out of their stacks, and its stacking of the updated pool: the
    # whole pool moves through here every tick
    with jax.named_scope("layer_scan"):
        x, out = jax.lax.scan(layer_body, x, scanned)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _head_logits(params, x, cdt)
    if quant:
        pool = {"k": out[0], "v": out[1], "ks": out[2], "vs": out[3]}
    else:
        pool = {"k": out[0], "v": out[1]}
    return logits, pool


@functools.lru_cache(maxsize=8)
def verify_slots_paged_fn(cfg: LlamaConfig, kv_dtype: str | None = None,
                          mesh=None):
    """Jitted ``(params, pool, tables [B, max_blocks] i32, tokens
    [B,T], pos [B], draft_len [B], key_data [B,T,2] u32, temperature
    [B], top_k [B], top_p [B], active [B]) -> (sampled [B,T], counts
    [B], pool)``: one speculative tick through the block arena.
    ``tokens`` = [current token, draft_0..draft_{k-1}] per slot (pads
    beyond ``draft_len`` are ignored by acceptance); ``counts[b]``
    tokens of ``sampled[b]`` are the slot's emission this tick.
    Retraces once per draft-width bucket T (powers of two)."""
    quant = kv_dtype == "int8"

    def run(params, pool, tables, tokens, pos, draft_len, key_data,
            temperature, top_k, top_p, active):
        if mesh is not None:
            params = _tp_params(params, cfg, mesh)
            pool = _tp_kv(pool, mesh)
        logits, pool = _verify_slots_paged_block(
            params, cfg, tokens, pool, tables, pos, active, quant
        )
        if mesh is not None:
            logits = _tp_replicated(logits, mesh)
            pool = _tp_kv(pool, mesh)
        sampled = _sample_slots_multi(
            logits, key_data, temperature, top_k, top_p
        )
        counts = _accept_prefix(tokens, sampled, draft_len)
        return sampled, counts, pool

    return jax.jit(run, donate_argnums=_serve_donate())


# ---------------------------------------------------------------------------
# Two kinds of cache side by side (a mixed configuration's serve programs)
#
# Full-attention layers keep the paged pool and the block tables, one
# pool ``[num_blocks, block_size, Hkv, hd]`` a layer: every token of a
# request stays. Sliding-window layers hold a RING a slot,
# ``[slots, R, Hkv, hd]`` with ``R = window + chunk_size`` rows that no
# block table addresses: position ``p`` lives in row ``p mod R``. Both
# programs write first and attend after. Once a call has written up to
# position ``last`` of a slot, row ``r`` of its ring holds position
# ``last - ((last - r) mod R)``: the mask is rebuilt from the slot's
# position alone, so a ring is never cleared (a row of an earlier
# request reads as a negative or too old position) and the rows a
# right-padded final chunk writes past the prompt are older than any
# window by the time a query could meet them (``R`` is a chunk wider
# than the window). A chunk of C rows so attends to the ring's last
# ``window - 1`` rows and to itself.
#
# The cache is ``run_layers``' ``{"lead", "period"}`` (llama.py): one
# entry a layer, of its own kind and shape; the block tables are the
# full layers' alone and address every full layer's pool alike. With
# one period the scan over periods has one trip and no pool moves
# through a loop's operands.
# ---------------------------------------------------------------------------


def init_mixed_serve_cache(cfg: LlamaConfig, slots: int, ring_rows: int,
                           num_blocks: int, block_size: int, comp_rows: int = 0) -> dict:
    """The serve cache of a mixed configuration: a pool
    ``[num_blocks, block_size, Hkv, hd]`` for each full layer, a ring
    ``[slots, ring_rows, Hkv, hd]`` for each sliding layer, k and v, in
    the compute dtype; for each sparse layer a pool ``[num_blocks, Hkv,
    block_size, hd]`` and its compressed keys ``c`` ``[slots, comp_rows,
    Hkv, hd]`` (row j of a slot is its stream's compressed key j); for
    each linear layer a float32 state ``s`` ``[slots, H, hd, hd]``."""
    cdt = jnp.dtype(cfg.dtype)
    nkv, nh, hd = cfg.kv_heads, cfg.num_attention_heads, cfg.head_dim
    shapes = {
        "full_attention": (num_blocks, block_size, nkv, hd),
        "sliding_attention": (slots, ring_rows, nkv, hd),
        # a (block, KV head) is one contiguous tile: a tick gathers the
        # blocks each KV group chose
        "sparse_attention": (num_blocks, nkv, block_size, hd),
    }

    def entry(kind):
        if kind == "linear_attention":  # a state and no rows
            return {"s": jnp.zeros((slots, nh, hd, hd), jnp.float32)}
        rows = {"k": jnp.zeros(shapes[kind], cdt), "v": jnp.zeros(shapes[kind], cdt)}
        if kind == "sparse_attention":
            # the selector's cache, one row a stride of a slot's stream,
            # addressed by slot and row as a ring is: a tick reads every
            # live stream's rows, and read through the block tables they
            # were 512-byte pieces that the chip gathered at a twentieth
            # of its bandwidth (PERF.md, PR 34); here they are one slice
            rows["c"] = jnp.zeros((slots, comp_rows, nkv, hd), cdt)
        return rows

    return _plan_cache(cfg, entry)


def mixed_cache_bytes(cfg: LlamaConfig, cache: dict) -> dict:
    """Bytes the serve cache holds, by kind of layer."""
    lead, period = _plan_kinds(cfg)
    out = {"full_attention": 0, "sliding_attention": 0}
    for kind, entry in zip(lead + period, cache["lead"] + cache["period"]):
        for name, a in entry.items():
            # a sparse layer's compressed keys are a cache kind of their own
            of = "compressed_keys" if name == "c" else kind
            out[of] = out.get(of, 0) + a.nbytes
    return out


def _sparse_attend(cfg: LlamaConfig, entry, tables, ring_slot, qpos, active, token_valid):
    """``attend(q, k, v)`` of a sparse layer through its pool ``k``,
    ``v`` [blocks, Hkv, bs, hd] behind ``tables`` [B, mb] and its
    compressed keys ``c`` [slots, rows, Hkv, hd] (row b is slot b, or
    the one row is slot ``ring_slot``): writes the new rows and the
    compressed keys they complete, then attends. A chunk (T > 1) reads
    the table's view at a rung of ``view_ladder`` and masks every
    query's own choice over it (``sparse_attention.masked``). A tick (T
    = 1) reads every slot's compressed keys, chooses, and gathers the
    chosen blocks alone (``sparse_attention.gathered``);
    rows still within ``dense_len`` read their view, a narrow one, and
    either side is skipped where no live row needs it. Returns
    (attention [B, T, H * hd], (entry, COUNTERS, choice))."""
    nb, nkv, bs, hd = entry["k"].shape
    slots = entry["c"].shape[0]
    b, t = qpos.shape
    mb = tables.shape[1]
    kern, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    head = jnp.arange(nkv)
    ladder = view_ladder(mb)
    live = (token_valid > 0) & (active[:, None] > 0)             # [B, T]

    def block_of(at):
        return jnp.take_along_axis(tables, jnp.clip(at // bs, 0, mb - 1), axis=1)

    with jax.named_scope("kv_write"):
        phys = jnp.where(active[:, None] > 0, block_of(qpos), nb)   # dead rows drop
        off = qpos % bs
    # the compressed keys this call completes: the one that ENDS at
    # position e starts at e - kernel + 1, a multiple of the stride. A
    # chunk starts at a multiple of the stride, so its candidates are
    # known by their place in it; any other call asks every position
    cand = [i for i in range(t) if (i - kern + 1) % stride == 0] if t % stride == 0 \
        else list(range(t))
    with jax.named_scope("attention"), jax.named_scope("kv_compress"):
        cand = jnp.asarray(cand, jnp.int32)
        start = qpos[:, cand] - (kern - 1)                           # [B, M]
        good = (start >= 0) & (start % stride == 0) & live[:, cand]
        kpos = start[..., None] + jnp.arange(kern)                   # [B, M, kern]
        kphys = block_of(kpos.reshape(b, -1)).reshape(kpos.shape)
        cslot = jnp.arange(b)[:, None] if ring_slot is None else ring_slot[None, None]
        cslot = jnp.where(good, cslot, slots)                        # the others drop
        crow = jnp.maximum(start, 0) // stride

    def need(on):
        """Rows the longest of the rows ``on`` holds after this call."""
        return jnp.max(jnp.where(on, qpos[:, -1] + 1, 0))

    def attend(q, k, v):
        cdt = entry["k"].dtype
        with jax.named_scope("kv_write"):
            at = (phys[:, :, None], head[None, None, :], off[:, :, None])
            pk = entry["k"].at[at].set(k.astype(cdt), mode="drop")
            pv = entry["v"].at[at].set(v.astype(cdt), mode="drop")
        with jax.named_scope("attention"), jax.named_scope("kv_compress"):
            rows = pk[kphys[..., None], head, (kpos % bs)[..., None]]   # [B, M, kern, Hkv, hd]
            made = jnp.mean(rows.astype(jnp.float32), axis=2).astype(cdt)
            pc = entry["c"].at[cslot, crow].set(made, mode="drop")

        @jax.named_scope("kv_gather")
        def view(w):
            tw = tables[:, :w]
            rows = lambda p: jnp.moveaxis(p[tw], 2, 1).reshape(b, nkv, w * bs, hd)
            return rows(pk), rows(pv)

        def comp_view(w):
            with jax.named_scope("attention"), jax.named_scope("sparse_select"):
                mine = pc if ring_slot is None else \
                    jax.lax.dynamic_index_in_dim(pc, ring_slot, 0, keepdims=True)
                return mine[:, :w * bs // stride]

        if t > 1:
            def at_width(w):
                return lambda q: sparse_attention.masked(
                    cfg, q, *view(w), comp_view(w), qpos, live)

            # every second width: a branch here is a loop over query
            # blocks with a choice in it, a quarter of a minute of
            # compiling each at published widths, and the masked scores
            # are a fifth of a chunk
            wide = ladder[1::2] if len(ladder) % 2 == 0 else ladder
            rung = view_rung(wide, need(active > 0), bs)
            out, idx, counters = jax.lax.switch(rung, [at_width(w) for w in wide], q)
            return out, ({"k": pk, "v": pv, "c": pc}, counters, idx)

        far = (active > 0) & sparse_attention.chooses(cfg, qpos[:, 0])
        near = (active > 0) & ~far
        # rows within dense_len see at most dense_len keys: the ladder's
        # widths up to the first that holds them
        short = ladder[:next((i for i, w in enumerate(ladder)
                              if w * bs >= cfg.sparse_dense_len), len(ladder) - 1) + 1]

        def chosen(q):
            # every slot's compressed keys at the table's whole width: a
            # slice of 76 MB where all 32 streams are longest, a tenth of
            # a millisecond, so no rung is taken for it
            out, idx, counters = sparse_attention.gathered(
                cfg, q[:, 0], pk, pv, comp_view(mb), tables, qpos[:, 0], live[:, 0], bs)
            return out[:, None], idx, counters

        def whole(q):
            def at_width(w):
                def run(q):
                    ok = jnp.arange(w * bs)[None, None, None, :] <= qpos[:, None, :, None]
                    return sparse_attention.attend(q, *view(w), jnp.where(ok, 0.0, MASK_VALUE))
                return run

            rung = view_rung(short, need(near), bs)
            return jax.lax.switch(rung, [at_width(w) for w in short], q)

        none = (jnp.zeros((b, 1, q.shape[2] * hd), q.dtype),
                jnp.full((b, 1, nkv, cfg.sparse_topk), -1, jnp.int32),
                jnp.zeros((len(sparse_attention.COUNTERS),), jnp.int32))
        out, idx, counters = jax.lax.cond(jnp.any(far), chosen, lambda q: none, q)
        dense = jax.lax.cond(jnp.any(near), whole, lambda q: none[0], q)
        out = jnp.where(far[:, None, None], out, dense)
        return out, ({"k": pk, "v": pv, "c": pc}, counters, idx)

    return attend


def _serve_block_mixed(params, cfg: LlamaConfig, tokens, cache, tables, ring_slot,
                       pos, active, token_valid):
    """The decoder over ``tokens`` [B, T] at per-slot positions
    ``pos..pos+T-1`` through both kinds of cache. ``tables`` [B, mb] are
    the rows' block tables, read by a full layer at the narrowest width
    of ``view_ladder`` that holds ``pos + T`` rows of the longest live
    row (``_table_attention``: a tick's longest stream, a chunk's own
    end); ``ring_slot`` is None where row b IS ring
    slot b (the tick: B = slots) or the traced ring slot of the one row
    (a prefill chunk: B = 1); ``active`` [B] drops dead rows' writes.
    Returns (final-normed hidden [B, T, d], cache, counters int32[4],
    chosen experts [L_sparse, B, T, k])."""
    cdt = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    window = cfg.sliding_window or 0
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
        if cfg.scale_emb != 1.0:
            x = x * cfg.scale_emb
    qpos = pos[:, None] + jnp.arange(t)[None, :]             # [B, T]
    cos, sin = _slot_rope_tables(cfg, qpos, cdt)

    def rope(a):
        half = a.shape[-1] // 2
        a1, a2 = a[..., :half], a[..., half:]
        return a * cos + jnp.concatenate([-a2, a1], axis=-1) * sin

    def attend_full(entry):
        nb, bs = entry["k"].shape[:2]
        mb = tables.shape[1]
        view = _table_attention(tables, bs, qpos, active)
        with jax.named_scope("kv_write"):
            phys = jnp.take_along_axis(tables, jnp.clip(qpos // bs, 0, mb - 1), axis=1)
            phys = jnp.where(active[:, None] > 0, phys, nb)  # dead rows drop
            off = qpos % bs

        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                pk = entry["k"].at[phys, off].set(k.astype(cdt), mode="drop")
                pv = entry["v"].at[phys, off].set(v.astype(cdt), mode="drop")

            @jax.named_scope("kv_gather")
            def read(tw):
                rows = tw.shape[1] * bs
                return (pk[tw].reshape(b, rows, *pk.shape[2:]),
                        pv[tw].reshape(b, rows, *pv.shape[2:]))

            return view(q, read), {"k": pk, "v": pv}

        return attend

    def attend_ring(entry):
        slots, rows = entry["k"].shape[:2]
        with jax.named_scope("attention"):
            # what each row holds once this call has written up to `last`
            last = qpos[:, -1]
            held = last[:, None] - (last[:, None] - jnp.arange(rows)[None, :]) % rows
            held = held[:, None, :]                          # [B, 1, R]
            ok = (held >= 0) & (held <= qpos[:, :, None]) \
                & (qpos[:, :, None] - held < window)
            mask = jnp.where(ok, 0.0, MASK_VALUE)[:, None]   # [B, 1, T, R]
        with jax.named_scope("kv_write"):
            at = jnp.arange(b)[:, None] if ring_slot is None else ring_slot[None, None]
            at = jnp.broadcast_to(jnp.where(active[:, None] > 0, at, slots), (b, t))
            row = qpos % rows

        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                rk = entry["k"].at[at, row].set(k.astype(cdt), mode="drop")
                rv = entry["v"].at[at, row].set(v.astype(cdt), mode="drop")
            if ring_slot is None:
                ck, cv = rk, rv
            else:
                with jax.named_scope("kv_gather"):
                    ck = jax.lax.dynamic_index_in_dim(rk, ring_slot, 0, keepdims=True)
                    cv = jax.lax.dynamic_index_in_dim(rv, ring_slot, 0, keepdims=True)
            return _slot_attention(q, ck, cv, mask), {"k": rk, "v": rv}

        return attend

    def attend_sparse(entry):
        return _sparse_attend(cfg, entry, tables, ring_slot, qpos, active, token_valid)

    def attend_linear(entry, log_decay):
        def attend(q, k, v):
            whole = entry["s"]
            state = whole if ring_slot is None else \
                jax.lax.dynamic_index_in_dim(whole, ring_slot, 0, keepdims=True)
            # a slot taken again starts from zero: a state has no mask
            # that could hide what the last stream left in it
            fresh = (active > 0) & (pos == 0)
            state = jnp.where(fresh[:, None, None, None], 0.0, state)
            if t == 1:
                o, new = linear_attention.step(q[:, 0], k[:, 0], v[:, 0], state,
                                               log_decay, active)
                o = o[:, None]
            else:
                o, new = linear_attention.chunk(q, k, v, state, log_decay,
                                                jnp.sum(token_valid, axis=1))
            if ring_slot is not None:
                new = jax.lax.dynamic_update_index_in_dim(whole, new[0], ring_slot, 0)
            counters = jnp.zeros((len(sparse_attention.COUNTERS),), jnp.int32)
            return o.reshape(b, t, -1), ({"s": new}, counters.at[-1].set(jnp.sum(active)), None)

        return attend

    def body(x, layer, kind, c):
        if kind[0] == "linear_attention":
            attend = attend_linear(c, layer["log_decay"])
        else:
            attend = {"sliding_attention": attend_ring, "sparse_attention": attend_sparse}.get(
                kind[0], attend_full)(c)
        return _mixed_layer(cfg, x, layer, kind, rope, attend, token_valid)

    x, cache, counters, recs = run_layers(cfg, params, x, body, cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if cfg.head_divisor is not None:
        x = x / cfg.head_divisor
    chosen = [r for r in recs if r is not None]
    chosen = jnp.stack(chosen) if chosen else jnp.zeros((0, b, t, 1), jnp.int32)
    return x, cache, counters, chosen


@functools.lru_cache(maxsize=8)
def prefill_chunk_mixed_fn(cfg: LlamaConfig):
    """``prefill_chunk_paged_fn`` over both kinds of cache: jitted
    ``(params, cache, table [max_blocks] i32, slot, chunk [1,C],
    chunk_valid [1,C], pos, last_idx, key_data, temperature, top_k,
    top_p) -> (token, logits [1,V], cache, counters int32[4], chosen
    experts [L_sparse, 1, C, k])``."""

    def run(params, cache, table, slot, chunk, chunk_valid, pos, last_idx,
            key_data, temperature, top_k, top_p):
        x, cache, counters, chosen = _serve_block_mixed(
            params, cfg, chunk, cache, table[None], slot, pos[None],
            jnp.ones((1,), jnp.int32), chunk_valid)
        xl = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)[:, 0]
        logits = _head_logits(params, xl, jnp.dtype(cfg.dtype))
        tok = _sample_one(logits, key_data, temperature, top_k, top_p)
        return tok, logits, cache, counters, chosen

    return jax.jit(run, donate_argnums=_serve_donate())


@functools.lru_cache(maxsize=8)
def decode_slots_mixed_fn(cfg: LlamaConfig):
    """``decode_slots_paged_fn`` over both kinds of cache: jitted
    ``(params, cache, tables [B, max_blocks] i32, tokens [B], pos [B],
    key_data [B,2] u32, temperature [B], top_k [B], top_p [B],
    active [B]) -> (next_tokens [B], cache, counters int32[4], chosen
    experts [L_sparse, B, 1, k])``: one tick advancing every slot, the
    full layers reading the tables' first blocks up to the longest live
    slot's rows and no further (``_table_attention``)."""

    def run(params, cache, tables, tokens, pos, key_data,
            temperature, top_k, top_p, active):
        x, cache, counters, chosen = _serve_block_mixed(
            params, cfg, tokens[:, None], cache, tables, None, pos, active,
            active[:, None])
        logits = _head_logits(params, x[:, 0], jnp.dtype(cfg.dtype))
        keys = jax.random.wrap_key_data(key_data)
        nxt = _sample_slots(logits, keys, temperature, top_k, top_p)
        if cfg.state_layers:
            # the logits too: the engine's ``capture_decode_logits`` probe
            return nxt, cache, counters, chosen, logits
        return nxt, cache, counters, chosen

    return jax.jit(run, donate_argnums=_serve_donate())
