"""Llama-family decoder as pure functions over a parameter pytree.

TPU-first design notes:
- Parameters are a nested dict of ``jnp`` arrays; per-layer weights are
  STACKED on a leading layer axis and the decoder runs as one
  ``lax.scan`` over layers. One layer gets traced/compiled, whatever the
  depth — compile time stays flat from the 6-layer tiny config
  (ref configs/llama_default.json) to 32-layer 8B. The stacked layout also
  gives every layer an identical shape, so a single PartitionSpec per
  weight name shards the whole depth (see parallel/sharding.py).
- All matmuls keep the [batch*seq, feature] shapes large and contiguous so
  XLA tiles them onto the MXU; compute dtype is a config knob (bfloat16 on
  TPU), while norms and softmax run in float32 for stability.
- No data-dependent Python control flow: causal masking is an explicit
  mask computed from broadcasted iotas, static shapes throughout.

Numerics match HF ``LlamaForCausalLM`` (the reference's model, ref
nanodiloco/main.py:9,97-99): rotate-half RoPE, RMSNorm with float32
accumulation, SwiGLU MLP, pre-norm residuals, untied LM head by default.
Weights here are stored [in_features, out_features] (x @ W); the HF/torch
layout is the transpose.

Loss fixes two reference quirks on purpose (SURVEY §2): labels are
shifted inside the loss (HF did it internally for the reference,
ref nanodiloco/main.py:87 cloned input_ids unshifted), and pad positions
are masked out of the loss instead of being trained on.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from nanodiloco_tpu.models import linear_attention, sparse_attention
from nanodiloco_tpu.models.config import LAYER_KINDS, STATE_LAYER_KINDS, LlamaConfig
from nanodiloco_tpu.models.moe import COUNTERS, ROUTER_STATS

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Random init matching HF Llama: N(0, initializer_range) everywhere,
    RMSNorm scales at 1. DiLoCo's init-broadcast (ref
    nanodiloco/diloco/diloco.py:21-22) is replaced by construction: every
    worker derives params from the same PRNG key, so replicas are
    bit-identical with zero communication.
    """
    std = cfg.initializer_range
    pdt = jnp.dtype(cfg.param_dtype)
    d, f, v, l = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_hidden_layers
    nh, nkv, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim

    keys = jax.random.split(rng, 10)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(pdt)

    if cfg.mixed:
        return _init_mixed_params(cfg, keys, normal)
    layers = {
        "attn_norm": jnp.ones((l, d), pdt),
        "wq": normal(keys[0], (l, d, nh * hd)),
        "wk": normal(keys[1], (l, d, nkv * hd)),
        "wv": normal(keys[2], (l, d, nkv * hd)),
        "wo": normal(keys[3], (l, nh * hd, d)),
        "mlp_norm": jnp.ones((l, d), pdt),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        layers["router"] = normal(keys[9], (l, d, e))
        layers["w_gate"] = normal(keys[4], (l, e, d, f))
        layers["w_up"] = normal(keys[5], (l, e, d, f))
        layers["w_down"] = normal(keys[6], (l, e, f, d))
    else:
        layers["w_gate"] = normal(keys[4], (l, d, f))
        layers["w_up"] = normal(keys[5], (l, d, f))
        layers["w_down"] = normal(keys[6], (l, f, d))
    params: Params = {
        "embed": normal(keys[7], (v, d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), pdt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(keys[8], (d, v))
    return params


def _init_mixed_params(cfg: LlamaConfig, keys, normal) -> Params:
    """A mixed configuration's tree follows its ``layer_plan``:
    ``lead_layers`` is one dict a leading layer (the dense layers, then
    any remainder of the period), unstacked; ``layers`` is one dict a
    layer OF THE PERIOD, each leaf stacked over the periods ``[n, ...]``
    (what ``run_layers`` scans). No leaf holds two layers of one
    program step, so no program slices a layer's weights out of a larger
    array: a grouped-product kernel takes its operand as a buffer of its
    own, and a slice of a stack would be copied for it at every call
    (PERF.md, PR 26: 4.8 GB of expert weights a tick). Sparse layers
    hold the router at its full width, the weights of the experts HELD
    here and the shared experts' one fused SwiGLU. A sigmoid gate's
    selection bias is drawn like a weight and not left at zero, so that
    seeded weights exercise it."""
    pdt = jnp.dtype(cfg.param_dtype)
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    plan = layer_plan(cfg)

    def layer(tag: int, n: tuple, sparse: bool) -> Params:
        """One layer's weights, each leaf with the leading shape ``n``."""
        ks = iter(jax.random.split(jax.random.fold_in(keys[0], tag), 12))
        g = {
            "attn_norm": jnp.ones(n + (d,), pdt),
            "wq": normal(next(ks), n + (d, nh * hd)),
            "wk": normal(next(ks), n + (d, nkv * hd)),
            "wv": normal(next(ks), n + (d, nkv * hd)),
            "wo": normal(next(ks), n + (nh * hd, d)),
            "mlp_norm": jnp.ones(n + (d,), pdt),
        }
        if cfg.qk_norm:
            g["q_norm"] = jnp.ones(n + (hd,), pdt)
            g["k_norm"] = jnp.ones(n + (hd,), pdt)
        kind = plan.kinds[tag][0]
        if kind == "linear_attention":
            # every head its own k and v; the fixed decays ride with the
            # weights (one row a period where the plan stacks them)
            g["wk"] = normal(next(ks), n + (d, nh * hd))
            g["wv"] = normal(next(ks), n + (d, nh * hd))
            ld = [cfg.linear_log_decay(tag + p * plan.period) for p in range(n[0] if n else 1)]
            g["log_decay"] = jnp.asarray(ld if n else ld[0], jnp.float32)
            if cfg.linear_output_norm:
                g["o_norm"] = jnp.ones(n + (nh * hd,), pdt)
        if (kind == "linear_attention" and cfg.linear_output_gate) or (
                kind == "sparse_attention" and cfg.attn_output_gate):
            g["w_og"] = normal(next(ks), n + (d, nh * hd))
        if not sparse:
            g["w_gate"] = normal(next(ks), n + (d, f))
            g["w_up"] = normal(next(ks), n + (d, f))
            g["w_down"] = normal(next(ks), n + (f, d))
            return g
        fe, held = cfg.expert_width, cfg.held_experts[1]
        g["router"] = normal(next(ks), n + (d, cfg.num_experts))
        if cfg.scoring_func == "sigmoid":
            g["router_bias"] = cfg.initializer_range * jax.random.normal(
                next(ks), n + (cfg.num_experts,), jnp.float32)
        g["w_gate"] = normal(next(ks), n + (held, d, fe))
        g["w_up"] = normal(next(ks), n + (held, d, fe))
        g["w_down"] = normal(next(ks), n + (held, fe, d))
        if cfg.num_shared_experts:
            fs = cfg.num_shared_experts * fe
            g["shared_gate"] = normal(next(ks), n + (d, fs))
            g["shared_up"] = normal(next(ks), n + (d, fs))
            g["shared_down"] = normal(next(ks), n + (fs, d))
        return g

    params: Params = {
        "embed": normal(keys[7], (v, d)),
        "lead_layers": tuple(layer(i, (), plan.kinds[i][1]) for i in range(plan.lead)),
        "layers": tuple(layer(plan.lead + j, (plan.periods,), plan.kinds[plan.lead + j][1])
                        for j in range(plan.period if plan.periods else 0)),
        "final_norm": jnp.ones((d,), pdt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal(keys[8], (d, v))
    return params


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """How a mixed configuration's layers run. Layers [0, lead) run one
    by one (``params["lead_layers"]``: the leading dense layers and any
    remainder of the period), the rest as ``periods`` repeats of
    ``period`` layers, scanned (``params["layers"]``): one period is
    traced and compiled, whatever the depth. ``kinds[i]`` is layer i's
    (attention kind, sparse?)."""

    kinds: tuple[tuple[str, bool], ...]
    lead: int
    period: int
    periods: int


@functools.lru_cache(maxsize=32)
def layer_plan(cfg: LlamaConfig) -> LayerPlan:
    """The shortest period that the layers after the leading dense ones
    repeat, a remainder run first: 48 layers ``LLLG`` x 12 with one
    leading dense layer are 1 + 3 layers and 11 periods ``LLLG``."""
    n = cfg.num_hidden_layers
    kinds = tuple(cfg.layer_kind(i) for i in range(n))
    dense = cfg.first_k_dense_replace if cfg.num_experts else 0
    rest = kinds[dense:]
    for p in range(1, len(rest) + 1):
        r = len(rest) % p
        if all(rest[i] == rest[i + p] for i in range(r, len(rest) - p)):
            if len(rest) // p == 1:  # nothing repeats: one whole period, no remainder
                return LayerPlan(kinds, dense, len(rest), 1)
            return LayerPlan(kinds, dense + r, p, len(rest) // p)
    return LayerPlan(kinds, dense, 1, 0)  # no layer after the dense ones


def layer_counters(cfg: LlamaConfig) -> tuple[str, ...]:
    """The names of what a mixed stack's layers count: the attention's
    (``sparse_attention.COUNTERS``) where the stack has sparse or linear
    layers, else the expert layers' (``moe.COUNTERS``); a stack has one
    or the other."""
    return sparse_attention.COUNTERS if cfg.state_layers else COUNTERS


def run_layers(cfg: LlamaConfig, params: Params, x, body, cache=None):
    """Run a mixed configuration's layers over ``x`` by its
    ``layer_plan``. ``body(x, layer, kind, c) -> (x, c, counters, rec)``
    is one layer: ``layer`` its weights, ``kind`` its static (attention
    kind, sparse?), ``c`` its own cache entry or None, ``counters`` the
    int32[4] of ``moe.sparse_mlp`` (zeros for a dense layer), summed
    here over the layers, ``rec`` an array the layer hands out (the
    experts it chose) or None. ``cache`` is None or ``{"lead": one entry
    a leading layer, "period": one entry a layer of the period, each
    stacked over the periods}`` (``models/generate.py`` builds it: the
    entries of two kinds of layer need not have one shape). Returns
    (x, cache, summed counters, the layers' recs in layer order)."""
    plan = layer_plan(cfg)
    lead_c = list(cache["lead"]) if cache is not None else [None] * plan.lead
    counters = jnp.zeros((len(layer_counters(cfg)),), jnp.int32)
    recs = []
    for i in range(plan.lead):
        x, lead_c[i], n, rec = body(x, params["lead_layers"][i], plan.kinds[i], lead_c[i])
        counters = counters + n
        recs.append(rec)
    period_c = ()
    if plan.periods:
        p = plan.period
        kinds = plan.kinds[plan.lead:plan.lead + p]

        def period(carry, scanned):
            x, counters = carry
            layers, cs = scanned
            out, rec = [], []
            for j in range(p):
                x, c, n, r_j = body(x, layers[j], kinds[j], None if cs is None else cs[j])
                out.append(c)
                rec.append(r_j)
                counters = counters + n
            return (x, counters), (None if cs is None else tuple(out), tuple(rec))

        cs = None if cache is None else tuple(cache["period"])
        # layer_scan: the scan's own slicing and stacking of a period's
        # weights and cache entries
        with jax.named_scope("layer_scan"):
            (x, counters), (period_c, rec) = jax.lax.scan(
                period, (x, counters), (tuple(params["layers"]), cs))
        recs += [None if rec[j] is None else rec[j][i]
                 for i in range(plan.periods) for j in range(p)]
    if cache is None:
        return x, None, counters, recs
    return x, {"lead": tuple(lead_c), "period": period_c}, counters, recs


# ---------------------------------------------------------------------------
# Building blocks
#
# Each block runs under a ``jax.named_scope`` (``embed``, ``norm``,
# ``attn_proj``, ``attention``, ``mlp``, ``head``, ``loss``, and
# ``layer_scan`` for the layer scan's own slicing and stacking; the serve
# programs of models/generate.py add ``kv_write``, ``kv_gather`` and
# ``sample``): trace-time only, the scope rides inside every operation's
# ``op_name`` through ``jvp``, ``transpose`` and ``remat``, so a device
# trace's operations read as layers, forward, recomputed and backward
# alike. ``attention`` is exactly what a fused kernel replaces (scores,
# mask, softmax, values), whichever of dense, flash or ring runs.
# ---------------------------------------------------------------------------

def checkpoint_policy(cfg: LlamaConfig):
    """``cfg.remat_policy`` -> jax.checkpoint policy, shared by every
    remat site (this forward and the pipeline stages, ops/pipeline.py) so
    a new policy value can never be honored in one path and silently
    fall back to full recompute in the other."""
    return (
        jax.checkpoint_policies.dots_saveable
        if cfg.remat_policy == "dots"
        else None  # "nothing": recompute the full layer
    )


@jax.named_scope("norm")
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with float32 accumulation (HF casts to fp32 for the variance)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def yarn_ramp(rope: dict, hd: int) -> tuple[int, int]:
    """(low, high) of YaRN's linear ramp over the ``hd // 2`` rotary
    dimensions (arXiv:2309.00071): the dimension that turns ``beta``
    times over the original context is d(beta) = hd ln(L / (2 pi beta))
    / (2 ln theta); dimensions below ``low`` = floor(d(beta_fast)) keep
    their frequency, those from ``high`` = ceil(d(beta_slow)) on are
    divided by ``factor``, the ones between are blended. Clipped to
    [0, hd - 1] as the published code does."""
    def d(beta):
        return hd * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * beta)) / (
            2 * math.log(rope["rope_theta"]))

    return (max(math.floor(d(rope["beta_fast"])), 0),
            min(math.ceil(d(rope["beta_slow"])), hd - 1))


def rope_tables(
    cfg: LlamaConfig, seq_len: int, offset: int | jax.Array = 0, kind: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables in the HF rotate-half convention: frequencies are
    computed for the half head-dim then concatenated with themselves.
    Shapes [seq_len, head_dim], float32. ``offset`` may be a traced scalar
    (e.g. ``axis_index`` under shard_map for sequence parallelism).
    ``kind`` (one of LAYER_KINDS) takes the layer kind's own parameters
    (``cfg.rope_for``): the default table at its ``rope_theta``, or
    YaRN's blended frequencies with cos and sin times
    ``attention_factor``. None: the one table of ``cfg.rope_theta``,
    refused for a configuration with a table a layer kind (the cached
    and pipelined programs know one table: they would run such a model
    with its rotary parameters left out)."""
    hd = cfg.head_dim
    if kind is None and cfg.rope_parameters is not None:
        raise ValueError(
            "this configuration has rotary parameters by layer kind (rope_parameters) "
            "and this program builds one table: only the training forward pass "
            "(models/llama.py:forward) builds a table a layer kind")
    rope = {"rope_theta": cfg.rope_theta} if kind is None else cfg.rope_for(kind)
    inv_freq = 1.0 / (rope["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    scale = None
    if rope.get("rope_type", "default") == "yarn":
        low, high = yarn_ramp(rope, hd)
        ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        inv_freq = (1.0 - ramp) * inv_freq + ramp * inv_freq / rope["factor"]
        scale = rope.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(rope["factor"]) + 1.0
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset
    freqs = jnp.outer(pos, inv_freq)                     # [S, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)       # [S, hd]
    if scale is not None:
        return jnp.cos(emb) * scale, jnp.sin(emb) * scale
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., S, H, hd]; cos/sin: [S, hd]. HF rotate_half convention."""
    cos = cos[:, None, :].astype(x.dtype)  # [S, 1, hd]
    sin = sin[:, None, :].astype(x.dtype)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


# Large-but-finite mask value (HF uses finfo.min similarly): a fully-masked
# score row softmaxes to uniform instead of NaN, so loss-masked padding rows
# can never poison the batch loss via NaN * 0.
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def causal_mask(s: int, valid: jax.Array | None = None, start: int = 0,
                window: int | None = None, first_key: int = 0) -> jax.Array:
    """Additive [B|1, 1, S - start, S - first_key] float32 mask for query
    rows ``start..S`` over keys ``first_key..S``: causal, optionally
    restricted to ``valid`` [B, S] key positions (1 = real token) and to
    the last ``window`` keys of each row (a sliding layer: i - window <
    j <= i)."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (s - start, s - first_key), 0) + start
    ki = jax.lax.broadcasted_iota(jnp.int32, (s - start, s - first_key), 1)
    if first_key:
        ki = ki + first_key
        valid = None if valid is None else valid[:, first_key:]
    ok = qi >= ki
    if window is not None:
        ok = ok & (qi - ki < window)
    ok = ok[None]                              # [1, S - start, S - first_key]
    if valid is not None:
        ok = ok & (valid[:, None, :] > 0)      # [B, S - start, S - first_key]
    return jnp.where(ok, 0.0, MASK_VALUE)[:, None]


# Causal dense attention runs over blocks of query rows, each against the
# keys at or before its last row only: the score area falls from S*S to
# (n + 1) / 2n of it over n blocks, with every row's softmax still whole.
# DENSE_BLOCK_Q rows a block, from one chip sweep of 128, 256 and 512 at
# S 2048 on a v5e (PERF.md, PR 25): 128 runs the step 5% faster still,
# but every block is unrolled into the program, and tracing and lowering
# sixteen of them add 10 s to a process's start, eight add 5 s. For the
# same reason a sequence longer than DENSE_MAX_BLOCKS blocks takes
# longer ones: the rule is a function of S alone.
DENSE_BLOCK_Q = 256
DENSE_MAX_BLOCKS = 16
# The probabilities of every block are what a backward pass keeps of one
# layer's attention. Where they pass this many bytes (2 x 8,192 tokens,
# 32 heads: 4.6 GB a full layer in bf16, 1.5 GB a window layer of 1,024)
# each block is a ``jax.checkpoint`` of its own and the backward pass
# makes a block's scores again when it reaches it: one block's
# probabilities live at a time. A function of the shapes alone.
DENSE_SAVED_PROBS_MAX = 1 << 30


def dense_block_rows(s: int, bq: int | None = None) -> int:
    """Rows of a query block of ``dense_attention`` at sequence length
    ``s``; ``s`` itself (one block: full scores, full mask) where ``s``
    is no longer than a block or is not a whole number of them."""
    if bq is None:
        bq = max(DENSE_BLOCK_Q, -(-s // DENSE_MAX_BLOCKS))
    return bq if s > bq and s % bq == 0 else s


def dense_score_share(s: int, bq: int | None = None) -> float:
    """Score entries ``dense_attention`` computes under a causal mask,
    over ``s * s``: 0.5625 at 2048 in blocks of 256, 1.0 in one block."""
    n = s // dense_block_rows(s, bq)
    return (n + 1) / (2 * n)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None = None,
    *, bq: int | None = None, window: int | None = None,
) -> jax.Array:
    """Reference attention: q,k,v [B, S, H, hd] (k/v already GQA-expanded),
    softmax in float32. ``mask`` is None (causal), a [B, S] 0/1 validity
    mask (causal over the valid keys) or an explicit additive [B?, 1, S, S]
    mask, taken as given. Every block's scores are an array in HBM: the
    path of every shape ``fused_attention_applies`` does not send to the
    kernel (heads not of 128, short or ragged sequences, an explicit
    mask, a partitioned mesh, any backend but a TPU), and the statement
    of the mathematics the kernel is tested against.

    Causal attention runs in query blocks of ``dense_block_rows(S, bq)``
    rows (``bq`` is for tests; the program's size follows from S): block
    ``i`` meets keys ``0..(i+1)*rows`` alone, and every row still softmaxes
    over all of its allowed keys at once, so the result is the one-block
    form's up to float32 reassociation. An explicit mask may allow any
    key, so it runs in one block. A row with no valid key at all (left
    padding) softmaxes to uniform over its block's keys, not over S:
    finite either way, and loss-masked. ``window`` (a sliding layer) is
    one more term of the causal mask, and bounds the keys a block meets:
    the block of rows [start, end) reads keys [max(0, start - window +
    1), end) and no others, the first key its first row may see.

    Where all the blocks' probabilities together pass
    ``DENSE_SAVED_PROBS_MAX`` bytes, each block is recomputed in the
    backward pass (``jax.checkpoint`` around a block)."""
    b, s, h, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    explicit = mask is not None and mask.ndim == 4
    rows = s if explicit else dense_block_rows(s, bq)

    def keys_from(start: int) -> int:
        return 0 if window is None else max(0, start - window + 1)

    def one_block(start, q, k, v, valid):
        # the slices are made in here: a block made again in the backward
        # pass keeps q, k and v whole, which the layer holds anyway
        end, lo = start + rows, keys_from(start)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, start:end], k[:, lo:end]
        ).astype(jnp.float32) * scale
        block = mask if explicit else causal_mask(
            end, None if valid is None else valid[:, :end], start, window, lo
        )
        scores = scores + block.astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, lo:end])

    saved = b * h * q.dtype.itemsize * sum(
        rows * (start + rows - keys_from(start)) for start in range(0, s, rows))
    if saved > DENSE_SAVED_PROBS_MAX:
        one_block = jax.checkpoint(one_block, static_argnums=0)
    out = [one_block(start, q, k, v, None if explicit else mask)
           for start in range(0, s, rows)]
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def fused_attention_applies(
    platform: str, s: int, head_dim: int, mask_ndim: int | None,
    sp_axis: bool, partitioned: bool,
) -> bool:
    """Whether ``_attention`` hands a layer of the default
    (``attention_impl="dense"``) implementation to the fused kernel and
    not to ``dense_attention``'s blocks: one algorithm whose best
    implementation depends on what the call can observe. The kernel
    where the backend is a TPU, the heads are whole lanes of 128, the
    mask is none (``mask_ndim`` None) or a [B, S] validity array (an
    explicit [B?, 1, S, S] mask is taken as given, which only dense
    blocks can), no sequence-parallel axis is bound, the ambient mesh is
    one device or wholly manual (``partitioned`` false: Mosaic refuses a
    kernel inside an automatically partitioned program), and the
    sequence is whole tiles of 1,024: one tile is the least length the
    chip sweep measured, and the kernel was 2.1 to 4.6 times ahead of
    the blocks at every length up to 8,192 (PERF.md section 6, PR 33)."""
    if (platform != "tpu" or head_dim % 128 or mask_ndim not in (None, 2)
            or sp_axis or partitioned):
        return False
    # the kernels' module loads Pallas: only where a kernel can run
    from nanodiloco_tpu.ops.splash_attention import whole_tiles

    return whole_tiles(s)


def mesh_partitions() -> bool:
    """Whether the ambient mesh (``jax.set_mesh``) spans devices over an
    axis no enclosing ``shard_map`` has made manual: a program the
    compiler partitions itself."""
    mesh = jax.sharding.get_abstract_mesh()
    return not (mesh.empty or mesh.size == 1
                or not set(mesh.axis_names) - set(mesh.manual_axes))


def attention_paths(cfg: LlamaConfig, s: int, *, sp_axis: bool = False,
                    partitioned: bool = False) -> dict[str, int]:
    """How many of the stack's layers take the fused kernel and how many
    ``dense_attention``'s blocks at sequence length ``s``, by the rule
    ``_attention`` applies when the program is traced (a [B, S] validity
    array or no mask: the rule does not tell them apart). Neither for
    "flash" and "ring", which name their own kernels."""
    n = cfg.num_hidden_layers if cfg.attention_impl == "dense" else 0
    fused = fused_attention_applies(
        jax.default_backend(), s, cfg.head_dim, None, sp_axis, partitioned)
    return {"fused": n if fused else 0, "dense": 0 if fused else n}


@jax.named_scope("attention")
def _attention(cfg: LlamaConfig, q, k, v, valid, axis_name: str | None,
               window: int | None = None):
    """Dispatch on cfg.attention_impl. Ring attention requires being inside
    a shard_map with the sequence axis bound to ``axis_name``; flash and
    ring ignore ``valid``, the [B, S] padding mask (packed fixed-length
    sequences don't need one) and know one causal mask. ``"dense"``, the
    default, names the mathematics (causal over the valid keys, a window
    where the layer has one, every row's softmax whole in float32) and
    the program picks its implementation from the call
    (``fused_attention_applies``): on a TPU, heads of 128 over a long
    sequence in whole tiles run the fused kernel forward and backward
    (ops/splash_attention.py: no score reaches HBM, K and V stay at Hkv
    heads); every other shape, platform and mesh runs ``dense_attention``'s
    query blocks, for which K and V are expanded to the query heads
    here. flash and ring take k/v at Hkv heads too."""
    if cfg.attention_impl not in ("dense", "flash", "ring"):
        raise ValueError(f"unknown attention_impl: {cfg.attention_impl!r}")
    if cfg.attention_impl == "flash":
        from nanodiloco_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    if cfg.attention_impl == "ring" and axis_name is not None:
        from nanodiloco_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name=axis_name)
    if cfg.attention_impl == "dense" and fused_attention_applies(
            jax.default_backend(), q.shape[1], q.shape[3],
            None if valid is None else valid.ndim, axis_name is not None,
            mesh_partitions()):
        from nanodiloco_tpu.ops.splash_attention import splash_attention

        return splash_attention(q, k, v, valid, window=window)
    # dense blocks (and the ring-without-axis fallback, e.g. sp=1): expand
    # GQA K/V to the query heads — dense scores are computed per query head
    if k.shape[2] != q.shape[2]:
        g = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    return dense_attention(q, k, v, valid if cfg.attention_impl == "dense" else None,
                           window=window)


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def qkv_proj(cfg: LlamaConfig, h, layer: Params, rope, attn_kind: str = "full_attention"):
    """q [B, T, H, hd], k and v [B, T, Hkv, hd] of normed ``h``: the
    projections, the per-head RMSNorm of q and k where the configuration
    has one, and ``rope`` (a function of one array) on q and k in the
    layers that rotate (``cfg.rope_layers``). The one projection of the
    training forward and of every cached program. Under ``attn_proj``."""
    b, t, _ = h.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    if attn_kind == "linear_attention":
        nkv = nh
    cdt = h.dtype
    q = (h @ layer["wq"].astype(cdt)).reshape(b, t, nh, hd)
    k = (h @ layer["wk"].astype(cdt)).reshape(b, t, nkv, hd)
    v = (h @ layer["wv"].astype(cdt)).reshape(b, t, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if cfg.rotates(attn_kind):
        q, k = rope(q), rope(k)
    return q, k, v


def attn_output(cfg: LlamaConfig, attn, h, layer: Params, attn_kind: str):
    """What stands between a layer's attention [B, T, H * hd] and its
    output projection: a linear layer's RMSNorm over the joined heads
    and the sigmoid gate ``sigmoid(h W_g)`` of the layers that have one
    (``w_og``); the compute dtype out. Nothing for the other kinds."""
    if attn_kind == "linear_attention" and cfg.linear_output_norm:
        attn = rms_norm(attn, layer["o_norm"], cfg.rms_norm_eps)
    if "w_og" in layer:
        with jax.named_scope("attn_proj"):
            attn = attn * jax.nn.sigmoid(h @ layer["w_og"].astype(h.dtype)).astype(attn.dtype)
    return attn.astype(h.dtype)


def residual(cfg: LlamaConfig, x, branch):
    """x + branch, the branch times the configuration's muP scale."""
    s = cfg.residual_scale
    return x + branch if s is None else x + (s * branch).astype(x.dtype)


def _attn_block(cfg: LlamaConfig, x, layer: Params, cos, sin, attn_valid, sp_axis,
                attn_kind: str = "full_attention"):
    """The attention half of a decoder layer: x + Wo attention(...)."""
    b, s, _ = x.shape
    cdt = x.dtype
    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn_proj"):
        q, k, v = qkv_proj(cfg, h, layer, lambda a: apply_rope(a, cos, sin), attn_kind)
    # GQA K/V stay at Hkv heads here; flash/ring are GQA-native (K/V are
    # never expanded in HBM/ICI — the bandwidth GQA exists to save) and
    # _attention expands only for its dense paths.
    if attn_kind in STATE_LAYER_KINDS:
        attn = _state_attention(cfg, q, k, v, layer, attn_valid, attn_kind)
        attn = attn_output(cfg, attn, h, layer, attn_kind)
        with jax.named_scope("attn_proj"):
            return residual(cfg, x, attn @ layer["wo"].astype(cdt))
    window = cfg.sliding_window if attn_kind == "sliding_attention" else None
    attn = _attention(cfg, q, k, v, attn_valid, sp_axis, window)
    with jax.named_scope("attn_proj"):
        return residual(cfg, x, attn.reshape(b, s, -1) @ layer["wo"].astype(cdt))


def _state_attention(cfg: LlamaConfig, q, k, v, layer: Params, attn_valid, attn_kind: str):
    """A sparse or linear layer over a whole sequence from position 0:
    every query's own choice of blocks as a mask, the recurrence from a
    zero state in its chunked form. [B, S, H * hd]."""
    if attn_valid is not None:
        raise ValueError(
            f"a {attn_kind} layer takes whole sequences: a padding mask would have to "
            "reach its compressed keys or its state, and neither carries one")
    b, s, nh, hd = q.shape
    if attn_kind == "linear_attention":
        state = jnp.zeros((b, nh, hd, hd), jnp.float32)
        return linear_attention.chunk(q, k, v, state, layer["log_decay"])[0].reshape(b, s, -1)
    qpos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    comp = sparse_attention.compress_keys(cfg, k)
    if comp.shape[1] == 0:  # shorter than one compressed key: nobody chooses
        comp = jnp.zeros((b, 1) + k.shape[2:], k.dtype)
    return sparse_attention.masked(
        cfg, q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), comp, qpos,
        jnp.ones((b, s), jnp.int32))[0]


def _decoder_layer(
    cfg: LlamaConfig, x, layer: Params, cos, sin, attn_valid, sp_axis, valid=None,
    with_stats: bool = False,
):
    """Returns (x, aux_loss) — aux is the router load-balance term for
    MoE layers, 0.0 for dense. ``attn_valid`` [B, S] marks the keys dense
    attention may see (None: causal alone); ``valid`` [B, S] marks real
    tokens so MoE routing never spends expert capacity on padding.
    ``with_stats`` adds the router observability vector (see moe_mlp)."""
    x = _attn_block(cfg, x, layer, cos, sin, attn_valid, sp_axis)
    return mlp_block(cfg, x, layer, valid, sp_axis=sp_axis, with_stats=with_stats)


def mixed_mlp_block(cfg: LlamaConfig, x, layer: Params, valid=None, with_stats=False):
    """``mlp_block`` of a mixed configuration's layer, dense or sparse by
    the weights it holds: (x, counters int32[4], chosen experts
    [B, S, k] or None) with the sparse layer's counters and choice
    (``moe.sparse_mlp``), zeros and None for a dense one. Shared by the
    training forward and the cached programs. ``with_stats`` (the
    training forward asks, the cached programs do not) puts the float32
    vector ``moe.ROUTER_STATS`` in the choice's place, zeros for a dense
    layer."""
    cdt = x.dtype
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        if "router" in layer:
            from nanodiloco_tpu.models.moe import sparse_mlp

            out, counters, rec = sparse_mlp(cfg, h, layer, valid, with_stats)
            return x + out, counters, rec
        gate = jax.nn.silu(h @ layer["w_gate"].astype(cdt))
        up = h @ layer["w_up"].astype(cdt)
        return (residual(cfg, x, (gate * up) @ layer["w_down"].astype(cdt)),
                jnp.zeros((len(layer_counters(cfg)),), jnp.int32),
                jnp.zeros((len(ROUTER_STATS),), jnp.float32) if with_stats else None)


def mlp_block(
    cfg: LlamaConfig, x, layer: Params, valid=None, sp_axis=None,
    with_stats: bool = False,
):
    """The norm + (dense SwiGLU | MoE) residual half of a decoder layer,
    shared by the training forward and the cached decode path
    (models/generate.py) so the two can never drift. Returns
    (x, aux_loss) — aux is the router load-balance term, 0.0 for dense.
    ``sp_axis``: see moe_mlp (sequence-sharded routing). ``with_stats``
    appends the [dropped_frac, router_entropy] vector (zeros for dense)
    — the diagnostics-probe channel, never on the training path."""
    cdt = x.dtype
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    if cfg.num_experts:
        from nanodiloco_tpu.models.moe import moe_mlp

        with jax.named_scope("mlp"):
            out = moe_mlp(
                cfg, h, layer, valid=valid, sp_axis=sp_axis,
                with_stats=with_stats,
            )
            if with_stats:
                mlp_out, aux, stats = out
                return x + mlp_out, aux, stats
            mlp_out, aux = out
            return x + mlp_out, aux
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(h @ layer["w_gate"].astype(cdt))
        up = h @ layer["w_up"].astype(cdt)
        x = x + (gate * up) @ layer["w_down"].astype(cdt)
    if with_stats:
        return x, jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.float32)
    return x, jnp.zeros((), jnp.float32)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    attn_mask: jax.Array | None = None,
    sp_axis: str | None = None,
    position_offset: int | jax.Array = 0,
    return_hidden: bool = False,
    with_aux: bool = False,
    collect_stats: bool = False,
    with_choices: bool = False,
) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] float32 (or the final
    normed hidden states [B, S, d] in compute dtype if ``return_hidden`` —
    the blockwise-loss path applies the vocabulary head itself). With
    ``with_aux`` returns ``(out, aux)`` where aux is the summed router
    load-balance loss over MoE layers (0.0 for dense models).

    ``attn_mask`` is an optional [B, S] 0/1 validity mask (1 = real token);
    it is combined with causal masking. ``sp_axis`` names the mesh axis the
    sequence dim is sharded over when running ring attention inside a
    shard_map; ``position_offset`` is this shard's global start position.

    ``collect_stats`` (implies an extra return value; diagnostics only,
    never the training program) appends the layer-mean MoE router stats
    [dropped_frac, router_entropy] — see moe.make_router_stats_fn.

    A mixed sparse configuration's ``with_aux`` returns ``(out, aux,
    counters)``: ``moe.TRAIN_COUNTERS`` as int32, summed over the layers
    (the four ``moe.COUNTERS`` and the largest group's rows).
    ``with_choices`` (the same configurations; a probe, never the
    training program) returns ``(out, choices)``: the experts each
    sparse layer chose, [sparse layers, B, S, k] int32.
    """
    cdt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
        if cfg.scale_emb != 1.0:
            x = x * cfg.scale_emb
    with jax.named_scope("attn_proj"):
        if cfg.mixed:
            # one table a layer kind, built once a pass: the kind's own
            # rotary parameters where the configuration has them
            with jax.named_scope("rope"):
                if cfg.rope_parameters is None:
                    tables = dict.fromkeys(LAYER_KINDS + STATE_LAYER_KINDS,
                                           rope_tables(cfg, s, position_offset))
                else:
                    tables = {kind: rope_tables(cfg, s, position_offset, kind)
                              for kind in sorted({k[0] for k in layer_plan(cfg).kinds})}
        else:
            cos, sin = rope_tables(cfg, s, offset=position_offset)

    # flash and ring are PACKED-sequence kernels: attn_mask only weights
    # the loss, it never restricts attention (dense honors it for the
    # reference's padded-document layout, ref nanodiloco/main.py:79-88).
    # Dense attention builds its mask from attn_mask inside the layer,
    # where it fuses into the scores: no [B, 1, S, S] array crosses the scan.

    # Bind all non-array arguments (cfg, sp_axis) BEFORE jax.checkpoint so
    # only JAX types flow through the remat boundary.
    def layer_fn(x, layer, cos, sin, valid):
        return _decoder_layer(
            cfg, x, layer, cos, sin, valid, sp_axis, valid,
            with_stats=collect_stats,
        )

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=checkpoint_policy(cfg))

    def scan_body(carry, layer):
        out = layer_fn(carry, layer, cos, sin, attn_mask)
        return out[0], out[1:]

    if cfg.mixed:
        # leading layers and scanned periods (run_layers). Nothing is
        # dropped (no capacity); a softmax gate has the Switch balance
        # term over the router's full width, a sigmoid gate balances by
        # its selection bias and has none
        sparse = bool(cfg.num_experts)

        def mixed_layer(x, layer, kind, _):
            def fn(x, layer):
                cos, sin = tables[kind[0]]
                x = _attn_block(cfg, x, layer, cos, sin, attn_mask, sp_axis, kind[0])
                return mixed_mlp_block(cfg, x, layer, attn_mask, sparse and not with_choices)

            if cfg.remat:
                fn = jax.checkpoint(fn, policy=checkpoint_policy(cfg))
            x, counters, rec = fn(x, layer)
            return x, None, counters, rec

        x, _, counters, recs = run_layers(cfg, params, x, mixed_layer)
        aux, stats = jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.float32)
        if sparse and not with_choices:
            balance, max_rows, entropy = jnp.sum(jnp.stack(recs), axis=0)
            if cfg.scoring_func == "softmax":
                aux = balance
            n_sparse = sum(kind[1] for kind in layer_plan(cfg).kinds)
            stats = jnp.stack([jnp.zeros((), jnp.float32), entropy / n_sparse])
            # a layer's largest group is at most k*T rows: exact in float32
            moe_counters = jnp.concatenate([counters, max_rows.astype(jnp.int32)[None]])
    else:
        # the scan's own work (a layer's weights sliced out of the stack,
        # the residuals stacked for the backward pass) reads as layer_scan
        with jax.named_scope("layer_scan"):
            x, ys = jax.lax.scan(scan_body, x, params["layers"])
        aux = jnp.sum(ys[0])
        stats = jnp.mean(ys[1], axis=0) if collect_stats else None  # [2]
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    def pack(out):
        if with_choices:
            if not (cfg.mixed and cfg.num_experts):
                raise ValueError("with_choices reads a mixed sparse configuration's layers")
            return out, jnp.stack([r for r in recs if r is not None])
        if collect_stats:
            return (out, aux, stats) if with_aux else (out, stats)
        if with_aux and cfg.mixed and cfg.num_experts:
            return out, aux, moe_counters
        return (out, aux) if with_aux else out

    if cfg.head_divisor is not None:
        x = x / cfg.head_divisor
    if return_hidden:
        return pack(x)
    with jax.named_scope("head"):
        head = params.get("lm_head", None)
        if head is None:
            head = params["embed"].T
        logits = (x @ head.astype(cdt)).astype(jnp.float32)
    return pack(logits)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def causal_lm_loss(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    loss_mask: jax.Array | None = None,
    sp_axis: str | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mean next-token cross-entropy with internal label shift.

    ``loss_mask`` [B, S] marks real (non-pad) tokens; positions whose
    TARGET is padding are excluded — the reference trained on pad tokens
    (ref nanodiloco/main.py:87, SURVEY §2 quirks), which we deliberately fix.
    Returns (loss, aux) with aux = {"n_tokens": ..., "sum_loss": ...} so
    microbatch losses can be combined exactly under grad accumulation.
    """
    targets = tokens[:, 1:]
    # a mixed sparse configuration's pass also hands out what its expert
    # layers did (forward): "moe_counters" in the loss's aux
    if cfg.loss_chunk:
        from nanodiloco_tpu.ops.fused_ce import chunked_softmax_xent

        h, aux, *moe = forward(
            params, tokens, cfg, attn_mask=loss_mask, sp_axis=sp_axis,
            return_hidden=True, with_aux=True,
        )
        b, s, d = h.shape
        with jax.named_scope("loss"):
            head = params.get("lm_head", None)
            if head is None:
                head = params["embed"].T
            m = (
                loss_mask[:, 1:] if loss_mask is not None
                else jnp.ones_like(targets)
            ).astype(jnp.float32)
            sum_loss, n_tok = chunked_softmax_xent(
                h[:, :-1].reshape(b * (s - 1), d),
                head.astype(h.dtype),
                targets.reshape(-1),
                m.reshape(-1),
                chunk=cfg.loss_chunk,
            )
            n = jnp.maximum(n_tok, 1.0)
            loss = sum_loss / n + cfg.router_aux_coef * aux
        return loss, {
            "n_tokens": n_tok, "sum_loss": sum_loss, "router_aux": aux,
            **({"moe_counters": moe[0]} if moe else {}),
        }

    logits, aux, *moe = forward(
        params, tokens, cfg, attn_mask=loss_mask, sp_axis=sp_axis, with_aux=True
    )
    with jax.named_scope("loss"):
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]  # [B, S-1]
        if loss_mask is not None:
            m = loss_mask[:, 1:].astype(nll.dtype)
        else:
            m = jnp.ones_like(nll)
        sum_loss = jnp.sum(nll * m)
        n = jnp.maximum(jnp.sum(m), 1.0)
        loss = sum_loss / n + cfg.router_aux_coef * aux
    return loss, {
        "n_tokens": jnp.sum(m), "sum_loss": sum_loss, "router_aux": aux,
        **({"moe_counters": moe[0]} if moe else {}),
    }


def causal_lm_loss_sp(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh,
    loss_mask: jax.Array | None = None,
    axis_name: str = "sp",
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """``causal_lm_loss`` with the SEQUENCE dimension sharded over a mesh
    axis — the long-context training path (the reference caps sequence
    length at 1024 by truncation, ref nanodiloco/training_utils/utils.py:50;
    here S scales with the ``sp`` axis at O(S/N) activation memory).

    Runs the forward under ``jax.shard_map`` manual over ``axis_name`` only
    (ring attention's ppermute needs the axis bound) while fsdp/tp stay
    auto-partitioned by XLA. Requires ``cfg.attention_impl == 'ring'``
    (local dense attention would silently drop cross-shard context) and
    packed sequences (no attention padding mask; ``loss_mask`` still
    weights the loss). The label shift crosses shard boundaries: each
    shard's last target is its right neighbor's first token, fetched with
    one tiny ppermute; the global last position is masked out.
    """
    if loss_mask is None:
        loss_mask = jnp.ones_like(tokens)

    def shard_fn(params, tokens, loss_mask):
        sum_local, n_local, aux = sp_shard_loss(
            params, tokens, cfg, loss_mask, axis_name
        )
        sum_loss = jax.lax.psum(sum_local, axis_name)
        n_tok = jax.lax.psum(n_local, axis_name)
        # aux's VALUE is already globally exact (moe_mlp reduces its
        # statistics over the axis); the psum/size mean only replicates
        # its manual-axis TYPE for the out_specs
        aux = jax.lax.psum(aux, axis_name) / jax.lax.psum(1, axis_name)
        loss = sum_loss / jnp.maximum(n_tok, 1.0) + cfg.router_aux_coef * aux
        return loss, {
            "n_tokens": n_tok, "sum_loss": sum_loss, "router_aux": aux,
        }

    from jax.sharding import PartitionSpec as P

    pspec = jax.tree.map(lambda _: P(), params)
    seq_spec = P(None, axis_name)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(pspec, seq_spec, seq_spec),
        out_specs=(P(), {"n_tokens": P(), "sum_loss": P(), "router_aux": P()}),
        axis_names={axis_name},
    )(params, tokens, loss_mask)


def sp_shift_targets(
    tokens: jax.Array, loss_mask: jax.Array, axis_name: str
) -> tuple[jax.Array, jax.Array]:
    """Cross-shard label shift for sequence-sharded [B, S_local] tokens:
    the right neighbor's first token completes this shard's targets (one
    tiny ppermute), and the GLOBAL last position — whose "target" wrapped
    around the ring — is masked out. Returns (targets, float32 weights).
    Shared by sp_shard_loss and the pipeline exit loss (ops/pipeline.py)
    so the shift contract can never drift between them."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_loc = tokens.shape[1]
    to_left = [(j, (j - 1) % n) for j in range(n)]
    next_tok = jax.lax.ppermute(tokens[:, :1], axis_name, to_left)
    next_m = jax.lax.ppermute(loss_mask[:, :1], axis_name, to_left)
    targets = jnp.concatenate([tokens[:, 1:], next_tok], axis=1)
    m = jnp.concatenate([loss_mask[:, 1:], next_m], axis=1).astype(jnp.float32)
    is_global_last = (idx == n - 1) & (jnp.arange(s_loc) == s_loc - 1)  # [S_loc]
    return targets, m * (1.0 - is_global_last[None].astype(jnp.float32))


def sp_shard_loss(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    loss_mask: jax.Array,
    axis_name: str,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard UNREDUCED loss body for sequence parallelism: must run
    inside a region manual over ``axis_name``. Returns this shard's
    (sum_loss, n_tokens, router_aux) — callers psum the first two (and
    psum parameter grads); ``router_aux`` is already GLOBALLY exact (its
    statistics reduce over the axis inside moe_mlp; 0.0 for dense), so
    callers use it as-is, never psummed. tokens/loss_mask: [B, S_local].

    MoE composes via token-choice routing with per-shard capacity — see
    moe_mlp for the exact-when-capacity-is-ample semantics."""
    if cfg.mixed:
        raise ValueError(
            "the sequence-parallel loss does not carry a mixed layer stack "
            "(sliding-window layers, a held share of the experts): ring "
            "attention knows one causal mask")
    if cfg.attention_impl != "ring":
        raise ValueError(
            "sequence-parallel loss requires attention_impl='ring'; "
            f"got {cfg.attention_impl!r}"
        )
    idx = jax.lax.axis_index(axis_name)
    b, s_loc = tokens.shape
    targets, m = sp_shift_targets(tokens, loss_mask, axis_name)

    if cfg.loss_chunk:
        # blockwise CE on this shard's rows — long context is exactly
        # where materializing [B, S_loc, V] logits hurts most
        from nanodiloco_tpu.ops.fused_ce import chunked_softmax_xent

        h, aux = forward(
            params, tokens, cfg, attn_mask=None, sp_axis=axis_name,
            position_offset=idx * s_loc, return_hidden=True, with_aux=True,
        )
        with jax.named_scope("loss"):
            head = params.get("lm_head", None)
            if head is None:
                head = params["embed"].T
            sl, n = chunked_softmax_xent(
                h.reshape(b * s_loc, h.shape[-1]),
                head.astype(h.dtype),
                targets.reshape(-1),
                m.reshape(-1),
                chunk=cfg.loss_chunk,
            )
        return sl, n, aux

    logits, aux = forward(
        params, tokens, cfg, attn_mask=None, sp_axis=axis_name,
        position_offset=idx * s_loc, with_aux=True,
    )
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * m), jnp.sum(m), aux
