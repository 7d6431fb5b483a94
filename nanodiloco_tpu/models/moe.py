"""Mixture-of-Experts MLP with expert parallelism over the ``ep`` axis.

The reference is dense-Llama-only (SURVEY §2: "Expert parallelism
(EP / MoE): NO"); this is a TPU-native capability add in the classic
Mesh-TF / Switch-Transformer shape:

- **Dense dispatch, static shapes.** Routing is expressed as einsums
  against one-hot dispatch/combine tensors ``[T, E, C]`` (tokens ×
  experts × capacity) — no data-dependent gathers, no dynamic shapes,
  exactly what XLA tiles well. Tokens beyond an expert's capacity
  ``C = ceil(k·T/E · capacity_factor)`` are dropped (their combine
  weight is zero, so the residual path carries them through).
- **Experts are a sharding.** Expert weights are stacked on a leading
  ``[E, ...]`` axis with PartitionSpec ``P('ep', ...)``; the dispatch /
  expert-FFN / combine einsums contract over sharded axes and GSPMD
  inserts the all-to-alls. No manual collectives here.
- **Router in float32** with the Switch load-balance auxiliary loss
  ``E · Σ_e f_e · P_e`` (fraction of tokens routed to e × mean router
  probability of e), scaled by ``router_aux_coef`` in the LM loss.

Where dense dispatch stops scaling (measured, round 5 —
``scripts/moe_evidence.py`` phase "scale", ``runs/moe_evidence_r5.jsonl``):
the ``[T, E, C]`` dispatch/combine tensors have ``E·C ≈ k·T·cf``
elements regardless of E, so their MEMORY is O(T²) per layer, not
O(E); what grows with E is router math and einsum padding. On the CPU
mesh at fixed per-expert width, tokens/s degrades gently through E=32
(−27% vs E=8) and visibly at E=64 (−46%). The large-E alternative IS
implemented: ``moe_dispatch="ragged"`` (``_ragged_mlp``) argsorts
token-slot assignments by expert and runs the SwiGLU as exact-sized
``jax.lax.ragged_dot`` grouped matmuls over contiguous runs — the
shape used by Mixtral-style megablocks kernels. No capacity, no
dropped tokens, no one-hot padding FLOPs, and cached decode loses its
capacity-divergence caveat; the trade is a data-dependent permutation
(gather/scatter + group-size vector, all static shapes).

Honest CPU-mesh caveat (same ``scale`` phase, ``dispatch: "ragged"``
rows): on XLA:CPU ragged is SLOWER than dense at every measured E
(0.59× at E=8 falling to 0.16× at E=64) — the grouped-matmul loop and
gather/scatter lowering dominate there, so the padding-FLOPs win this
path exists for is a TPU (Mosaic grouped matmul) property, queued for
on-chip measurement as bench.py's ``single_ragged`` MoE entry. The
correctness wins (zero drops, exact decode) hold on any backend.
tokens_choose routing with replicated experts only
(config.py / train_loop.py validate); dense dispatch remains the
default and the ep>1 path — every shipped config with E ≤ 8 sits well
inside its regime (``configs/llama_moe_64e.json`` ships the 64-expert
ragged shape).

A held share of the experts (a mixed configuration, ``sparse_mlp``): a
chip that holds ``count`` of the router's experts sees about
``count / num_experts`` of the k*T token-expert pairs, sorted to the
front. ``_ragged_mlp`` then runs its gather, grouped products, select
and scatter-add over the first ``short_rows`` sorted rows alone (twice
the expected held pairs, in an odd number of 128s) whenever the held
pairs fit them, and over all k*T rows otherwise (a ``lax.cond`` on
``sum(group_sizes)``): the same pairs, groups and weights either way,
nothing dropped. Where a held expert expects whole tiles of 512 rows (a
training step) the short rows are three times the expected pairs in
such tiles (``short_rows``). Where all experts are held there is no
short path and no conditional.
``sparse_mlp`` returns four counters (``COUNTERS``); the fourth says how
often the short path was taken. On the chip (PERF.md, PR 28). Trained,
a softmax gate's layer also hands out its balance term over the router's
full width, its largest group's rows and its scores' entropy
(``ROUTER_STATS``; PERF.md, PR 32).

Capacity factor (measured, round 5 — phase "cf", fixed 120-step budget
on the pylib corpus, 8 experts top-2, ``runs/moe_evidence_r5.jsonl``):
final train loss is FLAT across cf ∈ {1.0, 1.25, 1.5, 2.0}
(2.357–2.383, within run noise) while mean dropped_frac falls
0.34 → 0.21 → 0.15 → 0.09 — the residual path really does carry
dropped tokens at no measured quality cost at this scale/budget, and
cf=2.0's +60% expert FLOPs buy nothing. The 1.25 default is therefore
kept as a cheap safety margin over 1.0, not because drops were shown
to hurt; re-run the sweep before trusting that at larger scale or
longer budgets (capacity pressure grows with batch·seq).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from nanodiloco_tpu.models.config import LlamaConfig

# The short path of ``_ragged_mlp`` takes the first ``short_rows`` rows of
# the sorted pairs: SHORT_ROWS_FACTOR times the pairs a chip expects to
# hold (k*T * held / router width), in an ODD number of SHORT_ROWS_TILE
# rows. Settled by two sweeps on the chip (PERF.md, PR 28): the TPU
# compiler's grouped-product kernel tiles its rows by the largest power
# of two up to 512 that divides them and computes a whole tile for each
# (tile, group) it meets, so at some thirty rows a group 1,152 rows
# (tiles of 128) take 3.3 ms a layer where 1,024 (tiles of 512) take 4.8
# and all 4,096 take 5.4; among odd counts the time hardly moves with the
# factor (640 rows 3.24, 1,408 rows 3.37), so the factor buys headroom.
SHORT_ROWS_FACTOR = 2
SHORT_ROWS_TILE = 128
# Where a held expert expects a whole tile of 512 rows or more (a training
# step: 2,048 rows a group at 16,384 tokens, 8 of 64 experts held) the
# same kernel wants its largest tile, and a skewed router's share passes
# twice the expected pairs in a fifth of the calls. One sweep on the chip
# (PERF.md, PR 32: one sparse layer forward, made again and backward):
# over 32,896 rows (tiles of 128) 63.2 ms at the expected load and 34 ms
# more for each further expected load; over 32,768 (tiles of 512: an
# expert's 4 MB of weights read once a tile) 44.1 and 13 more; over 49,152
# 48.2 and 14 more; all 131,072 rows 70.6 and 89 at 2.3 times the
# expected. So there the
# rows come in whole tiles of 512, three times the expected pairs of
# them: a call at twice the expected takes 61 ms and not 89.
SHORT_ROWS_WIDE_FACTOR = 3
SHORT_ROWS_WIDE_TILE = 512

# What ``sparse_mlp``'s int32 counter vector holds, in order.
COUNTERS = ("moe_held_pairs", "moe_experts_hit", "moe_pairs", "moe_short_path")
# What the training forward sums over a pass's layers
# (``llama.forward(with_aux=True)``): the counters and the rows of
# each layer's largest held group (what imbalance the grouped products saw)
TRAIN_COUNTERS = COUNTERS + ("moe_max_group_rows",)
# What ``sparse_mlp`` hands out in the choice's place when asked
# (``with_stats``), a float32 each: the Switch balance term E sum_e f_e
# P_e over the router's full width, the largest held group's rows, the
# mean entropy of a token's scores in nats
ROUTER_STATS = ("balance", "max_group_rows", "entropy")


def expert_capacity(cfg: LlamaConfig, n_tokens: int) -> int:
    """Static per-expert token capacity, ceil(k*T/E * capacity_factor)."""
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    return max(1, math.ceil(n_tokens * k / e * cfg.expert_capacity_factor))


def make_router_stats_fn(cfg: LlamaConfig):
    """Jitted diagnostics probe ``(params, tokens[B, S]) ->
    {"moe_dropped_frac", "moe_router_entropy"}`` (floats, layer-means)
    on the UNSHARDED snapshot — the training loop runs it once per outer
    sync on one microbatch, so a collapsed router or capacity-bound
    token dropping shows up in the JSONL instead of staying silent
    (VERDICT r3 weak #4). One extra forward per sync (~1/H of a step);
    the training program itself is untouched. A mixed stack has no
    capacity, so nothing is dropped and the first reads 0; its entropy
    is that of a token's scores over their sum. Ring attention swaps to
    the numerically-identical blockwise flash, as Evaluator does."""
    import dataclasses

    if cfg.attention_impl == "ring":
        cfg = dataclasses.replace(cfg, attention_impl="flash")

    @jax.jit
    def fn(params, tokens):
        from nanodiloco_tpu.models.llama import forward

        _, _, stats = forward(
            params, tokens, cfg, with_aux=True, collect_stats=True,
            return_hidden=True,  # skip the vocab head: stats don't need it
        )
        return {"moe_dropped_frac": stats[0], "moe_router_entropy": stats[1]}

    return fn


def _router_entropy(
    probs: jax.Array, valid_t: jax.Array | None, sp_axis: str | None
) -> jax.Array:
    """Mean per-token router entropy in nats over real tokens (globally
    reduced under sp). A healthy router sits well above 0; a collapsed
    router (all mass on one expert) drives this to ~0 — the failure mode
    VERDICT r3 weak #4 asked to make visible."""
    ent = -jnp.sum(probs * jnp.log(jnp.clip(probs, 1e-20)), axis=-1)  # [T]
    if valid_t is not None:
        v = valid_t.astype(jnp.float32)
        num, den = jnp.sum(ent * v), jnp.sum(v)
    else:
        num, den = jnp.sum(ent), jnp.float32(ent.shape[0])
    if sp_axis is not None:
        num = jax.lax.psum(num, sp_axis)
        den = jax.lax.psum(den, sp_axis)
    return num / jnp.maximum(den, 1.0)


def _experts_choose(
    cfg: LlamaConfig, x: jax.Array, probs: jax.Array, layer: dict,
    valid_t: jax.Array | None,
) -> tuple[jax.Array, jax.Array]:
    """Expert-choice routing (arXiv:2202.09368): each expert selects its
    top-C tokens by router affinity — every expert processes exactly C
    slots (perfect load balance by construction, no auxiliary loss). A
    token may be picked by several experts (contributions sum) or by
    none (the residual stream carries it). x: [T, d]; probs: [T, E]
    router affinities; valid_t: [T] or None. Returns (y [T, d], aux 0.0,
    dropped-token fraction)."""
    t, d = x.shape
    cap = min(expert_capacity(cfg, t), t)  # an expert can't pick a token twice
    cdt = x.dtype
    if valid_t is not None:
        # pad tokens: zero affinity — sorted last by top_k, and a zero
        # combine weight even when slots outnumber real tokens
        probs = probs * valid_t.astype(jnp.float32)[:, None]
    g, idx = jax.lax.top_k(jnp.swapaxes(probs, 0, 1), cap)  # [E, C]
    disp = jax.nn.one_hot(idx, t, dtype=cdt)                # [E, C, T]
    expert_in = jnp.einsum("ect,td->ecd", disp, x)
    out_e = _expert_ffn(expert_in, layer)
    y = jnp.einsum("ect,ec,ecd->td", disp, g.astype(cdt), out_e)
    # dropped = real tokens picked by NO expert (the residual path
    # carries them); expert-choice's analog of capacity overflow
    picked = (jnp.sum(disp.astype(jnp.float32), axis=(0, 1)) > 0).astype(
        jnp.float32
    )                                                       # [T]
    if valid_t is not None:
        v = valid_t.astype(jnp.float32)
        dropped = jnp.sum((1.0 - picked) * v) / jnp.maximum(jnp.sum(v), 1.0)
    else:
        dropped = 1.0 - jnp.sum(picked) / t
    return y, jnp.zeros((), jnp.float32), dropped


def short_rows(cfg: LlamaConfig, n_pairs: int) -> int | None:
    """Rows the short path of ``_ragged_mlp`` takes of ``n_pairs`` = k*T
    sorted token-expert pairs, or None where there is no short path:
    ``SHORT_ROWS_FACTOR`` times the pairs expected at held experts
    (``n_pairs`` x held / router width), rounded up to an odd number of
    ``SHORT_ROWS_TILE`` rows; where a held expert expects a whole
    ``SHORT_ROWS_WIDE_TILE`` rows or more, ``SHORT_ROWS_WIDE_FACTOR``
    times the expected in whole such tiles. None where that passes half
    of ``n_pairs``: every configuration that holds all its experts, and
    a share too large for the short path to save much."""
    expected = n_pairs * cfg.held_experts[1] / cfg.num_experts
    if expected >= SHORT_ROWS_WIDE_TILE * cfg.held_experts[1]:
        cap = SHORT_ROWS_WIDE_TILE * math.ceil(
            SHORT_ROWS_WIDE_FACTOR * expected / SHORT_ROWS_WIDE_TILE)
    else:
        tiles = max(1, math.ceil(SHORT_ROWS_FACTOR * expected / SHORT_ROWS_TILE))
        cap = SHORT_ROWS_TILE * (tiles + 1 - tiles % 2)
    return cap if 2 * cap <= n_pairs else None


@jax.custom_vjp
def _held_rows_gradient(xg: jax.Array, held: jax.Array) -> jax.Array:
    """``xg`` [n, d] as it is; in the backward pass the gradient of the
    rows that ``held`` [n] marks and zero for the rest. A grouped
    product leaves the rows in no group alone, in its transpose too, so
    on the chip the gathered rows' gradient holds whatever was there for
    a pair routed elsewhere, and the gather's transpose would add it
    into a token's gradient (my chip run, PR 32: a first backward pass
    of NaN). Forward programs are what they were. Where every expert is
    held only padding lies in no group, and that path is left as it
    was (ROADMAP B5)."""
    return xg


_held_rows_gradient.defvjp(
    lambda xg, held: (xg, held),
    lambda held, ct: (jnp.where(held[:, None], ct, 0), None))


def _weigh(out: jax.Array, w: jax.Array, held: jax.Array) -> jax.Array:
    """The grouped products' rows [n, d] times their weights [n], zero
    where ``held`` [n] is not set. A select, not a product by 0: a row
    in no group holds whatever the grouped product left there."""
    return jnp.where(held[:, None], out * w[:, None], 0)


def _weigh_held_bwd(res, ct):
    out, w, held = res
    ct = jnp.where(held[:, None], ct, 0)
    d_w = jnp.sum(ct.astype(jnp.float32) * jnp.where(held[:, None], out, 0), axis=-1)
    return ct * w[:, None], d_w.astype(w.dtype), None


# ``_weigh`` for a share: its backward pass (``_held_rows_gradient`` says
# why) keeps what a row in no group holds out of the weights' gradient
# too, where a zero gradient times such a row would be NaN
_weigh_held = jax.custom_vjp(_weigh)
_weigh_held.defvjp(lambda out, w, held: (_weigh(out, w, held), (out, w, held)),
                   _weigh_held_bwd)


def _ragged_mlp(
    cfg: LlamaConfig, x: jax.Array, topk_p: jax.Array, topk_e: jax.Array,
    layer: dict, valid_t: jax.Array | None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sorted/ragged token-choice dispatch (the Mixtral/megablocks shape;
    implements the large-E alternative the module docstring previously
    only design-documented). Flatten the [T, k] (token, slot) routing
    assignments, stable-argsort them by expert id so each expert's
    tokens are a contiguous run, and run the SwiGLU as three
    ``jax.lax.ragged_dot`` grouped matmuls with exact per-expert group
    sizes — no capacity, no dropped tokens, no one-hot [T, E, C] padding
    FLOPs. All shapes stay static; the data dependence is
    confined to the gather/scatter indices and the group-size vector,
    which is what keeps it XLA-compilable. x: [T, d]; topk_p/topk_e:
    [T, k] combine weights / expert ids over the ROUTER's experts.
    Returns (y [T, d], group sizes [count] int32, short int32: 1 where
    this call took the short path).

    The chip's share (``cfg.held_experts`` = (first, count); all of them
    by default): ``layer`` holds weights for experts first..first+count-1
    alone, a pair routed elsewhere sorts behind the last held group and
    lies in no group (``ragged_dot`` leaves rows past its groups alone;
    their output is dropped), and ``y`` is the held experts' part of the
    layer's output. ``topk_p`` is used as given: the caller normalised
    it over all k chosen, held or not.

    The short path: held pairs sort first, so while they number at most
    ``short_rows`` the first that many sorted rows are the whole of the
    held work, and the gather, the grouped products, the select and the
    scatter-add run over those rows alone (the same groups over fewer
    trailing rows); a ``lax.cond`` on ``sum(group_sizes)`` takes all
    k*T rows otherwise, so no pair is ever dropped. Where
    ``short_rows`` is None (all experts held) there is one body over
    k*T rows and no conditional. Under a ``vmap`` the conditional
    would become a select and both branches run, so DiLoCo runs such a
    configuration's workers unbatched (``Diloco._over_workers``).

    Padding tokens (valid_t = 0) are treated as routed elsewhere: no
    group, no output. Numerics vs dense dispatch at non-binding
    capacity: IDENTICAL routing and weights; summation order within an
    expert differs (contiguous run vs one-hot einsum), so outputs agree
    to dtype tolerance, not bit-exactly.
    """
    t, d = x.shape
    k = topk_e.shape[1]
    first, count = cfg.held_experts
    cdt = x.dtype

    with jax.named_scope("moe_route"):
        e_flat = topk_e.reshape(t * k) - first               # [kT] held-local ids
        tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)  # [kT]
        held = (e_flat >= 0) & (e_flat < count)
        if valid_t is not None:
            held = held & (valid_t[tok_flat] > 0)
        e_flat = jnp.where(held, e_flat, count)              # the rest sort last
        order = jnp.argsort(e_flat, stable=True)             # expert-contiguous
        group_sizes = jnp.bincount(e_flat, length=count + 1)[:count].astype(jnp.int32)
        w_sorted = topk_p.reshape(t * k)[order]
        held_sorted = held[order]
        rows = tok_flat[order]

    def experts(rows, w_sorted, held_sorted):
        xg = x[rows]                                         # [n, d] gather
        if count != cfg.num_experts:
            xg = _held_rows_gradient(xg, held_sorted)
        gate = jax.nn.silu(
            jax.lax.ragged_dot(xg, layer["w_gate"].astype(cdt), group_sizes)
        )
        up = jax.lax.ragged_dot(xg, layer["w_up"].astype(cdt), group_sizes)
        out = jax.lax.ragged_dot(
            gate * up, layer["w_down"].astype(cdt), group_sizes
        )                                                    # [n, d]
        out = (_weigh_held if count != cfg.num_experts else _weigh)(
            out, w_sorted.astype(cdt), held_sorted)
        return jnp.zeros((t, d), cdt).at[rows].add(out)

    cap = short_rows(cfg, t * k)
    with jax.named_scope("moe_experts"):
        if cap is None:
            y, short = experts(rows, w_sorted, held_sorted), jnp.zeros((), jnp.int32)
        else:
            fits = jnp.sum(group_sizes) <= cap
            y = jax.lax.cond(
                fits,
                lambda: experts(rows[:cap], w_sorted[:cap], held_sorted[:cap]),
                lambda: experts(rows, w_sorted, held_sorted))
            short = fits.astype(jnp.int32)
    return y, group_sizes, short


def route(cfg: LlamaConfig, x: jax.Array, layer: dict):
    """The gate of a mixed configuration's sparse layer. x [T, d] ->
    (weights [T, k] float32, experts [T, k] int32, scores [T, E]
    float32). Scores are softmax
    or sigmoid of the router's float32
    logits; the k experts are those with the largest score plus the
    layer's selection bias (``router_bias``, where the layer has one:
    it picks and does not weigh); the weights are the chosen experts'
    own scores, normalised over ALL k chosen where ``norm_topk_prob``,
    times ``routed_scaling_factor``."""
    logits = jnp.dot(x, layer["router"].astype(x.dtype),
                     preferred_element_type=jnp.float32)
    scores = (jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choose = scores + layer["router_bias"].astype(jnp.float32) \
        if "router_bias" in layer else scores
    _, topk_e = jax.lax.top_k(choose, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, topk_e, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.routed_scaling_factor, topk_e, scores


@jax.named_scope("moe_aux")
def balance_term(cfg: LlamaConfig, scores: jax.Array, topk_e: jax.Array,
                 valid_t: jax.Array | None) -> jax.Array:
    """The Switch load-balance term of one sparse layer, float32:
    E sum_e f_e P_e over ALL the router's experts, held here or not, with
    f_e the share of the real tokens' k*T pairs that chose e (no
    gradient) and P_e the mean of a real token's score for e."""
    e = cfg.num_experts
    v = jnp.ones(scores.shape[:1], jnp.float32) if valid_t is None \
        else (valid_t > 0).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(v), 1.0)
    chose = jnp.zeros((e,), jnp.float32).at[topk_e.reshape(-1)].add(
        jnp.repeat(v, topk_e.shape[1]))
    f = chose / (n * topk_e.shape[1])
    p = jnp.sum(scores * v[:, None], axis=0) / n
    return e * jnp.sum(f * p)


def sparse_mlp(cfg: LlamaConfig, h: jax.Array, layer: dict,
               valid: jax.Array | None = None, with_stats: bool = False):
    """The sparse feed-forward of a mixed configuration: ``route``, the
    held experts' grouped products (``_ragged_mlp``: no capacity, no
    dropped token) and the shared experts (one SwiGLU of width
    ``num_shared_experts * expert_width`` that every token passes).
    h [B, S, d] normed hidden states; ``valid`` [B, S] marks real
    tokens. Returns (out [B, S, d], counters int32[4], the chosen
    experts [B, S, k] int32); the counters (``COUNTERS`` names them):
    token-expert pairs routed to held experts, held experts at least one
    token chose, all pairs (k a real token), and 1 where the grouped
    products took the short path (``_ragged_mlp``). ``with_stats`` (the
    training forward asks, the serving programs do not) puts the float32
    vector ``ROUTER_STATS`` in the choice's place."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    valid_t = None if valid is None else valid.reshape(b * s)
    with jax.named_scope("moe_route"):
        w, topk_e, scores = route(cfg, x, layer)
    y, group_sizes, short = _ragged_mlp(cfg, x, w, topk_e, layer, valid_t)
    if "shared_gate" in layer:
        with jax.named_scope("moe_shared"):
            cdt = x.dtype
            gate = jax.nn.silu(x @ layer["shared_gate"].astype(cdt))
            up = x @ layer["shared_up"].astype(cdt)
            y = y + (gate * up) @ layer["shared_down"].astype(cdt)
    n_tok = jnp.int32(b * s) if valid_t is None else jnp.sum(valid_t > 0).astype(jnp.int32)
    counters = jnp.stack([jnp.sum(group_sizes), jnp.sum(group_sizes > 0).astype(jnp.int32),
                          n_tok * cfg.num_experts_per_tok, short])
    if with_stats:  # what a pass does not read of them the compiler drops
        return y.reshape(b, s, d), counters, jnp.stack([
            balance_term(cfg, scores, topk_e, valid_t),
            jnp.max(group_sizes).astype(jnp.float32),
            _router_entropy(scores / jnp.sum(scores, axis=-1, keepdims=True), valid_t, None)])
    return y.reshape(b, s, d), counters, topk_e.reshape(b, s, -1)


def _expert_ffn(expert_in: jax.Array, layer: dict) -> jax.Array:
    """Per-expert SwiGLU over dispatched slots [E, C, d] -> [E, C, d] —
    the one FFN body both router types share."""
    cdt = expert_in.dtype
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, layer["w_gate"].astype(cdt)))
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer["w_up"].astype(cdt))
    return jnp.einsum("ecf,efd->ecd", gate * up, layer["w_down"].astype(cdt))


def moe_mlp(
    cfg: LlamaConfig, h: jax.Array, layer: dict,
    valid: jax.Array | None = None, sp_axis: str | None = None,
    with_stats: bool = False,
):
    """h: [B, S, d] normed hidden states; layer carries ``router``
    [d, E] and expert FFN weights ``w_gate``/``w_up`` [E, d, f],
    ``w_down`` [E, f, d]; ``valid`` [B, S] 0/1 marks real tokens —
    padding claims no expert capacity and is excluded from the aux-loss
    statistics. Returns (mlp_out [B, S, d], aux_loss scalar). Routing is
    Switch-style top-k per token, or expert-choice with
    ``cfg.router_type == "experts_choose"``.

    ``sp_axis`` composes MoE with sequence parallelism (S is this
    shard's slice, the region is manual over that axis). Token-choice
    routing is per-token, so shard-local routing is IDENTICAL to the
    unsharded forward as long as expert capacity does not bind; capacity
    itself is sized from the shard's local tokens, so WHICH tokens
    overflow to the residual path differs from the unsharded order when
    it does bind (the same documented divergence as cached decode,
    models/generate.py). Ragged dispatch has no capacity, so its
    shard-local routing is the global routing EXACTLY at any capacity
    factor (tested at cf=0.25, where dense binds hard). The load-balance statistics stay globally
    exact: f_e/p_e reduce over ``sp_axis`` (three [E]-sized psums), so
    the aux value equals the unsharded one on every shard. Expert-choice
    routing stays sequence-local-only: top-C token selection over a
    shard is a different function than over the sequence, at any
    capacity.

    Why the expert-choice x sp rejection stays (VERDICT r3 weak #7 asked
    for the workaround to be costed, not hand-waved): global top-C CAN
    be recovered under sp — all-gather the router affinities [T, E] over
    the sp axis (cheap: E << d) and have every shard compute the same
    global top-C selection, restricted to its local tokens. But the
    FLOPs or bandwidth to then EXECUTE that selection defeats sp's
    purpose either way: (a) keep the static dense dispatch and each
    shard's [E, C_global, d] expert pass computes every global slot —
    zero rows for other shards' tokens are still multiplied — an
    sp-fold FLOPs inflation of the expert FFN; or (b) psum the sparse
    [E, C_global, d] expert inputs so slots carry real data exactly
    once, costing two [E, C, d] ≈ k*cf*T*d-float collectives per MoE
    layer — the same order as all-gathering the hidden states
    themselves, i.e. the traffic sp exists to avoid at long S. Use
    token-choice routing under sp (shard-local = globally identical
    while capacity is ample); expert-choice remains the short-sequence
    / no-sp router.

    ``with_stats`` additionally returns ``stats`` = [dropped_frac,
    router_entropy] float32[2] — the observability channel (VERDICT r3
    weak #4: silent capacity-bound dropping and router collapse must be
    visible). Off the training path (the diagnostics probe sets it), so
    the training program is unchanged."""
    if cfg.mixed:
        raise ValueError(
            "moe_mlp is the softmax top-k layer; a mixed configuration "
            "(sigmoid / bias-corrected gate, shared experts, a held share "
            "of the experts) runs models.moe.sparse_mlp")
    b, s, d = h.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cdt = h.dtype
    x = h.reshape(b * s, d)
    t = b * s

    logits = (x @ layer["router"].astype(cdt)).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.router_type == "experts_choose":
        if sp_axis is not None:
            raise ValueError(
                "expert-choice routing does not compose with sequence "
                "parallelism: each expert's top-C token selection sees "
                "the whole sequence, so per-shard selection computes a "
                "different function at any capacity (arXiv:2202.09368). "
                "The global-top-C workaround is costed out in moe_mlp's "
                "docstring (sp-fold FFN FLOPs or ~k*cf*T*d traffic per "
                "layer); use router_type='tokens_choose' with --sp"
            )
        y, aux, dropped = _experts_choose(
            cfg, x, probs, layer, None if valid is None else valid.reshape(t)
        )
        if with_stats:
            stats = jnp.stack([dropped, _router_entropy(probs, None if valid is None else valid.reshape(t), None)])
            return y.reshape(b, s, d), aux, stats
        return y.reshape(b, s, d), aux
    cap = expert_capacity(cfg, t)
    topk_p, topk_e = jax.lax.top_k(probs, k)                        # [T, k]
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(topk_e, e, dtype=jnp.float32)           # [T, k, E]
    if valid is not None:
        # pad tokens route nowhere: no capacity consumed, zero output
        # (the residual stream carries them), no aux-statistics weight
        onehot = onehot * valid.reshape(t).astype(jnp.float32)[:, None, None]

    if cfg.moe_dispatch == "ragged":
        # exact-sized grouped matmuls, no capacity, nothing dropped;
        # `keep` stays the full assignment for the shared stats below
        y = _ragged_mlp(
            cfg, x, topk_p, topk_e, layer,
            None if valid is None else valid.reshape(t),
        )[0]
        keep = onehot
    else:
        # per-(token, slot) position in the chosen expert's queue: a
        # cumsum over tokens of that expert's one-hots, k slots
        # interleaved in priority order (slot 0 claims capacity first)
        slot_major = jnp.swapaxes(onehot, 0, 1).reshape(k * t, e)   # [k*T, E]
        pos = jnp.cumsum(slot_major, axis=0) - slot_major           # arrival index
        keep = (pos < cap) * slot_major                             # [k*T, E]
        pos = jnp.swapaxes(pos.reshape(k, t, e), 0, 1)              # [T, k, E]
        keep = jnp.swapaxes(keep.reshape(k, t, e), 0, 1)            # [T, k, E]

        cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        # dispatch/combine [T, E, C]
        dispatch = jnp.einsum("tke,tkec->tec", keep, cap_onehot)
        combine = jnp.einsum("tke,tkec->tec", keep * topk_p[..., None], cap_onehot)

        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(cdt), x
        )                                                            # [E, C, d]
        out_e = _expert_ffn(expert_in, layer)
        y = jnp.einsum("tec,ecd->td", combine.astype(cdt), out_e)

    # Switch load-balance loss on the top-1 assignment (pre-capacity),
    # statistics over REAL tokens only — and over the WHOLE sequence
    # under sp (global means, not a mean of per-shard products: f_e*p_e
    # is nonlinear, so per-shard auxes would not average to the
    # unsharded value)
    if valid is not None:
        v = valid.reshape(t).astype(jnp.float32)
        num_f = jnp.sum(onehot[:, 0, :], axis=0)                     # [E]
        num_p = jnp.sum(probs * v[:, None], axis=0)
        den = jnp.sum(v)
    else:
        num_f = jnp.sum(onehot[:, 0, :], axis=0)
        num_p = jnp.sum(probs, axis=0)
        den = jnp.float32(t)
    if sp_axis is not None:
        num_f = jax.lax.psum(num_f, sp_axis)
        num_p = jax.lax.psum(num_p, sp_axis)
        den = jax.lax.psum(den, sp_axis)
    den = jnp.maximum(den, 1.0)
    aux = e * jnp.sum((num_f / den) * (num_p / den))
    if with_stats:
        # dropped = (token, slot) routing assignments that exceeded the
        # chosen expert's capacity — globally reduced under sp so every
        # shard reports the same number
        assigned = jnp.sum(onehot)
        kept = jnp.sum(keep)
        if sp_axis is not None:
            assigned = jax.lax.psum(assigned, sp_axis)
            kept = jax.lax.psum(kept, sp_axis)
        dropped = 1.0 - kept / jnp.maximum(assigned, 1.0)
        v_t = None if valid is None else valid.reshape(t)
        stats = jnp.stack([dropped, _router_entropy(probs, v_t, sp_axis)])
        return y.reshape(b, s, d), aux, stats
    return y.reshape(b, s, d), aux
