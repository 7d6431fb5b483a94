"""Pipeline parallelism: the layer stack sharded over a ``pp`` mesh axis,
microbatches streamed through the stages GPipe-style.

The reference has no pipeline parallelism (SURVEY §2 "Pipeline
parallelism (PP): NO"); this is a TPU-native capability add. Design:

- **Stages are a sharding of the stacked layer axis.** The model's
  per-layer weights are already stacked on a leading ``[L, ...]`` axis
  (models/llama.py); stage p simply holds the contiguous slice
  ``layers[p*L/P : (p+1)*L/P]`` — the PartitionSpec puts the layer axis
  on ``pp`` and ``shard_map`` hands each stage its local slice. No
  parameter surgery, no per-stage module classes.
- **SPMD schedule, not per-stage programs.** All stages run ONE traced
  program: a ``lax.scan`` over ``T = M + P - 1`` ticks. At each tick a
  stage runs its layers on whatever activation sits in its buffer, then
  ``ppermute``s the result to the next stage. Stage 0 ingests microbatch
  ``t`` from the (grad-accumulation) microbatch axis; the last stage
  emits a loss for microbatch ``t - (P-1)`` when valid. The pipeline
  bubble is the standard GPipe ``(P-1)/(M+P-1)``.
- **Backward for free (GPipe), or scheduled (1F1B).** ``jax.grad``
  through the scan+ppermute forward yields the reverse pipeline schedule
  automatically (the cotangent of a ``ppermute`` is the inverse
  ``ppermute``) — no hand-written backward, at the cost of keeping every
  tick's stage input alive (``M + P - 1`` microbatches).
  ``pp_shard_grads_1f1b`` instead runs one forward AND one per-microbatch
  ``jax.vjp`` backward per cycle, capping live activations at ``2P - 1``
  stage inputs — select with ``DilocoConfig.pp_schedule`` /
  ``--pp-schedule``; gradients agree up to fp summation order (the
  schedules accumulate microbatch gradients in different orders;
  ~1e-7 observed, test_pp.py).
- **Head/embed replicated over pp.** Only stage 0's embedding lookup and
  the last stage's LM head contribute (masked straight-line compute —
  per-stage divergent ``lax.cond`` deadlocks the transposed collectives,
  and in lockstep SPMD it would save no wall clock anyway); their
  gradients are zero on the other stages and get one ``psum`` in the
  caller.

Must be called inside ``jax.shard_map`` with ``axis_name`` bound (the
callers: Diloco._pp_inner_update for training, tests for parity).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.models.llama import (
    _decoder_layer,
    checkpoint_policy,
    rms_norm,
    rope_tables,
    sp_shift_targets,
)
from nanodiloco_tpu.ops.fused_ce import chunked_softmax_xent


def _pipeline_setup(cfg: LlamaConfig, S: int, sp_axis: str | None):
    """Shared stage machinery for BOTH schedules (GPipe and 1F1B):
    validated sp setup, rope tables (shard-global offsets under sp), and
    the (possibly rematerialized) per-layer function. One copy, so a
    semantics change can never diverge the two schedules silently."""
    if cfg.mixed:
        raise ValueError(
            "the pipeline stages do not carry a mixed layer stack (window and "
            "full attention layers, leading dense layers, a held share of the "
            "experts): a stage scans one kind of layer")
    if sp_axis is not None:
        if cfg.attention_impl != "ring":
            raise ValueError(
                "pipeline + sequence parallelism requires "
                f"attention_impl='ring'; got {cfg.attention_impl!r}"
            )
        if cfg.num_experts and cfg.router_type == "experts_choose":
            # token-choice MoE composes with sp (moe_mlp routes locally
            # with globally-exact aux stats); expert-choice cannot — see
            # moe_mlp's rejection
            raise ValueError(
                "expert-choice routing does not compose with sequence "
                "parallelism; use router_type='tokens_choose' with sp"
            )
        sp_idx = lax.axis_index(sp_axis)
        cos, sin = rope_tables(cfg, S, offset=sp_idx * S)
    else:
        cos, sin = rope_tables(cfg, S)

    def layer_fn(x, layer, cos, sin, valid):
        return _decoder_layer(cfg, x, layer, cos, sin, None, sp_axis, valid)

    if cfg.remat:
        # honor cfg.remat_policy exactly like the unsharded forward
        # (ADVICE r2) — one shared mapping, models/llama.py
        layer_fn = jax.checkpoint(layer_fn, policy=checkpoint_policy(cfg))
    return cos, sin, layer_fn


def _exit_loss(cfg: LlamaConfig, prm: dict, y, tok, msk, sp_axis: str | None):
    """Pipe-exit loss: final norm -> (sp-shifted) targets -> chunked CE,
    with the head falling back to tied embeddings. Derived entirely from
    ``prm`` so a vjp through it routes every parameter cotangent."""
    head = prm.get("lm_head")
    if head is None:
        head = prm["embed"].T
    h = rms_norm(y, prm["final_norm"], cfg.rms_norm_eps)
    if sp_axis is None:
        return _hidden_ce(
            h[:, :-1], head, tok[:, 1:],
            msk[:, 1:].astype(jnp.float32), cfg.loss_chunk,
        )
    targets, w = sp_shift_targets(tok, msk, sp_axis)
    return _hidden_ce(h, head, targets, w, cfg.loss_chunk)


def _mb_token_counts(loss_mask_mb, sp_axis: str | None):
    """Per-microbatch CE-target counts [M] — the router-aux gradient
    weights, which must equal the n_tokens the exit loss reports (the
    vmap path weights aux by exactly that count). Under sp the count
    follows sp_shift_targets: the right neighbor's first mask completes
    each shard's targets and the GLOBAL last position is dropped — raw
    ``msk[:, :, 1:]`` sums would underweight by (sp-1)/(S-1)."""
    if sp_axis is None:
        return jnp.sum(loss_mask_mb[:, :, 1:].astype(jnp.float32), axis=(1, 2))
    n = lax.psum(1, sp_axis)
    idx = lax.axis_index(sp_axis)
    to_left = [(j, (j - 1) % n) for j in range(n)]
    nxt = lax.ppermute(loss_mask_mb[:, :, :1], sp_axis, to_left)
    m = jnp.concatenate(
        [loss_mask_mb[:, :, 1:], nxt], axis=2
    ).astype(jnp.float32)
    s_loc = loss_mask_mb.shape[2]
    last_pos = (jnp.arange(s_loc) == s_loc - 1)[None, None]
    m = m * (1.0 - last_pos * (idx == n - 1)).astype(jnp.float32)
    return jnp.sum(m, axis=(1, 2))


@jax.named_scope("loss")
def _hidden_ce(h, head, targets, weights, chunk: int):
    """(sum_loss, n_tokens) from final hidden states [B, S-1 rows]."""
    b, s1, d = h.shape
    if chunk:
        return chunked_softmax_xent(
            h.reshape(b * s1, d), head.astype(h.dtype),
            targets.reshape(-1), weights.reshape(-1), chunk=chunk,
        )
    logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights), jnp.sum(weights)


def pp_shard_loss(
    params: dict,
    tokens_mb: jax.Array,     # [M, B, S] — microbatches = pipeline slots
    cfg: LlamaConfig,
    loss_mask_mb: jax.Array,  # [M, B, S]
    axis_name: str = "pp",
    sp_axis: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-stage UNREDUCED (sum_loss, n_tokens, aux_weighted,
    metric_sum): callers ``psum`` all four over ``axis_name`` (and psum
    the replicated embed/head/norm grads). ``aux_weighted`` is the MoE
    router load-balance loss of this stage's layers, summed over
    microbatches weighted by each microbatch's token count — psummed it
    equals ``sum_m n_m * aux_m`` exactly as the unsharded
    grad-accumulation path weights its gradients (zero for dense
    models). ``metric_sum`` psummed is ``sum_m (ce_mean_m + coef*aux_m)``
    — divide by M for the same mean-of-microbatch-means loss METRIC the
    vmap path reports.

    ``params`` is this stage's view: ``layers`` leaves are the local
    ``[L/P, ...]`` slice; ``embed``/``final_norm``/``lm_head`` are the
    full replicated arrays.

    With ``sp_axis`` the sequence dim is additionally sharded over that
    (manual) mesh axis: stages run ring attention over ``sp_axis``, rope
    positions carry each shard's global offset, and the exit loss shifts
    labels across shard boundaries with one tiny ppermute (the same
    contract as models.llama.sp_shard_loss). sum_loss/n_tok come back
    shard-local — callers psum them over BOTH axes. ``metric``'s VALUE is
    already sp-uniform (reduced in-tick) but its scan-carry TYPE is still
    sp-varying: callers must apply a value-preserving
    ``psum(metric, sp_axis) / psum(1, sp_axis)`` to replicate its type
    before using it in sp-replicated out_specs, then psum over
    ``axis_name`` as usual (see Diloco._pp_inner_update).
    """
    p_idx = lax.axis_index(axis_name)
    n_stages = lax.psum(1, axis_name)
    M, B, S = tokens_mb.shape  # S is the LOCAL shard length under sp
    cdt = jnp.dtype(cfg.dtype)
    cos, sin, layer_fn = _pipeline_setup(cfg, S, sp_axis)

    def run_stage(x, valid):
        """Local layers on [B, S, d] -> (x, summed router aux).
        ``valid`` [B, S] is the processed microbatch's pad mask — MoE
        routing must never spend expert capacity on padding (same
        contract as the unsharded path)."""

        def body(carry, layer):
            x, aux = layer_fn(carry, layer, cos, sin, valid)
            return x, aux

        x, auxes = lax.scan(body, x, params["layers"])
        return x, jnp.sum(auxes)

    def mb_loss(y, t):
        """Loss of the microbatch leaving the pipe at tick t (valid only
        on the final stage for 0 <= t-(P-1) < M). Returns this device's
        shard-local (sum_loss, n_tokens)."""
        m_out = jnp.clip(t - (n_stages - 1), 0, M - 1)
        tok = lax.dynamic_index_in_dim(tokens_mb, m_out, 0, keepdims=False)
        msk = lax.dynamic_index_in_dim(loss_mask_mb, m_out, 0, keepdims=False)
        return _exit_loss(cfg, params, y, tok, msk, sp_axis)

    # per-microbatch token counts (the loss-shift weights), for aux
    # weighting identical to the vmap grad-accumulation path
    n_per_mb = _mb_token_counts(loss_mask_mb, sp_axis)

    coef = cfg.router_aux_coef

    def tick(carry, t):
        buf, sum_loss, n_tok, aux_w, metric = carry
        # stage 0 ingests microbatch t (clamped; drained ticks recompute
        # the last microbatch and their outputs are never used)
        m_in = jnp.clip(t, 0, M - 1)
        tok_in = lax.dynamic_index_in_dim(tokens_mb, m_in, 0, keepdims=False)
        x0 = params["embed"].astype(cdt)[tok_in]
        x = jnp.where(p_idx == 0, x0, buf)
        # this stage processes microbatch t - p_idx at tick t; its pad
        # mask rides along so MoE routing stays padding-blind
        m_here = t - p_idx
        valid_mb = lax.dynamic_index_in_dim(
            loss_mask_mb, jnp.clip(m_here, 0, M - 1), 0, keepdims=False
        )
        y, stage_aux = run_stage(x, valid_mb)
        # straight-line masking, no lax.cond: per-stage divergent control
        # flow around code whose transpose touches collectives deadlocks
        # the backward (devices reach collectives in different orders),
        # and in lockstep SPMD skipping the head matmul on non-final
        # stages saves no wall clock anyway — every stage waits for the
        # slowest one each tick.
        valid = (
            (p_idx == n_stages - 1) & (t >= n_stages - 1)
        ).astype(jnp.float32)
        sl, n = mb_loss(y, t)
        sl, n = valid * sl, valid * n
        pass_valid = ((m_here >= 0) & (m_here < M)).astype(jnp.float32)
        n_here = n_per_mb[jnp.clip(m_here, 0, M - 1)]
        aux_w = aux_w + pass_valid * n_here * stage_aux
        # metric accumulators mirror the vmap path's mean-of-means
        # convention: per-microbatch ce mean (last stage) + unweighted
        # aux (every stage's layers). Under sp the per-microbatch mean
        # needs the GLOBAL sum/count, so the metric term reduces over sp
        # here (making metric sp-replicated — callers psum it over pp
        # only); sum_loss/n_tok stay shard-local for the caller's psum.
        sl_m, n_m = (
            (lax.psum(sl, sp_axis), lax.psum(n, sp_axis))
            if sp_axis is not None
            else (sl, n)
        )
        metric = (
            metric
            + valid * sl_m / jnp.maximum(n_m, 1.0)
            + coef * pass_valid * stage_aux
        )
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = lax.ppermute(y, axis_name, perm)
        return (buf, sum_loss + sl, n_tok + n, aux_w, metric), None

    # carries start typed as varying over the pp axis (their updates
    # are); data-derived zeros carry any other manual axes' vary-ness
    first = params["embed"].astype(cdt)[tokens_mb[0]]
    buf0 = lax.pcast(first * 0.0, (axis_name,), to="varying")
    z = lax.pcast(
        jnp.sum(first[..., 0]).astype(jnp.float32) * 0.0,
        (axis_name,),
        to="varying",
    )
    T = M + n_stages - 1
    (_, sum_loss, n_tok, aux_w, metric), _ = lax.scan(
        tick, (buf0, z, z, z, z), jnp.arange(T, dtype=jnp.int32)
    )
    return sum_loss, n_tok, aux_w, metric


def pp_shard_grads_1f1b(
    params: dict,
    tokens_mb: jax.Array,     # [M, B, S]
    cfg: LlamaConfig,
    loss_mask_mb: jax.Array,  # [M, B, S]
    axis_name: str = "pp",
    sp_axis: str | None = None,
):
    """1F1B schedule: gradients of the same summed loss as
    ``pp_shard_loss``, computed by a hand-scheduled per-microbatch VJP so
    activation memory is O(P), not O(M).

    GPipe-via-autodiff (``jax.grad`` over ``pp_shard_loss``'s tick scan)
    must keep every tick's stage input alive until the reverse wave —
    ``M + P - 1`` microbatch activations per stage. Here each cycle of a
    single scan runs, per stage, ONE forward (microbatch ``c - s``, as in
    GPipe) and ONE backward (microbatch ``c - (2P-2-s)``: the backward
    wave departs the last stage the same cycle its forward lands and
    trails back down). A backward recomputes its stage from the SAVED
    STAGE INPUT via ``jax.vjp``, so the only live activations are a
    ``2P-1``-slot input queue — at M=32, P=4 that is 7 saved microbatch
    inputs versus GPipe's 35 per-tick carries (each of which multiplies
    by L/P inner-scan carries under per-layer remat).

    Trade-off, stated honestly: the fused F+B cycle idles its B half
    during warmup and its F half during drain, so the bubble is
    ``2(P-1)`` cycles — twice GPipe's per-wave bubble. The win is memory:
    at fixed HBM the cheaper activations buy a larger M, which is what
    actually shrinks the bubble fraction ``2(P-1)/(M+2P-2)``.

    Compute trade-off (ADVICE r3): each cycle runs the full cell once in
    its forward half and AGAIN inside ``jax.vjp`` for its backward half
    — the forward-half outputs are not reused by the backward, so every
    stage pays ~2 forwards + 1 backward per microbatch. That matches
    GPipe-with-per-layer-remat (which also recomputes each stage inside
    the reverse wave) and is ~1.33x the forward FLOPs of a no-remat
    GPipe — but a no-remat GPipe's O(M+P) live activations are exactly
    the regime 1F1B exists to avoid, so against the schedules this module
    actually offers the FLOPs are a wash and the choice is purely the
    activation-memory / bubble trade above.

    Same contract as ``pp_shard_loss`` for the loss statistics; returns
    ``(grads, sum_loss, n_tok, aux_weighted, metric_sum)`` where
    ``grads`` is the UNREDUCED per-stage gradient of
    ``psum(sum_loss) + coef * psum(aux_weighted)`` — callers psum the
    replicated (embed/head/norm) leaves over ``axis_name`` exactly as
    they do for the autodiff path. Cross-stage dependencies flow through
    the reverse ``ppermute`` of input cotangents; the forward ring's
    wraparound (last stage -> stage 0) carries a cotangent that is
    identically zero because stage 0's ``where`` selects the embedding
    branch — no special-casing at the ends.
    """
    p_idx = lax.axis_index(axis_name)
    n_stages = lax.psum(1, axis_name)  # static: mesh axis sizes are known
    M, B, S = tokens_mb.shape
    cdt = jnp.dtype(cfg.dtype)
    cos, sin, layer_fn = _pipeline_setup(cfg, S, sp_axis)

    def cell(prm, m, x_prev):
        """One stage pass of microbatch m, everything derived from
        ``prm`` so a vjp routes every parameter's cotangent: ingest (stage
        0) or receive, local layers, exit loss (counted by the caller only
        on the last stage). Straight-line like the GPipe tick — masked,
        never branched, so the transposed collectives stay in lockstep."""
        tok = lax.dynamic_index_in_dim(tokens_mb, m, 0, keepdims=False)
        msk = lax.dynamic_index_in_dim(loss_mask_mb, m, 0, keepdims=False)
        x_in = jnp.where(p_idx == 0, prm["embed"].astype(cdt)[tok], x_prev)

        def body(carry, layer):
            x, aux = layer_fn(carry, layer, cos, sin, msk)
            return x, aux

        y, auxes = lax.scan(body, x_in, prm["layers"])
        sl, n = _exit_loss(cfg, prm, y, tok, msk, sp_axis)
        aux = jnp.sum(auxes)
        # the aux term exactly as it enters the total loss: weighted by
        # the exit loss's OWN token count (shard-local under sp; the
        # per-shard weights psum to the vmap path's global n_tokens). A
        # separate output from the raw ``aux`` because the two need
        # different backward cotangents: the loss term backprops on
        # every stage (mask bv), the raw statistic never does. ``n`` has
        # no parameter dependence, so routing it into the weight adds no
        # gradient path.
        return y, sl, n, aux, coef * n * aux

    n_per_mb = _mb_token_counts(loss_mask_mb, sp_axis)
    coef = cfg.router_aux_coef
    Q = 2 * n_stages - 1   # max in-flight stage inputs: 2(P-1-s)+1 <= 2P-1
    T = M + 2 * n_stages - 2
    is_last = (p_idx == n_stages - 1).astype(jnp.float32)
    perm_f = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_b = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def cycle(carry, c):
        buf, dybuf, queue, grads, sum_loss, n_tok, aux_w, metric = carry

        # ---- forward half: microbatch c - s, exactly GPipe's wave ----
        m_raw = c - p_idx
        f_valid = (m_raw >= 0) & (m_raw < M)
        m_f = jnp.clip(m_raw, 0, M - 1)  # clamped: edge cycles recompute
        fv = f_valid.astype(jnp.float32)
        y, sl, n, aux, _auxw = cell(params, m_f, buf)
        lv = is_last * fv
        sl, n = lv * sl, lv * n
        aux_w = aux_w + fv * n_per_mb[m_f] * aux
        sl_m, n_m = (
            (lax.psum(sl, sp_axis), lax.psum(n, sp_axis))
            if sp_axis is not None else (sl, n)
        )
        metric = metric + lv * sl_m / jnp.maximum(n_m, 1.0) + coef * fv * aux
        # save this cycle's received input for the microbatch's backward;
        # guarded so clamped edge cycles can't clobber a live slot
        slot = m_f % Q
        old = lax.dynamic_index_in_dim(queue, slot, 0, keepdims=False)
        queue = lax.dynamic_update_index_in_dim(
            queue, jnp.where(f_valid, buf, old), slot, 0
        )

        # ---- backward half: microbatch c - (2P-2-s), the reverse wave --
        mb_raw = c - (2 * n_stages - 2 - p_idx)
        b_valid = (mb_raw >= 0) & (mb_raw < M)
        bv = b_valid.astype(jnp.float32)
        m_b = jnp.clip(mb_raw, 0, M - 1)
        x_saved = lax.dynamic_index_in_dim(queue, m_b % Q, 0, keepdims=False)
        (y_p, sl_p, n_p, aux_p, auxw_p), pull = jax.vjp(
            lambda prm, xp: cell(prm, m_b, xp), params, x_saved
        )
        # cotangents of (y, sl, n, aux, aux_weighted): y's arrives from
        # the next stage (zero into the last stage via the ring, see
        # docstring); sl counts once at the exit; n and the raw aux
        # statistic carry no gradient; aux_weighted backprops on every
        # stage that processed a valid microbatch. Each adds primal * 0
        # so its manual-axis vary-ness matches the primal's (vjp rejects
        # a cotangent typed differently from its output — e.g. the raw
        # MoE aux under sp is sp-invariant after its stats psums, while
        # bv-derived masks are not).
        # dense models: aux terms are the constant 0.0 (replicated type)
        # and contribute nothing — cotangents must stay replicated too
        auxw_ct = bv + auxw_p * 0 if cfg.num_experts else auxw_p * 0
        dprm, dx = pull((
            (dybuf * bv).astype(cdt) + y_p * 0,
            bv * is_last + sl_p * 0,
            n_p * 0,
            aux_p * 0,
            auxw_ct,
        ))
        grads = jax.tree.map(lambda g, d: g + d, grads, dprm)

        buf = lax.ppermute(y, axis_name, perm_f)
        dybuf = lax.ppermute((dx * bv).astype(cdt), axis_name, perm_b)
        return (buf, dybuf, queue, grads, sum_loss + sl, n_tok + n,
                aux_w, metric), None

    # carries start typed as varying over the manual axes: derive a zero
    # from the (sharded) data and add it everywhere (same trick as
    # pp_shard_loss's pcast'd zeros)
    first = params["embed"].astype(cdt)[tokens_mb[0]]
    z = lax.pcast(
        jnp.sum(first[..., 0]).astype(jnp.float32) * 0.0,
        (axis_name,), to="varying",
    )
    buf0 = jnp.zeros_like(first) + z.astype(cdt)
    queue0 = jnp.zeros((Q,) + first.shape, cdt) + z.astype(cdt)
    grads0 = jax.tree.map(
        lambda p: jnp.zeros_like(p) + z.astype(p.dtype), params
    )
    (_, _, _, grads, sum_loss, n_tok, aux_w, metric), _ = lax.scan(
        cycle,
        (buf0, buf0, queue0, grads0, z, z, z, z),
        jnp.arange(T, dtype=jnp.int32),
    )
    return grads, sum_loss, n_tok, aux_w, metric
