"""DiLoCo core: jitted inner/outer steps over a ``diloco`` mesh axis.

Re-design of the reference's ``Diloco`` class
(ref nanodiloco/diloco/diloco.py:7-74) for the XLA programming model:

- Every worker's parameters live in ONE stacked pytree with a leading
  worker axis of size W, sharded over the ``diloco`` mesh axis. The inner
  step is ``vmap`` over that axis — XLA partitions it so each worker's
  compute lands on its own mesh slice with zero communication, exactly the
  DiLoCo contract (ref nanodiloco/main.py:106-113 has no collectives in
  the inner loop either).
- The outer step is a pure function: pseudo-gradient
  ``snapshot - mean_over_workers(params)`` — the mean over the stacked
  axis IS the all-reduce (XLA lowers it to an all-reduce over ``diloco``,
  riding ICI intra-slice / DCN across slices), replacing
  ``dist.all_reduce(AVG)`` per tensor (ref diloco.py:49). Nesterov SGD
  then advances the snapshot (ref diloco.py:52) and every worker resets
  to it (ref diloco.py:50) — here a broadcast back over the worker axis.
- The reference's init-time ``dist.broadcast`` per parameter
  (ref diloco.py:21-22) is replaced by construction: one PRNG-keyed init
  tiled across the worker axis is bit-identical by definition.
- The reference's CPU offload of the sync snapshot (ref diloco.py:27-32)
  is optional here (``offload_snapshot``): on TPU the snapshot moves to
  pinned host memory between outer steps via async device_put, freeing
  HBM without blocking dispatch. Default off — on-chip is faster when
  HBM allows.
- Unlike the reference, inner/outer stepping cadence is owned by this
  class (the reference accepted ``inner_steps`` and ignored it,
  ref diloco.py:8-25 / SURVEY §2 quirks), and grad accumulation divides
  correctly (the reference backpropped the undivided loss,
  ref nanodiloco/main.py:110-111).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.models.llama import causal_lm_loss, init_params
from nanodiloco_tpu.obs.tracer import trace_span
from nanodiloco_tpu.parallel.sharding import batch_spec, constrain, param_specs
from nanodiloco_tpu.training.optim import inner_optimizer, outer_optimizer


@dataclasses.dataclass(frozen=True)
class DilocoConfig:
    """Knobs mirroring the reference CLI (ref nanodiloco/main.py:42-55)."""

    num_workers: int = 1
    inner_steps: int = 100          # H: inner steps between outer syncs
    warmup_steps: int = 100
    total_steps: int = 10_000
    lr: float = 4e-4                # inner AdamW lr
    outer_lr: float = 0.7           # outer SGD lr
    outer_momentum: float = 0.9
    nesterov: bool = True
    weight_decay: float = 0.01
    clip_norm: float | None = 1.0
    grad_accum: int = 1             # microbatches per inner step
    # pipeline schedule: "gpipe" (autodiff through the tick scan; stores
    # M+P-1 stage inputs) or "1f1b" (hand-scheduled per-microbatch vjp;
    # stores 2P-1 — see ops/pipeline.py:pp_shard_grads_1f1b for the
    # bubble/memory trade)
    pp_schedule: str = "gpipe"
    # Park the sync snapshot in pinned_host BETWEEN dispatches (honest
    # scope: inside a dispatched program — a fused round, or each step
    # of a stepwise round once fetched — the snapshot is device-resident
    # because the outer step consumes it; the HBM relief is the window
    # between dispatches, where checkpoint saves, eval forwards, and the
    # next round's batch prep happen). Public entries fetch it back to
    # device automatically (_fetch). Classic DiLoCo only.
    offload_snapshot: bool = False
    # Wire format of the outer all-reduce payload (e.g. "bfloat16" halves
    # DCN/ICI traffic; pseudo-gradients are noise-tolerant — the reference
    # always reduced in fp32). None = reduce in the snapshot's dtype.
    outer_comm_dtype: str | None = None
    # Carry the quantized payload ON the collective (requires a
    # signed-int outer_comm_dtype): the outer mean runs as a
    # shard_map-manual region over ``diloco`` where workers quantize
    # against a SHARED scale (one pmax'd scalar per tensor), the
    # all-reduce operand is an integer tensor of the narrowest width
    # the worst-case sum W*q_max fits (int8 for an "int4" wire at
    # W<=18 — one byte per element; int16 for int8 payloads; int32
    # beyond), and dequantization happens after the collective — so
    # the bytes that travel ICI/DCN are the quantized payload, matching
    # what the reference's wire actually carries
    # (ref nanodiloco/diloco/diloco.py:49). Default off: the default
    # path keeps per-(worker, tensor) scales (finer quantization) at the
    # cost of an f32 reduce. Trade-off: the shared scale is the max over
    # surviving workers, so a worker with an outsized delta coarsens
    # everyone's bins by up to W× vs per-worker scales.
    outer_wire_collective: bool = False
    # Divergence quarantine: a worker whose replica holds any non-finite
    # value at sync time (exact criterion, checked in _outer_step; a
    # non-finite inner loss during the round ANDs in as an extra reason)
    # is masked out of the outer mean (see _pseudograd's worker_mask),
    # its Adam moments are zeroed (NaN moments never decay, so a reset
    # without this is permanent W-1 degradation), and it resets — like
    # every worker — to the healthy survivors' new snapshot: one
    # replica's blow-up self-heals at the next sync instead of poisoning
    # the global model. Computed INSIDE the fused round program (no host
    # round-trip). The reference has no analog: its NaN would all-reduce
    # into every rank.
    quarantine_nonfinite: bool = False
    # DiLoCo dynamics telemetry, computed ON DEVICE inside the same
    # program as the outer step (fused round or stepwise sync — never an
    # extra dispatch, never an extra snapshot fetch): per-worker
    # pseudo-gradient norms, cross-worker replica drift (max/mean
    # pairwise distance normalized by the snapshot norm), the outer
    # Nesterov momentum norm, and the cosine between the averaged
    # pseudo-gradient and the applied outer update. Pure readouts of
    # values the sync already computes — training numerics are
    # bit-identical on or off (asserted by the smoke gate). When on,
    # ``round_step`` returns a 4th element and ``outer_step`` a 2nd:
    # the dynamics dict (see ``_sync_dynamics``).
    dynamics_metrics: bool = False
    # Async delayed-apply outer step (the whole-model analog of
    # streaming's per-fragment launch/apply split, arXiv:2501.18512):
    # at each round boundary the pseudo-gradient all-reduce + Nesterov
    # update is LAUNCHED into a pending slot without blocking, the next
    # round's inner steps start from the PREVIOUS merge (a base
    # ``outer_delay`` outer updates stale), and the pending merge is
    # applied ``outer_delay`` round boundaries after its launch. With
    # ``outer_delay=0`` the launch and apply coincide and the math is
    # bit-identical to the synchronous ``_outer_step`` (pinned by
    # tests/test_async_outer.py, the classic-DiLoCo analog of
    # streaming's ``test_p1_delay0_equals_classic_diloco``). The fused
    # async round program puts the boundary FIRST (launch + apply, then
    # the H-step inner scan): the collective's output feeds only the
    # NEXT boundary, so XLA's latency-hiding scheduler is free to
    # overlap the all-reduce with the whole round of inner compute —
    # the ``outer_sync_share`` dead time this mode exists to recover.
    async_outer: bool = False
    # rounds between a pending merge's launch and its apply (the
    # staleness bound; each apply's actual lateness is surfaced as the
    # ``outer_staleness`` JSONL key / telemetry gauge)
    outer_delay: int = 1
    # Heterogeneous per-worker H (elastic DiLoCo): worker w applies
    # inner updates only on the first ``inner_steps_per_worker[w]``
    # steps of each round (its replica freezes for the remainder, Adam
    # moments and schedule count included — a worker that did fewer
    # steps also warmed up less), and its pseudo-gradient enters the
    # outer merge weighted by its REALIZED step share
    # (``sum_w H_w * delta_w / sum_w H_w`` — equal budgets reduce to
    # the exact worker mean). This is the straggler story: a slow
    # island degrades its own contribution instead of stalling the
    # sync. None (the default) keeps the uniform-H program bit-identical
    # to classic DiLoCo — no masking ops are ever traced. The tuple here
    # is the INITIAL schedule; ``Diloco.set_inner_budget`` retargets it
    # between rounds (a runtime [W] program input, no recompile), which
    # is how the train loop's straggler policy demotes/restores. The
    # PR-5 drift metrics keep the exact worker-mean math either way
    # (``_sync_dynamics`` recomputes the true mean itself). vmap inner
    # path only (sp/pp manual regions unsupported); incompatible with
    # ``outer_wire_collective`` (the integer wire's psum carries
    # unweighted payloads).
    inner_steps_per_worker: tuple[int, ...] | None = None


def _wire_accumulator_dtype(num_workers: int, q_max: float):
    """Narrowest signed accumulator the worst-case sum W*q_max fits —
    the dtype the integer-collective wire actually carries. int4
    payloads (q_max 7) ride an INT8 wire up to W=18: one byte per
    element, 4x narrower than f32, the 4-bit outer-sync regime of
    arXiv:2501.18512. One source of truth for the wire program
    (_pseudograd_integer_wire) and the payload report
    (sync_payload_report)."""
    if num_workers * q_max <= float(jnp.iinfo(jnp.int8).max):
        return jnp.int8
    if num_workers * q_max <= float(jnp.iinfo(jnp.int16).max):
        return jnp.int16
    return jnp.int32


class DilocoState(struct.PyTreeNode):
    params: Any          # stacked [W, ...] — each worker's current params
    inner_opt_state: Any  # stacked [W, ...]
    snapshot: Any        # unstacked — params at last sync (θ in the paper)
    outer_opt_state: Any  # unstacked — Nesterov momentum buffer
    inner_step_count: jax.Array  # completed inner steps (scalar int32)


class AsyncDilocoState(struct.PyTreeNode):
    """Classic DiLoCo state plus the in-flight outer merge(s) of the
    async delayed-apply path (``DilocoConfig.async_outer``).

    ``snapshot`` is the base every worker started the CURRENT round
    from — the last APPLIED merge, ``outer_delay`` outer updates behind
    the newest launch. ``pending`` is the FIFO of launched-but-unapplied
    merged models, oldest first (length ``max(outer_delay, 1)``; with
    ``outer_delay=0`` the single slot mirrors the just-applied merge so
    the pytree shape — and therefore checkpoints — stay uniform across
    delays). ``pending_round`` records each slot's launch round (0 =
    init copy, never a real launch); ``launched_round`` is the newest
    round whose boundary has run — the marker that lets a resume decide
    whether a boundary is still owed for ``inner_step_count``'s round
    (fused checkpoints land pre-boundary, stepwise ones post-boundary;
    both must resume bit-exact through either loop)."""

    params: Any
    inner_opt_state: Any
    snapshot: Any
    outer_opt_state: Any
    pending: Any                 # tuple of unstacked param trees, oldest first
    pending_round: jax.Array     # int32 [len(pending)] launch round per slot
    launched_round: jax.Array    # int32 scalar — newest boundary that ran
    inner_step_count: jax.Array


class Diloco:
    """Builds and owns the jitted inner/outer step functions.

    ``loss_fn(params, tokens, loss_mask) -> (loss, aux)`` defaults to the
    Llama causal-LM loss; ``inner_tx``/``outer_tx`` default to the
    reference's AdamW+cosine / Nesterov-SGD but are pluggable (the sync-DP
    equivalence test swaps plain SGD in).
    """

    def __init__(
        self,
        model_cfg: LlamaConfig,
        cfg: DilocoConfig,
        mesh: Mesh,
        loss_fn: Callable | None = None,
        inner_tx: optax.GradientTransformation | None = None,
        outer_tx: optax.GradientTransformation | None = None,
    ):
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh
        self.sp = int(dict(mesh.shape).get("sp", 1))
        self.pp = int(dict(mesh.shape).get("pp", 1))
        if (self.sp > 1 or self.pp > 1) and loss_fn is not None:
            raise ValueError(
                "custom loss_fn is not supported with sequence or pipeline "
                "parallelism: the inner step runs the loss inside a manual "
                "shard_map region"
            )
        if cfg.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"unknown pp_schedule {cfg.pp_schedule!r}: use 'gpipe' or '1f1b'"
            )
        if self.pp > 1:
            if model_cfg.num_hidden_layers % self.pp:
                raise ValueError(
                    f"num_hidden_layers {model_cfg.num_hidden_layers} must "
                    f"divide evenly into {self.pp} pipeline stages"
                )
            if self.sp > 1 and model_cfg.attention_impl != "ring":
                raise ValueError("pp + sp requires attention ring")
            if self.sp == 1 and model_cfg.attention_impl == "ring":
                raise ValueError("pp without sp requires attention dense or flash")
        if (
            model_cfg.num_experts
            and self.sp > 1
            and model_cfg.router_type == "experts_choose"
        ):
            raise ValueError(
                "expert-choice routing does not compose with sequence "
                "parallelism (per-shard top-C token selection is a "
                "different function at any capacity); use "
                "router_type='tokens_choose' with sp"
            )
        if (
            (self.sp > 1 or self.pp > 1)
            and int(dict(mesh.shape)["diloco"]) != cfg.num_workers
        ):
            raise ValueError(
                "sp/pp > 1 requires one mesh shard per DiLoCo worker "
                f"(diloco axis {dict(mesh.shape)['diloco']} != num_workers "
                f"{cfg.num_workers})"
            )
        if (
            model_cfg.num_experts
            and model_cfg.moe_dispatch == "ragged"
            and int(dict(mesh.shape).get("ep", 1)) > 1
        ):
            # enforced HERE, not only in the CLI path: any library caller
            # building Diloco on an ep>1 mesh would otherwise get GSPMD
            # silently all-gathering every expert's weights per MoE layer
            # — semantics preserved, expert parallelism defeated, no
            # diagnostic
            raise ValueError(
                "moe_dispatch='ragged' requires replicated experts (ep=1): "
                "the sorted dispatch's grouped matmuls see every expert's "
                "weights; sharding experts over ep needs the "
                "megablocks-style all-to-all (models/moe.py design note). "
                "Use dense dispatch on ep>1 meshes"
            )
        if cfg.outer_comm_dtype is not None:
            wire = jnp.dtype(cfg.outer_comm_dtype)  # raises on garbage
            if not (
                jnp.issubdtype(wire, jnp.floating)
                or jnp.issubdtype(wire, jnp.signedinteger)
            ):
                raise ValueError(
                    f"outer_comm_dtype {cfg.outer_comm_dtype!r} must be a "
                    "float (cast wire) or signed-int (absmax-quantized "
                    "wire) dtype"
                )
        if cfg.outer_wire_collective:
            if cfg.outer_comm_dtype is None or not jnp.issubdtype(
                jnp.dtype(cfg.outer_comm_dtype), jnp.signedinteger
            ):
                raise ValueError(
                    "outer_wire_collective requires a signed-int "
                    f"outer_comm_dtype (got {cfg.outer_comm_dtype!r}): the "
                    "integer collective carries a quantized payload"
                )
            wire = jnp.dtype(cfg.outer_comm_dtype)
            if wire.itemsize > 2:
                # a >=4-byte "narrow" wire is no narrower than f32 AND
                # W * q_max would overflow the int32 accumulator
                # (int32 wire: clip(±2^31-1) wraps on the very cast)
                raise ValueError(
                    f"outer_wire_collective wire dtype {wire.name} is not "
                    "narrow: use int8 or int16 (int32 would match f32's "
                    "width and overflow the psum accumulator)"
                )
            if cfg.num_workers * float(jnp.iinfo(wire).max) > float(
                jnp.iinfo(jnp.int32).max
            ):
                raise ValueError(
                    f"num_workers={cfg.num_workers} with wire {wire.name} "
                    "overflows the int32 psum accumulator"
                )
        if cfg.inner_steps_per_worker is not None:
            hs = tuple(int(h) for h in cfg.inner_steps_per_worker)
            if len(hs) != cfg.num_workers:
                raise ValueError(
                    f"inner_steps_per_worker has {len(hs)} entries but "
                    f"num_workers is {cfg.num_workers}"
                )
            if any(h < 1 or h > cfg.inner_steps for h in hs):
                raise ValueError(
                    f"inner_steps_per_worker entries must be in "
                    f"[1, inner_steps={cfg.inner_steps}]; got {hs}"
                )
            if self.sp > 1 or self.pp > 1:
                raise ValueError(
                    "inner_steps_per_worker requires the vmap inner path "
                    "(sp=1, pp=1): the manual shard_map regions run every "
                    "worker's shard group in lockstep"
                )
            if cfg.outer_wire_collective:
                raise ValueError(
                    "inner_steps_per_worker is incompatible with "
                    "outer_wire_collective: the integer-collective psum "
                    "carries unweighted payloads (a shared scale cannot "
                    "express per-worker step-share weights)"
                )
            self._h_budget = np.asarray(hs, np.int32)
        else:
            self._h_budget = None
        # budgets the most recent fused async round dispatched under —
        # the weights its deferred boundary must merge with (see the
        # async_round_step entry)
        self._h_budget_prev: np.ndarray | None = None
        if cfg.async_outer:
            if cfg.outer_delay < 0:
                raise ValueError(f"outer_delay must be >= 0, got {cfg.outer_delay}")
            if cfg.quarantine_nonfinite:
                raise ValueError(
                    "quarantine_nonfinite is synchronous-outer-only: the "
                    "async boundary sits at the top of the NEXT round's "
                    "program, after the round's [W] loss-finiteness verdict "
                    "has left the program that computed it; run the "
                    "synchronous outer step for fault quarantine"
                )
            if cfg.offload_snapshot:
                raise ValueError(
                    "offload_snapshot is synchronous-outer-only: the async "
                    "path keeps the snapshot AND the pending merge(s) as "
                    "live program inputs every round — there is no "
                    "between-syncs window to park them in host memory"
                )
        if loss_fn is None and model_cfg.state_layers:
            raise ValueError(
                "training does not carry sparse_attention / linear_attention "
                "layers: the chunked form of the decayed recurrence "
                "(models/linear_attention.py) has no backward pass written for "
                "it (its state would have to be recomputed or saved a chunk), "
                "and the block choice is a top-k with no gradient path to the "
                "compressed keys; these layers are served only")
        # a mixed sparse configuration's expert layer reports what it did
        # (models/moe.py TRAIN_COUNTERS, the balance term): the inner step
        # and the round hand it out beside their losses
        self.moe_stats = bool(
            loss_fn is None and model_cfg.mixed and model_cfg.num_experts
            and self.sp == 1 and self.pp == 1)  # those paths refuse a mixed stack
        self._stats_keys = ("router_aux", "moe_counters") if self.moe_stats else ()
        # a held share of the experts picks one of two bodies for its
        # grouped products by a scalar (moe._ragged_mlp's lax.cond); under
        # a vmap over the worker axis the scalar is batched, the cond a
        # select, and both bodies run. Such a configuration's workers run
        # unbatched instead (_over_workers)
        self._unbatched_workers = bool(
            loss_fn is None and model_cfg.mixed and model_cfg.experts_held is not None)
        self.loss_fn = loss_fn or (
            lambda p, t, m: causal_lm_loss(p, t, model_cfg, loss_mask=m)
        )
        # Under pipeline parallelism each stage holds only its layer
        # slice, so optax's clip_by_global_norm would clip by the LOCAL
        # norm; the chain is built clip-free and _pp_inner_update clips
        # with a psum'd global norm instead.
        self.inner_tx = inner_tx or inner_optimizer(
            cfg.lr, cfg.warmup_steps, cfg.total_steps,
            weight_decay=cfg.weight_decay,
            clip_norm=None if self.pp > 1 else cfg.clip_norm,
        )
        self.outer_tx = outer_tx or outer_optimizer(
            cfg.outer_lr, cfg.outer_momentum, cfg.nesterov
        )
        from nanodiloco_tpu.parallel.feed import BatchFeeder

        self._pspec = param_specs(model_cfg, worker_axis=False, pp=self.pp > 1)
        self._wspec = param_specs(model_cfg, worker_axis=True, pp=self.pp > 1)
        bspec = batch_spec(sp=self.sp > 1)
        # multi-host-safe batch placement: [W, A, B, S] steps and
        # [H, W, A, B, S] stacked rounds
        self.feed = BatchFeeder(mesh, bspec)
        self.feed_round = BatchFeeder(mesh, P(None, *bspec))
        self._pspec_struct = jax.tree.structure(
            self._pspec, is_leaf=lambda x: isinstance(x, P)
        )
        self._host_shardings = None
        self._snap_device_shardings = None
        if cfg.offload_snapshot:
            try:
                self._host_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s, memory_kind="pinned_host"),
                    self._pspec, is_leaf=lambda x: isinstance(x, P),
                )
                # the return path: consumers inside the jitted programs
                # need the snapshot back in DEVICE memory (an elementwise
                # op on a pinned_host operand is a compile error, round-5
                # review finding)
                self._snap_device_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s, memory_kind="device"),
                    self._pspec, is_leaf=lambda x: isinstance(x, P),
                )
            except Exception:  # backend without pinned_host support
                self._host_shardings = None
                self._snap_device_shardings = None

        # Public entries are wrapped with _fetch: a snapshot offloaded to
        # pinned_host between syncs must come back to device memory
        # BEFORE entering a jitted program — jit's executable cache does
        # not key on memory kind, so feeding a host buffer into the
        # device-compiled executable fails at runtime (round-5 review
        # finding; no-op without offload_snapshot).
        # the raw jit objects are kept (not just the wrapped callables):
        # cost analytics lowers them AOT without executing
        # (round_cost_analysis — jax.stages.Lowered has no donation or
        # dispatch side effects, so the probe never touches state)
        self._inner_jit = jax.jit(self._inner_step, donate_argnums=(0,))
        _inner_call = self._with_mesh(self._inner_jit)
        # the step's and the round's last output is a dict of what they
        # measured of themselves ({"router_aux", "moe_counters"} from a
        # mixed sparse configuration, the dynamics readout); the public
        # entries drop it where it is empty
        def _sans_empty(out):
            return out[:-1] if isinstance(out[-1], dict) and not out[-1] else out

        self.inner_step = lambda state, tokens, mask: _sans_empty(_inner_call(
            self._fetch(state), tokens, mask, *self._hb()
        ))
        _outer_jit = self._with_mesh(
            jax.jit(self._outer_step_state, donate_argnums=(0,))
        )

        # the round-level entries dispatch under a span (obs/tracer: the
        # tracer's and the profiler's), so that a capture of a caller
        # that drives Diloco directly shows the program's phases too
        def _outer_step_entry(state, worker_mask=None):
            with trace_span("diloco.outer"):
                return _outer_jit(self._fetch(state), worker_mask, *self._hb())

        self.outer_step = _outer_step_entry
        self._round_jit = jax.jit(self._round_step, donate_argnums=(0,))
        _round_call = self._with_mesh(self._round_jit)

        def _round_step_entry(state, tokens, mask):
            with trace_span("diloco.round"):
                return _sans_empty(_round_call(
                    self._fetch(state), tokens, mask, *self._hb()))

        self.round_step = _round_step_entry
        # H inner steps with NO outer sync: same dispatch count as
        # round_step, so differencing the two isolates the outer
        # all-reduce's true wall clock even in fused mode (the metric the
        # reference stubbed, ref diloco.py:23-24,62-64). Used by bench.py
        # and the train loop's fused-mode comm_share estimate.
        _inner_round_call = self._with_mesh(
            jax.jit(self._inner_round_step, donate_argnums=(0,))
        )

        def _inner_round_step_entry(state, tokens, mask):
            with trace_span("diloco.inner_round"):
                out = _inner_round_call(state, tokens, mask, *self._hb())
            if self._h_budget is not None:
                # record this round-scan's budget: the async fused
                # loop's FIRST program is this inner-only scan, and the
                # next program's deferred boundary must merge its delta
                # with the budget it actually ran under
                self._h_budget_prev = np.array(self._h_budget)
            return out

        self.inner_round_step = _inner_round_step_entry
        if cfg.async_outer:
            # boundary-first fused round (launch + apply, THEN the H-step
            # scan — the collective's consumers all live one program
            # later, so the scheduler may overlap it with the scan), the
            # stepwise boundary, and the end-of-run flush/drain
            self._async_round_jit = jax.jit(
                self._async_round_step, donate_argnums=(0,)
            )
            _async_round_call = self._with_mesh(self._async_round_jit)

            def _async_round_step_entry(state, tokens, mask):
                if self._h_budget is None:
                    return _async_round_call(state, tokens, mask)
                # the fused program's boundary merges the PREVIOUS
                # round's delta: weight it with the budgets that round
                # dispatched under, while the scan runs the current ones
                # (they differ for exactly one round after every
                # straggler-policy retarget; a fresh session has no
                # previous dispatch and falls back to the current —
                # also the resume approximation, where the sidecar
                # budget stands in for the interrupted round's)
                cur = np.array(self._h_budget)
                prev = (
                    cur if self._h_budget_prev is None
                    else self._h_budget_prev
                )
                self._h_budget_prev = cur
                return _async_round_call(
                    state, tokens, mask, jnp.asarray(cur), jnp.asarray(prev)
                )

            self.async_round_step = _async_round_step_entry
            _async_boundary_call = self._with_mesh(
                jax.jit(self._async_boundary, donate_argnums=(0,))
            )
            self.async_boundary = lambda state: _async_boundary_call(
                state, *self._hb()
            )
            _async_flush_call = self._with_mesh(
                jax.jit(self._async_flush, donate_argnums=(0,))
            )
            self.async_flush = lambda state: _async_flush_call(
                state, *self._hb()
            )
            self.async_drain = self._with_mesh(
                jax.jit(self._async_drain, donate_argnums=(0,))
            )

    def _with_mesh(self, fn):
        """Run ``fn`` with this mesh as the ambient mesh — the partial-manual
        shard_map in the sp path (and auto-axis sharding propagation in
        general) resolves axis names against it; callers shouldn't have to
        remember ``jax.set_mesh``. Skipped on a single-device mesh (see
        ``_constrain`` — unsharded dispatch is the fast path)."""
        if self.mesh.size == 1:
            return fn

        def call(*args, **kwargs):
            with jax.set_mesh(self.mesh):
                return fn(*args, **kwargs)

        return call

    # -- heterogeneous per-worker H (elastic DiLoCo) -------------------------

    def _hb(self) -> tuple:
        """Extra jit argument carrying the live per-worker step budget —
        EMPTY when heterogeneous H is off, so the uniform path's traced
        programs stay byte-identical to classic DiLoCo (the smoke-gate
        bit-exactness contract). When on, the [W] int32 array is a plain
        runtime input: retargeting budgets between rounds never
        recompiles."""
        if self._h_budget is None:
            return ()
        return (jnp.asarray(self._h_budget),)

    def set_inner_budget(self, budgets) -> None:
        """Retarget the per-worker inner-step budgets for SUBSEQUENT
        dispatches (the straggler policy's demote/restore lever). Only
        valid when the instance was built with ``inner_steps_per_worker``
        — the budget is a program input only the hetero trace consumes."""
        if self._h_budget is None:
            raise RuntimeError(
                "heterogeneous H is not enabled: build Diloco with "
                "DilocoConfig.inner_steps_per_worker to get a runtime "
                "step budget"
            )
        hs = np.asarray([int(h) for h in budgets], np.int32)
        if hs.shape != (self.cfg.num_workers,):
            raise ValueError(
                f"budget must have one entry per worker "
                f"({self.cfg.num_workers}); got shape {hs.shape}"
            )
        if (hs < 1).any() or (hs > self.cfg.inner_steps).any():
            raise ValueError(
                f"budget entries must be in [1, inner_steps="
                f"{self.cfg.inner_steps}]; got {hs.tolist()}"
            )
        self._h_budget = hs

    @property
    def inner_budget(self) -> tuple[int, ...] | None:
        """Current per-worker step budgets (None = uniform-H classic)."""
        if self._h_budget is None:
            return None
        return tuple(int(h) for h in self._h_budget)

    def _constrain(self, tree: Any, worker_axis: bool) -> Any:
        """Apply sharding constraints when ``tree`` is the model's param
        tree; pass through unchanged for custom param trees (tests and
        non-Llama losses plug those in).

        On a single-device mesh constraints are skipped entirely: there is
        nothing to shard, and keeping arrays on SingleDeviceSharding keeps
        dispatch on the single-device fast path (NamedSharding-committed
        arrays take the sharded-execution dispatch path)."""
        if self.mesh.size == 1:
            return tree
        if jax.tree.structure(tree) != self._pspec_struct:
            return tree
        return constrain(tree, self.mesh, self._wspec if worker_axis else self._pspec)

    # -- init ---------------------------------------------------------------

    def init_state(self, rng: jax.Array, params: Any = None) -> DilocoState:
        """Fresh training state. ``params`` optionally supplies the model
        weights (e.g. an HF import for continued pretraining) instead of
        the PRNG init — every worker and the snapshot start from the same
        tree either way, the reference's init-broadcast contract
        (ref diloco.py:21-22)."""
        W = self.cfg.num_workers

        def _init(p):
            p = self._constrain(p, worker_axis=False)
            stacked = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), p
            )
            stacked = self._constrain(stacked, worker_axis=True)
            inner_state = jax.vmap(self.inner_tx.init)(stacked)
            if self.mesh.size > 1:
                # zeros take no sharding from ``stacked``: left alone, every
                # chip holds all workers' moments, and the first round_step
                # (which returns them over the worker axis) compiles twice.
                # The moments lie as the workers' parameters do (over fsdp
                # and tp too), the counters over the worker axis
                inner_state = constrain(inner_state, self.mesh, self._opt_state_spec(
                    inner_state, self._wspec, self._pspec_struct))
            outer_state = self.outer_tx.init(p)
            if self.mesh.size > 1:  # the outer momentum lies as the snapshot does
                outer_state = constrain(outer_state, self.mesh, self._opt_state_spec(
                    outer_state, self._pspec, self._pspec_struct, other=P()))
            return DilocoState(
                params=stacked,
                inner_opt_state=inner_state,
                snapshot=p,
                outer_opt_state=outer_state,
                inner_step_count=jnp.zeros((), jnp.int32),
            )

        if params is not None:
            # as a jit ARGUMENT (not a closed-over constant): an 8B
            # import must not be baked into the executable
            fn = lambda: jax.jit(_init)(params)
        else:
            fn = jax.jit(lambda: _init(init_params(rng, self.model_cfg)))
        if self.mesh.size == 1:
            state = fn()
        else:
            with jax.set_mesh(self.mesh):
                state = fn()
        if self.cfg.async_outer:
            return self._as_async_state(state)
        return self._offload(state)

    def _as_async_state(self, base: DilocoState) -> AsyncDilocoState:
        """Fresh async state: every pending slot starts as a copy of the
        init snapshot with launch round 0 (the init marker), so the
        warm-up boundaries are uniform programs whose applies are
        no-ops — no special-cased first round inside the executable."""
        slots = max(self.cfg.outer_delay, 1)
        pending = tuple(
            jax.tree.map(jnp.copy, base.snapshot) for _ in range(slots)
        )

        def rep(x):
            # replicated over the mesh, like every other scalar in the
            # state: an eagerly-created counter would sit committed on
            # one device and collide with the mesh-sharded params at the
            # first jitted dispatch
            if self.mesh.size == 1:
                return x
            return jax.device_put(x, NamedSharding(self.mesh, P()))

        return AsyncDilocoState(
            params=base.params,
            inner_opt_state=base.inner_opt_state,
            snapshot=base.snapshot,
            outer_opt_state=base.outer_opt_state,
            pending=pending,
            pending_round=rep(jnp.zeros((slots,), jnp.int32)),
            launched_round=rep(jnp.zeros((), jnp.int32)),
            inner_step_count=base.inner_step_count,
        )

    # -- inner step (H of these between syncs; zero cross-worker comms) -----

    def _inner_step(
        self,
        state: DilocoState,
        tokens: jax.Array,
        loss_mask: jax.Array,
        h_budget: jax.Array | None = None,
    ):
        """tokens/loss_mask: [W, accum, B, S]. One optimizer update per
        worker from ``accum`` accumulated microbatch gradients. Unlike the
        reference (which backpropped the undivided loss, ref
        nanodiloco/main.py:110-111), accumulation here is an exact
        token-weighted mean: microbatch gradients are weighted by their
        real-token counts when the loss provides ``n_tokens`` aux.

        ``h_budget`` ([W] int32, hetero-H only): worker w applies this
        update only when its position within the round
        (``inner_step_count % H``) is below its budget; past it the
        replica AND its optimizer state freeze (a worker that ran fewer
        steps also advanced its schedule less). The vmapped compute
        still runs for frozen workers — in this stacked single-program
        representation the wall-clock saving belongs to a real
        multi-island deployment; what CPU pins is the MATH (freeze +
        weighted merge). The per-step loss of a frozen worker is still
        the real loss of its (frozen) replica on the step's batch."""
        if tokens.ndim != 4:
            raise ValueError(f"tokens must be [W, accum, B, S]; got shape {tokens.shape}")
        if tokens.shape[0] != self.cfg.num_workers:
            raise ValueError(
                f"batch worker axis is {tokens.shape[0]} but num_workers is "
                f"{self.cfg.num_workers}"
            )
        if tokens.shape[1] != self.cfg.grad_accum:
            raise ValueError(
                f"batch accumulation axis is {tokens.shape[1]} but grad_accum is "
                f"{self.cfg.grad_accum}"
            )
        if self.mesh.size > 1:
            bspec = batch_spec(sp=self.sp > 1)
            tokens = jax.lax.with_sharding_constraint(
                tokens, NamedSharding(self.mesh, bspec)
            )
            loss_mask = jax.lax.with_sharding_constraint(
                loss_mask, NamedSharding(self.mesh, bspec)
            )

        def worker_update(params, opt_state, w_tokens, w_mask):
            grad_fn = jax.value_and_grad(self.loss_fn, has_aux=True)

            def micro(carry, batch):
                g_acc, loss_acc, n_acc = carry
                (loss, aux), g = grad_fn(params, batch[0], batch[1])
                # token-weighted accumulation when the loss reports counts
                # (causal_lm_loss does); plain mean-of-means otherwise.
                w = (
                    aux["n_tokens"].astype(jnp.float32)
                    if isinstance(aux, dict) and "n_tokens" in aux
                    else jnp.ones((), jnp.float32)
                )
                g_acc = jax.tree.map(lambda a, b: a + w * b, g_acc, g)
                stats = {k: aux[k] for k in self._stats_keys}
                return (g_acc, loss_acc + loss, n_acc + w), stats

            zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
            (g_sum, loss_sum, n_sum), stats = jax.lax.scan(
                micro,
                (zeros, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (w_tokens, w_mask),
            )
            accum = w_tokens.shape[0]
            params, opt_state = self._inner_update(
                g_sum, n_sum, opt_state, params)
            if stats:  # the step's microbatches: mean term, summed counts
                stats = {"router_aux": jnp.mean(stats["router_aux"]),
                         "moe_counters": jnp.sum(stats["moe_counters"], axis=0)}
            return params, opt_state, loss_sum / accum, stats

        stats = {}
        if self.pp > 1:  # handles sp>1 too (sequence-sharded pipeline)
            params, inner_opt_state, loss = self._pp_inner_update(state, tokens, loss_mask)
        elif self.sp > 1:
            params, inner_opt_state, loss = self._sp_inner_update(state, tokens, loss_mask)
        else:
            params, inner_opt_state, loss, stats = self._over_workers(
                worker_update, state.params, state.inner_opt_state, tokens, loss_mask)
        if h_budget is not None:
            pos = jnp.mod(state.inner_step_count, self.cfg.inner_steps)
            active = pos < h_budget  # [W]

            def keep(new, old):
                k = active.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(k, new, old)

            params = jax.tree.map(keep, params, state.params)
            inner_opt_state = jax.tree.map(
                keep, inner_opt_state, state.inner_opt_state
            )
        params = self._constrain(params, worker_axis=True)
        state = state.replace(
            params=params,
            inner_opt_state=inner_opt_state,
            inner_step_count=state.inner_step_count + 1,
        )
        # loss: [W] per-worker mean microbatch loss; stats: {}, or from a
        # mixed sparse configuration {"router_aux": [W], "moe_counters": [W, 5]}
        return state, loss, stats

    def _over_workers(self, fn, *args):
        """``fn`` (one worker's update) over the leading worker axis of
        ``args``: a ``vmap``, XLA partitioning it over ``diloco``. A
        configuration that holds a share of its experts runs its workers
        unbatched instead, so that the scalar that picks the grouped
        products' body stays one (``__init__``): a region manual over
        ``diloco`` where the axis spans devices, and within a device its
        workers one after the other."""
        on_diloco = self.mesh.shape["diloco"] > 1
        if not self._unbatched_workers:
            # naming the mesh axis tells a shard_map inside the loss (the
            # flash kernel's, ops/flash_attention.py) that the worker
            # dimension is sharded over ``diloco``; without it the region
            # would gather every worker's activations onto every device
            return jax.vmap(fn, spmd_axis_name="diloco" if on_diloco else None)(*args)

        def local(*args):
            if jax.tree.leaves(args)[0].shape[0] == 1:
                out = fn(*jax.tree.map(lambda x: x[0], args))
                return jax.tree.map(lambda x: x[None], out)
            return jax.lax.map(lambda one: fn(*one), args)

        if not on_diloco:
            return local(*args)
        return jax.shard_map(
            local, mesh=self.mesh, in_specs=P("diloco"), out_specs=P("diloco"),
            axis_names={"diloco"},
            check_vma=False,  # the model's scans start from constants, which vary over no axis
        )(*args)

    @jax.named_scope("inner_opt")
    def _inner_update(self, g_sum, n_sum, opt_state, params):
        """One worker's update from its token-weighted gradient sum:
        the mean, then the inner chain (clip, AdamW). Returns (params,
        opt_state)."""
        grads = jax.tree.map(lambda g: g / jnp.maximum(n_sum, 1e-9), g_sum)
        updates, opt_state = self.inner_tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def _sp_inner_update(self, state: DilocoState, tokens, loss_mask):
        """Sequence-parallel inner step: ONE shard_map manual over
        ``(diloco, sp)`` — each worker's shard group runs ring attention
        over ``sp`` with explicit grad/loss psums, while fsdp/tp stay
        auto-partitioned by XLA inside the manual region. (A shard_map
        manual over sp alone nested under the worker vmap trips an XLA
        SPMD-partitioner CHECK when two more mesh axes are nontrivial, so
        the worker axis is manual here too — which is also the more honest
        statement of DiLoCo: no collective EVER crosses ``diloco`` in the
        inner step, now by construction.)"""
        from nanodiloco_tpu.models.llama import sp_shard_loss

        def body(params_w, opt_w, tok_w, mask_w):
            # manual over diloco: local leading worker axis has size 1
            params = jax.tree.map(lambda x: x[0], params_w)
            opt_state = jax.tree.map(lambda x: x[0], opt_w)
            w_tokens, w_mask = tok_w[0], mask_w[0]  # [accum, B, S_loc]

            coef = self.model_cfg.router_aux_coef

            def sum_loss_fn(p, t, m):
                sl, n, aux = sp_shard_loss(p, t, self.model_cfg, m, "sp")
                # aux is globally exact (stats reduced over sp inside
                # moe_mlp); weight it by the microbatch's GLOBAL token
                # count so the psum'd gradient matches the vmap path's
                # token-weighted accumulation exactly
                n_glob = jax.lax.psum(n, "sp")
                return sl + coef * n_glob * aux, (sl, n, aux)

            grad_fn = jax.value_and_grad(sum_loss_fn, has_aux=True)

            def micro(carry, batch):
                g_acc, sl_acc, n_acc, aux_acc = carry
                (_t, (sl, n, aux)), g = grad_fn(params, batch[0], batch[1])
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, sl_acc + sl, n_acc + n, aux_acc + aux), None

            # carries must enter the scan already typed as varying over the
            # manual axes (their updates are), hence the explicit pcasts
            zeros = jax.tree.map(
                lambda p: jax.lax.pcast(
                    jnp.zeros_like(p, jnp.float32), ("sp",), to="varying"
                ),
                params,
            )
            zscalar = jax.lax.pcast(
                jnp.zeros((), jnp.float32), ("diloco", "sp"), to="varying"
            )
            accum = w_tokens.shape[0]
            (g_sum, sl_sum, n_sum, aux_sum), _ = jax.lax.scan(
                micro, (zeros, zscalar, zscalar, zscalar), (w_tokens, w_mask)
            )
            # grads of the SUM loss: combine shard contributions over sp,
            # then normalize by the global token count — identical math to
            # the vmap path's token-weighted accumulation.
            g_sum = jax.tree.map(lambda x: jax.lax.psum(x, "sp"), g_sum)
            sl_sum = jax.lax.psum(sl_sum, "sp")
            n_sum = jax.lax.psum(n_sum, "sp")
            # aux's value is sp-uniform already; psum/size replicates its
            # manual-axis type for the out_specs
            aux_sum = jax.lax.psum(aux_sum, "sp") / jax.lax.psum(1, "sp")
            params, opt_state = self._inner_update(
                g_sum, n_sum, opt_state, params)
            # per-worker mean token loss (== mean of per-micro means for
            # the packed equal-length sequences this path requires) plus
            # the mean router aux, matching the vmap path's loss metric
            loss = (
                sl_sum / jnp.maximum(n_sum, 1e-9) + coef * aux_sum / accum
            )
            return (
                jax.tree.map(lambda x: x[None], params),
                jax.tree.map(lambda x: x[None], opt_state),
                loss[None],
            )

        wspec = lambda tree: jax.tree.map(lambda _: P("diloco"), tree)
        bspec = P("diloco", None, None, "sp")
        params, inner_opt_state, loss = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(wspec(state.params), wspec(state.inner_opt_state), bspec, bspec),
            out_specs=(wspec(state.params), wspec(state.inner_opt_state), P("diloco")),
            axis_names={"diloco", "sp"},
        )(state.params, state.inner_opt_state, tokens, loss_mask)
        return params, inner_opt_state, loss

    def _pp_param_spec(self, params: Any):
        """Per-leaf PartitionSpecs for the pp manual region: stacked
        params' layer leaves are [W, L, ...] -> P('diloco', 'pp');
        everything else (embed/head/norms) carries only the worker
        axis."""
        return {
            k: (
                jax.tree.map(lambda _: P("diloco", "pp"), v)
                if k == "layers"
                else jax.tree.map(lambda _: P("diloco"), v)
            )
            for k, v in params.items()
        }

    def _opt_state_spec(self, tree: Any, param_spec: Any, pstruct, other=P("diloco")):
        """Spec tree for an optimizer state: param-structured subtrees
        (mu/nu, the outer trace) get ``param_spec``; other leaves ``other``
        (the workers' step counters lie over the worker axis)."""

        def is_param_tree(x):
            try:
                return jax.tree.structure(x) == pstruct
            except Exception:
                return False

        return jax.tree.map(
            lambda sub: param_spec if is_param_tree(sub) else other,
            tree,
            is_leaf=is_param_tree,
        )

    def _pp_inner_update(self, state: DilocoState, tokens, loss_mask):
        """Pipeline-parallel inner step: ONE shard_map manual over
        ``(diloco, pp)`` — each worker's stage group streams the
        grad-accumulation microbatches through the layer-stage pipeline
        (ops/pipeline.py), with fsdp/tp left auto-partitioned inside the
        manual region. Gradient post-processing per stage: replicated
        (embed/head/norm) grads are psum'd over pp, layer grads stay
        stage-local, and global-norm clipping uses a psum'd norm (each
        parameter counted exactly once)."""
        from nanodiloco_tpu.ops.pipeline import pp_shard_loss

        clip = self.cfg.clip_norm
        sp_axis = "sp" if self.sp > 1 else None

        def body(params_w, opt_w, tok_w, mask_w):
            params = jax.tree.map(lambda x: x[0], params_w)
            opt_state = jax.tree.map(lambda x: x[0], opt_w)
            w_tokens, w_mask = tok_w[0], mask_w[0]  # [accum(M), B, S(_loc)]

            coef = self.model_cfg.router_aux_coef
            accum = w_tokens.shape[0]

            def sum_loss_fn(p):
                sl, n, aux_w, metric = pp_shard_loss(
                    p, w_tokens, self.model_cfg, w_mask, "pp", sp_axis=sp_axis
                )
                # the differentiated value: summed CE + token-weighted
                # router aux (zero for dense models; globally-exact stats
                # under sp, weighted by shard-local counts that psum to
                # the global token weight), combined over the stages —
                # and over the sequence shards, each of which saw only
                # its slice
                total = jax.lax.psum(sl + coef * aux_w, "pp")
                if sp_axis is not None:
                    total = jax.lax.psum(total, sp_axis)
                return total, (n, metric)

            if self.cfg.pp_schedule == "1f1b":
                # hand-scheduled per-microbatch vjp: same summed loss,
                # O(P) activation memory (ops/pipeline.py). Gradients and
                # statistics come back unreduced exactly like autodiff's.
                from nanodiloco_tpu.ops.pipeline import pp_shard_grads_1f1b

                g, _sl, n, _aux_w, metric = pp_shard_grads_1f1b(
                    params, w_tokens, self.model_cfg, w_mask, "pp",
                    sp_axis=sp_axis,
                )
            else:
                (_t, (n, metric)), g = jax.value_and_grad(
                    sum_loss_fn, has_aux=True
                )(params)
            # ONE statistics-normalization tail for both schedules:
            # global token count, and the mean-of-microbatch-means metric.
            n = jax.lax.psum(n, "pp")
            if sp_axis is not None:
                # metric's VALUE is already sp-uniform (pipeline.py
                # reduces it in-tick) but its scan-carry TYPE is still
                # varying-over-sp; the psum/size mean keeps the value
                # and makes the type replicated for the out_specs.
                n = jax.lax.psum(n, sp_axis)
                metric = jax.lax.psum(metric, sp_axis) / jax.lax.psum(
                    1, sp_axis
                )
            metric = jax.lax.psum(metric, "pp") / accum
            # replicated leaves: every stage holds a copy, only one
            # computed a nonzero grad — combine so the copies stay equal
            g = {
                k: (v if k == "layers" else jax.tree.map(
                    lambda x: jax.lax.psum(x, "pp"), v))
                for k, v in g.items()
            }
            if sp_axis is not None:
                # every shard saw only its sequence slice of the SUM loss:
                # grads combine over sp for ALL leaves
                g = jax.tree.map(lambda x: jax.lax.psum(x, sp_axis), g)
            with jax.named_scope("inner_opt"):  # clip and AdamW
                grads = jax.tree.map(lambda x: x / jnp.maximum(n, 1e-9), g)
                if clip is not None:
                    sq_layers = sum(
                        jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(grads["layers"])
                    )
                    sq_rep = sum(
                        jnp.sum(jnp.square(x))
                        for k, v in grads.items() if k != "layers"
                        for x in jax.tree.leaves(v)
                    )
                    g_norm = jnp.sqrt(jax.lax.psum(sq_layers, "pp") + sq_rep)
                    # optax.clip_by_global_norm semantics: untouched below
                    # the threshold, scaled by max_norm/norm above it
                    grads = jax.tree.map(
                        lambda t: jnp.where(g_norm < clip, t, (t / g_norm) * clip),
                        grads,
                    )
                updates, opt_state = self.inner_tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            loss = metric
            return (
                jax.tree.map(lambda x: x[None], params),
                jax.tree.map(lambda x: x[None], opt_state),
                loss[None],
            )

        pstruct = jax.tree.structure(state.snapshot)
        param_spec = self._pp_param_spec(state.params)
        opt_spec = self._opt_state_spec(
            state.inner_opt_state, param_spec, pstruct
        )
        # [W, M, B, S]: sequence over sp when present, B/fsdp/tp left auto
        bspec = P("diloco", None, None, "sp") if sp_axis else P("diloco")
        axis_names = {"diloco", "pp", "sp"} if sp_axis else {"diloco", "pp"}
        params, inner_opt_state, loss = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(param_spec, opt_spec, bspec, bspec),
            out_specs=(param_spec, opt_spec, P("diloco")),
            axis_names=axis_names,
        )(state.params, state.inner_opt_state, tokens, loss_mask)
        return params, inner_opt_state, loss

    # -- outer step (the ONLY recurring communication) -----------------------

    def _pseudograd(
        self, snapshot: Any, params_w: Any, worker_mask: jax.Array | None = None
    ) -> Any:
        """Worker-averaged pseudo-gradient ``mean_w(snapshot - params_w)``.
        The mean over the stacked worker axis is the all-reduce over the
        ``diloco`` mesh axis (ref diloco.py:48-49); with ``outer_comm_dtype``
        set, each worker's delta is quantized to the wire dtype FIRST (the
        lossy step happens per worker, before any cross-worker traffic),
        then the mean accumulates in float32 so rounding error does not
        grow with worker count beyond the intended quantization.

        ``worker_mask`` ([W], bool/0-1 — or nonnegative float WEIGHTS
        under heterogeneous H, where each worker's weight is its
        realized step count) restricts the mean to SURVIVING workers:
        a dead (zero-weight) worker's stale replica contributes nothing
        and the denominator shrinks to the surviving weight total —
        DiLoCo's natural fault story, which the reference cannot
        express (a dead rank kills its NCCL all-reduce outright,
        SURVEY §5). With float weights the result is the weighted
        average ``sum_w w_w * delta_w / sum_w w_w`` — equal weights
        reduce to the plain worker mean. All-dead is guarded to a zero
        pseudo-gradient (denominator clamped to 1), so the outer step
        degenerates to momentum-only rather than NaN."""
        if self.cfg.outer_wire_collective:
            return self._pseudograd_integer_wire(
                snapshot, params_w, worker_mask
            )
        cdt = self.cfg.outer_comm_dtype
        if worker_mask is None:
            if cdt is None:
                return jax.tree.map(
                    lambda s, p: s - jnp.mean(p, axis=0), snapshot, params_w
                )
            return jax.tree.map(
                lambda s, p: jnp.mean(
                    self._wire_quantize(s[None] - p), axis=0
                ).astype(s.dtype),
                snapshot, params_w,
            )
        w = worker_mask.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)

        def masked_mean(s, p):
            d = s[None] - p
            if cdt is not None:
                d = self._wire_quantize(d)
            d = d.astype(jnp.float32)
            # hard-exclude masked rows BEFORE the contraction: a dead
            # worker's replica may be non-finite (divergence is a prime
            # reason to mask it) and 0 * NaN = NaN would poison the
            # survivor mean through a plain weighted sum
            keep = (w > 0).reshape((-1,) + (1,) * (d.ndim - 1))
            d = jnp.where(keep, d, 0.0)
            # weighted sum contracts the worker axis in float32 — the
            # all-reduce over `diloco`, just with per-worker weights
            d = jnp.tensordot(w, d, axes=(0, 0))
            return (d / denom).astype(s.dtype)

        return jax.tree.map(masked_mean, snapshot, params_w)

    def _pseudograd_integer_wire(
        self, snapshot: Any, params_w: Any, worker_mask: jax.Array | None = None
    ) -> Any:
        """Worker-averaged pseudo-gradient where the cross-worker
        collective carries an INTEGER payload (``outer_wire_collective``).

        The default quantized path (`_wire_quantize`) dequantizes to f32
        before the mean, so XLA's all-reduce moves f32 — the quantization
        bounds numerics, not bytes. This path makes the wire itself
        narrow, matching the reference's contract that the all-reduce
        payload IS the wire dtype (ref nanodiloco/diloco/diloco.py:49):

        1. each worker zeroes masked rows, then computes its local
           per-tensor absmax;
        2. ONE f32 ``pmax`` over ``diloco`` of the [num_tensors] absmax
           vector yields a scale shared by every worker (collective
           payload: one scalar per tensor — negligible);
        3. workers quantize ``round(delta/scale)`` into the configured
           signed-int dtype and sum locally into an accumulator wide
           enough for W summands (int16 when ``W * q_max`` fits, else
           int32);
        4. the all-reduce (``psum`` over ``diloco``) carries that
           integer tensor — the narrow wire;
        5. dequantize ``psum * scale / survivors`` in f32 after.

        Runs as a shard_map partial-manual region over ``diloco`` only,
        so fsdp/tp/pp shardings inside each tensor stay with the auto
        partitioner; streaming's per-fragment launches reuse this path
        unchanged (fragment subtrees are just smaller pytrees). Max
        per-element error is scale/2 with scale = global absmax / q_max —
        coarser than per-worker scales by at most the spread in worker
        absmaxes; pseudo-gradients tolerate this (arXiv:2501.18512 runs
        4-bit outer wires)."""
        dt = jnp.dtype(self.cfg.outer_comm_dtype)
        q_max = float(jnp.iinfo(dt).max)
        W = self.cfg.num_workers
        acc_dt = _wire_accumulator_dtype(W, q_max)
        snap_leaves, treedef = jax.tree.flatten(snapshot)
        pw_leaves = jax.tree.leaves(params_w)
        mask = (
            jnp.ones((W,), jnp.float32)
            if worker_mask is None
            else worker_mask.astype(jnp.float32)
        )

        def region(snaps, pws, w):
            keepf = w > 0

            def masked_delta(s, p):
                d = (s[None] - p).astype(jnp.float32)
                keep = keepf.reshape((-1,) + (1,) * (d.ndim - 1))
                # zero masked rows BEFORE absmax/quantize: a dead
                # worker's NaN must poison neither the shared scale nor
                # the integer cast (NaN->int is undefined)
                return jnp.where(keep, d, 0.0)

            # deltas are recomputed per loop rather than kept across the
            # pmax barrier: holding every leaf's f32 [W_local, ...] copy
            # live simultaneously would spike peak HBM by a full f32
            # replica-set during each sync (one subtract+where per leaf
            # is cheaper than that on the 8B-scale runs this wire is for)
            absmaxes = [
                jnp.max(jnp.abs(masked_delta(s, p)))
                for s, p in zip(snaps, pws)
            ]
            amax = jax.lax.pmax(jnp.stack(absmaxes), "diloco")
            scales = jnp.maximum(
                amax / q_max, jnp.finfo(jnp.float32).tiny
            )
            if worker_mask is None:
                denom = jnp.float32(W)
            else:
                denom = jnp.maximum(
                    jax.lax.psum(jnp.sum(w), "diloco"), 1.0
                )
            outs = []
            for i, (s, p) in enumerate(zip(snaps, pws)):
                d = masked_delta(s, p)
                q = jnp.clip(
                    jnp.round(d / scales[i]), -q_max, q_max
                ).astype(dt)
                local = jnp.sum(q.astype(acc_dt), axis=0, dtype=acc_dt)
                total = jax.lax.psum(local, "diloco")  # the narrow wire
                outs.append(
                    (total.astype(jnp.float32) * scales[i] / denom)
                    .astype(s.dtype)
                )
            return tuple(outs)

        out = jax.shard_map(
            region,
            mesh=self.mesh,
            in_specs=(
                tuple(P() for _ in snap_leaves),
                tuple(P("diloco") for _ in pw_leaves),
                P("diloco"),
            ),
            out_specs=tuple(P() for _ in snap_leaves),
            axis_names={"diloco"},
        )(tuple(snap_leaves), tuple(pw_leaves), mask)
        return jax.tree.unflatten(treedef, out)

    def _wire_quantize(self, d: jax.Array) -> jax.Array:
        """Quantize-dequantize a stacked worker delta [W, ...] to the
        configured wire format, returning float32.

        Float dtypes (e.g. "bfloat16") are a plain cast — the lossy step
        per worker, before any cross-worker traffic. Signed-int dtypes
        (e.g. "int8") use symmetric per-(worker, tensor) absmax scaling:
        q = round(d / scale) in [-Q, Q], scale = absmax/Q — the
        low-bit outer sync Streaming DiLoCo runs at (arXiv:2501.18512
        ships 4-bit outer gradients; pseudo-gradients tolerate coarse
        wires because the outer optimizer's momentum integrates over
        rounds). The scale is one scalar per worker per tensor.

        Honest scope: this controls the sync's NUMERICS — the dequant
        back to float32 happens before the cross-worker mean so rounding
        error does not grow with worker count, which also means XLA is
        free to move f32 over the wire when it lowers the mean's
        all-reduce. For guaranteed narrow-dtype traffic set
        ``outer_wire_collective``: `_pseudograd_integer_wire` carries
        the quantized payload on the collective itself (shared pmax'd
        scale, integer psum, dequant after), at the cost of a scale
        shared across workers instead of per-worker."""
        dt = jnp.dtype(self.cfg.outer_comm_dtype)
        if jnp.issubdtype(dt, jnp.integer):
            q_max = float(jnp.iinfo(dt).max)
            axes = tuple(range(1, d.ndim))
            scale = (
                jnp.max(jnp.abs(d), axis=axes, keepdims=True).astype(jnp.float32)
                / q_max
            )
            scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
            q = jnp.clip(
                jnp.round(d.astype(jnp.float32) / scale), -q_max, q_max
            ).astype(dt)
            return q.astype(jnp.float32) * scale
        return d.astype(dt).astype(jnp.float32)

    def attention_paths(self, seq_len: int) -> dict[str, int]:
        """``{"fused": n, "dense": m}``: how many of the model's layers run
        their attention through the fused kernel and how many through
        dense blocks in this object's programs at rows of ``seq_len``
        (``models/llama.py:attention_paths``; decided when a program is
        traced, from the platform, the shapes and the sequence-parallel
        axis). On a mesh of several devices the kernel runs inside a
        ``shard_map`` over the axes the compiler would partition
        (``ops/on_mesh.py:kernel_on_mesh``), so the mesh changes no count."""
        from nanodiloco_tpu.models.llama import attention_paths

        return attention_paths(self.model_cfg, seq_len, sp_axis=self.sp > 1)

    def sync_payload_report(self) -> dict:
        """What one outer sync actually moves per worker, by wire mode —
        the byte-accounting companion to the measured sync wall-clock
        (the comm metric the reference stubbed and never implemented,
        ref nanodiloco/diloco/diloco.py:23-24,62-64). Returns
        ``{"bytes_per_sync", "wire", "guaranteed", "f32_bytes"}``;
        ``guaranteed`` is True only under ``outer_wire_collective``,
        where a test pins the compiled all-reduce operand dtype — in
        every other mode the number describes the reduce's INPUT dtype
        and XLA's lowering owns what travels. Scales (one f32 per
        tensor under the collective wire) are O(num_tensors), omitted.
        """
        n = self.model_cfg.num_params()
        f32 = 4 * n
        cfg = self.cfg
        if cfg.outer_comm_dtype is None:
            return {"bytes_per_sync": f32, "wire": "f32 (unquantized)",
                    "guaranteed": False, "f32_bytes": f32}
        wire = jnp.dtype(cfg.outer_comm_dtype)
        if jnp.issubdtype(wire, jnp.floating):
            # the float cast is quantize-dequantize BEFORE the mean
            # (_wire_quantize returns f32), so the reduce's input — and
            # therefore the honest number — is f32, same as the int
            # numerics-only mode; XLA may or may not narrow the transfer
            return {"bytes_per_sync": f32,
                    "wire": f"{wire.name} numerics only (f32 reduce — "
                            "XLA owns the wire)",
                    "guaranteed": False, "f32_bytes": f32}
        if not cfg.outer_wire_collective:
            return {"bytes_per_sync": f32,
                    "wire": f"{wire.name} numerics only (f32 reduce — "
                            "XLA owns the wire; set outer_wire_collective "
                            "to pin it)",
                    "guaranteed": False, "f32_bytes": f32}
        acc = jnp.dtype(_wire_accumulator_dtype(
            cfg.num_workers, float(jnp.iinfo(wire).max)
        ))
        return {"bytes_per_sync": acc.itemsize * n,
                "wire": f"{wire.name} payload on s{acc.itemsize * 8} "
                        "all-reduce (HLO-pinned)",
                "guaranteed": True, "f32_bytes": f32}

    def sync_wire_bytes(self, snapshot: Any | None = None) -> dict:
        """Per-worker wire-byte accounting for one outer-sync ROUND —
        the comm-volume side of the compute/communication ratio that IS
        DiLoCo's claim (arXiv:2311.08105). ``sync_payload_report`` is
        the human-readable startup banner; this is the machine-readable
        per-round ledger the train loop folds into every sync's JSONL
        record (and ``summarize_run`` totals over the run).

        ``snapshot`` (optional) supplies the ACTUAL synced tree — its
        leaf shapes capture fit_vocab shrinks, HF imports, anything the
        config-derived count would miss; without it the model config's
        parameter count stands in. Streaming inherits this unchanged:
        every fragment launches exactly once per round, so the
        whole-tree number IS the per-round total there too (the
        per-LAUNCH division lives in streaming's sync_payload_report).

        Returns::

            wire_bytes_per_sync   bytes this worker puts on the wire per
                                  round under the configured mode (HLO-
                                  pinned only under outer_wire_collective;
                                  otherwise the reduce's input width —
                                  XLA's lowering owns the transfer)
            raw_bytes_per_sync    the f32 reference wire (what the
                                  torch reference's all_reduce moves)
            wire_compression      raw / wire (1.0 = no narrowing)
            wire_overhead_bytes   scale vector + survivor-count scalar
                                  riding the integer-collective wire
        """
        if snapshot is not None:
            leaves = jax.tree.leaves(snapshot)
            n = sum(int(np.prod(l.shape)) for l in leaves)
            n_leaves = len(leaves)
        else:
            n = self.model_cfg.num_params()
            n_leaves = len(
                jax.tree.leaves(
                    self._pspec, is_leaf=lambda x: isinstance(x, P)
                )
            )
        raw = 4 * n
        cfg = self.cfg
        if cfg.outer_wire_collective:
            acc = jnp.dtype(
                _wire_accumulator_dtype(
                    cfg.num_workers,
                    float(jnp.iinfo(jnp.dtype(cfg.outer_comm_dtype)).max),
                )
            )
            # one f32 absmax scalar per tensor (the shared-scale pmax)
            # plus the survivor-count scalar — the only float traffic a
            # clean integer wire carries (allreduce_wire_report audits
            # exactly this shape)
            overhead = 4 * n_leaves + 4
            wire = acc.itemsize * n + overhead
        else:
            # every other mode reduces in f32 (quantize-dequantize
            # happens before the mean — _wire_quantize's honest-scope
            # note); the wire number must say so, never flatter itself
            overhead = 0
            wire = raw
        return {
            "wire_bytes_per_sync": int(wire),
            "raw_bytes_per_sync": int(raw),
            "wire_compression": round(raw / wire, 4) if wire else 1.0,
            "wire_overhead_bytes": int(overhead),
        }

    def _replica_finite_mask(self, params_w: Any) -> jax.Array:
        """[W] bool: worker w's replica contains only finite values.
        The EXACT quarantine criterion — loss finiteness alone has a
        one-step hole (per-step losses are computed from PRE-update
        params, so a gradient spike on the round's final inner update
        slips past a loss-only mask; found by round-4 review)."""
        flags = [
            jnp.all(jnp.isfinite(p), axis=tuple(range(1, p.ndim)))
            for p in jax.tree.leaves(params_w)
        ]
        ok = flags[0]
        for f in flags[1:]:
            ok = ok & f
        return ok

    def _heal_inner_opt(
        self, inner_opt_state: Any, keep: jax.Array, params_w: Any
    ) -> Any:
        """Zero masked workers' float optimizer leaves (Adam m/v etc.) —
        a fresh-init equivalent. Without this the quarantined worker's
        NaN moments re-poison it on the next round's first update (NaN
        propagates through b1*m + (1-b1)*g forever) and the 'self-heal'
        is permanent W-1 degradation. Integer leaves (schedule counts)
        are shared cadence, kept in sync for every worker.

        Worker-stacked leaves are identified EXACTLY against the
        optimizer's own shape signature: ``inner_tx.init`` on one
        worker's (unstacked) param shapes says what each leaf looks like
        without the worker axis, so a leaf is per-worker iff its shape
        is ``(W,) + unstacked``. (The previous ``shape[0] == W``
        heuristic could silently zero a future non-stacked float leaf
        whose leading dim coincidentally equals W — round-4 advisor
        finding.)"""
        W = self.cfg.num_workers
        unstacked = jax.eval_shape(
            self.inner_tx.init,
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params_w
            ),
        )

        def heal(leaf, u):
            if (
                not hasattr(leaf, "dtype")
                or not jnp.issubdtype(leaf.dtype, jnp.inexact)
                or leaf.shape != (W,) + u.shape
            ):
                return leaf
            k = keep.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(k, leaf, jnp.zeros_like(leaf))

        return jax.tree.map(heal, inner_opt_state, unstacked)

    def _replicated_scalar_constraint(self, x: jax.Array) -> jax.Array:
        """Replicate a small dynamics output across the mesh so the host
        can fetch it on a pod (a [W] vector reduced from diloco-sharded
        params stays diloco-sharded; np.asarray of a non-addressable
        shard raises on multi-process runs — the same hazard the loss
        path handles by reducing on device first)."""
        if self.mesh.size == 1:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P())
        )

    def _sync_dynamics(
        self,
        old_snapshot: Any,
        params_w: Any,
        delta: Any,
        updates: Any,
        outer_opt_state: Any,
    ) -> dict[str, jax.Array]:
        """The DiLoCo dynamics readout, fused into the sync program
        (``dynamics_metrics``). Everything here is a pure function of
        values the outer step already holds — pre-reset worker params,
        the old snapshot, the averaged pseudo-gradient, the applied
        update, the new momentum — so it adds zero dispatches and
        cannot perturb training numerics. All accumulation is float32.

        Returns (host-fetchable: replicated on multi-device meshes):

        - ``pg_norm`` [W]: each worker's pseudo-gradient norm
          ``||snapshot - params_w||`` — the per-worker magnitude whose
          spread is the first sign of one replica running away.
        - ``drift_max`` / ``drift_mean``: max / RMS pairwise distance
          between worker replicas, normalized by ``||snapshot||`` — the
          drift H inner steps actually opened up, the quantity
          quantized outer comm (arXiv:2501.18512) needs to stay tame.
          Pairwise distances are computed from the deviation gram
          ``G_ij = <p_i - mean, p_j - mean>`` (all entries O(drift²),
          so the ``G_ii + G_jj - 2 G_ij`` combination is
          well-conditioned — a raw-params gram would cancel
          catastrophically when replicas are close). The exact worker
          mean is recomputed here (under a quantized wire ``delta`` is
          coarsened; drift must measure the real replicas).
        - ``outer_momentum_norm``: norm of the outer optimizer's float
          state (the Nesterov trace) AFTER the update.
        - ``outer_update_cos``: cosine between the averaged
          pseudo-gradient and the DESCENT direction of the applied
          update (``-updates``): +1 when momentum and the fresh
          pseudo-gradient agree, falling toward 0/negative as they
          fight — drift in this cosine precedes loss-visible
          divergence. Under quarantine a dead replica's NaN flows
          through (honest: the watchdog's divergence sentinel treats
          non-finite drift as alarming)."""
        W = self.cfg.num_workers
        f32 = jnp.float32

        def leaf_sq(t):
            return sum(
                jnp.sum(jnp.square(x.astype(f32))) for x in jax.tree.leaves(t)
            )

        # per-worker pseudo-gradient norms: [W]
        pg_sq = sum(
            jnp.sum(
                jnp.square((s[None] - p).astype(f32)),
                axis=tuple(range(1, p.ndim)),
            )
            for s, p in zip(jax.tree.leaves(old_snapshot), jax.tree.leaves(params_w))
        )
        pg_norm = jnp.sqrt(pg_sq)

        snap_norm = jnp.sqrt(leaf_sq(old_snapshot))
        tiny = jnp.finfo(f32).tiny

        if W > 1:
            # deviation gram accumulated leaf-by-leaf (one f32 deviation
            # copy of one leaf at a time — no full-tree f32 replica-set
            # held live, same discipline as the integer wire)
            gram = jnp.zeros((W, W), f32)
            for p in jax.tree.leaves(params_w):
                e = p.astype(f32)
                e = e - jnp.mean(e, axis=0, keepdims=True)
                e2 = e.reshape((W, -1))
                gram = gram + e2 @ e2.T
            diag = jnp.diagonal(gram)
            sq_dist = diag[:, None] + diag[None, :] - 2.0 * gram
            iu, ju = jnp.triu_indices(W, k=1)
            pair = jnp.sqrt(jnp.maximum(sq_dist[iu, ju], 0.0))
            drift_max = jnp.max(pair) / jnp.maximum(snap_norm, tiny)
            drift_mean = jnp.sqrt(jnp.mean(jnp.square(pair))) / jnp.maximum(
                snap_norm, tiny
            )
        else:
            drift_max = jnp.zeros((), f32)
            drift_mean = jnp.zeros((), f32)

        mom_sq = sum(
            jnp.sum(jnp.square(x.astype(f32)))
            for x in jax.tree.leaves(outer_opt_state)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)
        )
        mom_norm = jnp.sqrt(jnp.asarray(mom_sq, f32))

        dot = sum(
            jnp.sum(d.astype(f32) * u.astype(f32))
            for d, u in zip(jax.tree.leaves(delta), jax.tree.leaves(updates))
        )
        d_norm = jnp.sqrt(leaf_sq(delta))
        u_norm = jnp.sqrt(leaf_sq(updates))
        # -dot: `updates` is what apply_updates ADDS (−lr · direction);
        # the reported cosine is against the descent direction, so a
        # healthy momentum-aligned round reads near +1
        # clipped: a quotient of separately rounded f32 sums can land an
        # ulp outside [-1, 1] (it reads 1.0000001 on the first round
        # under jax 0.9.0's fusion), and a cosine is reported inside it
        cos = jnp.clip(-dot / jnp.maximum(d_norm * u_norm, tiny), -1.0, 1.0)

        rep = self._replicated_scalar_constraint
        return {
            "pg_norm": rep(pg_norm),
            "drift_max": rep(drift_max),
            "drift_mean": rep(drift_mean),
            "outer_momentum_norm": rep(mom_norm),
            "outer_update_cos": rep(cos),
        }

    @jax.named_scope("outer")  # pseudo-gradient, all-reduce, Nesterov
    def _outer_step(
        self,
        state: DilocoState,
        worker_mask: jax.Array | None = None,
        h_budget: jax.Array | None = None,
    ) -> tuple[DilocoState, jax.Array]:
        """Returns ``(state, effective_mask, dynamics)``: the [W] bool
        mask of workers that actually contributed to the outer mean —
        the EXACT quarantine criterion (caller's loss mask AND
        replica-params finiteness), so logging can report the true
        quarantine count instead of re-deriving a loss-only
        approximation (round-4 advisor finding); all-ones when
        quarantine is off. ``dynamics`` is the ``_sync_dynamics``
        readout dict when ``dynamics_metrics`` is on, else None.

        ``h_budget`` (hetero-H): each worker's delta enters the merge
        weighted by its realized step count — the weighted outer
        average ``sum_w H_w * delta_w / sum_w H_w``. A quarantined
        worker's weight is zeroed (mask AND weights compose by
        multiplication)."""
        W = self.cfg.num_workers
        inner_opt_state = state.inner_opt_state
        old_snapshot = state.snapshot
        if self.cfg.quarantine_nonfinite:
            # exact criterion, applied in BOTH dispatch paths: replica
            # params must be finite (any caller-provided loss-based mask
            # is ANDed in — it can only add reasons to quarantine)
            pmask = self._replica_finite_mask(state.params)
            worker_mask = (
                pmask if worker_mask is None
                else (worker_mask.astype(bool) & pmask)
            )
            inner_opt_state = self._heal_inner_opt(
                inner_opt_state, worker_mask, state.params
            )
        weights = worker_mask
        if h_budget is not None:
            share = h_budget.astype(jnp.float32)
            weights = (
                share if worker_mask is None
                else share * worker_mask.astype(jnp.float32)
            )
        # pseudo-gradient, pre-averaged (ref diloco.py:48-49)
        delta = self._pseudograd(old_snapshot, state.params, weights)
        delta = self._constrain(delta, worker_axis=False)
        updates, outer_opt_state = self.outer_tx.update(
            delta, state.outer_opt_state, old_snapshot
        )
        # dynamics readout BEFORE the reset overwrites the replicas —
        # pure arithmetic over values this step already computed
        dyn = (
            self._sync_dynamics(
                old_snapshot, state.params, delta, updates, outer_opt_state
            )
            if self.cfg.dynamics_metrics
            else None
        )
        snapshot = optax.apply_updates(old_snapshot, updates)
        snapshot = self._constrain(snapshot, worker_axis=False)
        # every worker resets to the new sync point (ref diloco.py:50)
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), snapshot
        )
        params = self._constrain(params, worker_axis=True)
        eff = (
            jnp.ones((W,), bool) if weights is None
            else weights.astype(bool)
        )
        return state.replace(
            params=params, snapshot=snapshot,
            inner_opt_state=inner_opt_state,
            outer_opt_state=outer_opt_state,
        ), eff, dyn

    def _outer_step_state(
        self,
        state: DilocoState,
        worker_mask: jax.Array | None = None,
        h_budget: jax.Array | None = None,
    ):
        """Public stepwise entry: the new state (the stepwise train loop
        derives the exact quarantine count itself — pre-reset params are
        still host-reachable there, unlike in the fused round), plus the
        dynamics dict as a second element when ``dynamics_metrics`` is
        on (the return arity is a per-config constant, so every compiled
        program has a fixed output structure)."""
        new, _, dyn = self._outer_step(state, worker_mask, h_budget)
        return (new, dyn) if self.cfg.dynamics_metrics else new

    def _round_step(
        self,
        state: DilocoState,
        tokens: jax.Array,
        loss_mask: jax.Array,
        h_budget: jax.Array | None = None,
    ):
        """One FULL DiLoCo round — ``inner_steps`` inner updates
        (``lax.scan``) plus the outer sync — as a single XLA executable.
        tokens/loss_mask: [H, W, accum, B, S]. Returns (state, [H, W]
        losses, [W] effective sync mask — the workers whose replicas
        entered the outer mean; all ones when quarantine is off), plus
        a 4th element, ONE dict of what the round measured of itself,
        where the configuration has any such thing (the public entry
        drops an empty one): the ``_sync_dynamics`` keys when
        ``dynamics_metrics`` is on, and from a mixed sparse
        configuration what its expert layers did at each inner step,
        ``"router_aux"`` [H, W] float32 and ``"moe_counters"`` [H, W, 5]
        int32 (the balance term and ``moe.TRAIN_COUNTERS``, each summed
        over the step's layers).

        One program per round is the TPU-native shape of the training
        loop: no host round-trips between steps, no executable switching
        (the reference's per-microbatch Python loop, ref
        nanodiloco/main.py:106-116, is exactly what this avoids)."""
        if tokens.ndim != 5 or tokens.shape[0] != self.cfg.inner_steps:
            raise ValueError(
                f"round tokens must be [inner_steps={self.cfg.inner_steps}, "
                f"W, accum, B, S]; got {tokens.shape}"
            )

        def one(s, batch):
            s, loss, stats = self._inner_step(s, batch[0], batch[1], h_budget)
            return s, (loss, stats)

        state, (losses, stats) = jax.lax.scan(one, state, (tokens, loss_mask))
        wmask = None
        if self.cfg.quarantine_nonfinite:
            # [H, W] -> [W]: a non-finite inner loss is an EXTRA reason
            # to quarantine; the exact criterion (replica-params
            # finiteness, which also catches a blow-up on the round's
            # final update) is applied inside _outer_step
            wmask = jnp.all(jnp.isfinite(losses), axis=0)
        state, eff, dyn = self._outer_step(state, wmask, h_budget)
        return state, losses, eff, {**(dyn or {}), **stats}

    def _inner_round_step(
        self, state: DilocoState, tokens, loss_mask,
        h_budget: jax.Array | None = None,
    ):
        """``_round_step`` minus the outer sync — the differencing baseline
        for measuring the fused outer step's marginal cost. Same first
        three outputs as ``_round_step`` (the all-ones mask stands in) so
        the two dispatch identically; under ``dynamics_metrics`` the full
        round additionally carries the on-device dynamics readout, whose
        (tiny) cost is honestly billed to the sync by the differencing."""

        def one(s, batch):
            s, loss, _ = self._inner_step(s, batch[0], batch[1], h_budget)
            return s, loss

        state, losses = jax.lax.scan(one, state, (tokens, loss_mask))
        return state, losses, jnp.ones((self.cfg.num_workers,), bool)

    def measure_inner_round_time(
        self, state: DilocoState, tokens, loss_mask, repeats: int = 1
    ) -> float:
        """Seconds for one WARM inner-only round (min over ``repeats``
        timed calls after one untimed compile call), measured on throwaway
        copies of ``state`` (one alive at a time — transient 2x state
        HBM). Subtracting this from a warm full round isolates the outer
        sync's marginal cost. Training state is untouched — the copies
        feed the donating jit."""
        import time

        best = float("inf")
        for i in range(repeats + 1):  # +1 warmup/compile call
            probe = jax.tree.map(jnp.copy, state)
            t0 = time.perf_counter()
            probe, loss, _ = self.inner_round_step(probe, tokens, loss_mask)
            jax.block_until_ready(loss)
            if i > 0:
                best = min(best, time.perf_counter() - t0)
        del probe
        return best

    # -- async delayed-apply outer step (DilocoConfig.async_outer) -----------

    @jax.named_scope("outer")
    def _async_boundary(
        self, state: AsyncDilocoState, h_budget: jax.Array | None = None
    ):
        """The uniform round-boundary program of the async outer path:
        LAUNCH this round's outer update and APPLY the oldest pending
        merge, in one traced region.

        - Launch: the pseudo-gradient is measured from ``snapshot`` (the
          base this round's workers actually started from) against the
          pre-reset worker params; the Nesterov update is anchored at the
          HEAD of the outer trajectory (the newest pending merge), so the
          outer optimizer advances one coherent model — the gradient is
          ``outer_delay`` updates stale, classic bounded-staleness async
          SGD.
        - Apply: every worker resets to ``pending[0]`` — the merge
          launched ``outer_delay`` boundaries ago — which becomes the new
          ``snapshot``. No worker delta is ever dropped or double-counted:
          each round's progress enters exactly one pseudo-gradient,
          measured from the base the round really ran on.

        With ``outer_delay=0`` the head is ``snapshot`` and the apply is
        the just-launched merge: op-for-op the synchronous
        ``_outer_step``. The warm-up boundaries (pending slots still
        holding init copies) are value no-ops by construction: Δ of a
        just-reset worker set is exactly zero, and a zero pseudo-gradient
        through Nesterov SGD moves nothing.

        Returns ``(state, aux)``: aux carries ``boundary_round``,
        ``applied_launch_round`` (0 = warm-up init slot), the
        ``outer_staleness`` rounds the applied merge landed late, and —
        under ``dynamics_metrics`` — the ``_sync_dynamics`` dict, all
        replicated for pod-safe host fetches."""
        W = self.cfg.num_workers
        d = self.cfg.outer_delay
        # hetero-H: the launch's merge weights each worker's delta by
        # its realized step count, same math as the synchronous path
        weights = None if h_budget is None else h_budget.astype(jnp.float32)
        delta = self._pseudograd(state.snapshot, state.params, weights)
        delta = self._constrain(delta, worker_axis=False)
        head = state.pending[-1] if d > 0 else state.snapshot
        updates, outer_opt = self.outer_tx.update(
            delta, state.outer_opt_state, head
        )
        dyn = (
            self._sync_dynamics(
                state.snapshot, state.params, delta, updates, outer_opt
            )
            if self.cfg.dynamics_metrics
            else None
        )
        new = optax.apply_updates(head, updates)
        new = self._constrain(new, worker_axis=False)
        # this boundary's round index: the scan for round b has run, so
        # inner_step_count == b * H
        rnd = (state.inner_step_count // self.cfg.inner_steps).astype(jnp.int32)
        if d > 0:
            applied = state.pending[0]
            applied_launch = state.pending_round[0]
            pending = tuple(state.pending[1:]) + (new,)
            pending_round = jnp.concatenate(
                [state.pending_round[1:], rnd[None]]
            )
        else:
            # immediate apply; the single slot mirrors the merge so the
            # pytree (and checkpoint) shape is delay-invariant
            applied = new
            applied_launch = rnd
            pending = (new,)
            pending_round = rnd[None]
        snapshot = self._constrain(applied, worker_axis=False)
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), snapshot
        )
        params = self._constrain(params, worker_axis=True)
        rep = self._replicated_scalar_constraint
        aux = {
            "boundary_round": rep(rnd),
            "applied_launch_round": rep(applied_launch),
            "outer_staleness": rep(rnd - applied_launch),
        }
        if dyn is not None:
            aux["dynamics"] = dyn
        return state.replace(
            params=params,
            snapshot=snapshot,
            outer_opt_state=outer_opt,
            pending=pending,
            pending_round=pending_round,
            launched_round=rnd,
        ), aux

    def _async_round_step(
        self, state: AsyncDilocoState, tokens, loss_mask,
        h_budget: jax.Array | None = None,
        boundary_h_budget: jax.Array | None = None,
    ):
        """One steady-state async round as a SINGLE XLA program, boundary
        FIRST: [launch round N's outer update + apply the pending merge]
        then [round N+1's H-step inner scan]. The scan depends only on
        the applied merge (resident since ``outer_delay`` rounds ago);
        the launch's all-reduce feeds nothing until the NEXT program's
        boundary — the dataflow independence that lets XLA's
        latency-hiding scheduler run the collective under the round's
        compute. tokens/loss_mask: [H, W, accum, B, S]. Returns
        (state, [H, W] losses, boundary aux)."""
        if tokens.ndim != 5 or tokens.shape[0] != self.cfg.inner_steps:
            raise ValueError(
                f"round tokens must be [inner_steps={self.cfg.inner_steps}, "
                f"W, accum, B, S]; got {tokens.shape}"
            )
        # the boundary at the top of this program launches the PREVIOUS
        # round's delta — its merge weights must be the budgets that
        # round actually ran under (boundary_h_budget), not the possibly
        # just-retargeted budgets this round's scan uses (h_budget)
        state, aux = self._async_boundary(
            state, h_budget if boundary_h_budget is None else boundary_h_budget
        )

        def one(s, batch):
            s, loss, _ = self._inner_step(s, batch[0], batch[1], h_budget)
            return s, loss

        state, losses = jax.lax.scan(one, state, (tokens, loss_mask))
        return state, losses, aux

    def _async_drain(self, state: AsyncDilocoState) -> AsyncDilocoState:
        """Apply every remaining pending merge in launch order (the net
        effect: the NEWEST pending becomes the model) without launching
        anything — the end-of-run settling step, so the final
        checkpoint/eval see all completed outer work. The refilled slots
        are init-marked copies of the final snapshot: the drained state
        is a valid warm-up state, so extending a finished run resumes
        through the ordinary machinery."""
        if self.cfg.outer_delay == 0:
            return state  # applies are never deferred
        final = state.pending[-1]
        snapshot = self._constrain(final, worker_axis=False)
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (self.cfg.num_workers,) + x.shape
            ),
            snapshot,
        )
        params = self._constrain(params, worker_axis=True)
        return state.replace(
            params=params,
            snapshot=snapshot,
            pending=tuple(snapshot for _ in state.pending),
            pending_round=jnp.zeros_like(state.pending_round),
        )

    def _async_flush(
        self, state: AsyncDilocoState, h_budget: jax.Array | None = None
    ):
        """Final round boundary + drain: launch the last round's outer
        update, apply it (and any older pendings) immediately. Run once
        after the last round's inner scan; with ``outer_delay=0`` the
        drain is a no-op and this IS the ordinary boundary."""
        state, aux = self._async_boundary(state, h_budget)
        return self._async_drain(state), aux

    def async_round_cost_analysis(self, state, tokens, loss_mask):
        """Cost analysis of the fused ASYNC round program (boundary +
        H-step scan) — the executable an async fused run dispatches."""
        return self._jit_cost_analysis(
            self._async_round_jit, state, tokens, loss_mask, *self._hb()
        )

    # -- XLA cost analytics (obs/costs) --------------------------------------

    def _jit_cost_analysis(self, jit_fn, state: DilocoState, *args):
        """``{"flops", "bytes_accessed"}`` from XLA's cost model for one
        of this instance's jitted programs, or None when the backend's
        cost model yields nothing. Lowering only where that suffices (a
        host-side trace + StableHLO emission); on the chip
        ``lowered_cost`` also compiles — and the state is never
        consumed (donation applies at execution, which never happens
        here). ``_fetch`` mirrors the real call path so an
        offloaded snapshot lowers with device shardings."""
        from nanodiloco_tpu.obs.costs import lowered_cost

        try:
            fetched = self._fetch(state)
            if self.mesh.size > 1:
                with jax.set_mesh(self.mesh):
                    lowered = jit_fn.lower(fetched, *args)
            else:
                lowered = jit_fn.lower(fetched, *args)
            return lowered_cost(lowered)
        except Exception:
            # analytics must never take down training: an exotic
            # sharding the AOT path can't lower just means "no record"
            return None

    def round_cost_analysis(self, state: DilocoState, tokens, loss_mask):
        """Cost analysis of the FUSED round program (H inner steps +
        outer sync as one executable) — the program a fused training
        run actually dispatches, so its FLOPs are the honest numerator
        for analytic MFU."""
        return self._jit_cost_analysis(
            self._round_jit, state, tokens, loss_mask, *self._hb()
        )

    def inner_cost_analysis(self, state: DilocoState, tokens, loss_mask):
        """Cost analysis of one inner step — the stepwise path's unit of
        dispatch (the outer sync's FLOPs are a rounding error next to
        H steps of fwd+bwd, so per-token numbers match the fused
        program's)."""
        return self._jit_cost_analysis(
            self._inner_jit, state, tokens, loss_mask, *self._hb()
        )

    def microbatch_cost_analysis(self, state: DilocoState, batch_shape):
        """Per-token-normalizable cost analysis: ONE microbatch's
        fwd+bwd (``loss_fn`` value_and_grad at ``batch_shape`` =
        [B, S]) lowered with every scan force-unrolled, so XLA bills
        all L layers and every CE chunk instead of one loop body each
        (obs/costs loop caveat — the dispatched executable's own
        numbers cannot be normalized per token). Abstract inputs (one
        worker's unstacked param shapes), never executed.
        Optimizer/outer-sync FLOPs are excluded — the same scope as the
        hand formula this number reconciles against. None when the
        probe can't lower (e.g. a manual-collective loss path)."""
        from nanodiloco_tpu.obs.costs import lowered_cost, unrolled_scans

        try:
            p1 = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                state.params,
            )
            tok = jax.ShapeDtypeStruct(tuple(batch_shape), jnp.int32)

            def probe(p, t, m):
                return jax.value_and_grad(self.loss_fn, has_aux=True)(p, t, m)

            with unrolled_scans():
                if self.mesh.size > 1:
                    with jax.set_mesh(self.mesh):
                        lowered = jax.jit(probe).lower(p1, tok, tok)
                else:
                    lowered = jax.jit(probe).lower(p1, tok, tok)
            return lowered_cost(lowered)
        except Exception:
            return None

    # -- snapshot host offload (ref diloco.py:27-32, made async) -------------

    def _offload(self, state: DilocoState) -> DilocoState:
        if self._host_shardings is None:
            return state
        if jax.tree.structure(state.snapshot) != self._pspec_struct:
            return state
        snap = jax.device_put(state.snapshot, self._host_shardings)
        return state.replace(snapshot=snap)

    def _fetch(self, state: DilocoState) -> DilocoState:
        """Inverse of ``_offload``: bring a pinned_host snapshot back to
        device memory before a jitted program consumes it. No-op when
        offload is off, the tree shape is foreign (streaming states), or
        the snapshot already lives on device."""
        if self._snap_device_shardings is None:
            return state
        if jax.tree.structure(state.snapshot) != self._pspec_struct:
            return state
        leaves = jax.tree.leaves(state.snapshot)
        if not leaves or getattr(
            leaves[0].sharding, "memory_kind", None
        ) != "pinned_host":
            return state
        snap = jax.device_put(state.snapshot, self._snap_device_shardings)
        return state.replace(snapshot=snap)

    def stack_round_batches(self, batches) -> tuple[jax.Array, jax.Array]:
        """Draw ``cfg.inner_steps`` batches and stack them into the
        [H, W, accum, B, S] arrays ``round_step`` consumes, placed via the
        multi-host-safe feeder. Raises StopIteration if the data runs out
        mid-round (the caller decides whether a partial round should
        sync)."""
        it = iter(batches)
        toks, masks = [], []
        for _ in range(self.cfg.inner_steps):
            tokens, mask = next(it)
            toks.append(np.asarray(tokens))
            masks.append(np.asarray(mask))
        return self.feed_round(np.stack(toks)), self.feed_round(np.stack(masks))

    def run_round(self, state: DilocoState, batches) -> tuple[DilocoState, jax.Array]:
        """One full DiLoCo round: exactly ``cfg.inner_steps`` inner steps,
        then the outer sync, dispatched as ONE fused executable
        (``round_step``). ``batches`` is an iterator yielding
        ([W, accum, B, S] tokens, same-shape mask); cadence is owned here —
        the reference accepted ``inner_steps`` and ignored it
        (ref diloco.py:8-25, SURVEY §2 quirks)."""
        toks, masks = self.stack_round_batches(batches)
        out = self.round_step(state, toks, masks)
        state, losses = out[0], out[1]
        return self._offload(state), losses
