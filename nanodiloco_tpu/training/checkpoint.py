"""Checkpoint/resume via Orbax — absent in the reference (its output
volume was mounted but never written, ref scripts/train_modal.py:43-45 +
SURVEY §5 "Checkpoint / resume: Absent"); table stakes for multi-hour
TPU runs.

The full DiLoCo state is saved: every worker's params, inner optimizer
states, the sync snapshot, outer momentum, and the inner-step counter —
a restore resumes bit-exactly mid-round.
"""

from __future__ import annotations

import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import orbax.checkpoint as ocp

from nanodiloco_tpu.parallel.diloco import DilocoState
from nanodiloco_tpu.resilience import faults as _faults
from nanodiloco_tpu.resilience.retry import RetryPolicy, retry_call


def _path_names(path) -> tuple:
    """Normalize a jax key path to comparable name strings: orbax's
    keyed-dict layout (DictKey('mu'), DictKey('0')) must match the live
    optax NamedTuple/tuple layout (GetAttrKey('mu'), SequenceKey(0))."""
    out = []
    for e in path:
        if hasattr(e, "key"):        # DictKey / FlattenedIndexKey
            out.append(str(e.key))
        elif hasattr(e, "name"):     # GetAttrKey (NamedTuple fields)
            out.append(str(e.name))
        elif hasattr(e, "idx"):      # SequenceKey (tuples/lists)
            out.append(str(e.idx))
        else:
            out.append(str(e))
    return tuple(out)


def _path_leaf_map(tree) -> dict:
    return {
        _path_names(p): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


class CheckpointManager:
    """``retry``: a resilience RetryPolicy wrapped around every save and
    restore attempt (jittered exponential backoff with a deadline) —
    None keeps the raw single-attempt behavior. ``on_event`` receives a
    ``{"retry": op, "attempt": ..., ...}`` record per backoff (the train
    loop passes the metrics logger, so IO flakiness lands in the same
    JSONL the fault timeline reads from)."""

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        retry: RetryPolicy | None = None,
        on_event: Callable[[dict], None] | None = None,
        synchronous: bool = True,
    ) -> None:
        self.directory = os.path.abspath(directory)
        self.retry = retry
        self._on_event = on_event or (lambda rec: None)
        # Synchronous (default): every save commits before save() returns,
        # so a write error surfaces AT the failing save — straight into
        # the retry/alarm path — and a crash one step later can never
        # lose a checkpoint the run believed it had. The async mode
        # (synchronous=False) keeps orbax's background write for
        # wall-clock overlap, at the cost of deferred errors (bounded by
        # check_async_errors at the next save). On jax 0.4.37 / orbax 0.7
        # a pending background write racing the train loop corrupted the
        # process heap and tore checkpoint contents; not re-tested on
        # jax 0.9.0 / orbax 0.11 (ROADMAP C10).
        self.synchronous = synchronous
        self._mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
            # explicit handler so item_metadata works on a manager that
            # has not saved in this process (restore_raw's metadata-driven
            # cross-device restore needs it)
            item_handlers=ocp.StandardCheckpointHandler(),
        )

    def _attempt(self, op: str, fn: Callable[[], Any]) -> Any:
        """Run one save/restore under the retry policy (or bare), with a
        per-backoff event record for the run's JSONL."""

        def note(attempt: int, exc: BaseException, delay: float) -> None:
            self._on_event({
                "retry": op, "attempt": attempt,
                "delay_s": round(delay, 3),
                "error": f"{type(exc).__name__}: {exc}"[:300],
            })

        if self.retry is None:
            return fn()
        return retry_call(fn, op=op, policy=self.retry, on_retry=note)

    def check_async_errors(self) -> None:
        """Surface a failed BACKGROUND write now. Orbax saves commit on a
        background thread; without this, a failed write only reports at
        teardown ``wait()`` — the run spends its whole life believing it
        has checkpoints it doesn't. Called at the top of every ``save``
        (a bounded, non-blocking check) so the failure routes into the
        same retry/alarm path as a synchronous save error."""
        check = getattr(self._mngr, "check_for_errors", None)
        if check is not None:
            check()

    def save(self, step: int, state: DilocoState, force: bool = False) -> None:
        if not self.synchronous:
            # async mode: snapshot the live buffers BEFORE the background
            # write — orbax's writer reads the arrays while the caller's
            # next jitted dispatch DONATES them, and a torn read lands
            # garbage in the checkpoint (the seed's flaky non-bit-exact
            # resume). One device-side copy per save, freed at commit.
            state = jax.tree.map(jnp.copy, state)

        def attempt():
            self.check_async_errors()
            _faults.check_io("save")
            self._mngr.save(step, args=ocp.args.StandardSave(state), force=force)
            if self.synchronous:
                # commit before returning: an IO failure surfaces HERE,
                # inside the retry wrapper, never at a later teardown
                self._mngr.wait_until_finished()

        self._attempt("ckpt_save", attempt)

    def wait(self) -> None:
        self._mngr.wait_until_finished()

    @property
    def latest_step(self) -> int | None:
        return self._mngr.latest_step()

    def restore(self, abstract_state: Any, step: int | None = None) -> DilocoState:
        """``abstract_state``: a DilocoState of jax.ShapeDtypeStruct leaves
        (e.g. from ``jax.eval_shape`` of init) carrying target shardings,
        so arrays restore directly to their mesh placement. Per-leaf, the
        SAVED partition spec overrides the caller's when the mesh matches
        (see ``_with_saved_shardings``)."""
        step = self.latest_step if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        abstract_state = self._with_saved_shardings(abstract_state, step)

        def attempt():
            _faults.check_io("restore")
            return self._mngr.restore(
                step, args=ocp.args.StandardRestore(abstract_state)
            )

        return self._attempt("ckpt_restore", attempt)

    def _with_saved_shardings(self, abstract_state: Any, step: int) -> Any:
        """Re-target each leaf's restore sharding to the partition spec it
        was SAVED with (same mesh only). The caller's abstract state comes
        from a fresh init state, and init-time shardings can differ from
        the steady-state shardings the jitted step programs settle on
        (inner Adam moments: unconstrained at init, 'diloco'-propagated by
        the first compiled step's output). Restoring onto the init
        sharding is bit-exact on the wire but makes the resumed process's
        jits specialize on DIFFERENT input shardings than the interrupted
        run's — the partitioner reassociates differently and the resumed
        trajectory drifts by ulps (observed ~4e-9 on the async-outer
        stepwise resume; resume must be bit-exact). Falls back per leaf to
        the caller's sharding when the checkpoint predates sharding
        metadata or was written on a different mesh (elastic resumes go
        through ``restore_elastic``, never here)."""
        try:
            meta = self._mngr.item_metadata(step)
            meta = getattr(meta, "tree", meta)
        except Exception:
            return abstract_state
        if meta is None:
            return abstract_state
        meta_map = _path_leaf_map(meta)

        def retarget(path, ab):
            sh = getattr(ab, "sharding", None)
            saved = getattr(meta_map.get(_path_names(path)), "sharding", None)
            if not isinstance(sh, jax.sharding.NamedSharding) or saved is None:
                return ab
            names = getattr(saved, "axis_names", None)
            mesh_shape = getattr(saved, "shape", None)
            if (
                names is None
                or mesh_shape is None
                or tuple(names) != tuple(sh.mesh.axis_names)
                or tuple(mesh_shape) != tuple(sh.mesh.devices.shape)
            ):
                return ab
            new = jax.sharding.NamedSharding(
                sh.mesh, jax.sharding.PartitionSpec(*saved.partition_spec)
            )
            if getattr(sh, "memory_kind", None) is not None:
                # an offloaded target (pinned_host snapshot) stays
                # offloaded regardless of where the save ran from
                new = new.with_memory_kind(sh.memory_kind)
            return jax.ShapeDtypeStruct(ab.shape, ab.dtype, sharding=new)

        leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
        return jax.tree_util.tree_unflatten(
            treedef, [retarget(p, ab) for p, ab in leaves]
        )

    def restore_raw(
        self, step: int | None = None, only: set[str] | None = None
    ) -> Any:
        """Restore without a caller-supplied target: returns the saved
        pytree as nested dicts of single-device arrays. The target is
        rebuilt from the checkpoint's own metadata WITHOUT the saved
        shardings, so a checkpoint written on one mesh (e.g. 8 training
        devices) loads on any other device count. ``only`` names
        top-level DilocoState fields to materialize (e.g. {"snapshot"});
        the rest stay un-read placeholders — at multi-worker 8B scale the
        full state (W x params + optimizer moments) would not fit the one
        device this restores onto when the snapshot alone does."""
        step = self.latest_step if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        # A separate read-only manager: partial (PLACEHOLDER) restores go
        # through PyTreeRestore, which the training manager's standard
        # handler does not accept.
        mngr = ocp.CheckpointManager(
            self.directory, item_handlers=ocp.PyTreeCheckpointHandler()
        )
        try:
            # newer orbax wraps the metadata tree in an object with a
            # ``.tree`` attribute; 0.7-era returns the tree itself
            meta = mngr.item_metadata(step)
            meta = getattr(meta, "tree", meta)
            sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

            def abstract(tree):
                return jax.tree.map(
                    lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sharding),
                    tree,
                )

            if only is not None:
                missing = only - set(meta)
                if missing:
                    raise KeyError(
                        f"checkpoint has no field(s) {sorted(missing)}; "
                        f"available: {sorted(meta)}"
                    )
            if only is None or not hasattr(ocp, "PLACEHOLDER"):
                # legacy orbax has no PLACEHOLDER partial restore:
                # materialize everything and let the caller take the
                # fields it wants — correctness preserved, the
                # skip-the-read memory saving is modern-orbax-only
                item = abstract(meta)
            else:
                item = {
                    k: (abstract(v) if k in only
                        else jax.tree.map(lambda _: ocp.PLACEHOLDER, v))
                    for k, v in meta.items()
                }
            rargs = jax.tree.map(
                lambda _: ocp.ArrayRestoreArgs(sharding=sharding), meta
            )
            return mngr.restore(
                step, args=ocp.args.PyTreeRestore(item=item, restore_args=rargs)
            )
        finally:
            mngr.close()

    def saved_worker_count(self, step: int | None = None) -> int:
        """Leading (worker) dimension of the checkpoint's stacked params,
        read from metadata only — no array data touched."""
        step = self.latest_step if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        # the training manager's explicit StandardCheckpointHandler makes
        # item_metadata work without a save in this process (see __init__)
        meta = self._mngr.item_metadata(step)
        meta = getattr(meta, "tree", meta)
        return int(jax.tree.leaves(meta["params"])[0].shape[0])

    def restore_elastic(
        self, fresh_state: DilocoState, step: int | None = None
    ) -> DilocoState:
        """Restore into a DIFFERENT worker count — the capacity-change
        story the fault path needs (a permanently lost slice must not
        strand the checkpoint; the reference's stacked NCCL world can
        only ever come back at the same size).

        Valid because checkpoints are written at outer-sync boundaries,
        where every worker equals the snapshot: the restored snapshot,
        outer optimizer state, and step count are exact, and the new
        worker stacking is rebuilt by re-broadcasting the snapshot —
        precisely what ``_outer_step``'s reset would produce. The cost,
        stated honestly: inner Adam MOMENTS restart at zero for every
        worker (they are per-worker state with the old W and cannot be
        reshaped meaningfully); the schedule count is advanced to the
        restored step so the LR does NOT re-warm. MEASURED cost
        (scripts/elastic_cost.py, runs/elastic_cost_r5.jsonl: same-W
        elastic vs bit-exact control from one checkpoint, identical
        data): +3.9% mean loss gap over the first 10 post-resume steps,
        +1.7% over steps 11-40, indistinguishable from batch noise by
        ~50 steps (10-step rolling mean < 1%). Same-W resumes keep
        using ``restore`` (bit-exact, moments included).

        ``fresh_state``: a freshly initialized state at the NEW worker
        count whose leaves carry the target shardings. The restore is
        SHARDED end to end: orbax reads each leaf straight into the
        fresh state's sharding (no single-device staging), so elastic
        resume works at 8B scale and from every process of a pod."""
        step = self.latest_step if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {self.directory}")
        # streaming states carry per-fragment outer opt states + pending
        # merges instead of the single outer_opt_state — both are
        # unstacked (no worker axis), so they re-broadcast across a
        # worker-count change exactly like the classic snapshot. Async
        # classic states (AsyncDilocoState) likewise carry unstacked
        # pending merge(s) plus the launch bookkeeping — all global
        # state, restored exactly; only the worker stacking is rebuilt.
        is_streaming = hasattr(fresh_state, "outer_opt_states")
        is_async = not is_streaming and hasattr(fresh_state, "pending")
        if is_streaming:
            only = {"snapshot", "outer_opt_states", "pending",
                    "inner_step_count"}
            fresh_map = {
                "snapshot": fresh_state.snapshot,
                "outer_opt_states": fresh_state.outer_opt_states,
                "pending": fresh_state.pending,
                "inner_step_count": fresh_state.inner_step_count,
            }
        elif is_async:
            only = {"snapshot", "outer_opt_state", "pending",
                    "pending_round", "launched_round", "inner_step_count"}
            fresh_map = {
                "snapshot": fresh_state.snapshot,
                "outer_opt_state": fresh_state.outer_opt_state,
                "pending": fresh_state.pending,
                "pending_round": fresh_state.pending_round,
                "launched_round": fresh_state.launched_round,
                "inner_step_count": fresh_state.inner_step_count,
            }
        else:
            only = {"snapshot", "outer_opt_state", "inner_step_count"}
            fresh_map = {
                "snapshot": fresh_state.snapshot,
                "outer_opt_state": fresh_state.outer_opt_state,
                "inner_step_count": fresh_state.inner_step_count,
            }
        mngr = ocp.CheckpointManager(
            self.directory, item_handlers=ocp.PyTreeCheckpointHandler()
        )
        try:
            # newer orbax wraps the metadata tree in an object with a
            # ``.tree`` attribute; 0.7-era returns the tree itself
            meta = mngr.item_metadata(step)
            meta = getattr(meta, "tree", meta)
            missing = only - set(meta)
            if missing:
                kind = "streaming" if is_streaming else "classic"
                raise KeyError(
                    f"checkpoint has no field(s) {sorted(missing)}; "
                    f"available: {sorted(meta)} (target state is {kind} — "
                    "a classic checkpoint cannot elastic-restore into a "
                    "streaming run or vice versa; match "
                    "streaming_fragments to the checkpoint)"
                )
            # graft the fresh state's shardings onto the SAVED tree
            # structure (orbax stores optax NamedTuples as keyed dicts),
            # matching leaves BY KEY PATH — flattened order is not
            # trustworthy across orbax's key-sorted dict layout vs the
            # optax NamedTuple layout (Adam's mu/nu only align by order
            # because 'mu' < 'nu' alphabetically; round-4 advisor
            # finding) — with a shape guard per matched pair
            item: dict = {}
            rargs: dict = {}
            for k, v in meta.items():
                if k not in only:
                    if hasattr(ocp, "PLACEHOLDER"):
                        item[k] = jax.tree.map(lambda _: ocp.PLACEHOLDER, v)
                        rargs[k] = jax.tree.map(lambda _: ocp.RestoreArgs(), v)
                    else:
                        # legacy orbax: no skip-the-read — restore the
                        # discarded leaves anyway (modern orbax keeps
                        # the memory saving). The sharding must be
                        # addressable from EVERY process: a
                        # SingleDeviceSharding of global device 0 is
                        # foreign to every other pod process and orbax
                        # deadlocks on it at the first multi-process
                        # elastic resume (found by the newly-runnable
                        # 2-process elastic test) — replicate over all
                        # devices instead
                        import numpy as _np

                        rep_mesh = jax.sharding.Mesh(
                            _np.array(jax.devices()), ("all",)
                        )
                        sd = jax.sharding.NamedSharding(
                            rep_mesh, jax.sharding.PartitionSpec()
                        )
                        item[k] = jax.tree.map(
                            lambda m: jax.ShapeDtypeStruct(
                                m.shape, m.dtype, sharding=sd
                            ), v,
                        )
                        rargs[k] = jax.tree.map(
                            lambda m: ocp.ArrayRestoreArgs(sharding=sd), v
                        )
                    continue
                meta_paths, treedef = jax.tree_util.tree_flatten_with_path(v)
                tgt_map = _path_leaf_map(fresh_map[k])
                if len(meta_paths) != len(tgt_map):
                    hint = (
                        "streaming_fragments differs from the checkpoint?"
                        if k in ("outer_opt_states", "pending")
                        else "different optimizer?"
                    )
                    raise ValueError(
                        f"elastic restore: {k} has {len(meta_paths)} "
                        f"saved leaves vs {len(tgt_map)} in the target "
                        f"({hint})"
                    )
                structs, args_ = [], []
                for p, m in meta_paths:
                    t = tgt_map.get(_path_names(p))
                    if t is None:
                        raise ValueError(
                            f"elastic restore: {k} saved leaf at "
                            f"{jax.tree_util.keystr(p)} has no same-keyed "
                            "leaf in the target (different optimizer or "
                            "model config?)"
                        )
                    if tuple(m.shape) != tuple(t.shape):
                        raise ValueError(
                            f"elastic restore: {k} leaf "
                            f"{jax.tree_util.keystr(p)} shape {m.shape} "
                            f"!= target {t.shape} (different model "
                            "config?)"
                        )
                    structs.append(
                        jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=t.sharding)
                    )
                    args_.append(ocp.ArrayRestoreArgs(sharding=t.sharding))
                item[k] = jax.tree.unflatten(treedef, structs)
                rargs[k] = jax.tree.unflatten(treedef, args_)
            raw = mngr.restore(
                step, args=ocp.args.PyTreeRestore(item=item, restore_args=rargs)
            )
        finally:
            mngr.close()

        def to_fresh(raw_tree, target_tree):
            # reorder raw leaves into the target structure by key path
            # (same rationale as above: container layouts differ)
            raw_map = _path_leaf_map(raw_tree)
            paths, tgt_def = jax.tree_util.tree_flatten_with_path(target_tree)
            return jax.tree.unflatten(
                tgt_def, [raw_map[_path_names(p)] for p, _ in paths]
            )

        snapshot = to_fresh(raw["snapshot"], fresh_state.snapshot)
        count = jnp.asarray(raw["inner_step_count"], jnp.int32)
        params = jax.tree.map(
            lambda t, s: jax.device_put(
                jnp.broadcast_to(s[None], t.shape), t.sharding
            ),
            fresh_state.params, snapshot,
        )
        if is_streaming:
            # per-fragment outer momentum and pending merges are global
            # (unstacked) state: restored exactly. Worker replicas reset
            # to the snapshot — the last globally-merged model — so a
            # restored pending fragment applying on schedule merges into
            # coherent params (the same state an apply-at-launch would
            # have produced under merge_alpha=1).
            outer_states = to_fresh(
                raw["outer_opt_states"], fresh_state.outer_opt_states
            )
            pending = to_fresh(raw["pending"], fresh_state.pending)
            inner = jax.tree.map(_advance_counts(count), fresh_state.inner_opt_state)
            return fresh_state.replace(
                params=params, snapshot=snapshot, inner_opt_state=inner,
                outer_opt_states=outer_states, pending=pending,
                inner_step_count=count,
            )
        outer = to_fresh(raw["outer_opt_state"], fresh_state.outer_opt_state)
        inner = jax.tree.map(_advance_counts(count), fresh_state.inner_opt_state)
        if is_async:
            # pending merges / launch markers are global state: exact.
            # Workers reset to the restored snapshot (the elastic
            # contract), so an owed boundary's pseudo-gradient reads
            # zero after the restart — the interrupted round's worker
            # deltas left with the old replicas; the outer trajectory
            # stays coherent and deterministic.
            pending = to_fresh(raw["pending"], fresh_state.pending)
            return fresh_state.replace(
                params=params, snapshot=snapshot, inner_opt_state=inner,
                outer_opt_state=outer, pending=pending,
                pending_round=jnp.asarray(raw["pending_round"], jnp.int32),
                launched_round=jnp.asarray(raw["launched_round"], jnp.int32),
                inner_step_count=count,
            )
        return fresh_state.replace(
            params=params, snapshot=snapshot, inner_opt_state=inner,
            outer_opt_state=outer, inner_step_count=count,
        )

    def close(self) -> None:
        self._mngr.close()


def _advance_counts(count):
    """Fresh inner-optimizer state with integer leaves (schedule + Adam
    bias-correction counts) advanced to the restored step, so the LR does
    not re-warm; float moments stay at fresh-init zero."""

    def advance(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return jax.device_put(
                jnp.full(leaf.shape, count, leaf.dtype), leaf.sharding
            )
        return leaf

    return advance


def abstract_state_like(state: DilocoState) -> DilocoState:
    """Shape/dtype/sharding skeleton of a concrete state, for restore."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), state
    )
