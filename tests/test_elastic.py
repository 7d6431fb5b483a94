"""Elastic DiLoCo: mid-run worker join, heterogeneous per-worker H,
straggler-tolerant outer sync.

The contract matrix: elastic restore works in BOTH directions (widen
2->4 with join replicas seeded from the snapshot, shrink re-pinned at
4->2), a crash at a round boundary with a width change owed resumes
wide, heterogeneous H freezes workers past their budget and weights
the outer merge by realized step share (uniform budgets reduce to the
exact worker mean), the straggler policy demotes/restores
deterministically from per-worker durations, and every decision is an
``elastic`` JSONL record the report/summary/telemetry stack surfaces
(older JSONLs tolerated).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.parallel import (
    Diloco,
    DilocoConfig,
    MeshConfig,
    StreamingConfig,
    StreamingDiloco,
    build_mesh,
)
from nanodiloco_tpu.resilience.faults import FaultPlan, InjectedCrash
from nanodiloco_tpu.training.elastic import (
    SCHEDULE_FILE,
    StragglerPolicy,
    load_schedule,
    resume_budgets,
    save_schedule,
)
from nanodiloco_tpu.training.train_loop import TrainConfig, train

TINY = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=32,
)

SMALL_MODEL = LlamaConfig(
    vocab_size=384, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)


def small_cfg(tmp_path, **kw):
    defaults = dict(
        seed=1337, batch_size=4, per_device_batch_size=2, seq_length=32,
        warmup_steps=2, total_steps=9, inner_steps=3, lr=1e-3, num_workers=2,
        model=SMALL_MODEL, log_dir=str(tmp_path / "runs"), quiet=True,
        measure_comm=False,
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


def read_lines(path):
    return [json.loads(line) for line in open(path)]


def run_jsonl(tmp_path, run_name):
    return str(tmp_path / "runs" / f"{run_name}.jsonl")


def make_round(key, W, H, accum=1, B=2, S=8):
    tokens = jax.random.randint(key, (H, W, accum, B, S), 0, TINY.vocab_size)
    return tokens, jnp.ones_like(tokens)


def one_device_diloco(W, H, **cfg_kw):
    mesh = build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1])
    cfg = DilocoConfig(num_workers=W, inner_steps=H, warmup_steps=2,
                      total_steps=30, lr=1e-3, **cfg_kw)
    return Diloco(TINY, cfg, mesh)


# ---------------------------------------------------------------------------
# heterogeneous per-worker H: freeze + weighted merge math
# ---------------------------------------------------------------------------

def test_hetero_uniform_budgets_match_classic():
    """Equal budgets reduce the weighted merge to the worker mean: the
    hetero program with uniform budgets tracks classic DiLoCo to float
    tolerance (bit-identity is only promised for the config-None path,
    which traces zero masking ops — the smoke gate pins that)."""
    W, H = 2, 3
    classic = one_device_diloco(W, H)
    hetero = one_device_diloco(W, H, inner_steps_per_worker=(H, H))
    sc = classic.init_state(jax.random.key(0))
    sh = hetero.init_state(jax.random.key(0))
    for r in range(2):
        t, m = make_round(jax.random.key(r), W, H)
        sc, lc, _ = classic.round_step(sc, t, m)
        sh, lh, _ = hetero.round_step(sh, t, m)
        np.testing.assert_allclose(np.asarray(lc), np.asarray(lh),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(sc.snapshot), jax.tree.leaves(sh.snapshot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_hetero_worker_freezes_past_budget():
    """A worker past its per-round budget stops updating: params AND
    optimizer state (moments + schedule count) freeze until the sync."""
    W, H = 2, 3
    dl = one_device_diloco(W, H, inner_steps_per_worker=(H, 1))
    state = dl.init_state(jax.random.key(0))
    t, m = make_round(jax.random.key(1), W, H)
    s1, _ = dl.inner_step(state, t[0], m[0])       # step 0: both update
    w1_params_1 = [np.asarray(p)[1].copy() for p in jax.tree.leaves(s1.params)]
    w1_opt_1 = [np.asarray(o)[1].copy()
                for o in jax.tree.leaves(s1.inner_opt_state)]
    w0_params_1 = [np.asarray(p)[0].copy() for p in jax.tree.leaves(s1.params)]
    s2, _ = dl.inner_step(s1, t[1], m[1])          # step 1: worker 1 frozen
    # worker 0 (full budget) keeps updating
    assert any(
        not np.array_equal(before, np.asarray(leaf)[0])
        for before, leaf in zip(w0_params_1, jax.tree.leaves(s2.params))
    )
    for before, leaf in zip(w1_params_1, jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(before, np.asarray(leaf)[1])
    for before, leaf in zip(w1_opt_1, jax.tree.leaves(s2.inner_opt_state)):
        np.testing.assert_array_equal(before, np.asarray(leaf)[1])


def test_hetero_weighted_merge_is_realized_share():
    """The outer pseudo-gradient is sum_w(H_w * delta_w) / sum_w(H_w):
    verified against a hand computation from the pre-sync replicas."""
    W, H = 2, 4
    budgets = (4, 1)
    dl = one_device_diloco(W, H, inner_steps_per_worker=budgets,
                           outer_momentum=0.0, nesterov=False, outer_lr=1.0)
    state = dl.init_state(jax.random.key(0))
    t, m = make_round(jax.random.key(1), W, H)

    # run the inner scan manually to capture pre-sync replicas
    s = state
    for h in range(H):
        s, _ = dl.inner_step(s, t[h], m[h])
    old_snap = jax.tree.map(np.asarray, s.snapshot)
    params_w = jax.tree.map(np.asarray, s.params)
    # expected new snapshot under plain SGD(lr=1, no momentum):
    # snapshot - pg where pg = sum(H_w * (snap - p_w)) / sum(H_w)
    wsum = float(sum(budgets))

    def expected(snap, pw):
        pg = sum(b * (snap - pw[w]) for w, b in enumerate(budgets)) / wsum
        return snap - pg

    synced = dl.outer_step(s)
    for snap_leaf, pw_leaf, new_leaf in zip(
        jax.tree.leaves(old_snap), jax.tree.leaves(params_w),
        jax.tree.leaves(synced.snapshot),
    ):
        np.testing.assert_allclose(
            expected(snap_leaf, pw_leaf), np.asarray(new_leaf),
            rtol=2e-5, atol=2e-6,
        )


def test_hetero_budget_validation_and_retarget():
    W, H = 2, 3
    with pytest.raises(ValueError, match="entries but"):
        one_device_diloco(W, H, inner_steps_per_worker=(3,))
    with pytest.raises(ValueError, match=r"\[1, inner_steps"):
        one_device_diloco(W, H, inner_steps_per_worker=(3, 0))
    with pytest.raises(ValueError, match="outer_wire_collective"):
        one_device_diloco(W, H, inner_steps_per_worker=(3, 3),
                          outer_comm_dtype="int8",
                          outer_wire_collective=True)
    dl = one_device_diloco(W, H, inner_steps_per_worker=(3, 3))
    with pytest.raises(ValueError, match="one entry per worker"):
        dl.set_inner_budget([1])
    with pytest.raises(ValueError, match="must be in"):
        dl.set_inner_budget([0, 3])
    dl.set_inner_budget([2, 3])
    assert dl.inner_budget == (2, 3)
    classic = one_device_diloco(W, H)
    assert classic.inner_budget is None
    with pytest.raises(RuntimeError, match="not enabled"):
        classic.set_inner_budget([3, 3])


def test_hetero_rejected_under_streaming():
    mesh = build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="classic-DiLoCo-only"):
        StreamingDiloco(
            TINY,
            DilocoConfig(num_workers=2, inner_steps=4, warmup_steps=2,
                         total_steps=8, lr=1e-3,
                         inner_steps_per_worker=(4, 2)),
            mesh, StreamingConfig(num_fragments=2, delay=1),
        )


def test_hetero_async_boundary_weights_merge():
    """The async launch weights each worker's delta by realized steps
    too — delay-0 async with unequal budgets matches the synchronous
    weighted outer step."""
    W, H = 2, 3
    budgets = (3, 1)
    sync_dl = one_device_diloco(W, H, inner_steps_per_worker=budgets)
    async_dl = one_device_diloco(W, H, inner_steps_per_worker=budgets,
                                 async_outer=True, outer_delay=0)
    ss = sync_dl.init_state(jax.random.key(0))
    sa = async_dl.init_state(jax.random.key(0))
    t, m = make_round(jax.random.key(1), W, H)
    for h in range(H):
        ss, _ = sync_dl.inner_step(ss, t[h], m[h])
        sa, _ = async_dl.inner_step(sa, t[h], m[h])
    ss = sync_dl.outer_step(ss)
    sa, _aux = async_dl.async_boundary(sa)
    for a, b in zip(jax.tree.leaves(ss.snapshot), jax.tree.leaves(sa.snapshot)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_fused_boundary_weights_previous_rounds_budgets():
    """A straggler retarget between rounds must not change the weights
    of the ALREADY-RUN round's deferred boundary: the fused async
    program launches round N's merge at the top of round N+1's program,
    after the policy may have retargeted — it must still weight round
    N's delta with the budgets round N ran under. Pinned against the
    stepwise sequence, whose boundary launches before the retarget."""
    W, H = 2, 2
    kw = dict(inner_steps_per_worker=(2, 1), async_outer=True,
              outer_delay=1)
    fused = one_device_diloco(W, H, **kw)
    stepw = one_device_diloco(W, H, **kw)
    t1, m1 = make_round(jax.random.key(1), W, H)
    t2, m2 = make_round(jax.random.key(2), W, H)

    # stepwise reference: scan1 @ (2,1); boundary1 (weights (2,1));
    # retarget to (2,2); scan2 @ (2,2); flush (weights (2,2))
    ss = stepw.init_state(jax.random.key(0))
    for h in range(H):
        ss, _ = stepw.inner_step(ss, t1[h], m1[h])
    ss, _ = stepw.async_boundary(ss)
    stepw.set_inner_budget([2, 2])
    for h in range(H):
        ss, _ = stepw.inner_step(ss, t2[h], m2[h])
    ss, _ = stepw.async_flush(ss)

    # fused: scan1 @ (2,1); retarget; [boundary1 + scan2] — the fused
    # boundary must weight (2,1) even though the current budget is
    # (2,2); then the flush (this round's own budgets)
    fs = fused.init_state(jax.random.key(0))
    fs, _, _ = fused.inner_round_step(fs, t1, m1)
    fused.set_inner_budget([2, 2])
    fs, _, _aux = fused.async_round_step(fs, t2, m2)
    fs, _ = fused.async_flush(fs)

    for a, b in zip(jax.tree.leaves(ss.snapshot),
                    jax.tree.leaves(fs.snapshot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-7)


# ---------------------------------------------------------------------------
# straggler policy (pure control logic — deterministic)
# ---------------------------------------------------------------------------

def test_straggler_policy_demotes_and_restores():
    p = StragglerPolicy(inner_steps=8, num_workers=4, factor=2.0)
    # worker 2 is 4x slower per step than the rest
    d = p.observe([1.0, 1.0, 4.0, 1.0])
    assert [x["elastic"] for x in d] == ["straggler_demote"]
    assert d[0]["worker"] == 2 and d[0]["h_from"] == 8
    assert d[0]["h_to"] == 2  # int(8 * (1/8) / (4/8)) = 2
    assert p.budgets == [8, 8, 2, 8] and p.demotions_total == 1
    # still 4x slower per step while demoted (its 2-step round takes as
    # long as the fleet's 8-step rounds): stays demoted at the same
    # proportional target — no new decision, no flapping
    d = p.observe([1.0, 1.0, 1.0, 1.0])
    assert d == [] and p.budgets == [8, 8, 2, 8]
    # recovered: per-step time back in line -> full restore
    d = p.observe([1.0, 1.0, 0.25, 1.0])
    assert [x["elastic"] for x in d] == ["straggler_restore"]
    assert d[0]["h_to"] == 8 and p.budgets == [8, 8, 8, 8]
    assert p.restores_total == 1


def test_straggler_policy_leave_one_out_median_at_w2():
    """At W=2 a plain median is the straggler-contaminated mean; the
    leave-one-out reference catches a 3x straggler factor 2 would miss."""
    p = StragglerPolicy(inner_steps=4, num_workers=2, factor=2.0)
    d = p.observe([1.0, 3.0])
    assert [x["elastic"] for x in d] == ["straggler_demote"]
    assert d[0]["worker"] == 1 and d[0]["h_to"] == 1


def test_straggler_policy_floor_and_validation():
    with pytest.raises(ValueError, match="factor must be > 1"):
        StragglerPolicy(4, 2, 1.0)
    with pytest.raises(ValueError, match="min_steps"):
        StragglerPolicy(4, 2, 2.0, min_steps=5)
    p = StragglerPolicy(4, 2, 2.0, min_steps=2)
    d = p.observe([0.1, 100.0])
    assert d[0]["h_to"] == 2  # floored, never 1
    # single worker: no fleet to straggle behind
    solo = StragglerPolicy(4, 1, 2.0)
    assert solo.observe([5.0]) == []


# ---------------------------------------------------------------------------
# H-schedule sidecar (width- and schedule-carrying checkpoints)
# ---------------------------------------------------------------------------

def test_schedule_sidecar_roundtrip_and_width_reset(tmp_path):
    d = str(tmp_path)
    save_schedule(d, step=12, num_workers=2, budgets=[3, 1],
                  demotions_total=2)
    doc = load_schedule(d)
    assert doc["inner_steps_per_worker"] == [3, 1]
    # same width: schedule restored exactly
    budgets, demotions, reset = resume_budgets(d, 2, 3, [3, 3])
    assert budgets == [3, 1] and demotions == 2 and not reset
    # width changed: uniform reset, flagged for the elastic record
    budgets, demotions, reset = resume_budgets(d, 4, 3, [3, 3, 3, 3])
    assert budgets == [3, 3, 3, 3] and demotions == 0 and reset
    # no sidecar / torn sidecar: configured schedule, no reset flag
    assert resume_budgets(str(tmp_path / "nope"), 2, 3, [3, 3]) == \
        ([3, 3], 0, False)
    (tmp_path / "torn").mkdir()
    (tmp_path / "torn" / SCHEDULE_FILE).write_text("{nope")
    assert resume_budgets(str(tmp_path / "torn"), 2, 3, [3, 3]) == \
        ([3, 3], 0, False)


# ---------------------------------------------------------------------------
# elastic restore, BOTH directions (widen 2->4 and shrink 4->2)
# ---------------------------------------------------------------------------

def test_elastic_restore_widens_2_to_4(tmp_path):
    """Mid-run worker JOIN: a W=2 checkpoint restores into a W=4 run —
    every join replica is seeded from the synchronized snapshot (the
    same broadcast discipline as init), drift metrics are finite on the
    first post-join round, and training completes at the new width."""
    from nanodiloco_tpu.training.checkpoint import CheckpointManager

    ckpt_dir = str(tmp_path / "ckpt")
    train(small_cfg(tmp_path / "a", num_workers=2, total_steps=3,
                    checkpoint_dir=ckpt_dir))
    mngr = CheckpointManager(ckpt_dir)
    assert mngr.saved_worker_count() == 2
    saved_snap = mngr.restore_raw(only={"snapshot"})["snapshot"]
    mngr.close()

    dl = Diloco(SMALL_MODEL, DilocoConfig(
        num_workers=4, inner_steps=3, warmup_steps=2, total_steps=6, lr=1e-3,
        grad_accum=2, dynamics_metrics=True,
    ), build_mesh(MeshConfig(diloco=4)))
    fresh = dl.init_state(jax.random.key(7))
    mngr = CheckpointManager(ckpt_dir)
    state = mngr.restore_elastic(fresh)
    mngr.close()
    assert int(state.inner_step_count) == 3
    for a, b in zip(jax.tree.leaves(state.snapshot),
                    jax.tree.leaves(saved_snap)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # all FOUR replicas (two joins included) == the snapshot
    for w in range(4):
        worker = jax.tree.map(lambda p: np.asarray(p[w]), state.params)
        for a, b in zip(jax.tree.leaves(worker),
                        jax.tree.leaves(state.snapshot)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # first post-join round: drift metrics finite (the join replicas
    # started from the snapshot, so drift grows from zero, not NaN)
    key = jax.random.key(3)
    t = jax.random.randint(key, (3, 4, 2, 2, 32), 0, SMALL_MODEL.vocab_size)
    state, losses, _eff, dyn = dl.round_step(state, t, jnp.ones_like(t))
    assert np.isfinite(np.asarray(losses)).all()
    assert np.isfinite(float(dyn["drift_max"]))
    assert np.isfinite(np.asarray(dyn["pg_norm"])).all()
    assert len(np.asarray(dyn["pg_norm"])) == 4

    # end-to-end: the W=4 run picks the W=2 checkpoint up and finishes
    summary = train(small_cfg(tmp_path / "b", num_workers=4, total_steps=6,
                              checkpoint_dir=ckpt_dir, run_name="widen"))
    assert np.isfinite(summary["final_loss"])
    lines = read_lines(run_jsonl(tmp_path / "b", "widen"))
    resume = [l for l in lines if "resume" in l][0]
    assert resume["elastic"] is True
    el = [l for l in lines if l.get("elastic") == "resize_widen"]
    assert el and el[0]["workers_from"] == 2 and el[0]["workers_to"] == 4
    # first post-join sync carries finite drift + 4 active workers
    sync = [l for l in lines if l.get("outer_synced")][0]
    assert sync.get("workers_active") == 4
    assert np.isfinite(sync["drift_max"])


def test_elastic_restore_shrink_repinned_4_to_2(tmp_path):
    """The existing shrink path, re-pinned in the elastic matrix: a W=4
    checkpoint resumes at W=2 with the shrink logged as an elastic
    record."""
    ckpt_dir = str(tmp_path / "ckpt")
    train(small_cfg(tmp_path / "a", num_workers=4, total_steps=3,
                    checkpoint_dir=ckpt_dir))
    summary = train(small_cfg(tmp_path / "b", num_workers=2, total_steps=6,
                              checkpoint_dir=ckpt_dir, run_name="shrink"))
    assert np.isfinite(summary["final_loss"])
    lines = read_lines(run_jsonl(tmp_path / "b", "shrink"))
    el = [l for l in lines if l.get("elastic") == "resize_shrink"]
    assert el and el[0]["workers_from"] == 4 and el[0]["workers_to"] == 2


def test_async_elastic_widen_preserves_pending_fifo(tmp_path):
    """Async widen 2->4: the pending merge FIFO (global, unstacked)
    restores exactly and keeps its delay-uniform shape; the two join
    replicas re-broadcast from the snapshot."""
    from nanodiloco_tpu.training.checkpoint import CheckpointManager

    H = 2
    mesh = build_mesh(MeshConfig(diloco=2))
    a = Diloco(TINY, DilocoConfig(num_workers=2, inner_steps=H,
                                  warmup_steps=2, total_steps=20, lr=1e-3,
                                  async_outer=True, outer_delay=1), mesh)
    state = a.init_state(jax.random.key(0))
    for t_step in range(1, 2 * H + 1):
        tok = jax.random.randint(jax.random.key(t_step), (2, 1, 2, 8), 0,
                                 TINY.vocab_size)
        state, _ = a.inner_step(state, tok, jnp.ones_like(tok))
        if t_step % H == 0:
            state, _ = a.async_boundary(state)
    ck = CheckpointManager(str(tmp_path / "ck"))
    ck.save(2 * H, state)
    ck.wait()

    mesh4 = build_mesh(MeshConfig(diloco=4))
    a4 = Diloco(TINY, DilocoConfig(num_workers=4, inner_steps=H,
                                   warmup_steps=2, total_steps=20, lr=1e-3,
                                   async_outer=True, outer_delay=1), mesh4)
    fresh = a4.init_state(jax.random.key(7))
    ck4 = CheckpointManager(str(tmp_path / "ck"))
    restored = ck4.restore_elastic(fresh)
    ck.close()
    ck4.close()
    assert len(restored.pending) == len(state.pending) == 1
    for x, y in zip(jax.tree.leaves(restored.pending),
                    jax.tree.leaves(state.pending)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(restored.launched_round) == 2
    for leaf, snap in zip(jax.tree.leaves(restored.params),
                          jax.tree.leaves(restored.snapshot)):
        assert np.asarray(leaf).shape[0] == 4
        for w in range(4):
            np.testing.assert_array_equal(
                np.asarray(leaf)[w], np.asarray(snap)
            )


def test_crash_at_boundary_with_width_change_owed(tmp_path):
    """The crash-at-boundary matrix: a raise-mode crash lands exactly at
    a round boundary with a width change owed; the relaunch at the new
    width (both directions) resumes from the boundary checkpoint and
    completes."""
    for tag, w_from, w_to in (("widen", 2, 4), ("shrink", 4, 2)):
        base = tmp_path / tag
        ckpt_dir = str(base / "ckpt")
        plan = str(base / "plan.json")
        os.makedirs(base, exist_ok=True)
        with open(plan, "w") as f:
            json.dump({"faults": [
                {"kind": "crash", "step": 6, "raise": True},
            ]}, f)
        with pytest.raises(InjectedCrash):
            train(small_cfg(base, num_workers=w_from, total_steps=9,
                            checkpoint_dir=ckpt_dir, fault_plan=plan,
                            run_name=f"{tag}-crashed"))
        from nanodiloco_tpu.resilience.supervisor import latest_checkpoint_step
        step = latest_checkpoint_step(ckpt_dir)
        assert step is not None and step % 3 == 0 and step >= 3
        summary = train(small_cfg(base, num_workers=w_to, total_steps=9,
                                  checkpoint_dir=ckpt_dir,
                                  run_name=f"{tag}-resumed"))
        assert np.isfinite(summary["final_loss"])
        lines = read_lines(run_jsonl(base, f"{tag}-resumed"))
        resume = [l for l in lines if "resume" in l][0]
        assert resume["resume"] == step and resume["elastic"] is True
        el = [l for l in lines if l.get("elastic") == f"resize_{tag}"]
        assert el and el[0]["workers_from"] == w_from
        assert el[0]["workers_to"] == w_to


# ---------------------------------------------------------------------------
# resize + straggler faults through the real train loop
# ---------------------------------------------------------------------------

def test_resize_fault_writes_target_and_preempts(tmp_path, monkeypatch):
    """The resize fault writes the supervisor's control file (via the
    exported env) and preempt-exits at the next round boundary — the
    full child half of the control-plane path."""
    from nanodiloco_tpu.resilience.supervisor import (
        PREEMPT_EXIT_CODE,
        WORKERS_TARGET_ENV,
        latest_checkpoint_step,
    )

    target = str(tmp_path / "workers.target")
    monkeypatch.setenv(WORKERS_TARGET_ENV, target)
    plan = str(tmp_path / "plan.json")
    with open(plan, "w") as f:
        json.dump({"faults": [{"kind": "resize", "step": 4, "workers": 4}]}, f)
    ck = str(tmp_path / "ckpt")
    with pytest.raises(SystemExit) as e:
        train(small_cfg(tmp_path, total_steps=9, fault_plan=plan,
                        checkpoint_dir=ck, run_name="resize"))
    assert e.value.code == PREEMPT_EXIT_CODE
    assert open(target).read().strip() == "4"
    step = latest_checkpoint_step(ck)
    assert step is not None and step % 3 == 0
    lines = read_lines(run_jsonl(tmp_path, "resize"))
    assert [l for l in lines if l.get("fault") == "resize"]
    pre = [l for l in lines if l.get("preempt")]
    assert pre and pre[0]["preempt"] == "resize"


def test_straggler_fault_demotes_then_restores_and_books_wait(tmp_path):
    """The injected straggler through the real fused loop: the measured
    wait lands as t_straggler + goodput straggler_wait (never inflating
    outer_sync), the policy demotes the straggler's H for the next
    round (a weighted merge with unequal realized H), and restores it
    when the fault passes."""
    plan = str(tmp_path / "plan.json")
    with open(plan, "w") as f:
        json.dump({"faults": [{"kind": "straggler", "step": 10, "worker": 1,
                               "seconds": 1.0, "rounds": 1}]}, f)
    summary = train(small_cfg(
        tmp_path, total_steps=18, fault_plan=plan, straggler_factor=2.0,
        checkpoint_dir=str(tmp_path / "ckpt"), run_name="straggle",
    ))
    assert summary["straggler_demotions"] == 1
    assert summary["inner_steps_per_worker"] == [3, 3]  # restored by the end
    lines = read_lines(run_jsonl(tmp_path, "straggle"))
    el = [l for l in lines if l.get("elastic")]
    kinds = [l["elastic"] for l in el]
    assert kinds == ["straggler_demote", "straggler_restore"]
    demote = el[0]
    assert demote["worker"] == 1 and demote["h_to"] < demote["h_from"]
    assert isinstance(demote["t_unix"], float)
    # the straggler fault fired through the real hook and is in the
    # fault timeline
    assert [l for l in lines if l.get("fault") == "straggler"]
    # the round after the demotion ran a weighted merge with unequal H
    syncs = [l for l in lines if l.get("outer_synced")]
    realized = [tuple(l["inner_steps_realized"]) for l in syncs]
    assert any(len(set(r)) > 1 for r in realized)
    # straggler wait attributed in the budget and the goodput ledger,
    # not silently inflating the sync share
    straggled = [l for l in syncs if l.get("t_straggler")]
    assert straggled and straggled[0]["t_straggler"] >= 1.0
    gp = [l for l in lines if l.get("goodput")][-1]["goodput"]
    assert gp["straggler_wait_s"] >= 1.0
    # schedule sidecar carries the final (restored) schedule
    sched = load_schedule(str(tmp_path / "ckpt"))
    assert sched["inner_steps_per_worker"] == [3, 3]


def test_hetero_schedule_resumes_at_same_width(tmp_path):
    """A demoted H schedule survives a same-width restart via the
    sidecar (the straggler policy picks up where it left off); a width
    change resets it with an h_schedule_reset elastic record."""
    ck = str(tmp_path / "ckpt")
    train(small_cfg(tmp_path / "a", total_steps=3, checkpoint_dir=ck,
                    inner_steps_per_worker=(3, 2), run_name="first"))
    # overwrite the sidecar as the straggler policy would mid-run
    save_schedule(ck, step=3, num_workers=2, budgets=[3, 1],
                  demotions_total=1)
    summary = train(small_cfg(tmp_path / "b", total_steps=6,
                              checkpoint_dir=ck,
                              inner_steps_per_worker=(3, 2),
                              run_name="second"))
    # resumed the SIDEcar schedule [3, 1], not the configured (3, 2)
    assert summary["inner_steps_per_worker"] == [3, 1]
    lines = read_lines(run_jsonl(tmp_path / "b", "second"))
    syncs = [l for l in lines if l.get("outer_synced")]
    assert tuple(syncs[0]["inner_steps_realized"]) == (3, 1)
    # width change: reset to uniform, logged
    summary = train(small_cfg(tmp_path / "c", num_workers=4, total_steps=9,
                              checkpoint_dir=ck,
                              straggler_factor=2.0, run_name="wide"))
    assert summary["inner_steps_per_worker"] == [3, 3, 3, 3]
    lines = read_lines(run_jsonl(tmp_path / "c", "wide"))
    assert [l for l in lines if l.get("elastic") == "h_schedule_reset"]


def test_fault_plan_validates_new_kinds(tmp_path):
    with pytest.raises(ValueError, match="integer worker"):
        FaultPlan([{"kind": "straggler", "step": 1}])
    with pytest.raises(ValueError, match="seconds must be > 0"):
        FaultPlan([{"kind": "straggler", "step": 1, "worker": 0,
                    "seconds": 0}])
    with pytest.raises(ValueError, match="rounds must be >= 1"):
        FaultPlan([{"kind": "straggler", "step": 1, "worker": 0,
                    "rounds": 0}])
    with pytest.raises(ValueError, match="workers >= 1"):
        FaultPlan([{"kind": "resize", "step": 1, "workers": 0}])
    # straggler fires once per round for `rounds` rounds, then never
    p = FaultPlan([{"kind": "straggler", "step": 2, "worker": 1,
                    "seconds": 0.5, "rounds": 2}])
    assert p.straggle_due() == {}
    p.advance(2)
    assert p.straggle_due() == {1: 0.5}
    assert p.straggle_due() == {1: 0.5}
    assert p.straggle_due() == {}
    assert [r["kind"] for r in p.drain_fired()] == ["straggler"]
    # worker bound checked against the run's width
    plan = str(tmp_path / "plan.json")
    with open(plan, "w") as f:
        json.dump({"faults": [{"kind": "straggler", "step": 1, "worker": 7,
                               "seconds": 1.0}]}, f)
    with pytest.raises(ValueError, match="only 2 worker"):
        train(small_cfg(tmp_path, fault_plan=plan))


# ---------------------------------------------------------------------------
# report / summarize / telemetry surfacing (older JSONLs tolerated)
# ---------------------------------------------------------------------------

def test_summarize_and_report_surface_elastic_records(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_faults_main
    from nanodiloco_tpu.training.metrics import summarize_run

    path = str(tmp_path / "run.jsonl")
    recs = [
        {"loss": 5.0, "step": 1, "outer_synced": 1, "workers_active": 2,
         "inner_steps_realized": [3, 3]},
        {"elastic": "resize_widen", "workers_from": 2, "workers_to": 4,
         "t_unix": 1.0, "step": 3},
        {"elastic": "straggler_demote", "worker": 1, "h_from": 3, "h_to": 1,
         "t_unix": 2.0, "step": 6},
        {"loss": 4.0, "step": 6, "outer_synced": 1, "workers_active": 4,
         "inner_steps_realized": [3, 1, 3, 3]},
        {"elastic": "straggler_restore", "worker": 1, "h_from": 1, "h_to": 3,
         "t_unix": 3.0, "step": 9},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    s = summarize_run(path)
    assert s["elastic_events"] == 3
    assert s["elastic_kinds"] == {"resize_widen": 1, "straggler_demote": 1,
                                  "straggler_restore": 1}
    assert s["straggler_demotions"] == 1
    assert s["workers_active_last"] == 4
    assert s["workers_active_min"] == 2 and s["workers_active_max"] == 4
    assert s["inner_steps_realized_last"] == [3, 1, 3, 3]
    assert s["hetero_h_rounds"] == 1
    report_faults_main([path, "--json"])
    events = json.loads(capsys.readouterr().out)
    assert [e["event"] for e in events] == ["elastic", "elastic", "elastic"]
    assert events[0]["kind"] == "resize_widen"


def test_report_faults_surfaces_supervisor_scale_events(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_faults_main

    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "launch", "restart": 0,
                            "workers": 2, "t_unix": 1.0}) + "\n")
        f.write(json.dumps({"event": "scale_up", "reason": "control_file",
                            "workers_from": 2, "workers_to": 4,
                            "t_unix": 2.0}) + "\n")
        f.write(json.dumps({"event": "scale_down", "reason": "crash_degrade",
                            "workers_from": 4, "workers_to": 2,
                            "t_unix": 3.0}) + "\n")
    report_faults_main([path, "--json"])
    events = json.loads(capsys.readouterr().out)
    assert [e["event"] for e in events] == ["scale_up", "scale_down"]
    assert events[0]["workers_to"] == 4


def test_summarize_tolerates_pre_elastic_jsonl(tmp_path):
    """Older JSONLs (no elastic/workers_active keys) summarize without
    any of the new keys appearing — the PR-8/9 tolerance pattern."""
    from nanodiloco_tpu.training.metrics import summarize_run

    path = str(tmp_path / "old.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"loss": 5.0, "step": 1, "outer_synced": 1}) + "\n")
    s = summarize_run(path)
    for k in ("elastic_events", "straggler_demotions", "workers_active_last",
              "inner_steps_realized_last", "hetero_h_rounds"):
        assert k not in s


def test_telemetry_elastic_gauges():
    from nanodiloco_tpu.obs.telemetry import TelemetryServer, parse_metrics_text

    srv = TelemetryServer(port=0)
    try:
        srv.observe({"workers_active": 2, "inner_steps_realized": [3, 3],
                     "step": 3})
        srv.observe({"elastic": "straggler_demote", "worker": 1})
        srv.observe({"elastic": "straggler_restore", "worker": 1})
        srv.observe({"workers_active": 4,
                     "inner_steps_realized": [3, 1, 3, 3], "step": 6})
        m = parse_metrics_text(srv.render_metrics())
        assert m["nanodiloco_workers_active"] == 4
        assert m["nanodiloco_straggler_demotions_total"] == 1
        assert m["nanodiloco_elastic_events_total"] == 2
        assert m['nanodiloco_elastic_events_total{kind="straggler_demote"}'] == 1
        assert m['nanodiloco_inner_steps_realized{worker="1"}'] == 1
        assert m['nanodiloco_inner_steps_realized{worker="3"}'] == 3
    finally:
        srv._httpd.server_close()


# ---------------------------------------------------------------------------
# acceptance: supervised scale-up 2->4 + absorbed straggler (real CLI)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_supervised_scale_up_and_straggler_absorbed(tmp_path):
    """The full story in real processes: a supervised 2-worker run whose
    resize fault requests width 4 through the control file (preempt ->
    scale_up -> elastic widen resume), then an injected straggler is
    demoted into a weighted merge and the goodput ledger attributes the
    wait."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ck = str(tmp_path / "ckpt")
    target = str(tmp_path / "workers.target")
    events_jsonl = str(tmp_path / "supervise.jsonl")
    plan = str(tmp_path / "plan.json")
    model_cfg = tmp_path / "model.json"
    model_cfg.write_text(json.dumps({
        "vocab_size": 384, "hidden_size": 32, "intermediate_size": 64,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "max_position_embeddings": 64,
    }))
    with open(plan, "w") as f:
        json.dump({"faults": [
            {"kind": "resize", "step": 4, "workers": 4},
            {"kind": "straggler", "step": 13, "worker": 1,
             "seconds": 2.0, "rounds": 1},
        ]}, f)
    args = [
        "--total-steps", "21", "--inner-steps", "3",
        "--batch-size", "4", "--per-device-batch-size", "2",
        "--seq-length", "32", "--warmup-steps", "2",
        "--llama-config-file", str(model_cfg), "--no-measure-comm",
        "--no-cost-analysis", "--quiet",
        "--num-workers", "2", "--straggler-factor", "2.0",
        "--checkpoint-dir", ck, "--log-dir", str(tmp_path / "runs"),
        "--run-name", "elastic", "--fault-plan", plan,
        # the children are fresh processes: the 2 -> 4 widening needs
        # four devices of their own (on the jax 0.4 line they inherited
        # conftest's XLA_FLAGS fallback, which jax 0.9.0 never sets)
        "--force-cpu-devices", "4",
    ]
    sup = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "supervise",
         "--max-restarts", "3", "--max-workers", "4",
         "--workers-target-file", target,
         "--events-jsonl", events_jsonl, "--", *args],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900,
    )
    assert sup.returncode == 0, sup.stdout[-2000:] + sup.stderr[-2000:]
    sup_events = read_lines(events_jsonl)
    ups = [e for e in sup_events if e.get("event") == "scale_up"]
    assert ups and ups[0]["workers_from"] == 2 and ups[0]["workers_to"] == 4
    lines = read_lines(run_jsonl(tmp_path, "elastic"))
    # join replicas seeded from the snapshot: the elastic resume record
    # plus finite drift on the first post-join sync
    assert [l for l in lines if l.get("elastic") == "resize_widen"]
    post_join_syncs = [l for l in lines
                       if l.get("outer_synced") and l.get("step", 0) > 3
                       and l.get("drift_max") is not None]
    assert post_join_syncs and np.isfinite(post_join_syncs[0]["drift_max"])
    # at least one weighted merge with unequal realized H
    assert [l for l in lines if l.get("elastic") == "straggler_demote"]
    realized = [tuple(l["inner_steps_realized"]) for l in lines
                if l.get("inner_steps_realized")]
    assert any(len(set(r)) > 1 for r in realized)
    # straggler wait attributed in the stitched ledger
    from nanodiloco_tpu.obs.goodput import stitch_goodput_records
    stitched = stitch_goodput_records(lines)
    assert stitched["straggler_wait_s"] >= 2.0
