"""DiLoCo core semantics on an 8-device virtual CPU mesh (SURVEY §4):
identical init (== the reference's init broadcast), zero-comm inner
divergence, outer-step math, and the H=1 sync-DP equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from nanodiloco_tpu.models import LlamaConfig
from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

TINY = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=32,
)


def make_batch(key, cfg, W, accum=1, B=2, S=8):
    tokens = jax.random.randint(key, (W, accum, B, S), 0, cfg.vocab_size)
    return tokens, jnp.ones_like(tokens)


def tree_max_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.fixture(scope="module")
def diloco4():
    mesh = build_mesh(MeshConfig(diloco=4, fsdp=2))
    cfg = DilocoConfig(num_workers=4, inner_steps=2, warmup_steps=2,
                       total_steps=20, lr=1e-3, grad_accum=2)
    return Diloco(TINY, cfg, mesh)


def test_init_workers_identical(diloco4):
    """Replaces the reference's per-param dist.broadcast (ref
    diloco.py:21-22): every worker slice must be bit-identical to the
    snapshot."""
    state = diloco4.init_state(jax.random.key(0))
    for w in range(4):
        worker = jax.tree.map(lambda p: p[w], state.params)
        assert tree_max_diff(worker, state.snapshot) == 0.0


def test_init_state_lays_the_optimizer_states_as_the_params(diloco4):
    """From ``init_state`` on, the inner optimizer's moments take the
    workers' layout (over ``diloco`` and, inside a worker, ``fsdp``) and
    the outer momentum the snapshot's: zeros inherit no sharding, and left
    replicated they would stand whole on every device and make the first
    round compile twice."""
    state = diloco4.init_state(jax.random.key(0))
    (adam,) = [s for s in jax.tree.leaves(
        state.inner_opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    (trace,) = [s.trace for s in jax.tree.leaves(
        state.outer_opt_state, is_leaf=lambda x: hasattr(x, "trace")) if hasattr(s, "trace")]
    for tree, like in ((adam.mu, state.params), (adam.nu, state.params),
                       (trace, state.snapshot)):
        for m, p in zip(jax.tree.leaves(tree), jax.tree.leaves(like), strict=True):
            assert m.sharding.is_equivalent_to(p.sharding, p.ndim), (m.sharding, p.sharding)
    assert any("fsdp" in str(p.sharding.spec) for p in jax.tree.leaves(state.snapshot))


def test_round_hands_the_state_back_as_init_state_laid_it():
    """One worker a device: the second call of the fused round compiles
    nothing (the benchmark's four-chip cell times that executable)."""
    dl = Diloco(TINY, DilocoConfig(num_workers=4, inner_steps=2, warmup_steps=2,
                                   total_steps=20, lr=1e-3, grad_accum=2),
                build_mesh(MeshConfig(diloco=4)))
    state = dl.init_state(jax.random.key(0))
    tokens, mask = make_batch(jax.random.key(1), TINY, W=4, accum=2)
    tokens, mask = jnp.stack([tokens] * 2), jnp.stack([mask] * 2)
    for _ in range(2):
        state, _, _ = dl.round_step(state, tokens, mask)
    assert dl._round_jit._cache_size() == 1


def test_inner_steps_diverge_outer_resyncs(diloco4):
    state = diloco4.init_state(jax.random.key(0))
    tokens, mask = make_batch(jax.random.key(1), TINY, W=4, accum=2)
    state, loss = diloco4.inner_step(state, tokens, mask)
    # lr at step 0 is exactly 0 (torch scheduler semantics) -> step 2 moves
    state, loss = diloco4.inner_step(state, tokens, mask)
    assert loss.shape == (4,)
    assert np.isfinite(np.asarray(loss)).all()
    # different data per worker -> parameters diverge (no hidden syncing)
    w0 = jax.tree.map(lambda p: p[0], state.params)
    w1 = jax.tree.map(lambda p: p[1], state.params)
    assert tree_max_diff(w0, w1) > 0.0
    # copy before outer_step: state buffers are donated to the jitted call
    old_snapshot = jax.tree.map(np.asarray, state.snapshot)
    state2 = diloco4.outer_step(state)
    for w in range(4):
        worker = jax.tree.map(lambda p: p[w], state2.params)
        assert tree_max_diff(worker, state2.snapshot) == 0.0
    # outer step moved the snapshot
    assert tree_max_diff(state2.snapshot, old_snapshot) > 0.0


def test_outer_step_hand_math():
    """First outer step, zero momentum buffer, Nesterov: the torch update
    (ref diloco.py:34-54 + torch SGD) gives
    snapshot' = snapshot - outer_lr * (1 + mu) * delta,
    delta = snapshot - mean_w(params)."""
    mesh = build_mesh(MeshConfig(diloco=2))
    outer_lr, mu = 0.7, 0.9
    cfg = DilocoConfig(num_workers=2, outer_lr=outer_lr, outer_momentum=mu)

    def quad_loss(params, tokens, mask):
        return jnp.sum(params["w"] ** 2), {}

    dl = Diloco(TINY, cfg, mesh, loss_fn=quad_loss)
    # Hand-build a state around a plain dict param tree.
    snapshot = {"w": jnp.asarray([1.0, 2.0])}
    params = {"w": jnp.asarray([[1.2, 2.0], [0.8, 1.6]])}  # mean = [1.0, 1.8]
    from nanodiloco_tpu.parallel.diloco import DilocoState

    state = DilocoState(
        params=params,
        inner_opt_state=dl.inner_tx.init(snapshot),
        snapshot=snapshot,
        outer_opt_state=dl.outer_tx.init(snapshot),
        inner_step_count=jnp.zeros((), jnp.int32),
    )
    new = dl.outer_step(state)
    delta = np.asarray([1.0 - 1.0, 2.0 - 1.8])
    expect = np.asarray([1.0, 2.0]) - outer_lr * (1 + mu) * delta
    np.testing.assert_allclose(np.asarray(new.snapshot["w"]), expect, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.params["w"]), np.stack([expect] * 2), rtol=1e-6)


def test_h1_sgd_equals_sync_dp():
    """DiLoCo with H=1, plain-SGD inner optimizer, outer_lr=1, no momentum
    is exactly synchronous data parallelism:
    mean_w(θ - η g_w) = θ - η mean_w(g_w)  (SURVEY §4's equivalence test)."""
    W, eta = 4, 0.05
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, inner_steps=1, outer_lr=1.0,
                       outer_momentum=0.0, nesterov=False)

    def loss_fn(params, tokens, mask):
        # per-worker quadratic with data-dependent target
        target = jnp.mean(tokens.astype(jnp.float32))
        return jnp.sum((params["w"] - target) ** 2), {}

    dl = Diloco(TINY, cfg, mesh, loss_fn=loss_fn, inner_tx=optax.sgd(eta))
    from nanodiloco_tpu.parallel.diloco import DilocoState

    w0_np = np.asarray([0.5, -0.3, 1.1], np.float32)
    w0 = jnp.asarray(w0_np)
    params = jnp.broadcast_to(w0[None], (W, 3))
    state = DilocoState(
        params={"w": params},
        inner_opt_state=jax.vmap(dl.inner_tx.init)({"w": params}),
        snapshot={"w": w0},
        outer_opt_state=dl.outer_tx.init({"w": w0}),
        inner_step_count=jnp.zeros((), jnp.int32),
    )
    tokens = jax.random.randint(jax.random.key(3), (W, 1, 2, 4), 0, 64)
    tokens_np = np.asarray(tokens)
    mask = jnp.ones_like(tokens)
    state, _ = dl.inner_step(state, tokens, mask)
    state = dl.outer_step(state)

    # sync-DP reference: average the per-worker gradients, one SGD step
    grads = [2.0 * (w0_np - tokens_np[w].astype(np.float32).mean()) for w in range(W)]
    expect = w0_np - eta * np.mean(grads, axis=0)
    np.testing.assert_allclose(np.asarray(state.snapshot["w"]), expect, rtol=1e-5, atol=1e-6)


def test_outer_comm_dtype_bf16():
    """outer_comm_dtype='bfloat16' quantizes each worker's pseudo-gradient
    delta to bf16 before the cross-worker mean (which accumulates in f32):
    the outer update must match hand-math computed on the bf16-rounded
    delta (proving the cast happens on the wire side of the mean), and a
    value below bf16 resolution must vanish."""
    mesh = build_mesh(MeshConfig(diloco=2))
    outer_lr, mu = 0.7, 0.9
    cfg = DilocoConfig(num_workers=2, outer_lr=outer_lr, outer_momentum=mu,
                       outer_comm_dtype="bfloat16")
    dl = Diloco(TINY, cfg, mesh, loss_fn=lambda p, t, m: (jnp.sum(p["w"] ** 2), {}))
    from nanodiloco_tpu.parallel.diloco import DilocoState

    # per-worker deltas: [1 + 2^-10, 2^-10] and [1 - 2^-10, -2^-10]
    # bf16 (8 mantissa bits) rounds 1 ± 2^-10 to exactly 1.0, keeps ±2^-10
    eps = 2.0 ** -10
    snapshot = {"w": jnp.asarray([2.0, 1.0])}
    params = {"w": jnp.asarray([[1.0 - eps, 1.0 - eps], [1.0 + eps, 1.0 + eps]])}
    state = DilocoState(
        params=params,
        inner_opt_state=dl.inner_tx.init(snapshot),
        snapshot=snapshot,
        outer_opt_state=dl.outer_tx.init(snapshot),
        inner_step_count=jnp.zeros((), jnp.int32),
    )
    new = dl.outer_step(state)
    # bf16(delta_w) = [1.0, 1.0] for both workers in dim 0 -> mean 1.0;
    # dim 1: bf16(±eps) = ±eps -> mean 0.0 exactly
    delta = np.asarray([1.0, 0.0])
    expect = np.asarray([2.0, 1.0]) - outer_lr * (1 + mu) * delta
    np.testing.assert_allclose(np.asarray(new.snapshot["w"]), expect, rtol=1e-6)


def test_mesh_sharded_matches_single_device():
    """The same training round on a (diloco=4, fsdp=2) mesh and on a
    1-device mesh must agree — sharding is a layout choice, not math."""
    cfg = DilocoConfig(num_workers=4, inner_steps=2, warmup_steps=1, total_steps=10,
                       lr=1e-3, grad_accum=2)
    tokens, mask = make_batch(jax.random.key(7), TINY, W=4, accum=2)

    results = []
    with jax.default_matmul_precision("highest"):
        for mc in [MeshConfig(diloco=4, fsdp=2), MeshConfig()]:
            mesh = build_mesh(mc)
            dl = Diloco(TINY, cfg, mesh)
            state = dl.init_state(jax.random.key(0))
            for _ in range(2):
                state, loss = dl.inner_step(state, tokens, mask)
            state = dl.outer_step(state)
            results.append((jax.tree.map(np.asarray, state.snapshot), np.asarray(loss)))
    (snap_a, loss_a), (snap_b, loss_b) = results
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    assert tree_max_diff(snap_a, snap_b) < 1e-4


def test_fused_round_matches_stepwise():
    """round_step (H inner steps + outer sync in ONE executable) must equal
    the stepwise inner_step x H + outer_step sequence."""
    W, H = 4, 3
    cfg = DilocoConfig(num_workers=W, inner_steps=H, warmup_steps=2,
                       total_steps=20, lr=1e-3, grad_accum=2)
    mesh = build_mesh(MeshConfig(diloco=W))
    batches = [make_batch(jax.random.key(30 + t), TINY, W=W, accum=2) for t in range(H)]

    dl = Diloco(TINY, cfg, mesh)
    s1 = dl.init_state(jax.random.key(0))
    step_losses = []
    for tok, m in batches:
        s1, loss = dl.inner_step(s1, tok, m)
        step_losses.append(np.asarray(loss))
    s1 = dl.outer_step(s1)

    s2 = dl.init_state(jax.random.key(0))
    s2, losses = dl.run_round(s2, iter(batches))
    np.testing.assert_allclose(np.asarray(losses), np.stack(step_losses), rtol=1e-6)
    assert tree_max_diff(s1.snapshot, s2.snapshot) < 1e-7
    assert tree_max_diff(s1.params, s2.params) < 1e-7


def test_grad_accum_scaling():
    """accum=4 with the same microbatch repeated must equal accum=1 with
    that microbatch (correct mean scaling — fixing ref main.py:110-111)."""
    mesh = build_mesh(MeshConfig(diloco=1))
    tok = jax.random.randint(jax.random.key(5), (1, 1, 2, 8), 0, TINY.vocab_size)
    tok4 = jnp.tile(tok, (1, 4, 1, 1))

    outs = []
    for tokens in [tok, tok4]:
        cfg = DilocoConfig(num_workers=1, lr=1e-3, warmup_steps=1, total_steps=10,
                           grad_accum=tokens.shape[1])
        dl = Diloco(TINY, cfg, mesh)
        state = dl.init_state(jax.random.key(0))
        state, loss = dl.inner_step(state, tokens, jnp.ones_like(tokens))
        outs.append(jax.tree.map(np.asarray, state.params))
    from nanodiloco_tpu.parallel.diloco import DilocoState  # noqa: F401

    assert tree_max_diff(outs[0], outs[1]) < 1e-6


def test_worker_mask_outer_sync():
    """Worker-dropout-tolerant outer sync (beyond the reference, whose
    dead rank kills the NCCL all-reduce, SURVEY §5): masking worker k out
    must equal the plain outer step on a state whose worker-k replica is
    overwritten with the survivors' mean (so the W-mean degenerates to
    the W-1 survivor mean); an all-ones mask must match the unmasked
    path; an all-zero mask must yield a zero pseudo-gradient (cold
    momentum -> snapshot unchanged), not NaN."""
    W = 4
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, inner_steps=2, warmup_steps=2,
                       total_steps=20, lr=1e-3)
    dl = Diloco(TINY, cfg, mesh)
    state = dl.init_state(jax.random.key(0))
    tokens, lmask = make_batch(jax.random.key(1), TINY, W=W)
    state, _ = dl.inner_step(state, tokens, lmask)
    state, _ = dl.inner_step(state, tokens, lmask)  # lr>0: workers diverged

    base = jax.tree.map(np.asarray, state)  # host master (outer_step donates)
    mk = lambda: jax.tree.map(jnp.asarray, base)

    masked = dl.outer_step(mk(), jnp.asarray([1.0, 1.0, 0.0, 1.0]))
    surg = mk()
    surv = jnp.asarray([0, 1, 3])
    params = jax.tree.map(
        lambda p: p.at[2].set(jnp.mean(p[surv], axis=0)), surg.params
    )
    ref = dl.outer_step(surg.replace(params=params))
    assert tree_max_diff(masked.snapshot, ref.snapshot) < 1e-6

    all_on = dl.outer_step(mk(), jnp.ones(W))
    plain = dl.outer_step(mk())
    assert tree_max_diff(all_on.snapshot, plain.snapshot) < 1e-6

    dead = dl.outer_step(mk(), jnp.zeros(W))
    assert tree_max_diff(dead.snapshot, base.snapshot) == 0.0
    for leaf in jax.tree.leaves(dead.params):
        assert np.isfinite(np.asarray(leaf)).all()

    # a NaN replica (divergence IS a prime reason to mask a worker out)
    # must not poison the survivor mean: masked NaN == masked finite run
    poisoned = mk()
    poisoned = poisoned.replace(params=jax.tree.map(
        lambda p: p.at[2].set(jnp.nan), poisoned.params
    ))
    nan_masked = dl.outer_step(poisoned, jnp.asarray([1.0, 1.0, 0.0, 1.0]))
    assert tree_max_diff(nan_masked.snapshot, masked.snapshot) == 0.0


def test_quarantine_nonfinite_self_heals():
    """quarantine_nonfinite: a worker whose replica blows up (non-finite
    loss in the round) is excluded from the outer mean and reset to the
    healthy survivors' snapshot — the fused round must end fully finite
    and equal the same round with the mask applied by hand."""
    W, H = 4, 2
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, inner_steps=H, warmup_steps=0,
                       total_steps=20, lr=1e-3, quarantine_nonfinite=True)
    dl = Diloco(TINY, cfg, mesh)
    state = dl.init_state(jax.random.key(0))
    # poison worker 2's replica: inf params -> non-finite loss every step
    state = state.replace(params=jax.tree.map(
        lambda p: p.at[2].set(jnp.inf), state.params
    ))
    batches = [make_batch(jax.random.key(40 + t), TINY, W=W) for t in range(H)]
    state, losses = dl.run_round(state, iter(batches))
    assert not bool(jnp.isfinite(losses[:, 2]).all())   # it DID blow up
    for leaf in jax.tree.leaves(state.params) + jax.tree.leaves(state.snapshot):
        assert np.isfinite(np.asarray(leaf)).all()      # and was healed
    for w in range(W):
        worker = jax.tree.map(lambda p: p[w], state.params)
        assert tree_max_diff(worker, state.snapshot) == 0.0
    # the heal must STICK: a second round must stay finite for every
    # worker — in particular the quarantined one, whose Adam moments
    # would stay NaN forever if the sync reset only its params (the
    # permanent W-1 degradation the round-4 review caught)
    batches2 = [make_batch(jax.random.key(50 + t), TINY, W=W) for t in range(H)]
    state, losses2 = dl.run_round(state, iter(batches2))
    assert bool(jnp.isfinite(losses2).all()), losses2
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_quarantine_catches_final_step_blowup():
    """Per-step losses are computed from PRE-update params, so a spike on
    the round's last inner update leaves every logged loss finite while
    the replica is already NaN. The exact replica-finiteness check inside
    _outer_step must quarantine it anyway (loss-only masking has this
    one-step hole)."""
    W = 4
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, inner_steps=2, warmup_steps=0,
                       total_steps=20, lr=1e-3, quarantine_nonfinite=True)
    dl = Diloco(TINY, cfg, mesh)
    state = dl.init_state(jax.random.key(0))
    tokens, lmask = make_batch(jax.random.key(1), TINY, W=W)
    state, _ = dl.inner_step(state, tokens, lmask)
    # simulate the last-update blow-up: poison AFTER the inner steps,
    # then sync with an all-finite loss mask (what the loop would pass)
    state = state.replace(params=jax.tree.map(
        lambda p: p.at[1].set(jnp.nan), state.params
    ))
    healthy = jax.tree.map(np.asarray, state.snapshot)
    state = dl.outer_step(state, jnp.ones(W, bool))
    for leaf in jax.tree.leaves(state.snapshot) + jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    del healthy


def test_quarantine_off_lets_nan_spread():
    """Control: without the knob, the reference semantics hold — the
    poisoned replica all-reduces into the global snapshot."""
    W, H = 4, 2
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, inner_steps=H, warmup_steps=0,
                       total_steps=20, lr=1e-3)
    dl = Diloco(TINY, cfg, mesh)
    state = dl.init_state(jax.random.key(0))
    state = state.replace(params=jax.tree.map(
        lambda p: p.at[2].set(jnp.inf), state.params
    ))
    batches = [make_batch(jax.random.key(40 + t), TINY, W=W) for t in range(H)]
    state, _ = dl.run_round(state, iter(batches))
    bad = any(
        not np.isfinite(np.asarray(l)).all()
        for l in jax.tree.leaves(state.snapshot)
    )
    assert bad


def test_quarantine_rejected_for_streaming():
    from nanodiloco_tpu.parallel import StreamingConfig, StreamingDiloco

    mesh = build_mesh(MeshConfig(diloco=2))
    cfg = DilocoConfig(num_workers=2, inner_steps=4, quarantine_nonfinite=True)
    with pytest.raises(ValueError, match="classic-DiLoCo-only"):
        StreamingDiloco(TINY, cfg, mesh, StreamingConfig(num_fragments=2, delay=1))


def test_outer_comm_dtype_int8():
    """int8 wire: symmetric per-(worker, tensor) absmax quantization —
    the outer update must match hand-math on the quantized deltas, and
    sub-resolution values must round away (the low-bit outer sync of
    arXiv:2501.18512; pseudo-gradients tolerate coarse wires)."""
    mesh = build_mesh(MeshConfig(diloco=2))
    outer_lr, mu = 0.7, 0.9
    cfg = DilocoConfig(num_workers=2, outer_lr=outer_lr, outer_momentum=mu,
                       outer_comm_dtype="int8")
    dl = Diloco(TINY, cfg, mesh, loss_fn=lambda p, t, m: (jnp.sum(p["w"] ** 2), {}))
    from nanodiloco_tpu.parallel.diloco import DilocoState

    # worker deltas: [1.27, 0.004] and [1.27, 0.004]; absmax 1.27 ->
    # scale 0.01 exactly, so dim0 -> q=127 -> 1.27 exact, dim1 ->
    # round(0.4)=0 -> vanishes
    snapshot = {"w": jnp.asarray([2.27, 1.004])}
    params = {"w": jnp.asarray([[1.0, 1.0], [1.0, 1.0]])}
    state = DilocoState(
        params=params,
        inner_opt_state=dl.inner_tx.init(snapshot),
        snapshot=snapshot,
        outer_opt_state=dl.outer_tx.init(snapshot),
        inner_step_count=jnp.zeros((), jnp.int32),
    )
    new = dl.outer_step(state)
    delta = np.asarray([1.27, 0.0])
    expect = np.asarray([2.27, 1.004]) - outer_lr * (1 + mu) * delta
    np.testing.assert_allclose(np.asarray(new.snapshot["w"]), expect, rtol=1e-5)


def test_int8_wire_bounded_error_and_mask_compat():
    """Random deltas: int8 round-trip error <= scale/2 per element; the
    masked path with an all-ones mask matches the unmasked quantized
    mean; garbage dtypes are rejected."""
    mesh = build_mesh(MeshConfig(diloco=4))
    cfg = DilocoConfig(num_workers=4, outer_comm_dtype="int8")
    dl = Diloco(TINY, cfg, mesh)
    d = jax.random.normal(jax.random.key(0), (4, 16, 8)) * 3.0
    q = dl._wire_quantize(d)
    scale = (np.abs(np.asarray(d)).max(axis=(1, 2), keepdims=True) / 127.0)
    assert (np.abs(np.asarray(q) - np.asarray(d)) <= scale / 2 + 1e-7).all()

    snapshot = {"w": jax.random.normal(jax.random.key(1), (16,))}
    params = {"w": snapshot["w"][None] + jax.random.normal(jax.random.key(2), (4, 16)) * 0.1}
    um = dl._pseudograd(snapshot, params)
    mm = dl._pseudograd(snapshot, params, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(um["w"]), np.asarray(mm["w"]), atol=1e-6)

    with pytest.raises(ValueError, match="float .* or signed-int"):
        Diloco(TINY, DilocoConfig(num_workers=2, outer_comm_dtype="uint8"),
               build_mesh(MeshConfig(diloco=2)))


def test_int8_wire_nan_worker_masked_scales():
    """Per-worker scales are the quarantine-compat contract: one NaN
    (masked) worker must not poison the survivors' quantization — a
    refactor to a global absmax scale would break exactly this."""
    mesh = build_mesh(MeshConfig(diloco=4))
    cfg = DilocoConfig(num_workers=4, outer_comm_dtype="int8")
    dl = Diloco(TINY, cfg, mesh)
    snapshot = {"w": jax.random.normal(jax.random.key(1), (16,))}
    params = {"w": snapshot["w"][None] + jax.random.normal(jax.random.key(2), (4, 16)) * 0.1}
    poisoned = {"w": params["w"].at[2].set(jnp.nan)}
    healthy_masked = dl._pseudograd(snapshot, params, jnp.asarray([1, 1, 0, 1], bool))
    nan_masked = dl._pseudograd(snapshot, poisoned, jnp.asarray([1, 1, 0, 1], bool))
    np.testing.assert_array_equal(
        np.asarray(nan_masked["w"]), np.asarray(healthy_masked["w"])
    )
    assert np.isfinite(np.asarray(nan_masked["w"])).all()


# -- integer-collective wire (outer_wire_collective) --------------------------

def _int_wire_dl(W=4, dtype="int8"):
    mesh = build_mesh(MeshConfig(diloco=W))
    cfg = DilocoConfig(num_workers=W, outer_comm_dtype=dtype,
                       outer_wire_collective=True)
    return Diloco(TINY, cfg, mesh), mesh


def test_integer_wire_numerics_and_mask():
    """outer_wire_collective: result within shared-scale tolerance of the
    exact f32 mean (scale = global absmax / q_max — coarser than the
    default per-worker scales, documented trade); all-ones mask matches
    no-mask; a NaN (masked) worker poisons neither the shared scale nor
    the integer cast."""
    dl, _ = _int_wire_dl()
    snapshot = {"w": jax.random.normal(jax.random.key(1), (16,)),
                "b": jax.random.normal(jax.random.key(3), (4, 4)) * 5.0}
    params = jax.tree.map(
        lambda s, k: s[None] + jax.random.normal(jax.random.key(k), (4,) + s.shape) * 0.1,
        snapshot, {"w": 2, "b": 4},
    )
    got = dl._pseudograd(snapshot, params)
    for k in snapshot:
        exact = np.asarray(snapshot[k]) - np.asarray(params[k]).mean(axis=0)
        scale = np.abs(np.asarray(snapshot[k])[None] - np.asarray(params[k])).max() / 127.0
        assert (np.abs(np.asarray(got[k]) - exact) <= scale + 1e-7).all(), k

    allmask = dl._pseudograd(snapshot, params, jnp.ones(4))
    for k in snapshot:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(allmask[k]), atol=1e-7
        )

    poisoned = jax.tree.map(lambda p: p.at[2].set(jnp.nan), params)
    healthy = dl._pseudograd(snapshot, params, jnp.asarray([1, 1, 0, 1], bool))
    masked = dl._pseudograd(snapshot, poisoned, jnp.asarray([1, 1, 0, 1], bool))
    for k in snapshot:
        np.testing.assert_array_equal(np.asarray(masked[k]), np.asarray(healthy[k]))
        assert np.isfinite(np.asarray(masked[k])).all()


def test_integer_wire_hlo_operand_dtype():
    """The contract the default quantized path cannot make (its docstring
    concedes XLA may move f32): under outer_wire_collective the compiled
    all-reduce that carries the payload has an INTEGER operand, and every
    f32 all-reduce left is the per-tensor scale pmax / survivor count —
    O(num_tensors) elements, not O(params). Mirrors the reference's wire
    carrying its payload dtype (ref nanodiloco/diloco/diloco.py:49)."""
    import re

    dl, mesh = _int_wire_dl()
    # non-trivial data: all-zero deltas would let XLA constant-fold the
    # integer psum out of the program entirely
    snapshot = {"w": jax.random.normal(jax.random.key(1), (64,)),
                "b": jax.random.normal(jax.random.key(2), (8, 8))}
    params = jax.tree.map(
        lambda s, k: s[None] + jax.random.normal(jax.random.key(k), (4,) + s.shape),
        snapshot, {"w": 3, "b": 4},
    )
    fn = jax.jit(lambda s, p: dl._pseudograd(s, p, jnp.ones(4)))
    with jax.set_mesh(mesh):
        txt = fn.lower(snapshot, params).compile().as_text()
    from nanodiloco_tpu.utils import allreduce_wire_report

    int_payload, wide_float = allreduce_wire_report(
        txt, scale_leaves=len(jax.tree.leaves(snapshot))
    )
    assert int_payload, "no integer-operand all-reduce in compiled HLO"
    assert not wide_float, (
        f"wide float all-reduce leaked onto the wire: {wide_float}"
    )


def test_integer_wire_requires_int_dtype():
    for bad in [None, "bfloat16", "float32"]:
        with pytest.raises(ValueError, match="outer_wire_collective requires"):
            Diloco(TINY, DilocoConfig(num_workers=2, outer_comm_dtype=bad,
                                      outer_wire_collective=True),
                   build_mesh(MeshConfig(diloco=2)))
    # int32 is no narrower than f32 AND clip(±2^31-1) wraps on the int32
    # cast, wrecking the psum (found by round-5 review: W identical
    # deltas of 1.0 came back as ~0)
    with pytest.raises(ValueError, match="not narrow"):
        Diloco(TINY, DilocoConfig(num_workers=2, outer_comm_dtype="int32",
                                  outer_wire_collective=True),
               build_mesh(MeshConfig(diloco=2)))


def test_integer_wire_outer_step_matches_default_within_tolerance():
    """End-to-end outer step under the integer wire stays within
    quantization tolerance of the default (per-worker scale) int8 path:
    same model, same state, outer updates differ by at most
    outer_lr*(1+momentum)*2*scale per element."""
    mesh = build_mesh(MeshConfig(diloco=4))
    base = dict(num_workers=4, outer_lr=0.7, outer_momentum=0.9,
                outer_comm_dtype="int8")
    dl_int = Diloco(TINY, DilocoConfig(**base, outer_wire_collective=True), mesh)
    dl_def = Diloco(TINY, DilocoConfig(**base), mesh)
    from nanodiloco_tpu.parallel.diloco import DilocoState

    snapshot = {"w": jax.random.normal(jax.random.key(1), (32,))}
    params = {"w": snapshot["w"][None]
              + jax.random.normal(jax.random.key(2), (4, 32)) * 0.05}

    def mk(dl):
        # fresh copies: outer_step donates its input state
        return DilocoState(
            params=jax.tree.map(jnp.copy, params),
            inner_opt_state=dl.inner_tx.init(snapshot),
            snapshot=jax.tree.map(jnp.copy, snapshot),
            outer_opt_state=dl.outer_tx.init(snapshot),
            inner_step_count=jnp.zeros((), jnp.int32),
        )

    s_int = dl_int.outer_step(mk(dl_int))
    s_def = dl_def.outer_step(mk(dl_def))
    scale = np.abs(np.asarray(snapshot["w"][None] - params["w"])).max() / 127.0
    tol = 0.7 * 1.9 * 2 * scale + 1e-7
    assert (np.abs(np.asarray(s_int.snapshot["w"])
                   - np.asarray(s_def.snapshot["w"])) <= tol).all()


def test_outer_step_effective_mask_counts_param_blowup():
    """_outer_step's returned effective mask applies the EXACT criterion:
    a worker whose replica params are non-finite is excluded even when
    its losses looked fine (the one-step hole the loss-only log recount
    missed — round-4 advisor finding)."""
    mesh = build_mesh(MeshConfig(diloco=4))
    cfg = DilocoConfig(num_workers=4, quarantine_nonfinite=True)
    dl = Diloco(TINY, cfg, mesh)
    from nanodiloco_tpu.parallel.diloco import DilocoState

    snapshot = {"w": jax.random.normal(jax.random.key(1), (16,))}
    params = {"w": snapshot["w"][None]
              + jax.random.normal(jax.random.key(2), (4, 16)) * 0.1}
    params = {"w": params["w"].at[2].set(jnp.inf)}
    state = DilocoState(
        params=params,
        inner_opt_state=dl.inner_tx.init(snapshot),
        snapshot=snapshot,
        outer_opt_state=dl.outer_tx.init(snapshot),
        inner_step_count=jnp.zeros((), jnp.int32),
    )
    # caller's loss-based mask is all-healthy; the replica check must
    # still quarantine worker 2
    new, eff, _dyn = dl._outer_step(state, jnp.ones(4, bool))
    np.testing.assert_array_equal(np.asarray(eff), [True, True, False, True])
    assert np.isfinite(np.asarray(new.snapshot["w"])).all()


def test_int4_wire_rides_int8_allreduce():
    """outer_comm_dtype="int4" (q_max 7): at W=4 the worst-case sum is
    28, so the accumulator — and therefore the all-reduce payload — is
    INT8: one byte per element on the wire, 4x narrower than f32 (the
    4-bit outer-sync regime of arXiv:2501.18512). The HLO must show an
    s8 all-reduce and no wide-float leak."""
    import re

    dl, mesh = _int_wire_dl(dtype="int4")
    snapshot = {"w": jax.random.normal(jax.random.key(1), (64,)),
                "b": jax.random.normal(jax.random.key(2), (8, 8))}
    params = jax.tree.map(
        lambda s, k: s[None] + jax.random.normal(jax.random.key(k), (4,) + s.shape),
        snapshot, {"w": 3, "b": 4},
    )
    fn = jax.jit(lambda s, p: dl._pseudograd(s, p, jnp.ones(4)))
    with jax.set_mesh(mesh):
        txt = fn.lower(snapshot, params).compile().as_text()
    from nanodiloco_tpu.utils import allreduce_wire_report

    int_payload, wide_float = allreduce_wire_report(
        txt, scale_leaves=len(jax.tree.leaves(snapshot))
    )
    assert int_payload, "no integer-operand all-reduce in compiled HLO"
    assert any(re.search(r"s8\[", r) for r in int_payload), (
        f"int4 wire did not ride an s8 all-reduce: {int_payload}"
    )
    assert not any(re.search(r"s(16|32)\[", r) for r in int_payload), (
        f"int4 wire widened past s8: {int_payload}"
    )
    assert not wide_float, (
        f"wide float all-reduce leaked onto the wire: {wide_float}"
    )


def test_int4_wire_numerics_bounded_and_mask_safe():
    """int4's per-element error bound is scale/2 with
    scale = global absmax / 7 — 18x coarser than int8, still bounded;
    the masked-NaN-worker contract holds identically."""
    dl, _ = _int_wire_dl(dtype="int4")
    snapshot = {"w": jax.random.normal(jax.random.key(1), (16,)),
                "b": jax.random.normal(jax.random.key(3), (4, 4)) * 5.0}
    params = jax.tree.map(
        lambda s, k: s[None] + jax.random.normal(jax.random.key(k), (4,) + s.shape) * 0.1,
        snapshot, {"w": 2, "b": 4},
    )
    got = dl._pseudograd(snapshot, params)
    for k in snapshot:
        exact = np.asarray(snapshot[k]) - np.asarray(params[k]).mean(axis=0)
        scale = np.abs(
            np.asarray(snapshot[k])[None] - np.asarray(params[k])
        ).max() / 7.0
        assert (np.abs(np.asarray(got[k]) - exact) <= scale + 1e-7).all(), k

    poisoned = jax.tree.map(lambda p: p.at[2].set(jnp.nan), params)
    healthy = dl._pseudograd(snapshot, params, jnp.asarray([1, 1, 0, 1], bool))
    masked = dl._pseudograd(snapshot, poisoned, jnp.asarray([1, 1, 0, 1], bool))
    for k in snapshot:
        np.testing.assert_array_equal(np.asarray(masked[k]), np.asarray(healthy[k]))
        assert np.isfinite(np.asarray(masked[k])).all()


def test_int4_wire_trains():
    """A few fused rounds under the 1-byte wire on a learnable task:
    loss must come down — 4-bit outer deltas train (the cited claim),
    now demonstrated by this repo's own wire."""
    mesh = build_mesh(MeshConfig(diloco=4))
    cfg = DilocoConfig(num_workers=4, inner_steps=4, warmup_steps=4,
                       total_steps=200, lr=3e-3, grad_accum=1,
                       outer_comm_dtype="int4", outer_wire_collective=True)
    dl = Diloco(TINY, cfg, mesh)
    state = dl.init_state(jax.random.key(0))
    key = jax.random.key(1)
    first = last = None
    for _ in range(6):
        key, k = jax.random.split(key)
        start = jax.random.randint(k, (4, 4, 1, 2, 1), 0, TINY.vocab_size)
        tok = ((start + jnp.arange(16)[None, None, None, None, :])
               % TINY.vocab_size).astype(jnp.int32)
        tok = tok.reshape(4, 4, 1, 2, 16)
        state, losses, _ = dl.round_step(state, tok, jnp.ones_like(tok))
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
    assert np.isfinite(last)
    assert last < first - 0.3, f"int4 wire failed to train: {first} -> {last}"


@pytest.mark.parametrize("platform,workers,want", [
    ("tpu", 1, {"fused": 2, "dense": 0}),   # one device: the kernel
    ("tpu", 4, {"fused": 2, "dense": 0}),   # a mesh the compiler partitions: the door
    ("cpu", 1, {"fused": 0, "dense": 2}),
])
def test_attention_paths_describe_the_round(monkeypatch, platform, workers, want):
    """``Diloco.attention_paths``: the count of layers whose attention
    takes the fused kernel and dense blocks in this object's programs,
    from the platform, the model's head size and the row length, on one
    device and on a mesh alike: heads of 128 and of 64 (SmolLM2's) alike,
    heads of 32 dense everywhere."""
    import dataclasses

    from nanodiloco_tpu.ops.splash_attention import TILE

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    wide = dataclasses.replace(
        TINY, hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        num_hidden_layers=2)
    mesh = build_mesh(MeshConfig(diloco=workers), devices=jax.devices()[:workers])
    dl = Diloco(wide, DilocoConfig(num_workers=workers), mesh)
    assert dl.attention_paths(2 * TILE) == want
    assert dl.attention_paths(TILE // 2)["fused"] == 0
    for heads, paths in ((4, want), (8, {"fused": 0, "dense": 2})):
        narrow = Diloco(dataclasses.replace(wide, num_attention_heads=heads), DilocoConfig(
            num_workers=workers), mesh)
        assert narrow.attention_paths(2 * TILE) == paths


def test_sync_payload_report_accounting():
    """Byte accounting per wire mode: every numerics-only mode (bf16
    cast included — _wire_quantize dequantizes to f32 BEFORE the mean)
    honestly reports the f32 reduce input; only the integer collective
    guarantees a narrow wire, at the ACCUMULATOR width (int8 payload ->
    s16 wire; int4 payload at W=4 -> s8 wire). Streaming divides by the
    fragment count (one launch moves one fragment)."""
    mesh = build_mesh(MeshConfig(diloco=4))
    n = TINY.num_params()

    def rep(**kw):
        return Diloco(
            TINY, DilocoConfig(num_workers=4, **kw), mesh
        ).sync_payload_report()

    r = rep()
    assert r["bytes_per_sync"] == 4 * n and not r["guaranteed"]
    r = rep(outer_comm_dtype="bfloat16")
    assert r["bytes_per_sync"] == 4 * n and not r["guaranteed"]  # honest
    r = rep(outer_comm_dtype="int8")
    assert r["bytes_per_sync"] == 4 * n and not r["guaranteed"]  # honest
    r = rep(outer_comm_dtype="int8", outer_wire_collective=True)
    assert r["bytes_per_sync"] == 2 * n and r["guaranteed"]      # s16
    r = rep(outer_comm_dtype="int4", outer_wire_collective=True)
    assert r["bytes_per_sync"] == 1 * n and r["guaranteed"]      # s8
    assert "s8" in r["wire"]

    from nanodiloco_tpu.parallel.streaming import StreamingConfig, StreamingDiloco

    sdl = StreamingDiloco(
        TINY,
        DilocoConfig(num_workers=4, inner_steps=4,
                     outer_comm_dtype="int4", outer_wire_collective=True),
        mesh, StreamingConfig(num_fragments=2, delay=1),
    )
    sr = sdl.sync_payload_report()
    assert sr["bytes_per_sync"] == (1 * n) // 2 and sr["guaranteed"]
    assert "fragment" in sr["wire"]


def test_offload_snapshot_trains_and_matches_device_resident():
    """--offload-snapshot keeps the sync snapshot in pinned_host between
    syncs (HBM headroom for big models); every public entry fetches it
    back to device before its jitted program (jit's executable cache
    does not key on memory kind — feeding a host buffer into the
    device-compiled executable is a runtime error; round-5 review found
    the path crashed on the SECOND round and was untested). Three fused
    rounds offloaded must bit-match the device-resident run, and the
    stepwise path must accept an offloaded state too."""
    mesh = build_mesh(MeshConfig(diloco=4))
    tok = jax.random.randint(jax.random.key(1), (2, 4, 1, 2, 16), 0,
                             TINY.vocab_size)
    mask = jnp.ones_like(tok)

    def run(offload):
        dl = Diloco(TINY, DilocoConfig(
            num_workers=4, inner_steps=2, warmup_steps=2, total_steps=50,
            lr=1e-3, offload_snapshot=offload,
        ), mesh)
        state = dl.init_state(jax.random.key(0))
        if offload:
            kind = jax.tree.leaves(state.snapshot)[0].sharding.memory_kind
            if kind != "pinned_host":
                pytest.skip("backend without pinned_host support")
        losses = []
        for _ in range(3):
            state, loss, _ = dl.round_step(state, tok, mask)
            state = dl._offload(state)
            losses.append(np.asarray(loss))
        if offload:
            assert (jax.tree.leaves(state.snapshot)[0]
                    .sharding.memory_kind == "pinned_host")
        # stepwise entries accept the (possibly offloaded) state as-is
        state, l2 = dl.inner_step(state, tok[0], mask[0])
        state = dl.outer_step(state)
        return losses, jax.tree.map(np.asarray, state.snapshot)

    loss_dev, snap_dev = run(False)
    loss_off, snap_off = run(True)
    for a, b in zip(loss_dev, loss_off):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(snap_dev), jax.tree.leaves(snap_off)):
        np.testing.assert_array_equal(a, b)
