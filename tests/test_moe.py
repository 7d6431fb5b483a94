"""Mixture-of-Experts (models/moe.py) + expert parallelism over ``ep``.

The reference is dense-only (SURVEY §2: "Expert parallelism (EP / MoE):
NO"); correctness contracts here: a single ample-capacity expert reduces
exactly to the dense MLP, routing respects capacity, the Switch aux loss
is sane, and ep-sharded training matches unsharded.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, causal_lm_loss, forward, init_params, moe
from nanodiloco_tpu.models.moe import _ragged_mlp, short_rows
from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

MOE = LlamaConfig(
    vocab_size=96, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=32,
    loss_chunk=16, num_experts=4, num_experts_per_tok=2,
)


def tree_max_diff(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(la, lb))


def test_moe_forward_shapes_and_params():
    params = init_params(jax.random.key(0), MOE)
    assert params["layers"]["w_gate"].shape == (2, 4, 32, 64)
    assert params["layers"]["router"].shape == (2, 32, 4)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == MOE.num_params()
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    logits, aux = forward(params, tokens, MOE, with_aux=True)
    assert logits.shape == (2, 16, 96)
    assert np.isfinite(np.asarray(logits)).all()
    # near-uniform router at init: Switch aux close to its balanced value 1
    assert 0.5 < float(aux) / MOE.num_hidden_layers < 2.0


def test_single_ample_expert_equals_dense_mlp():
    """E=1, k=1, capacity >= tokens: the MoE layer must reproduce the
    dense SwiGLU MLP exactly (combine weight 1 for every token)."""
    moe_cfg = LlamaConfig(**{
        **MOE.to_dict(), "num_experts": 1, "num_experts_per_tok": 1,
        "expert_capacity_factor": 1.0,
    })
    dense_cfg = LlamaConfig(**{**MOE.to_dict(), "num_experts": 0})
    mp = init_params(jax.random.key(0), moe_cfg)
    dp = init_params(jax.random.key(0), dense_cfg)
    # graft the single expert's FFN into the dense weights
    dp["layers"]["w_gate"] = mp["layers"]["w_gate"][:, 0]
    dp["layers"]["w_up"] = mp["layers"]["w_up"][:, 0]
    dp["layers"]["w_down"] = mp["layers"]["w_down"][:, 0]
    for k in ("embed", "final_norm", "lm_head"):
        dp[k] = mp[k]
    for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"):
        dp["layers"][k] = mp["layers"][k]
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    with jax.default_matmul_precision("highest"):
        out_moe = forward(mp, tokens, moe_cfg)
        out_dense = forward(dp, tokens, dense_cfg)
    np.testing.assert_allclose(
        np.asarray(out_moe), np.asarray(out_dense), rtol=2e-5, atol=2e-5
    )


def test_capacity_drops_tokens_but_stays_finite():
    """A brutally small capacity factor drops most tokens; the residual
    stream carries them and nothing NaNs (loss + grads finite)."""
    cfg = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 0.1})
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    loss, aux = causal_lm_loss(params, tokens, cfg)
    assert np.isfinite(float(loss))
    g = jax.grad(lambda p: causal_lm_loss(p, tokens, cfg)[0])(params)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))
    # the router gets gradient signal (aux loss + combine weights)
    assert float(jnp.max(jnp.abs(g["layers"]["router"]))) > 0


def test_loss_includes_router_aux():
    params = init_params(jax.random.key(0), MOE)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    loss, aux = causal_lm_loss(params, tokens, MOE)
    ce = float(aux["sum_loss"]) / float(aux["n_tokens"])
    np.testing.assert_allclose(
        float(loss), ce + MOE.router_aux_coef * float(aux["router_aux"]),
        rtol=1e-6,
    )


def test_ep_sharded_round_matches_unsharded():
    """Full DiLoCo round on a (diloco=2, ep=2) mesh == unsharded — the
    expert all-to-alls are a layout choice, not math."""
    cfg = DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=1,
                       total_steps=10, lr=1e-3, grad_accum=2)
    tok = jax.random.randint(jax.random.key(7), (2, 2, 2, 16), 0, 96)
    mask = jnp.ones_like(tok)
    results = []
    with jax.default_matmul_precision("highest"):
        for mc in [MeshConfig(diloco=2, ep=2), MeshConfig()]:
            dl = Diloco(MOE, cfg, build_mesh(mc))
            state = dl.init_state(jax.random.key(0))
            for _ in range(2):
                state, loss = dl.inner_step(state, tok, mask)
            state = dl.outer_step(state)
            results.append(
                (jax.tree.map(np.asarray, state.snapshot), np.asarray(loss))
            )
    (snap_a, loss_a), (snap_b, loss_b) = results
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    assert tree_max_diff(snap_a, snap_b) < 1e-4


def test_moe_config_json_loads():
    path = os.path.join(os.path.dirname(__file__), "..", "configs", "llama_moe.json")
    cfg = LlamaConfig.from_dict(json.load(open(path)))
    assert cfg.num_experts == 8 and cfg.num_experts_per_tok == 2


def test_moe_token_choice_accepted_under_sp():
    """Round 3: token-choice MoE composes with sequence parallelism
    (parity proven in test_moe_sp_matches_unsharded below); only
    expert-choice routing stays rejected
    (test_experts_choose_rejected_under_sp)."""
    Diloco(
        LlamaConfig(**{**MOE.to_dict(), "attention_impl": "ring"}),
        DilocoConfig(num_workers=2),
        build_mesh(MeshConfig(diloco=2, sp=2)),
    )


def test_moe_pp_round_matches_unsharded():
    """MoE composes with pipeline (and expert) parallelism: a full
    DiLoCo round on (diloco=2, pp=2, ep=2) with the router aux loss
    streamed through the stage pipeline must match unsharded — INCLUDING
    pad masking (routing must stay padding-blind inside the pipeline)."""
    cfg = DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=1,
                       total_steps=10, lr=1e-3, grad_accum=4)
    tok = jax.random.randint(jax.random.key(7), (2, 4, 2, 16), 0, 96)
    mask = jnp.ones_like(tok).at[:, 0, :, 12:].set(0)  # padded tails
    results = []
    with jax.default_matmul_precision("highest"):
        for mc in [MeshConfig(diloco=2, pp=2, ep=2), MeshConfig()]:
            dl = Diloco(MOE, cfg, build_mesh(mc))
            state = dl.init_state(jax.random.key(0))
            for _ in range(2):
                state, loss = dl.inner_step(state, tok, mask)
            state = dl.outer_step(state)
            results.append(
                (jax.tree.map(np.asarray, state.snapshot), np.asarray(loss))
            )
    (snap_a, loss_a), (snap_b, loss_b) = results
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    assert tree_max_diff(snap_a, snap_b) < 1e-4


def test_ep_cli_validation():
    from nanodiloco_tpu.cli import build_parser, config_from_args
    from nanodiloco_tpu.training.train_loop import train

    args = build_parser().parse_args(["--ep", "2"])
    with pytest.raises(ValueError, match="requires an MoE model"):
        train(config_from_args(args))


def test_padding_claims_no_expert_capacity():
    """Pad tokens must be invisible to MoE: they route nowhere, consume
    no expert capacity, and contribute nothing to the aux statistics —
    so two batches differing ONLY in pad content give identical losses.
    (Pre-fix, pads claimed queue slots first-come-first-served and
    changed which real tokens got dropped.)"""
    cfg = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 0.6,
                         "num_experts_per_tok": 1, "num_experts": 2})
    params = init_params(jax.random.key(0), cfg)
    real = jax.random.randint(jax.random.key(1), (1, 16), 1, 96)
    garbage = jax.random.randint(jax.random.key(2), (1, 16), 1, 96)
    batch_a = jnp.concatenate([real, jnp.zeros((1, 16), jnp.int32)], axis=0)
    batch_b = jnp.concatenate([real, garbage], axis=0)
    mask = jnp.concatenate(
        [jnp.ones((1, 16), jnp.int32), jnp.zeros((1, 16), jnp.int32)], axis=0
    )
    with jax.default_matmul_precision("highest"):
        loss_a, aux_a = causal_lm_loss(params, batch_a, cfg, loss_mask=mask)
        loss_b, aux_b = causal_lm_loss(params, batch_b, cfg, loss_mask=mask)
    assert float(aux_a["n_tokens"]) == float(aux_b["n_tokens"]) == 15.0
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    np.testing.assert_allclose(
        float(aux_a["router_aux"]), float(aux_b["router_aux"]), rtol=1e-6
    )


def test_k_exceeding_experts_rejected():
    with pytest.raises(ValueError, match="cannot exceed num_experts"):
        LlamaConfig(**{**MOE.to_dict(), "num_experts": 1,
                       "num_experts_per_tok": 2})


EC = LlamaConfig(**{**MOE.to_dict(), "router_type": "experts_choose",
                    "num_experts_per_tok": 1})


def test_expert_choice_single_ample_expert_equals_dense_mlp():
    """E=1 with capacity >= T: the one expert picks every token with
    combine weight softmax-over-1 == 1, reducing exactly to the dense
    SwiGLU MLP."""
    from nanodiloco_tpu.models.moe import moe_mlp

    cfg = LlamaConfig(**{**EC.to_dict(), "num_experts": 1,
                         "expert_capacity_factor": 2.0})
    key = jax.random.key(3)
    h = jax.random.normal(key, (2, 8, 32), jnp.float32)
    w_gate = jax.random.normal(jax.random.key(4), (1, 32, 64)) * 0.05
    w_up = jax.random.normal(jax.random.key(5), (1, 32, 64)) * 0.05
    w_down = jax.random.normal(jax.random.key(6), (1, 64, 32)) * 0.05
    layer = {"router": jnp.zeros((32, 1)), "w_gate": w_gate,
             "w_up": w_up, "w_down": w_down}
    with jax.default_matmul_precision("highest"):
        y, aux = moe_mlp(cfg, h, layer)
        gate = jax.nn.silu(h @ w_gate[0])
        dense = (gate * (h @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=2e-6, atol=2e-7)
    assert float(aux) == 0.0


def test_expert_choice_pads_get_zero_update():
    from nanodiloco_tpu.models.moe import moe_mlp

    params = init_params(jax.random.key(0), EC)
    h = jax.random.normal(jax.random.key(1), (1, 8, 32), jnp.float32)
    valid = jnp.ones((1, 8), jnp.int32).at[0, 5:].set(0)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    layer = {k: layer[k] for k in ("router", "w_gate", "w_up", "w_down")}
    y, _ = moe_mlp(EC, h, layer, valid=valid)
    np.testing.assert_array_equal(np.asarray(y[0, 5:]), 0.0)
    assert float(jnp.abs(y[0, :5]).sum()) > 0


def test_expert_choice_ep_round_matches_unsharded(devices):
    cfg = DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=1,
                       total_steps=10, lr=1e-3, grad_accum=2)
    tok = jax.random.randint(jax.random.key(11), (2, 2, 2, 16), 0, EC.vocab_size)
    mask = jnp.ones_like(tok)
    results = []
    with jax.default_matmul_precision("highest"):
        for mc in [MeshConfig(diloco=2, ep=2), MeshConfig()]:
            dl = Diloco(EC, cfg, build_mesh(mc))
            state = dl.init_state(jax.random.key(0))
            state, loss = dl.inner_step(state, tok, mask)
            state = dl.outer_step(state)
            results.append(
                (jax.tree.map(np.asarray, state.snapshot), np.asarray(loss))
            )
    (snap_a, loss_a), (snap_c, loss_c) = results
    np.testing.assert_allclose(loss_a, loss_c, rtol=1e-4)
    assert tree_max_diff(snap_a, snap_c) < 1e-4


def test_expert_choice_decode_rejected():
    from nanodiloco_tpu.models import generate

    params = init_params(jax.random.key(0), EC)
    with pytest.raises(ValueError, match="training-only"):
        generate(params, jnp.zeros((1, 4), jnp.int32), EC, 2)


def test_router_type_validated():
    with pytest.raises(ValueError, match="router_type"):
        LlamaConfig(router_type="top2")


# -- MoE x sequence parallelism (round 3; the last composition gap) ----------

def _run_inner_step(mc, model, schedule="gpipe", accum=2):
    cfg = DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=2,
                       total_steps=20, lr=1e-3, grad_accum=accum,
                       pp_schedule=schedule)
    dl = Diloco(model, cfg, build_mesh(mc))
    st = dl.init_state(jax.random.key(0))
    tok = jax.random.randint(
        jax.random.key(1), (2, accum, 2, 16), 0, model.vocab_size
    )
    st, loss = dl.inner_step(st, tok, jnp.ones_like(tok))
    return jax.device_get(st.params), np.asarray(loss)


@pytest.mark.parametrize("cf,dispatch", [
    (4.0, "dense"),    # dense needs ample capacity: shard-local routing
                       # == global only while nothing overflows
    (0.25, "ragged"),  # ragged has NO capacity: shard-local == global
                       # EXACTLY even where dense would bind hard; also
                       # proves argsort/bincount/ragged_dot/scatter run
                       # inside the shard_map manual region
])
def test_moe_sp_matches_unsharded(cf, dispatch):
    """Token-choice MoE under sequence parallelism: per-token routing is
    shard-local but identical to the unsharded forward (while capacity
    does not bind, for dense dispatch; unconditionally, for ragged), and
    the load-balance aux statistics are globally exact — so a full inner
    step on (diloco=2, sp=2) must reproduce the vmap path."""
    import dataclasses

    moe = dataclasses.replace(
        MOE, attention_impl="ring", expert_capacity_factor=cf,
        moe_dispatch=dispatch,
    )
    flash = dataclasses.replace(moe, attention_impl="flash")
    with jax.default_matmul_precision("highest"):
        pr, lr_ = _run_inner_step(MeshConfig(diloco=2), flash)
        ps, ls = _run_inner_step(MeshConfig(diloco=2, sp=2), moe)
    np.testing.assert_allclose(ls, lr_, atol=1e-5)
    for a, b in zip(jax.tree.leaves(pr), jax.tree.leaves(ps)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_pp_sp_both_schedules():
    """MoE composes with the sequence-sharded pipeline on BOTH pipeline
    schedules; the three-way (vmap, gpipe, 1f1b) results agree."""
    import dataclasses

    moe = dataclasses.replace(
        MOE, attention_impl="ring", expert_capacity_factor=4.0,
        num_hidden_layers=2,
    )
    flash = dataclasses.replace(moe, attention_impl="flash")
    with jax.default_matmul_precision("highest"):
        pr, lr_ = _run_inner_step(MeshConfig(diloco=2), flash, accum=4)
        pg, lg = _run_inner_step(
            MeshConfig(diloco=2, pp=2, sp=2), moe, "gpipe", accum=4
        )
        p1, l1 = _run_inner_step(
            MeshConfig(diloco=2, pp=2, sp=2), moe, "1f1b", accum=4
        )
    np.testing.assert_allclose(lg, lr_, atol=1e-5)
    np.testing.assert_allclose(l1, lg, atol=1e-5)
    for a, b in zip(jax.tree.leaves(pr), jax.tree.leaves(pg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_sp_aux_globally_exact():
    """The sp aux must equal the unsharded aux exactly (global means,
    not a mean of per-shard f_e*p_e products) — checked directly on
    causal_lm_loss_sp vs causal_lm_loss."""
    import dataclasses

    from nanodiloco_tpu.models.llama import causal_lm_loss_sp

    moe = dataclasses.replace(MOE, attention_impl="ring")
    flash = dataclasses.replace(moe, attention_impl="flash")
    params = init_params(jax.random.key(0), moe)
    tok = jax.random.randint(jax.random.key(1), (2, 16), 0, moe.vocab_size)
    mesh = build_mesh(MeshConfig(sp=2))
    with jax.default_matmul_precision("highest"):
        _, aux_sp = causal_lm_loss_sp(params, tok, moe, mesh)
        _, aux_ref = causal_lm_loss(params, tok, flash)
    np.testing.assert_allclose(
        float(aux_sp["router_aux"]), float(aux_ref["router_aux"]), rtol=1e-6
    )


def test_experts_choose_rejected_under_sp():
    import dataclasses

    ec = dataclasses.replace(
        MOE, attention_impl="ring", router_type="experts_choose"
    )
    with pytest.raises(ValueError, match="expert-choice"):
        Diloco(ec, DilocoConfig(num_workers=2),
               build_mesh(MeshConfig(diloco=2, sp=2)))


def test_router_stats_capacity_binding_fires():
    """The dropped-token metric must FIRE when capacity binds and stay
    exactly 0 when it is ample (VERDICT r3 weak #4: silent dropping)."""
    from nanodiloco_tpu.models.moe import moe_mlp

    params = init_params(jax.random.key(0), MOE)
    layer = jax.tree.map(lambda p: p[0], params["layers"])
    h = jax.random.normal(jax.random.key(1), (2, 16, 32), jnp.float32)

    ample = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 4.0})
    _, _, stats = moe_mlp(ample, h, layer, with_stats=True)
    assert float(stats[0]) == 0.0

    # capacity_factor far below 1: most assignments overflow
    tight = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 0.25})
    _, _, stats_t = moe_mlp(tight, h, layer, with_stats=True)
    assert float(stats_t[0]) > 0.1
    # near-uniform router at init: entropy close to log(E), far from 0
    assert 0.5 * np.log(MOE.num_experts) < float(stats_t[1]) <= np.log(MOE.num_experts) + 1e-3


def test_router_entropy_collapse_visible():
    """A collapsed router (all mass on one expert) must read ~0 nats."""
    from nanodiloco_tpu.models.moe import _router_entropy

    t, e = 64, 4
    collapsed = jnp.zeros((t, e)).at[:, 0].set(1.0)
    assert float(_router_entropy(collapsed, None, None)) < 1e-6
    uniform = jnp.full((t, e), 1.0 / e)
    np.testing.assert_allclose(
        float(_router_entropy(uniform, None, None)), np.log(e), rtol=1e-5
    )


def test_make_router_stats_fn_probe():
    """The per-sync diagnostics probe: finite floats, keyed for the
    JSONL, zero drop at ample capacity, and the training forward is
    untouched (same loss with and without the probe module imported)."""
    from nanodiloco_tpu.models.moe import make_router_stats_fn

    cfg = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 4.0})
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    stats = make_router_stats_fn(cfg)(params, tokens)
    assert set(stats) == {"moe_dropped_frac", "moe_router_entropy"}
    assert float(stats["moe_dropped_frac"]) == 0.0
    assert 0.0 < float(stats["moe_router_entropy"]) <= np.log(4) + 1e-3


def test_expert_choice_stats_coverage():
    """Expert-choice: dropped = tokens picked by no expert; at ample
    capacity every token is picked (cap >= T covers all tokens)."""
    from nanodiloco_tpu.models.moe import moe_mlp

    cfg = LlamaConfig(**{
        **MOE.to_dict(), "router_type": "experts_choose",
        "expert_capacity_factor": 8.0,
    })
    params = init_params(jax.random.key(0), cfg)
    layer = jax.tree.map(lambda p: p[0], params["layers"])
    h = jax.random.normal(jax.random.key(1), (2, 8, 32), jnp.float32)
    _, _, stats = moe_mlp(cfg, h, layer, with_stats=True)
    assert float(stats[0]) == 0.0


# ---------------------------------------------------------------------------
# ragged (sorted grouped-matmul) dispatch — moe_dispatch="ragged"
# ---------------------------------------------------------------------------


def _ragged_cfg(**over):
    return LlamaConfig(**{**MOE.to_dict(), "moe_dispatch": "ragged", **over})


def test_ragged_matches_dense_dispatch_at_ample_capacity():
    """With capacity non-binding, dense dispatch drops nothing, so ragged
    (which NEVER drops) must compute the same function: same routing,
    same combine weights, summation order the only difference."""
    dense_cfg = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 8.0})
    ragged_cfg = _ragged_cfg(expert_capacity_factor=8.0)
    params = init_params(jax.random.key(0), dense_cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    with jax.default_matmul_precision("highest"):
        out_d = forward(params, tokens, dense_cfg)
        out_r = forward(params, tokens, ragged_cfg)
        loss_d, aux_d = causal_lm_loss(params, tokens, dense_cfg)
        loss_r, aux_r = causal_lm_loss(params, tokens, ragged_cfg)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(out_d), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(float(loss_r), float(loss_d), rtol=2e-5)
    # the aux loss reads the pre-capacity assignment: identical by design
    np.testing.assert_allclose(
        float(aux_r["router_aux"]), float(aux_d["router_aux"]), rtol=1e-6
    )


def test_ragged_never_drops_where_dense_capacity_binds():
    """At a brutally small capacity factor dense dispatch drops most
    assignments; ragged ignores capacity entirely — it must match dense
    at UNBOUNDED capacity, not dense at the binding one, and its stats
    channel must report zero dropped."""
    from nanodiloco_tpu.models.moe import moe_mlp

    tight = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 0.25})
    ample = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 8.0})
    ragged = _ragged_cfg(expert_capacity_factor=0.25)  # cf must be ignored
    params = init_params(jax.random.key(0), tight)
    layer = jax.tree.map(lambda p: p[0], params["layers"])
    h = jax.random.normal(jax.random.key(1), (2, 16, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_tight, _, s_tight = moe_mlp(tight, h, layer, with_stats=True)
        y_ample, _, _ = moe_mlp(ample, h, layer, with_stats=True)
        y_ragged, _, s_ragged = moe_mlp(ragged, h, layer, with_stats=True)
    assert float(s_tight[0]) > 0.3            # dense really was binding
    assert float(s_ragged[0]) == 0.0          # ragged never drops
    np.testing.assert_allclose(
        np.asarray(y_ragged), np.asarray(y_ample), rtol=2e-5, atol=2e-5
    )
    assert float(jnp.max(jnp.abs(y_ragged - y_tight))) > 1e-3


def test_ragged_padding_rides_through_with_zero_weight():
    """Pad tokens keep their (garbage) expert assignment as wasted rows
    but their combine weight is zero: two batches differing only in pad
    content give identical losses, same contract as dense dispatch."""
    cfg = _ragged_cfg(num_experts_per_tok=1, num_experts=2)
    params = init_params(jax.random.key(0), cfg)
    real = jax.random.randint(jax.random.key(1), (1, 16), 1, 96)
    garbage = jax.random.randint(jax.random.key(2), (1, 16), 1, 96)
    batch_a = jnp.concatenate([real, jnp.zeros((1, 16), jnp.int32)], axis=0)
    batch_b = jnp.concatenate([real, garbage], axis=0)
    mask = jnp.concatenate(
        [jnp.ones((1, 16), jnp.int32), jnp.zeros((1, 16), jnp.int32)], axis=0
    )
    with jax.default_matmul_precision("highest"):
        loss_a, aux_a = causal_lm_loss(params, batch_a, cfg, loss_mask=mask)
        loss_b, aux_b = causal_lm_loss(params, batch_b, cfg, loss_mask=mask)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    np.testing.assert_allclose(
        float(aux_a["router_aux"]), float(aux_b["router_aux"]), rtol=1e-6
    )


def test_ragged_grads_flow_and_match_dense():
    """Gradients through the sort/gather/ragged_dot/scatter path: finite
    everywhere, router included, and equal to dense dispatch's grads at
    non-binding capacity (same function => same derivative)."""
    dense_cfg = LlamaConfig(**{**MOE.to_dict(), "expert_capacity_factor": 8.0})
    ragged_cfg = _ragged_cfg(expert_capacity_factor=8.0)
    params = init_params(jax.random.key(0), dense_cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 96)
    with jax.default_matmul_precision("highest"):
        g_d = jax.grad(lambda p: causal_lm_loss(p, tokens, dense_cfg)[0])(params)
        g_r = jax.grad(lambda p: causal_lm_loss(p, tokens, ragged_cfg)[0])(params)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g_r))
    assert float(jnp.max(jnp.abs(g_r["layers"]["router"]))) > 0
    assert tree_max_diff(g_d, g_r) < 2e-4


def test_ragged_trains_end_to_end():
    """One fused DiLoCo round through train()'s step machinery with
    ragged dispatch: loss finite and the program compiles on the mesh."""
    cfg = _ragged_cfg()
    params = init_params(jax.random.key(0), cfg)
    mesh = build_mesh(MeshConfig(diloco=2))
    dl = Diloco(
        cfg,
        DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=2,
                     total_steps=50, lr=1e-3, grad_accum=1),
        mesh,
    )
    state = dl.init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 2, 1, 2, 16), 0, 96)
    state, losses, _ = dl.round_step(state, tokens, jnp.ones_like(tokens))
    assert np.isfinite(np.asarray(losses)).all()


def test_ragged_rejected_with_expert_choice_and_ep():
    with pytest.raises(ValueError, match="tokens_choose"):
        _ragged_cfg(router_type="experts_choose")
    from nanodiloco_tpu.cli import build_parser, config_from_args
    from nanodiloco_tpu.training.train_loop import train

    import json as _json
    import tempfile as _tf

    mc = _ragged_cfg().to_dict()
    with _tf.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        _json.dump(mc, f)
        path = f.name
    try:
        args = build_parser().parse_args(
            ["--llama-config-file", path, "--ep", "2"]
        )
        with pytest.raises(ValueError, match="replicated experts"):
            train(config_from_args(args))
    finally:
        os.unlink(path)


def test_ragged_rejected_at_diloco_layer_on_ep_mesh():
    """The replicated-experts contract is enforced where the mesh is
    built, not only in the CLI: a library caller constructing Diloco on
    an ep>1 mesh with ragged dispatch gets an immediate error instead of
    GSPMD silently all-gathering every expert's weights per layer."""
    cfg = _ragged_cfg()
    dcfg = DilocoConfig(num_workers=2, inner_steps=2, warmup_steps=1,
                        total_steps=10, lr=1e-3)
    with pytest.raises(ValueError, match="replicated experts"):
        Diloco(cfg, dcfg, build_mesh(MeshConfig(diloco=2, ep=2)))


# -- the short path of _ragged_mlp (a held share of the experts) -------------

# 2 of 16 experts held, k = 4, 128 tokens: 512 sorted pairs of which 64
# are expected here, so the short path takes the first 128 rows
HELD = LlamaConfig(
    vocab_size=96, hidden_size=32, intermediate_size=64, num_attention_heads=4,
    num_hidden_layers=2, first_k_dense_replace=1, moe_intermediate_size=16,
    num_experts=16, num_experts_per_tok=4, moe_dispatch="ragged",
    experts_held=(6, 2),
)
_T, _K = 128, 4


def _held_case(n_held, pad=0):
    """x, combine weights, a hand-made choice with exactly ``n_held`` of
    the 512 pairs at held experts 6 and 7 (at most two a token, the rest
    at experts held elsewhere), held weights, and a validity mask whose
    first ``pad`` tokens are padding."""
    ks = jax.random.split(jax.random.key(n_held), 6)
    x = jax.random.normal(ks[0], (_T, 32))
    topk_p = jax.random.uniform(ks[1], (_T, _K), minval=0.1)
    elsewhere = np.array([0, 3, 9, 15])
    topk_e = np.tile(elsewhere, (_T, 1))
    for i in range(n_held):
        t, second = i % _T, i // _T
        topk_e[t, second] = (7 - t % 2) if second else 6 + t % 2
    layer = {"w_gate": 0.3 * jax.random.normal(ks[2], (2, 32, 16)),
             "w_up": 0.3 * jax.random.normal(ks[3], (2, 32, 16)),
             "w_down": 0.3 * jax.random.normal(ks[4], (2, 16, 32))}
    valid = jnp.asarray(np.arange(_T) >= pad, jnp.int32)
    return x, topk_p, jnp.asarray(topk_e, jnp.int32), layer, valid


def _full_rows(monkeypatch):
    """The parent's one body over all k*T rows: the reference."""
    monkeypatch.setattr(moe, "short_rows", lambda cfg, n: None)


SHORT_CASES = {
    # name: (pairs at held experts, padded tokens, held pairs that count, short path taken)
    "under": (60, 0, 60, 1),
    "at_cap": (128, 0, 128, 1),
    "one_over": (129, 0, 129, 0),
    "far_over": (256, 0, 256, 0),
    "none_held": (0, 0, 0, 1),
    # 140 pairs at held experts, 28 of them the 16 padded tokens': 112 count
    "padding_brings_it_under": (140, 16, 112, 1),
    "all_padding": (200, _T, 0, 1),
}


def test_short_rows_follow_the_held_share_and_the_pairs():
    exaone = LlamaConfig(**{**HELD.to_dict(), "num_experts": 128,
                            "num_experts_per_tok": 8, "experts_held": (0, 16)})
    # a 512-, 256-, 128-token chunk and a tick of 32 slots at 16 of 128 held
    assert [short_rows(exaone, 8 * t) for t in (512, 256, 128, 32)] == [1152, 640, 384, 128]
    assert short_rows(exaone, 8 * 16) is None  # 128 rows of 128: nothing to save
    assert short_rows(HELD, _T * _K) == 128
    # all experts held, or half of them: no short path at any size
    for cfg in (MOE, _ragged_cfg(), LlamaConfig(**{**HELD.to_dict(), "experts_held": (0, 8)})):
        assert all(short_rows(cfg, n) is None for n in (8, 512, 4096, 1 << 20))


@pytest.mark.parametrize("case", sorted(SHORT_CASES))
def test_short_path_equals_the_full_rows(case, monkeypatch):
    """Under the cap, exactly at it, one pair over it, with padding and
    with nothing held: the output is the full rows' and the flag says
    which branch ran."""
    n_held, pad, counted, short = SHORT_CASES[case]
    x, topk_p, topk_e, layer, valid = _held_case(n_held, pad)
    with jax.default_matmul_precision("highest"):
        y, sizes, flag = jax.jit(lambda *a: _ragged_mlp(HELD, *a))(
            x, topk_p, topk_e, layer, valid)
        _full_rows(monkeypatch)
        y_full, sizes_full, flag_full = jax.jit(lambda *a: _ragged_mlp(HELD, *a))(
            x, topk_p, topk_e, layer, valid)
    assert int(flag) == short and int(flag_full) == 0
    assert int(jnp.sum(sizes)) == counted
    np.testing.assert_array_equal(sizes, sizes_full)
    np.testing.assert_array_equal(y, y_full)  # the same rows in the same order
    assert (float(jnp.max(jnp.abs(y))) > 0.01) == (counted > 0)
    np.testing.assert_array_equal(y[:pad], 0)


@pytest.mark.parametrize("case", ["under", "one_over", "padding_brings_it_under"])
def test_short_path_gradients_equal_the_full_rows(case, monkeypatch):
    n_held, pad, _, _ = SHORT_CASES[case]
    x, topk_p, topk_e, layer, valid = _held_case(n_held, pad)
    ct = jax.random.normal(jax.random.key(9), x.shape)

    def grads():
        return jax.jit(jax.grad(
            lambda x, p, w: jnp.sum(ct * _ragged_mlp(HELD, x, p, topk_e, w, valid)[0]),
            argnums=(0, 1, 2)))(x, topk_p, layer)

    with jax.default_matmul_precision("highest"):
        got = grads()
        _full_rows(monkeypatch)
        want = grads()
    assert float(jnp.max(jnp.abs(want[0]))) > 0.01
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_short_path_under_a_vmap_stays_exact():
    """A vmapped conditional is a select over both branches: a batch of
    loads under and over the cap equals the calls made one by one."""
    cases = [_held_case(n) for n in (60, 256, 129)]
    layer = cases[0][3]
    one = lambda x, p, e, v: _ragged_mlp(HELD, x, p, e, layer, v)
    with jax.default_matmul_precision("highest"):
        ys, _, flags = jax.jit(jax.vmap(one))(
            *(jnp.stack([c[i] for c in cases]) for i in (0, 1, 2, 4)))
        for i, c in enumerate(cases):
            y, _, flag = jax.jit(one)(c[0], c[1], c[2], c[4])
            np.testing.assert_array_equal(ys[i], y)
            assert int(flags[i]) == int(flag) == (i == 0)


# a share whose held expert expects two tiles of 512 rows: 4,096 tokens
# top-2 of 8 experts, one held (8,192 pairs, 1,024 expected at it)
WIDE = LlamaConfig(
    vocab_size=96, hidden_size=16, intermediate_size=32, num_attention_heads=2,
    num_hidden_layers=2, first_k_dense_replace=1, moe_intermediate_size=8,
    num_experts=8, num_experts_per_tok=2, moe_dispatch="ragged", experts_held=(3, 1),
)


def test_wide_groups_take_whole_tiles_at_three_times_the_expected():
    assert moe.short_rows(WIDE, 8192) == 3072
    # the training cell's step: 16,384 tokens top-8 of 64, 8 held
    cell = LlamaConfig(**{**WIDE.to_dict(), "num_experts": 64, "num_experts_per_tok": 8,
                          "experts_held": (0, 8)})
    assert moe.short_rows(cell, 8 * 16384) == 49152
    # narrow groups keep their odd count of 128s
    exaone = LlamaConfig(**{**HELD.to_dict(), "num_experts": 128,
                            "num_experts_per_tok": 8, "experts_held": (0, 16)})
    assert moe.short_rows(exaone, 8 * 512) == 1152 and moe.short_rows(HELD, _T * _K) == 128


@pytest.mark.parametrize("n_held,short", [(1500, 1), (3072, 1), (3073, 0), (5000, 0)])
def test_the_wide_short_path_equals_the_full_rows(n_held, short, monkeypatch):
    """Under the wide rows, at them, one pair over and far over: the
    output and both gradients are those of the one body over all rows."""
    t, k = 4096, 2
    ks = jax.random.split(jax.random.key(n_held), 5)
    x = jax.random.normal(ks[0], (t, 16))
    topk_p = jax.random.uniform(ks[1], (t, k), minval=0.1)
    topk_e = np.tile(np.array([0, 6]), (t, 1))
    topk_e[:min(n_held, t), 0] = 3
    topk_e[:max(n_held - t, 0), 1] = 3  # (a second pick of the same expert: a pair as any)
    topk_e = jnp.asarray(topk_e, jnp.int32)
    layer = {"w_gate": 0.3 * jax.random.normal(ks[2], (1, 16, 8)),
             "w_up": 0.3 * jax.random.normal(ks[3], (1, 16, 8)),
             "w_down": 0.3 * jax.random.normal(ks[4], (1, 8, 16))}

    def run(x, layer):
        y, sizes, took = _ragged_mlp(WIDE, x, topk_p, topk_e, layer, None)
        return jnp.sum(y * y), (y, sizes, took)

    grad = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))
    (_, (y, sizes, took)), (dx, dw) = grad(x, layer)
    assert int(sizes[0]) == n_held and int(took) == short
    _full_rows(monkeypatch)
    (_, (y_full, _, _)), (dx_full, dw_full) = jax.jit(
        jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(x, layer)
    np.testing.assert_allclose(y, y_full, atol=1e-5)
    np.testing.assert_allclose(dx, dx_full, atol=1e-4)
    for name in layer:
        np.testing.assert_allclose(dw[name], dw_full[name], rtol=1e-4, atol=1e-4)
