"""Mellum2-12B-A2.5B's mechanisms on the training path, against the plain
reference ``benchmark/reference/mellum_ref.py`` on seeded weights: a tiny
preset (hidden 64, 8 experts top-4 behind a softmax gate of which 2 are
held, three window layers of 8 to one full layer, a default rotary table
on the window layers and YaRN on the full one, sequences of 32, float32).
"""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import correctness_sparse_train as cst
from benchmark.reference import mellum_ref as ref
from nanodiloco_tpu.models import LlamaConfig, init_params
from nanodiloco_tpu.models import llama
from nanodiloco_tpu.models.llama import (
    causal_lm_loss,
    causal_mask,
    dense_attention,
    forward,
    rope_tables,
    yarn_ramp,
)
from nanodiloco_tpu.models.moe import TRAIN_COUNTERS, make_router_stats_fn, sparse_mlp
from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

L, G = "sliding_attention", "full_attention"
ROPE = {G: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 2, "beta_slow": 0.05,
            "attention_factor": 0.1 * math.log(16) + 1.0},
        L: {"rope_type": "default", "rope_theta": 10000.0}}
TINY = LlamaConfig.from_dict(dict(
    vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, layer_types=[L, L, L, G],
    sliding_window=8, rope_parameters=ROPE, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=4, moe_intermediate_size=32, moe_dispatch="ragged",
    experts_held=[2, 2], router_aux_coef=0.05, loss_chunk=0, initializer_range=0.1))
HELD = (2, 2)
OPT = {"lr": 3e-3, "warmup_steps": 1, "total_steps": 50, "weight_decay": 0.01,
       "clip_norm": 1.0, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
# the published configuration, from the catalog's keys
PUBLISHED = dict(
    vocab_size=98304, hidden_size=2304, intermediate_size=7168, num_hidden_layers=28,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    layer_types=[G if i % 4 == 3 else L for i in range(28)], sliding_window=1024,
    rms_norm_eps=1e-6, num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
    norm_topk_prob=True, tie_word_embeddings=False, moe_dispatch="ragged",
    rope_parameters={
        G: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782},
        L: {"rope_type": "default", "rope_theta": 500000}})


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), TINY)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 32), 0, TINY.vocab_size)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_published_keys_are_read_and_counted():
    cfg = LlamaConfig.from_dict(PUBLISHED)
    assert cfg.mixed and cfg.head_dim == 128 and cfg.expert_width == 896
    assert cfg.rope_for(G)["rope_type"] == "yarn" and cfg.rope_for(L)["rope_theta"] == 500000
    assert cfg.num_params() == 12_149_915_904
    cut = LlamaConfig.from_dict({**PUBLISHED, "num_hidden_layers": 4, "vocab_size": 12288,
                                 "layer_types": PUBLISHED["layer_types"][:4],
                                 "experts_held": [0, 8]})
    assert cut.num_params() == 340_349_184
    # a checkpoint's model_config.json sidecar: through JSON and back, hashable
    again = LlamaConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert again == cfg and hash(again) == hash(cfg)
    with pytest.raises(ValueError, match="rope_parameters by layer kind"):
        LlamaConfig.from_dict({**PUBLISHED, "rope_parameters": {G: PUBLISHED["rope_parameters"][G]}})


def test_the_two_rotary_tables_are_the_formulas():
    cfg = LlamaConfig.from_dict(PUBLISHED)
    assert yarn_ramp(cfg.rope_for(G), 128) == (18, 35) == ref.yarn_range(cfg.rope_for(G), 128)
    n = np.arange(64, dtype=np.float64)
    base = 500000.0 ** (-2 * n / 128)
    ramp = np.clip((n - 18) / (35 - 18), 0, 1)
    yarn = (1 - ramp) * base + ramp * base / 16
    assert yarn[17] == base[17] and yarn[35] == base[35] / 16 and base[20] / 16 < yarn[20] < base[20]
    pos = np.arange(48, dtype=np.float64)[:, None]
    factor = 0.1 * math.log(16) + 1
    assert abs(factor - 1.2772588722239782) < 1e-15
    for kind, inv, scale in ((L, base, 1.0), (G, yarn, factor)):
        cos, sin = rope_tables(cfg, 48, kind=kind)
        want = np.concatenate([pos * inv, pos * inv], axis=-1)
        np.testing.assert_allclose(cos, scale * np.cos(want), atol=2e-5)
        np.testing.assert_allclose(sin, scale * np.sin(want), atol=2e-5)
        got, f = ref.inv_freq(cfg.rope_for(kind), 128)
        np.testing.assert_allclose(got, inv, rtol=1e-5)
        assert abs(f - scale) < 1e-12
    # one rope_theta and no kind: the table every other configuration builds
    plain = LlamaConfig(hidden_size=64, num_attention_heads=4, rope_theta=500000.0)
    np.testing.assert_array_equal(rope_tables(plain, 48)[0][:, :8],
                                  rope_tables(cfg, 48, kind=L)[0][:, :64:8])


def _parent_dense_attention(q, k, v):
    """``dense_attention`` without a mask as the parent commit traced it."""
    b, s, h, hd = q.shape
    rows = llama.dense_block_rows(s, None)
    out = []
    for start in range(0, s, rows):
        end = start + rows
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end]).astype(
            jnp.float32) * (1.0 / math.sqrt(hd))
        scores = scores + causal_mask(end, None, start, None).astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :end]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("s,window,bq", [
    (32, 8, 8), (32, 8, 16), (32, 3, 4), (48, 20, 8), (64, 8, 32), (64, 1, 8), (32, 40, 8),
    (512, 8, None)])
def test_a_window_layers_blocks_meet_their_keys_alone(s, window, bq):
    q, k, v = (jax.random.normal(jax.random.key(i), (2, s, 2, 8)) for i in range(3))
    valid = (jax.random.uniform(jax.random.key(3), (2, s)) > 0.2).astype(jnp.int32).at[:, 0].set(1)
    whole = dense_attention(q, k, v, causal_mask(s, None, 0, window))
    np.testing.assert_allclose(dense_attention(q, k, v, bq=bq, window=window), whole, atol=2e-6)
    masked = dense_attention(q, k, v, causal_mask(s, valid, 0, window))
    got = dense_attention(q, k, v, valid, bq=bq, window=window)
    seen = np.asarray(valid[:, :, None, None] > 0)  # a row with no valid key is loss-masked
    np.testing.assert_allclose(np.where(seen, got, 0), np.where(seen, masked, 0), atol=2e-6)
    # the blocks' key slices, from the traced program: no key more than
    # window - 1 rows before a block's first row
    rows = llama.dense_block_rows(s, bq)
    text = str(jax.make_jaxpr(lambda q, k, v: dense_attention(q, k, v, bq=bq, window=window))(
        q, k, v))
    widths = sorted({int(w.split(",")[3].rstrip("]")) for w in
                     __import__("re").findall(r"f32\[2,2,\d+,\d+\]", text)})
    assert max(widths) <= rows + min(window, s) - 1


def test_without_a_window_the_trace_is_the_parents():
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 512, 2, 8)) for i in range(3))
    assert str(jax.make_jaxpr(dense_attention)(q, k, v)) == str(
        jax.make_jaxpr(_parent_dense_attention)(q, k, v))


def test_blocks_made_again_in_the_backward_pass_give_the_same_gradients(monkeypatch):
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 32, 2, 8)) for i in range(3))
    loss = lambda q, k, v: jnp.sum(dense_attention(q, k, v, bq=8, window=12) ** 2)
    want = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    monkeypatch.setattr(llama, "DENSE_SAVED_PROBS_MAX", 0)
    again = lambda q, k, v: loss(q, k, v)  # a function jit has not traced yet
    assert "prevent_cse" in str(jax.make_jaxpr(jax.grad(again))(q, k, v))  # a remat a block
    for a, b in zip(jax.jit(jax.grad(again, argnums=(0, 1, 2)))(q, k, v), want):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_forward_logits_match_the_reference(params, tokens):
    got = forward(params, tokens, TINY)
    want = jax.jit(lambda w: ref.forward(w, tokens, cst.hyper(TINY), held=HELD))(
        cst.reference_weights(params))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # each mechanism moves the logits: the reference's controls are no no-ops
    for fault in ("window_ignored", "yarn_left_out", "gate_over_held_only"):
        other = jax.jit(lambda w, fault=fault: ref.forward(
            w, tokens, cst.hyper(TINY), held=HELD, fault=fault))(cst.reference_weights(params))
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2, fault


@pytest.mark.parametrize("loss_chunk,remat", [(0, False), (16, True)])
def test_loss_and_every_gradient_match_the_reference(params, tokens, loss_chunk, remat):
    cfg = dataclasses.replace(TINY, loss_chunk=loss_chunk, remat=remat)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, tokens, cfg), has_aux=True))(params)
    w = cst.reference_weights(params)
    (want, (ce, balance)), gref = jax.jit(jax.value_and_grad(
        lambda w: ref.loss(w, tokens, cst.hyper(cfg), held=HELD, with_parts=True),
        has_aux=True))(w)
    assert abs(float(loss) - float(want)) < 1e-5
    # the balance term: over all 8 outputs, one a layer, near 1 each, and in the loss
    assert abs(float(aux["router_aux"]) - float(balance)) < 1e-5 and 3.9 < float(balance) < 5.0
    assert abs(float(want) - float(ce) - 0.05 * float(balance)) < 1e-6
    got = cst.reference_weights(grads)
    for (path, a), b in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(gref)):
        np.testing.assert_allclose(a, b, atol=2e-6, err_msg=jax.tree_util.keystr(path))
    routers = [float(jnp.max(jnp.abs(layer["router"]))) for layer in gref["layers"]]
    assert min(routers) > 1e-3  # through the normalised weights and the balance term
    counters = dict(zip(TRAIN_COUNTERS, np.asarray(aux["moe_counters"])))
    assert counters["moe_pairs"] == 4 * 64 * 4 and counters["moe_short_path"] == 4
    assert 0 < counters["moe_max_group_rows"] <= counters["moe_held_pairs"] < counters["moe_pairs"]


def test_the_eight_shares_add_up_to_the_whole_layer(params, tokens):
    """Over 8 shares of 1 expert, the parts a sparse layer's chips give
    sum to the uncut reference's layer: the gate's weights are formed
    over all 4 chosen, held or not."""
    w = cst.reference_weights(init_params(
        jax.random.key(0), dataclasses.replace(TINY, experts_held=None)))["layers"][1]
    h = jax.random.normal(jax.random.key(3), (2, 24, 64))
    hp, mm = cst.hyper(TINY), ref._matmul(jnp.dtype(jnp.float32), None)
    weights, _, _ = ref.gate(h, w, hp, mm)
    want = sum(weights[..., e, None] * ref._swiglu(mm, h, w["experts_gate"][e], w["experts_up"][e],
                                                   w["experts_down"][e]) for e in range(8))
    total, pairs = 0.0, 0
    for first in range(8):
        cfg = dataclasses.replace(TINY, experts_held=(first, 1))
        layer = {"router": w["router"], "w_gate": w["experts_gate"][first:first + 1],
                 "w_up": w["experts_up"][first:first + 1],
                 "w_down": w["experts_down"][first:first + 1]}
        part, counters, _ = sparse_mlp(cfg, h, layer)
        total, pairs = total + part, pairs + int(counters[0])
    assert pairs == 4 * 2 * 24  # every pair is held by exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5)


def _round(workers: int, devices: int, params, tokens, steps: int = 3):
    dl = Diloco(dataclasses.replace(TINY, remat=True, loss_chunk=16),
                DilocoConfig(num_workers=workers, inner_steps=steps, **{
                    k: OPT[k] for k in ("lr", "warmup_steps", "total_steps", "weight_decay",
                                        "clip_norm")}),
                build_mesh(MeshConfig(diloco=devices), devices=jax.devices()[:devices]))
    state = dl.init_state(jax.random.key(0), params=params)
    tok = jnp.broadcast_to(tokens, (steps, workers, 1) + tokens.shape)
    return dl, state, tok


@pytest.fixture(scope="module")
def one_worker_round(params, tokens):
    dl, state, tok = _round(1, 1, params, tokens)
    with jax.default_matmul_precision("highest"):
        state, *out = dl.round_step(state, tok, jnp.ones_like(tok))
    left = jax.tree.map(lambda x: x[0], optax.tree_utils.tree_get(state.inner_opt_state, "mu"))
    return (*out, left)


def test_a_fused_round_falls_as_a_plain_adamw_loop_over_the_reference(params, tokens,
                                                                      one_worker_round):
    """Its losses, and the first moment it leaves in its state: the
    number the benchmark's check decides by. Each control's loop reads
    far from it, a control that decides nothing near."""
    losses, _, stats, left = one_worker_round
    want, moments = cst.reference_loop(ref, cst.reference_weights(params), cst.hyper(TINY),
                                       tokens, OPT, 3, 3, HELD, cst.CONTROLS)
    np.testing.assert_allclose(np.asarray(losses)[:, 0], want, atol=2e-5)
    assert want[0] - want[-1] > 0.01
    left = cst.on_host(cst.reference_weights(left))
    read = {name: cst.moment_distance(left, m)["worst_leaf"] for name, m in moments.items()}
    assert read[None] < 1e-4, read  # at this toy's coefficient of 0.05 the balance term decides too
    assert all(read[name] > cst.MOMENT_TOL for name in cst.MUST_REFUSE), read
    assert stats["router_aux"].shape == (3, 1) and stats["moe_counters"].shape == (3, 1, 5)
    assert (np.asarray(stats["moe_counters"])[:, 0, 2] == 4 * 64 * 4).all()


def _conds(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append((eqn.invars[0].aval.shape,
                          [str(b).count("ragged_dot") for b in eqn.params["branches"]]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _conds(sub, found)
    return found


@pytest.mark.parametrize("workers,devices", [(1, 1), (2, 1), (2, 2)])
def test_one_set_of_grouped_products_a_layer_under_the_worker_axis(
        params, tokens, one_worker_round, workers, devices):
    """The scalar that picks the grouped products' body stays a scalar
    under the worker axis: every ``cond`` over grouped products survives
    as one (a ``vmap`` would have made a select of it and run both)."""
    dl, state, tok = _round(workers, devices, params, tokens)
    with jax.set_mesh(dl.mesh):
        conds = _conds(jax.make_jaxpr(dl._round_step)(state, tok, jnp.ones_like(tok)).jaxpr, [])
    grouped = [c for c in conds if max(c[1])]
    assert len(grouped) >= 4 and all(shape == () for shape, _ in grouped)
    if workers == 1:
        return  # its round is the other cases' yardstick
    state, losses, _, stats = dl.round_step(state, tok, jnp.ones_like(tok))
    want = one_worker_round[0]
    np.testing.assert_allclose(losses, jnp.broadcast_to(want, losses.shape), atol=1e-6)
    assert (np.asarray(stats["moe_counters"])[..., 3] == 4).all()  # the short path, every layer


def test_the_router_probe_reads_a_mixed_stacks_entropy(params, tokens):
    stats = make_router_stats_fn(TINY)(params, tokens)
    assert float(stats["moe_dropped_frac"]) == 0.0
    assert 0.5 * math.log(8) < float(stats["moe_router_entropy"]) <= math.log(8)
    logits, choices = forward(params, tokens, TINY, with_choices=True)
    assert choices.shape == (4, 2, 32, 4) and int(choices.max()) < 8
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1]), tokens[:, 1:, None], -1)[..., 0]
    w, hp = cst.reference_weights(params), cst.hyper(TINY)
    passed = cst.followed_pass(ref, hp, HELD)
    clean = passed(w, tokens, choices, nll)
    assert clean["choice_shortfall"] <= 1e-6 and clean["choices_agree"] == 1.0
    assert clean["choice_shortfall_of_neighbours"] > 0.01 and clean["token_rms"] < 1e-5
    # a fault switched off at run time reads what the pass traced with it off reads
    for fault in ref.FAULTS:
        moved = passed(w, tokens, choices, nll, fault)
        each, balance = jax.jit(lambda w, fault=fault: ref.token_losses(
            w, tokens, hp, held=HELD, choice=choices, fault=fault))(w)
        want = float(jnp.mean(each) + hp["router_aux_coef"] * balance)
        assert abs(moved["loss"] - want) < 1e-6, fault
        # two faults are no part of a token's own loss
        assert (moved["token_rms"] > 0.01) == (
            fault not in ("balance_left_out", "half_the_batch_left_out")), fault


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "stepwise"])
def test_train_records_what_the_expert_layers_did(tmp_path, fused):
    """``train()`` steps the tiny configuration as any other and writes
    the balance term, the counters and the router's entropy (the probe
    once read zeros for a mixed stack) into its JSONL records."""
    from nanodiloco_tpu.training.train_loop import TrainConfig, train

    summary = train(TrainConfig(
        seed=3, batch_size=2, per_device_batch_size=2, seq_length=32, warmup_steps=1,
        total_steps=4, inner_steps=2, lr=3e-3, num_workers=1, fused_rounds=fused,
        model=dataclasses.replace(TINY, vocab_size=384, remat=True, loss_chunk=16,
                                  num_hidden_layers=2, layer_types=(L, G)),
        log_dir=str(tmp_path), quiet=True, measure_comm=False, cost_analysis=False))
    assert np.isfinite(summary["final_loss"])
    run, = [p for p in tmp_path.iterdir() if p.suffix == ".jsonl"]
    steps = [r for r in map(json.loads, run.read_text().splitlines()) if "loss" in r]
    assert len(steps) == 4
    for r in steps:
        assert 1.9 < r["router_aux"] < 4.0 and r["moe_pairs"] == 2 * 64 * 4
        assert 0 < r["moe_max_group_rows"] <= r["moe_held_pairs"] < r["moe_pairs"]
        assert r["moe_short_path"] == 2
    synced = [r for r in steps if r["outer_synced"]]
    assert len(synced) == 2 and all(r["moe_router_entropy"] > 1.0 for r in synced)


def test_what_a_grouped_product_leaves_in_rows_of_no_group_reaches_no_gradient(
        params, tokens, monkeypatch):
    """On the chip ``ragged_dot`` leaves the rows in no group alone, in
    its transposes too (the CPU's writes zeros there). Poisoned with NaN
    in both directions, a held share's loss and gradients stay the
    reference's."""
    real = jax.lax.ragged_dot

    def in_group(n, sizes):
        return (jnp.arange(n) < jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(in_group(lhs.shape[0], sizes), real(lhs, rhs, sizes), jnp.nan)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        seen = in_group(lhs.shape[0], sizes)  # the kernel reads no row outside its groups
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), jnp.where(seen, lhs, 0), rhs)[1](
            jnp.where(seen, ct, 0))
        return jnp.where(seen, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(lambda lhs, rhs, sizes: (poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)), bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    cfg = dataclasses.replace(TINY, remat=True, loss_chunk=16)
    loss, grads = jax.value_and_grad(lambda p: causal_lm_loss(p, tokens, cfg)[0])(params)
    monkeypatch.undo()
    want, gref = jax.jit(jax.value_and_grad(
        lambda w: ref.loss(w, tokens, cst.hyper(cfg), held=HELD)))(cst.reference_weights(params))
    assert abs(float(loss) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(cst.reference_weights(grads)), jax.tree.leaves(gref)):
        np.testing.assert_allclose(a, b, atol=2e-6)


@pytest.mark.parametrize("program", ["generate", "serve", "pipeline", "one_table"])
def test_a_program_that_knows_one_rotary_table_refuses_a_table_a_layer_kind(params, program):
    """The cached, serving and pipelined programs build one table from
    ``rope_theta``: run so, the full layers would lose their YaRN, the
    fault the benchmark's ``yarn_left_out`` control plants. They refuse
    by name."""
    from nanodiloco_tpu.models.generate import generate
    from nanodiloco_tpu.ops.pipeline import _pipeline_setup
    from nanodiloco_tpu.serve.engine import InferenceEngine

    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="rope_parameters|mixed layer stack"):
        if program == "generate":
            generate(params, prompt, TINY, max_new_tokens=2)
        elif program == "serve":
            InferenceEngine(params, TINY, num_slots=1, max_len=16, chunk_size=4)
        elif program == "pipeline":
            _pipeline_setup(TINY, 8, None)
        else:
            rope_tables(TINY, 8)
    # a configuration with one table builds the table it built
    one = dataclasses.replace(TINY, rope_parameters=None)
    np.testing.assert_array_equal(rope_tables(one, 8)[0], rope_tables(one, 8, kind=G)[0])
