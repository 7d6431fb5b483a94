"""What PR 26 added to the yardstick: ``costs_sparse.py`` against a
hand count, ``correctness_mesh.sync_check`` on outer steps made by hand,
the readers of the expert layer's metrics on what a run observed, and
``correctness_sparse.served_check``: the program passes and each negative
control is seen to move."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (
    correctness,
    correctness_mesh,
    correctness_sparse,
    costs_sparse,
    scope_times,
)
from benchmark.drivers import serve_ref
from benchmark.readers import (
    decode_hbm_pct_sparse,
    moe_expert_device_pct,
    moe_experts_hbm_pct,
    moe_tokens_per_expert_hit,
    serve_mfu_pct,
    wire_bytes,
)
from nanodiloco_tpu.models import LlamaConfig, forward, init_params

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SERVE = os.path.join(HERE, "data", "tiny_serve_spans_v5e.xplane.pb")

# a model small enough to count by hand: hidden 4, 2 heads over 1 KV
# head of 3, dense width 5, experts of width 2 (8 routed to, 1 shared),
# 3 layers (dense sliding, sparse full, sparse sliding), 7 rows held
HAND = SimpleNamespace(
    hidden_size=4, intermediate_size=5, moe_intermediate_size=2, num_attention_heads=2,
    num_key_value_heads=1, head_dim=3, first_k_dense_replace=1, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, sliding_window=10, vocab_size=7,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"])


def test_costs_of_a_hand_counted_model():
    attn = 4 * 6 + 2 * 4 * 3 + 6 * 4            # q, k and v, o: 72
    assert costs_sparse.attention_params(HAND) == attn == 72
    assert costs_sparse.expert_params(HAND) == 3 * 4 * 2 == 24
    assert costs_sparse.layer_counts(HAND) == (1, 2, 1, 2)
    fixed = 3 * 72 + 3 * 4 * 5 + 2 * (4 * 8 + 24) + 4 * 7
    assert costs_sparse.fixed_params(HAND) == fixed == 416
    # a tick of 2 streams of 30 rows, 1.5 held experts hit a sparse layer:
    # weights in 2 bytes, rows of k and v (2 x 1 x 3 x 2 bytes = 12) on
    # one full layer whole and on two sliding layers 10 of 30
    want = (416 + 2 * 1.5 * 24) * 2 + 2 * (30 + 2 * 10) * 12
    assert costs_sparse.decode_tick_bytes(HAND, 2, 30, 1.5, 2, 2) == want == 2176
    assert costs_sparse.decode_tick_bytes(HAND, 2, 4, 1.5, 2, 2) == 488 * 2 + 2 * 12 * 12
    assert costs_sparse.experts_bytes(HAND, 5, 2) == 5 * 24 * 2
    # a token with 0.25 held pairs a sparse layer at 30 keys
    flops = 2 * (416 + 2 * 0.25 * 24) + 4 * (30 + 2 * 10) * 2 * 3
    assert costs_sparse.flops_per_token(HAND, 0.25, 30) == flops == 2056


def test_the_cell_reckons_its_bytes_from_the_file():
    """The configuration as the driver builds it: 7.42 GB of weights,
    the issue's 6.6 GB a tick at 14 of 16 experts hit."""
    with open(os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")) as f:
        conf = json.load(f)
    program = serve_ref.program_config(conf)
    m = SimpleNamespace(**{k: program[k] for k in serve_ref.MODEL_KEYS})
    assert costs_sparse.layer_counts(m) == (1, 4, 1, 4)
    cfg = LlamaConfig.from_dict(program)
    assert cfg.mixed and cfg.head_dim == 128 and cfg.held_experts == (0, 16)
    assert cfg.vocab_size == 19200 and cfg.num_experts == 128 and cfg.rope_theta == 1e6
    assert 2 * cfg.num_params() == pytest.approx(7.424e9, rel=1e-3)
    tick = costs_sparse.decode_tick_bytes(m, 0, 0, 14, 2, 2)
    assert tick == pytest.approx(6.59e9, rel=5e-3)
    held_every = costs_sparse.fixed_params(m) + 4 * 16 * costs_sparse.expert_params(m)
    embed = m.hidden_size * m.vocab_size            # gathered, not counted
    norms = 5 * (2 * 6144 + 2 * 128) + 6144 + 4 * 128
    assert held_every + embed + norms == cfg.num_params()
    # a token: 8 of 128 meet 16 held once a layer; 2.66 GFLOP without attention
    assert costs_sparse.flops_per_token(m, 1.0, 0) == pytest.approx(2.66e9, rel=1e-2)


def _obs(**extra):
    with open(os.path.join(BENCH, "configs", "k-exaone-236b-a23b.json")) as f:
        program = serve_ref.program_config(json.load(f))
    return {"model": {k: program[k] for k in serve_ref.MODEL_KEYS},
            "weight_itemsize": 2, "kv_itemsize": 2, "device_kind": "TPU v5 lite",
            "kv_rows_per_stream": 1500.0, "prompt_context_rows": 1200.0, **extra}


def test_the_counter_readers_on_what_a_run_observed():
    moe = {"prefill_chunk": {"moe_held_pairs": 2100, "moe_experts_hit": 320, "moe_pairs": 16384},
           "decode": {"moe_held_pairs": 12800, "moe_experts_hit": 5600, "moe_pairs": 102400}}
    # the ticks' counters alone: a chunk's thirty rows an expert are no tick's load
    assert moe_tokens_per_expert_hit.read({"moe": moe}) == pytest.approx(12800 / 5600)
    assert moe_tokens_per_expert_hit.read({}) is None
    assert moe_tokens_per_expert_hit.read({"moe": {"prefill_chunk": moe["prefill_chunk"]}}) is None
    # 100 ticks of 32 streams, 14 experts hit a layer, 25 ms a tick
    obs = _obs(moe=moe, window_s=2.5, slots_decoding=[32, 32], devtime={
        "device_seconds": {"decode:1:paged-rings": 2.5},
        "dispatches": {"decode:1:paged-rings": 100}})
    m = SimpleNamespace(**obs["model"])
    need = costs_sparse.decode_tick_bytes(m, 32, 1500.0, 14.0, 2, 2)
    assert decode_hbm_pct_sparse.read(obs) == pytest.approx(100 * need / 819e9 / 0.025)
    assert 25 < decode_hbm_pct_sparse.read(obs) < 45
    assert decode_hbm_pct_sparse.read(_obs()) is None
    # 512 prompt tokens and 3,200 output tokens in 2.5 s
    flops = (512 * costs_sparse.flops_per_token(m, 2100 / 2048, 1200.0)
             + 3200 * costs_sparse.flops_per_token(m, 1.0, 1500.0))
    assert serve_mfu_pct.read(obs) == pytest.approx(100 * flops / 2.5 / 197e12)
    assert serve_mfu_pct.read(_obs()) is None
    assert wire_bytes.read({"wire_bytes_per_sync": 12}) == 12 and wire_bytes.read({}) is None


def test_scope_readers_see_the_expert_layer_inside_mlp(monkeypatch):
    assert scope_times.scope_of("jit(run)/mlp/moe_experts/ragged_dot") == "moe_experts"
    assert scope_times.scope_of("jit(run)/mlp/dot_general") == "mlp"
    assert scope_times.scope_of("ragged-dot-none") == "moe_experts"  # the kernel's own name
    assert scope_times.scope_of("jit(run)/while/body/dynamic_slice") is None
    for reader in (moe_expert_device_pct, moe_experts_hbm_pct):
        assert reader.read({}) is None and reader.read({"trace": None}) is None
    # a recorded v5e trace of a dense model: scopes, none of the expert layer's
    monkeypatch.setattr(scope_times.tr, "find_xplane", lambda root: SERVE)
    got = scope_times.of_run({"trace": {"busy_s": 1.0}})
    assert got["leaf_s"] == pytest.approx(sum(got["by_scope"].values()))
    assert "mlp" in got["by_scope"] and "moe_experts" not in got["by_scope"]
    run = _obs(trace={"busy_s": 1.0}, moe_traced={"moe_experts_hit": 50})
    assert moe_expert_device_pct.read(run) is None and moe_experts_hbm_pct.read(run) is None
    # the same trace with the MLP's time read as the grouped products'
    monkeypatch.setattr(scope_times, "by_scope", lambda path: {
        "leaf_s": 2.0, "by_scope": {"moe_experts": 0.5, "moe_route": 0.1, "attention": 1.4}})
    assert moe_expert_device_pct.read(run) == pytest.approx(30.0)
    want = 100 * (50 * 3 * 6144 * 2048 * 2 / 819e9) / 0.5
    assert moe_experts_hbm_pct.read(run) == pytest.approx(want)


def _tiny_sparse():
    with open(os.path.join(HERE, "rehearsal", "configs", "tiny-sparse.json")) as f:
        return LlamaConfig.from_dict(serve_ref.program_config(json.load(f)))


def test_unstacking_may_consume_the_programs_tree():
    """``consume`` leaves one copy of every stacked layer on the device:
    the same weights, and the stacks themselves gone."""
    cfg = _tiny_sparse()
    kept = correctness_sparse.reference_weights(init_params(jax.random.key(0), cfg))
    params = init_params(jax.random.key(0), cfg)
    got = correctness_sparse.reference_weights(params, consume=True)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(kept), strict=True):
        np.testing.assert_array_equal(a, b)
    assert all(v.is_deleted() for layer in params["layers"] for v in layer.values())
    assert not any(v.is_deleted() for layer in params["lead_layers"] for v in layer.values())


@pytest.fixture(scope="module")
def served():
    """Greedy decoding by whole forward passes of the bf16 program, with
    the experts it chose: what the engine's probes would hand over."""
    cfg = _tiny_sparse()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (24, 40)]

    from nanodiloco_tpu.models import llama

    def run(ids):
        """(logits, chosen experts [L_sparse, S, k]) of one padded row."""
        chosen = []

        def body(x, layer, kind, _):
            x = llama._attn_block(cfg, x, layer, cos, sin, None, None, kind[0])
            x, n, c = llama.mixed_mlp_block(cfg, x, layer)
            return x, None, n, c

        cdt = jnp.dtype(cfg.dtype)
        x = params["embed"].astype(cdt)[ids]
        cos, sin = llama.rope_tables(cfg, ids.shape[1])
        x, _, _, recs = llama.run_layers(cfg, params, x, body)
        chosen = jnp.stack([r[0] for r in recs if r is not None])
        return forward(params, ids, cfg), chosen

    run = jax.jit(run)
    streams, logits, routing = [], [], []
    for p in prompts:
        ids, first = list(p), None
        for _ in range(4):
            row = jnp.asarray([ids + [0] * (48 - len(ids))])
            out, chosen = run(row)
            step = np.asarray(out)[0, len(ids) - 1]
            first = step if first is None else first
            ids.append(int(step.argmax()))
        streams.append(ids[len(p):])
        logits.append(first)
        routing.append(np.asarray(chosen)[:, :len(p) + 3])
    return cfg, params, prompts, streams, logits, routing


def test_the_check_passes_the_program_and_each_control_moves(served):
    cfg, params, prompts, streams, logits, routing = served
    good = correctness_sparse.served_check(params, cfg, prompts, streams, logits, routing)
    floors = correctness.LOGIT_FLOORS
    assert good["ok"], good
    assert good["prefill_floors"] <= floors and good["decode_floors"] <= floors
    assert good["tokens"] == 8 and good["choices"] == 4 * (27 + 43) * 4
    assert good["choices_agree_share"] > 0.95
    assert good["choice_shortfall_max"] <= correctness_sparse.CHOICE_EPS
    assert good["choice_shortfall_if_bias_ignored"] > correctness_sparse.CHOICE_EPS
    assert set(good["controls"]) == {"kv_of_another_request", "window_ignored",
                                     "gate_over_held_only", "rope_on_full_layers",
                                     "reference_in_fp8"}
    for name in ("kv_of_another_request", "window_ignored", "gate_over_held_only"):
        assert good["controls"][name] > floors, (name, good)
    assert all(v > 1.0 for v in good["controls"].values()), good


@pytest.mark.parametrize("fault", ["another_prompt", "another_routing"])
def test_the_check_refuses_a_served_answer_that_is_not_the_models(served, fault):
    cfg, params, prompts, streams, logits, routing = served
    rng = np.random.default_rng(1)
    if fault == "another_prompt":
        prompts = [rng.integers(0, cfg.vocab_size, len(p)).tolist() for p in prompts]
    else:  # experts that the reference's scores do not bear out
        routing = [(r + 1) % cfg.num_experts for r in routing]
    bad = correctness_sparse.served_check(params, cfg, prompts, streams, logits, routing)
    assert not bad["ok"], bad
    if fault == "another_routing":
        assert bad["choice_shortfall_max"] > correctness_sparse.CHOICE_EPS
        assert bad["choices_agree_share"] < 0.5


@pytest.mark.parametrize("fault, ok", [
    (None, True), ("exchange_left_out", False), ("one_worker_dropped", False),
    ("summed_not_averaged", False), ("snapshot_not_stepped", False)])
def test_the_sync_check_against_outer_steps_made_by_hand(fault, ok):
    """Four workers that parted from s0 on their own data; the fused
    round's snapshot is the Nesterov step over the mean of their deltas
    (momentum from 0), and each wrong exchange reads over the limit."""
    rng = np.random.default_rng(0)
    s0 = [rng.normal(0, 0.02, shape).astype(np.float32) for shape in ((6, 40), (40,))]
    before = [s + rng.normal(0, 4e-4, (4,) + s.shape).astype(np.float32) for s in s0]
    lr, mu = 0.7, 0.9
    took = {None: lambda p: p.mean(axis=0), "exchange_left_out": lambda p: p[0],
            "one_worker_dropped": lambda p: p[:3].mean(axis=0)}.get(fault)
    if fault == "snapshot_not_stepped":
        after = [s.copy() for s in s0]
    elif fault == "summed_not_averaged":
        after = [(s - lr * (1 + mu) * (4 * s - p.sum(axis=0))).astype(np.float32)
                 for s, p in zip(s0, before)]
    else:
        after = [(s - lr * (1 + mu) * (s - took(p))).astype(np.float32)
                 for s, p in zip(s0, before)]
    got = correctness_mesh.sync_check(s0, before, after, lr, mu)
    assert got["ok"] is ok, got
    assert got["exchange_left_out"] > correctness_mesh.SYNC_TOL
    if ok:
        assert got["distance"] < 1e-3
    assert got["sampled_values"] == 6 * 40 + 40
