"""A mixed layer stack (models/config.py ``mixed``) against the plain
reference ``benchmark/reference/exaone_moe_ref.py`` on seeded weights:
a tiny preset with every mechanism of K-EXAONE-236B-A23B (1 dense + 4
sparse layers, sliding and full attention with a window of 8, 16
experts top-4 behind a sigmoid gate whose selection bias changes the
choice, 1 shared expert, q/k norms, RoPE on sliding layers only, a head
size that is not hidden / heads).
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness_sparse as cs
from benchmark.reference import exaone_moe_ref as ref
from nanodiloco_tpu.models import LlamaConfig, init_params
from nanodiloco_tpu.models.generate import (
    decode_slots_mixed_fn,
    decode_slots_paged_fn,
    generate,
    init_kv_pool,
    init_mixed_serve_cache,
    prefill_chunk_mixed_fn,
    prefill_chunk_paged_fn,
    verify_slots_paged_fn,
    view_ladder,
)
from nanodiloco_tpu.models.llama import causal_lm_loss, forward, layer_plan, sp_shard_loss
from nanodiloco_tpu.models.moe import sparse_mlp
from nanodiloco_tpu.ops.pipeline import _pipeline_setup
from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
from nanodiloco_tpu.serve import InferenceEngine
from nanodiloco_tpu.serve.scheduler import GenRequest, Scheduler

L, G = "sliding_attention", "full_attention"
TINY = LlamaConfig(
    vocab_size=128, hidden_size=48, intermediate_size=96, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, explicit_head_dim=16,
    layer_types=(L, L, L, G, L), sliding_window=8, qk_norm=True, rope_layers="sliding",
    first_k_dense_replace=1, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, scoring_func="sigmoid",
    routed_scaling_factor=2.5, moe_dispatch="ragged", loss_chunk=0,
    initializer_range=0.1)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.key(0), TINY)
    # a selection bias large enough to change the top-4 of most tokens
    p["layers"] = tuple(
        {**layer, "router_bias": 0.3 * jax.random.normal(
            jax.random.key(5 + j), layer["router_bias"].shape)}
        for j, layer in enumerate(p["layers"]))
    return p


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 40), 0, TINY.vocab_size)


@pytest.fixture(autouse=True)
def _highest(request):
    if "lowers" in request.node.name:  # the recorded text is the default's
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _held(params, first, count):
    """The weights of a chip that holds experts first..first+count-1."""
    out = dict(params)
    out["layers"] = tuple(
        {k: (v[:, first:first + count] if k in ("w_gate", "w_up", "w_down") else v)
         for k, v in layer.items()} for layer in params["layers"])
    return out


def _ref_forward(cfg, **kw):
    return jax.jit(lambda w, t: ref.forward(w, t, cs.hyper(cfg), **kw))


def test_head_dim_is_given_and_the_bias_changes_the_choice(params, tokens):
    assert TINY.head_dim == 16 != TINY.hidden_size // TINY.num_attention_heads
    # a checkpoint's model_config.json sidecar: through JSON and back
    assert LlamaConfig.from_dict(json.loads(json.dumps(TINY.to_dict()))) == TINY
    hf = {"head_dim": 16, "hidden_size": 48, "num_attention_heads": 4,
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert LlamaConfig.from_dict(hf).head_dim == 16
    assert LlamaConfig.from_dict(hf).rope_theta == 1e6
    w = cs.reference_weights(params)
    _, scores = _ref_forward(TINY, with_scores=True)(w, tokens)
    bias = w["layers"][1]["router_bias"]
    with_b = jax.lax.top_k(scores[0], 4)[1]
    without = jax.lax.top_k(scores[0] - bias, 4)[1]
    assert float(jnp.mean(jnp.sort(with_b) != jnp.sort(without))) > 0.2


def test_forward_logits_match_the_reference(params, tokens):
    got = forward(params, tokens, TINY)
    want = _ref_forward(TINY)(cs.reference_weights(params), tokens)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("loss_chunk,remat", [(0, False), (16, True)])
def test_loss_gradients_match_the_reference(params, tokens, loss_chunk, remat):
    cfg = dataclasses.replace(TINY, loss_chunk=loss_chunk, remat=remat)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: causal_lm_loss(p, tokens, cfg)[0]))(params)
    w = cs.reference_weights(params)
    want, gref = jax.jit(jax.value_and_grad(
        lambda w: ref.loss(w, tokens, cs.hyper(cfg))))(w)
    assert abs(float(loss) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(cs.reference_weights(grads)), jax.tree.leaves(gref)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(gref)) > 0.05


def test_generate_decodes_as_the_reference_does(params, tokens):
    prompt = tokens[:, :12]
    got = generate(params, prompt, TINY, 20)  # positions 12..31: past the window
    seq = jnp.concatenate([prompt, got], axis=1)
    logits = _ref_forward(TINY)(cs.reference_weights(params), seq)
    np.testing.assert_array_equal(got, jnp.argmax(logits[:, 11:-1], -1))


def test_engine_chunks_then_ticks_match_the_reference_full_pass(params):
    """Prefill in chunks of 8, then 40 decode ticks: the ring of 16 rows
    wraps several times; slot 0 is released and reused without clearing;
    the chip's share (experts 4..11) is the reference's."""
    cfg = dataclasses.replace(TINY, experts_held=(4, 8))
    held = _held(params, 4, 8)
    eng = InferenceEngine(held, cfg, num_slots=2, max_len=128, chunk_size=8,
                          kv_block_size=4)
    eng.capture_prefill_logits = True
    rng = np.random.default_rng(0)
    w = cs.reference_weights(held)
    for slot, n_prompt, n_new in ((0, 21, 41), (0, 13, 30), (1, 5, 10)):
        prompt = rng.integers(0, cfg.vocab_size, n_prompt).tolist()
        out = [eng.prefill(slot, GenRequest(prompt=tuple(prompt), max_new_tokens=n_new))]
        served = np.array(eng.last_prefill_logits[0])
        while len(out) < n_new:
            out.append(eng.step()[slot][0])
        want = np.asarray(_ref_forward(cfg, held=(4, 8))(w, jnp.asarray([prompt + out])))
        np.testing.assert_allclose(served, want[0, n_prompt - 1], atol=2e-5)
        assert out == [int(t) for t in want[0, n_prompt - 1:-1].argmax(-1)]
        eng.release(slot)
    kv = eng.kv_stats()
    assert kv["ring_rows_per_slot"] == 16 and kv["layers_by_kind"] == {G: 1, L: 4}
    row = 2 * 2 * 16 * 4  # k and v, 2 KV heads of 16, float32
    assert kv["kv_bytes_by_kind"] == {G: 2 * 32 * 4 * row, L: 4 * 2 * 16 * row}
    moe = eng.moe_stats()
    tokens_run = (21 + 40) + (13 + 29) + (5 + 9)
    assert moe["moe_pairs"] == 4 * 4 * tokens_run  # k a token a sparse layer
    assert 0 < moe["moe_experts_hit"] <= moe["moe_held_pairs"] < moe["moe_pairs"]
    assert eng.compile_counts()["prefill_chunk:paged-rings"] == 1


def test_the_shares_add_up(params, tokens):
    """Over 4 shares of 4 experts, the routed parts summed and the shared
    expert counted once equal the uncut reference's layer output."""
    layer = {k: v[0] for k, v in params["layers"][1].items()}  # layer 2
    h = jax.random.normal(jax.random.key(3), (2, 24, TINY.hidden_size))
    w = cs.reference_weights(params)["layers"][2]
    hp = cs.hyper(TINY)
    mm = lambda x, y: x @ y
    weights, _ = ref.gate(h, w, hp, mm)
    want = ref._swiglu(mm, h, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(16):
        want = want + weights[..., e, None] * ref._swiglu(
            mm, h, w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e])
    shared = {k: v for k, v in layer.items() if k.startswith("shared_")}
    only_shared = sparse_mlp(
        dataclasses.replace(TINY, experts_held=(0, 1)),
        h, {**{k: v for k, v in layer.items() if k not in shared},
            **{k: jnp.zeros_like(v[:1]) for k, v in layer.items()
               if k in ("w_gate", "w_up", "w_down")}, **shared})[0]
    total = only_shared  # the shared expert, counted once
    pairs = 0
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(TINY, experts_held=(first, 4))
        part = {k: (v[first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in layer.items() if k not in shared}
        y, counters, _ = sparse_mlp(cfg, h, part)
        total = total + y
        pairs += int(counters[0])
    assert pairs == 4 * 2 * 24  # every pair is held by exactly one share
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_48_layers_compile_as_one_period_does():
    kinds = tuple(G if i % 4 == 3 else L for i in range(48))
    plan = layer_plan(dataclasses.replace(TINY, num_hidden_layers=48, layer_types=kinds))
    assert (plan.lead, plan.period, plan.periods) == (4, 4, 11)
    assert layer_plan(TINY).lead == 1 and layer_plan(TINY).period == 4


def test_the_trainer_steps_a_mixed_configuration(params):
    cfg = dataclasses.replace(TINY, remat=True, loss_chunk=16)
    dl = Diloco(cfg, DilocoConfig(num_workers=2, inner_steps=2, lr=3e-3, warmup_steps=1,
                                  total_steps=50),
                build_mesh(MeshConfig(diloco=2), devices=jax.devices()[:2]))
    state = dl.init_state(jax.random.key(0), params=params)
    tok = jnp.broadcast_to(jax.random.randint(jax.random.key(2), (1, 1, 2, 32), 0, 128),
                           (2, 1, 2, 32))
    losses = []
    for _ in range(4):
        # a mixed sparse configuration's step hands out what its expert
        # layers did, a row a worker (a sigmoid gate has no balance term)
        state, loss, stats = dl.inner_step(state, tok, jnp.ones_like(tok))
        losses.append(float(loss[0]))
        assert stats["moe_counters"].shape == (2, 5) and float(stats["router_aux"][0]) == 0.0
        assert int(stats["moe_counters"][0, 2]) == 4 * 64 * 4  # sparse layers x tokens x k
    state = dl.outer_step(state)
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


def _engine(cfg=TINY, **kw):
    kw = {"num_slots": 2, "max_len": 64, "chunk_size": 8, "kv_block_size": 4, **kw}
    return InferenceEngine(init_params(jax.random.key(0), cfg), cfg, **kw)


REFUSED = {
    "moe_dispatch_dense": (lambda: dataclasses.replace(TINY, moe_dispatch="dense"),
                           "moe_dispatch='dense'"),
    "experts_choose": (lambda: dataclasses.replace(TINY, router_type="experts_choose"),
                       "experts_choose"),
    "flash": (lambda: dataclasses.replace(TINY, attention_impl="flash"), "flash"),
    "ring": (lambda: dataclasses.replace(TINY, attention_impl="ring"), "ring"),
    "pipeline": (lambda: _pipeline_setup(TINY, 16, None), "pipeline stages"),
    "sp_loss": (lambda: sp_shard_loss(None, None, TINY, None, "sp"),
                "sequence-parallel loss"),
    "ep": (lambda: Diloco(TINY, DilocoConfig(num_workers=1),
                          build_mesh(MeshConfig(ep=2), devices=jax.devices()[:2])),
           "ep=1"),
    "speculation": (lambda: _engine(spec_k=2), "speculation"),
    "prefix_cache": (lambda: _engine(prefix_cache_tokens=64), "prefix cache"),
    "int8": (lambda: _engine(kv_dtype="int8"), "int8"),
    "unpaged": (lambda: _engine(kv_block_size=0), "kv_block_size"),
    "serving_mesh": (lambda: _engine(tp=2), "serving mesh"),
    "export_kv": (lambda: _engine().export_kv(0), "export_kv"),
    "import_kv": (lambda: _engine().import_kv(0, None, None), "import_kv"),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_is_not_carried_over_refuses_by_name(path):
    call, named = REFUSED[path]
    with pytest.raises(ValueError, match=named):
        call()


DENSE_TOY = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2)
# softmax top-2 of 4 experts, all held, grouped products: moe_mlp -> _ragged_mlp
RAGGED_TOY = dataclasses.replace(DENSE_TOY, num_experts=4, num_experts_per_tok=2,
                                 moe_dispatch="ragged")
_S = jax.ShapeDtypeStruct


def _lower_toy(cfg, program):
    """One of the five programs the dense cells run, at a toy's size."""
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    tok = _S((2, 32), i32)
    if program == "forward":
        return jax.jit(lambda p, t: forward(p, t, cfg)).lower(shapes, tok)
    if program == "loss_gradient":
        return jax.jit(jax.grad(lambda p, t: causal_lm_loss(p, t, cfg)[0])).lower(shapes, tok)
    if program == "fused_round":
        dl = Diloco(cfg, DilocoConfig(num_workers=1, inner_steps=2, lr=1e-3, warmup_steps=1,
                                      total_steps=8),
                    build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1]))
        state = jax.eval_shape(lambda: dl.init_state(jax.random.key(0)))
        return dl._round_jit.lower(state, _S((2, 1, 1, 2, 32), i32), _S((2, 1, 1, 2, 32), i32))
    if program == "fused_round_4dev":  # the four-chip cell's layout: a worker a device
        dl = Diloco(cfg, DilocoConfig(num_workers=4, inner_steps=2, lr=1e-3, warmup_steps=1,
                                      total_steps=8),
                    build_mesh(MeshConfig(diloco=4), devices=jax.devices()[:4]))
        state = dl.init_state(jax.random.key(0))
        with jax.set_mesh(dl.mesh):
            return dl._round_jit.lower(
                state, _S((2, 4, 1, 2, 32), i32), _S((2, 4, 1, 2, 32), i32))
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 16, 4))
    if program == "paged_chunk":
        return prefill_chunk_paged_fn(cfg).lower(
            shapes, pool, _S((8,), i32), _S((1, 8), i32), _S((1, 8), i32), _S((), i32),
            _S((), i32), _S((2,), u32), _S((), f32), _S((), i32), _S((), f32))
    assert program == "paged_tick"
    return _lower_tick(cfg, "tick", 1)


def _lower_tick(cfg, program, mb):
    """A tick program of the serve engine over tables of ``mb`` blocks:
    ``tick`` or ``verify`` (three positions a slot), ``_int8`` for
    int8 rows; for the mixed stack ``tick`` or ``chunk`` (four tokens)."""
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    slot = (_S((2,), f32), _S((2,), i32), _S((2,), f32), _S((2,), i32))
    if cfg.mixed:
        cache = jax.eval_shape(lambda: init_mixed_serve_cache(cfg, 2, 16, 32, 4))
        if program == "chunk":
            return prefill_chunk_mixed_fn(cfg).lower(
                shapes, cache, _S((mb,), i32), _S((), i32), _S((1, 4), i32), _S((1, 4), i32),
                _S((), i32), _S((), i32), _S((2,), u32), _S((), f32), _S((), i32), _S((), f32))
        return decode_slots_mixed_fn(cfg).lower(
            shapes, cache, _S((2, mb), i32), _S((2,), i32), _S((2,), i32), _S((2, 2), u32), *slot)
    kv = "int8" if program.endswith("_int8") else None
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 16, 4, kv))
    if program.startswith("verify"):
        return verify_slots_paged_fn(cfg, kv).lower(
            shapes, pool, _S((2, mb), i32), _S((2, 3), i32), _S((2,), i32), _S((2,), i32),
            _S((2, 3, 2), u32), *slot)
    return decode_slots_paged_fn(cfg, kv).lower(
        shapes, pool, _S((2, mb), i32), _S((2,), i32), _S((2,), i32), _S((2, 2), u32), *slot)


# sha256 of the lowered text under jax 0.9.0. "dense": the programs of
# the dense cells; forward's was taken on the commit before the mixed
# stack (b70a8d0), the rest on the commit before the short path of
# models/moe.py (98f3879), as were "ragged"'s: a configuration that
# holds all its experts has no short path and lowers as it did. The
# ticks' since PR 30 over tables of ONE block, taken on the commit
# before the ladder of view widths (08b6a60): a ladder of one width
# emits no branch.
LOWERED = {
    ("dense", "forward"): "4cc0a5e8fbc000fa",
    ("dense", "loss_gradient"): "0a25538edc8f2e24",
    ("dense", "paged_chunk"): "22fd6380ece8aa8e",
    ("dense", "paged_tick"): "36ba83875010c97a",
    ("dense", "fused_round"): "2236461a9273367d",
    # four workers, one a device, taken on the commit before PR 32 (55034a2)
    ("dense", "fused_round_4dev"): "7b995d4f44e9668b",
    ("ragged", "forward"): "a67ab44a2c55b2b3",
    ("ragged", "loss_gradient"): "1dfd52542ec6df87",
    ("ragged", "paged_chunk"): "4c6051755649e1bf",
    ("ragged", "paged_tick"): "d4f3c9d1e798c798",
    ("ragged", "fused_round"): "d51b6466d5cc44df",
}


@pytest.mark.parametrize("toy,program", sorted(LOWERED))
def test_a_dense_configuration_lowers_to_the_program_it_had(toy, program):
    """The fields of a mixed configuration leave a dense one's programs
    alone, and the short path of the grouped products those of a
    configuration that holds all its experts."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    cfg = {"dense": DENSE_TOY, "ragged": RAGGED_TOY}[toy]
    assert not cfg.mixed
    text = _lower_toy(cfg, program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest().startswith(LOWERED[toy, program])
    if program in ("forward", "loss_gradient", "fused_round", "fused_round_4dev"):
        # sampling has a case of its own
        assert "stablehlo.case" not in text and "stablehlo.if" not in text


# -- the ladder of view widths (models/generate.py ``view_ladder``) ----------

# sha256 of the lowered text of the tick programs over tables of ONE
# block on the commit before the ladder (08b6a60), jax 0.9.0
ONE_RUNG = {
    ("dense", "tick_int8"): "19960ff43ba37937",
    ("dense", "verify"): "0040eb21b53023ad",
    ("dense", "verify_int8"): "c821b97e364966a6",
    ("mixed", "chunk"): "1eadc95d19219e28",
    ("mixed", "tick"): "96820e4f7a6b4953",
}


@pytest.mark.parametrize("toy,program", sorted(ONE_RUNG))
def test_a_table_of_one_width_lowers_to_the_program_it_had(toy, program):
    """A ladder of one width emits no branch: the text is the parent's.
    Over a table of 8 blocks the same program holds one conditional
    more, with a branch a width."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    cfg = {"dense": DENSE_TOY, "mixed": TINY}[toy]
    text = _lower_tick(cfg, program, 1).as_text()
    assert hashlib.sha256(text.encode()).hexdigest().startswith(ONE_RUNG[toy, program])
    wide = _lower_tick(cfg, program, 8).as_text()
    assert wide.count("stablehlo.case") == text.count("stablehlo.case") + 1
    assert len(view_ladder(8)) == 8 and view_ladder(1) == (1,)


def _serve_all(eng, reqs, ticks=200):
    sched = Scheduler(eng)
    tickets = [sched.submit(r) for r in reqs]
    for _ in range(ticks):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            break
    return [t.result["tokens"] for t in tickets]


def _prompt(n, seed):
    return tuple(np.random.default_rng(seed).integers(0, TINY.vocab_size, n).tolist())


# max_len 64 in blocks of 4 and chunks of 8: a table of 18 blocks, read at
# 12, 20, 28, 36, 48, 56, 64 or 72 rows
VIEW_STREAMS = {
    # the short stream (6-13 rows) ticks at 12 rows while the long one's
    # prompt is still in chunks, then beside its 42-48 rows at 48
    "short_beside_long": [(41, 8), (5, 8)],
    # rows 10..39 of one stream: the tick passes 12, 20, 28, 36 and 48 rows
    "crosses_widths_mid_decode": [(9, 30)],
    # the final chunk starts at 56 with 7 tokens and pads to 8: rows up to
    # 64, the top of the slot's allocation, in a view of 64
    "padded_chunk_at_the_top": [(63, 1)],
}


@pytest.mark.parametrize("streams", sorted(VIEW_STREAMS))
def test_mixed_streams_hold_through_the_view_widths(params, streams):
    """The full layer's read through the block table at the width the
    positions ask for: every stream is solo ``generate()``'s."""
    eng = InferenceEngine(params, TINY, num_slots=2, max_len=64, chunk_size=8,
                          kv_block_size=4)
    assert [w * 4 for w in view_ladder(eng.table_blocks)] == [12, 20, 28, 36, 48, 56, 64, 72]
    reqs = [GenRequest(prompt=_prompt(n, 7 + i), max_new_tokens=new)
            for i, (n, new) in enumerate(VIEW_STREAMS[streams])]
    got = _serve_all(eng, reqs)
    for r, tokens in zip(reqs, got):
        want = generate(params, jnp.asarray([r.prompt]), TINY, r.max_new_tokens)
        assert tokens == np.asarray(want[0]).tolist()
    taken = {int(r) for r in eng.kv_stats()["ticks_by_view"]}
    assert taken == {"short_beside_long": {12, 48},
                     "crosses_widths_mid_decode": {12, 20, 28, 36, 48},
                     "padded_chunk_at_the_top": set()}[streams]


def test_every_view_width_is_one_mixed_tick_program():
    """A stream that grows through all eight widths (chunks of one
    block: the table's last width is within a tick's reach) runs one
    decode executable and the chunk buckets it dispatched."""
    cfg = dataclasses.replace(TINY, initializer_range=0.11)  # programs of its own
    eng = _engine(cfg, chunk_size=4)
    out, = _serve_all(eng, [GenRequest(prompt=_prompt(5, 3), max_new_tokens=59)])
    assert len(out) == 59
    kv = eng.kv_stats()
    assert [int(r) for r in kv["ticks_by_view"]] == [w * 4 for w in view_ladder(17)]
    assert sum(kv["ticks_by_view"].values()) == 58
    counts = eng.compile_counts()
    assert counts["decode:paged-rings"] == 1
    assert counts["prefill_chunk:paged-rings"] == len(counts["buckets"]["prefill_chunk"]) == 2


# -- the short path of the grouped products, through the serve programs -----

# the chip's share as the benchmark's cell has it: an eighth of the experts
SHARE = dataclasses.replace(TINY, experts_held=(6, 2))


def _grouped_rows(jaxpr, under=()):
    """[(the conditionals' branch indices above it, lhs rows)] of every
    grouped product in ``jaxpr`` and the jaxprs inside it."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name.startswith("ragged_dot"):
            found.append((under, e.invars[0].aval.shape[0]))
        for name, val in e.params.items():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for i, sub in enumerate(subs):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    branch = under + (i,) if e.primitive.name == "cond" else under
                    found += _grouped_rows(inner, branch)
    return found


@pytest.mark.parametrize("cfg,chunk,want", [
    # 4 x 64 pairs, 32 expected here: 128 rows in the true branch, 256 in the other
    (SHARE, 64, {(1,): [128] * 12, (0,): [256] * 12}),
    # 4 x 32 pairs: 128 rows would be all of them, so one body and no conditional
    (SHARE, 32, {(): [128] * 12}),
    (TINY, 64, {(): [256] * 12}),  # all experts held
], ids=["a_share", "a_share_short_chunk", "all_held"])
def test_the_chunk_program_holds_both_row_counts(cfg, chunk, want):
    shapes = jax.eval_shape(lambda: _held(init_params(jax.random.key(0), TINY),
                                          *cfg.held_experts))
    cache = jax.eval_shape(lambda: init_mixed_serve_cache(cfg, 2, 8 + chunk, 32, 4))
    i32, f32 = jnp.int32, jnp.float32
    jaxpr = jax.make_jaxpr(prefill_chunk_mixed_fn(cfg))(
        shapes, cache, _S((32,), i32), _S((), i32), _S((1, chunk), i32),
        _S((1, chunk), i32), _S((), i32), _S((), i32), _S((2,), jnp.uint32),
        _S((), f32), _S((), i32), _S((), f32))
    got = {}
    for under, rows in _grouped_rows(jaxpr.jaxpr):
        got.setdefault(under, []).append(rows)
    assert got == want  # three products a sparse layer, four layers


def test_the_short_path_is_counted_by_program_kind(params):
    """Chunks of 64 tokens take the short path in all four sparse layers
    (some 32 of 256 pairs are held of 128 rows), a tick of 2 slots has
    none; the scheduler's stats carry the count by program kind, and the
    served logits are the reference's for this share."""
    held = _held(params, 6, 2)
    eng = InferenceEngine(held, SHARE, num_slots=2, max_len=192, chunk_size=64,
                          kv_block_size=4)
    eng.capture_prefill_logits = True
    sched = Scheduler(eng)
    prompt = np.random.default_rng(0).integers(0, SHARE.vocab_size, 128).tolist()
    ticket = sched.submit(GenRequest(prompt=tuple(prompt), max_new_tokens=6))
    for _ in range(20):
        if sched.tick() == 0 and ticket.done():
            break
    out = ticket.result["tokens"]
    want = np.asarray(_ref_forward(SHARE, held=(6, 2))(
        cs.reference_weights(held), jnp.asarray([prompt + out])))
    np.testing.assert_allclose(np.array(eng.last_prefill_logits[0]), want[0, 127], atol=2e-5)
    assert out == [int(t) for t in want[0, 127:-1].argmax(-1)]
    moe = sched.stats()["moe"]
    assert moe == eng.moe_stats()
    chunks, ticks = moe["by_program"]["prefill_chunk"], moe["by_program"]["decode"]
    assert chunks["moe_short_path"] == 4 * 2  # two chunks, four sparse layers each
    assert chunks["moe_pairs"] == 4 * 4 * 128
    assert 0 < chunks["moe_held_pairs"] <= 2 * 4 * 128  # under the rows the short path takes
    assert ticks["moe_short_path"] == 0 and ticks["moe_pairs"] == 4 * 4 * 5
    assert moe["moe_short_path"] == 8
    # a configuration that holds all its experts never counts one
    assert _engine().moe_stats()["moe_short_path"] == 0
