"""A stack of block-sparse and linear attention layers (MiniCPM-SALA's
two mixers) against its plain reference, at a tiny size on the CPU:
hidden 64, four layers in the order sparse, lightning, lightning,
sparse; blocks of 4, top-2, compressed keys of 4 every 2, a window of 8,
a dense length of 16, contexts to 96. Everything in float32 under
``default_matmul_precision("highest")``.

The tolerance on logits, 2e-5 on a span of about 1.6: program and
reference are the same float32 mathematics summed in another order (a
chunk's decayed products and a carried state against 512 single steps;
a softmax over a gathered or masked set against one over every key with
-inf), which reads 2e-7 to 6e-7 here; a wrong block, a stale state or a
missed compressed key reads 1e-3 and more.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness_state as cs
from benchmark.reference import minicpm_sala_ref as ref
from nanodiloco_tpu.models import LlamaConfig, init_params
from nanodiloco_tpu.models import linear_attention, sparse_attention
from nanodiloco_tpu.models.generate import generate
from nanodiloco_tpu.models.llama import forward
from nanodiloco_tpu.serve import InferenceEngine
from nanodiloco_tpu.serve.scheduler import GenRequest, Scheduler

TOL = 2e-5
CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, explicit_head_dim=16, qk_norm=True,
    layer_types=("sparse_attention", "linear_attention", "linear_attention",
                 "sparse_attention"),
    rope_layers="linear", attn_output_gate=True, linear_output_gate=True,
    linear_output_norm=True, scale_emb=12.0, scale_depth=1.4, dim_model_base=32,
    published_layers=8, first_layer_index=2,
    sparse_block_size=4, sparse_topk=2, sparse_kernel_size=4, sparse_kernel_stride=2,
    sparse_init_blocks=1, sparse_window_size=8, sparse_dense_len=16,
    rms_norm_eps=1e-6, initializer_range=0.1, max_position_embeddings=256)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.key(0), CFG)
    # norm scales off 1, so that a norm in the wrong place shows
    def jitter(path, a):
        if a.ndim <= 2 and path[-1].key.endswith("norm"):
            return a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(jitter, p)


@pytest.fixture(scope="module")
def weights(params):
    return cs.reference_weights(params), cs.hyper(CFG)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(params, **kw):
    args = dict(num_slots=3, max_len=128, chunk_size=16, kv_block_size=4)
    args.update(kw)
    eng = InferenceEngine(params, CFG, **args)
    eng.capture_prefill_logits = eng.capture_decode_logits = eng.capture_routing = True
    return eng


def _reference_logits(weights, prompt, stream, n):
    w, hp = weights
    rows, at = cs.padded(prompt, stream, n, multiple=1)
    # one compiled program a shape: op by op every shape compiles alone
    logits, info = jax.jit(lambda r: ref.forward(w, r, hp, at=at, with_choice=True))(rows)
    return np.asarray(logits)[0], np.asarray(info["own"])[:, 0]


def _request(prompt, n_new=25):
    return GenRequest(prompt=prompt, max_new_tokens=n_new, temperature=0.0, seed=0)


def test_prefill_then_ticks_match_the_reference_and_choose_its_blocks(params, weights):
    """(a) and (e): chunked prefill, then 24 ticks through the three
    caches: the last prompt position's logits and every decoded step's
    against the reference's full forward pass; the blocks every query
    past the dense length chose are the reference's own, exactly."""
    eng = _engine(params)
    prompt = np.random.default_rng(1).integers(0, CFG.vocab_size, 71).tolist()
    stream = [eng.prefill(0, _request(prompt))]
    served = [eng.last_prefill_logits[0]]
    for _ in range(24):
        stream.append(eng.step()[0][0])
        served.append(eng.decode_logits_log[0][-1])
    want, own = _reference_logits(weights, prompt, stream, 25)
    assert np.max(np.abs(np.stack(served) - want)) < TOL
    chosen = np.concatenate(eng.routing_log[0], axis=1)          # [L, 95, Hkv, topk]
    past = np.arange(chosen.shape[1]) + 1 > CFG.sparse_dense_len
    assert chosen.shape[:2] == (2, 95) and past.sum() == 79
    np.testing.assert_array_equal(chosen[:, past], own[:, :95][:, past])
    assert (chosen[:, 23:] >= 0).all()  # from 24 keys on, two blocks lie between


def test_three_streams_in_one_house_and_a_slot_taken_again(params, weights):
    """(b): three streams of different lengths prefilled between each
    other's ticks; then slot 1 is released and taken by a fourth stream,
    whose lightning layers must start from a zero state and whose
    compressed keys must be its own."""
    eng = _engine(params)
    rng = np.random.default_rng(2)
    prompts = {s: rng.integers(0, CFG.vocab_size, n).tolist()
               for s, n in ((0, 33), (1, 57), (2, 96))}
    streams, served = {}, {}

    def admit(slot, prompt):
        eng.start_prefill(slot, _request(prompt, 24))
        while (tok := eng.prefill_step(slot)) is None:
            tick()  # the streams already live decode between its chunks
        streams[slot], served[slot] = [tok], [eng.last_prefill_logits[0]]

    def tick():
        for slot, toks in enumerate(eng.step()):
            if toks and len(streams[slot]) < 24:
                streams[slot].append(toks[0])
                served[slot].append(eng.decode_logits_log[slot][-1])

    for slot, prompt in prompts.items():
        admit(slot, prompt)
    for _ in range(10):
        tick()
    done = {1: (prompts[1], streams.pop(1), served.pop(1))}
    eng.release(1)
    again = rng.integers(0, CFG.vocab_size, 40).tolist()
    admit(1, again)
    for _ in range(12):
        tick()
    done.update({0: (prompts[0], streams[0], served[0]), 2: (prompts[2], streams[2], served[2]),
                 "again": (again, streams[1], served[1])})
    for name, (prompt, stream, logits) in done.items():
        want, _ = _reference_logits(weights, prompt, stream, len(logits))
        assert np.max(np.abs(np.stack(logits) - want)) < TOL, name


def test_a_slot_taken_again_starts_from_a_zero_state(params):
    """The state a stream leaves in its slot is still there when the
    slot is released (nothing clears it on the host) and gone after the
    next stream's first chunk."""
    eng = _engine(params)
    rng = np.random.default_rng(3)
    eng.prefill(0, _request(rng.integers(0, CFG.vocab_size, 40).tolist()))
    state = lambda: np.asarray(eng.pool["period"][1]["s"])[0, 0]
    left = state()
    assert np.abs(left).max() > 0
    eng.release(0)
    np.testing.assert_array_equal(state(), left)
    short = rng.integers(0, CFG.vocab_size, 3).tolist()
    eng.prefill(0, _request(short))
    solo = _engine(params)
    solo.prefill(0, _request(short))
    np.testing.assert_allclose(state(), np.asarray(solo.pool["period"][1]["s"])[0, 0],
                               rtol=0, atol=1e-7)


@pytest.mark.parametrize("t,carried,real", [(16, False, 16), (64, True, 64), (64, True, 37),
                                           (512, True, 512)])
def test_chunked_lightning_is_the_step_recurrence(t, carried, real):
    """(c): a chunk's outputs and the state it leaves are T single
    steps', from a zero or a carried state, with a right-padded chunk
    leaving the state at its last real token. At T = 512 the fastest
    head's decay (0.43 a token) would leave float32 if its powers were
    ever inverted."""
    h, hd = 4, 16
    ks = jax.random.split(jax.random.key(t + real), 4)
    q, k, v = (jax.random.normal(ks[i], (2, t, h, hd)) for i in range(3))
    state = jax.random.normal(ks[3], (2, h, hd, hd)) if carried else jnp.zeros((2, h, hd, hd))
    ld = jnp.asarray([-0.84, -0.3, -0.02, -0.0014])
    o, new = linear_attention.chunk(q, k, v, state, ld, jnp.asarray([real, real]))
    def one(s, qkv):
        oi, s = linear_attention.step(*qkv, s, ld)
        return s, oi

    s, outs = jax.lax.scan(one, state, tuple(jnp.moveaxis(x[:, :real], 1, 0) for x in (q, k, v)))
    assert bool(jnp.isfinite(o).all())
    # sums of up to 512 products of unit normals, some cancelling: 2e-5 of
    # the largest entry
    for got, want in ((o[:, :real], jnp.moveaxis(outs, 0, 1)), (new, s)):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * float(jnp.abs(want).max()))


def test_a_dead_row_keeps_its_state():
    q, k, v = (jnp.ones((2, 4, 16)) for _ in range(3))
    state = jnp.full((2, 4, 16, 16), 0.5)
    _, new = linear_attention.step(q, k, v, state, jnp.full((4,), -0.1), jnp.asarray([1, 0]))
    assert float(jnp.abs(new[0] - state[0]).max()) > 0
    np.testing.assert_array_equal(new[1], state[1])


@pytest.mark.parametrize("cuts", [(0, 16, 32, 45), (0, 13, 45), (0, 45)])
def test_a_chunks_choice_is_every_querys_own(weights, cuts):
    """(d): chunks whose queries straddle the dense length (16) and end
    where n is no multiple of the stride (2) or the block (4) choose,
    query by query, what the reference's per-query selection chooses,
    and attend to the same keys."""
    _, hp = weights
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 45, 4, 16))
    k, v = (jax.random.normal(ks[i], (1, 45, 2, 16)) for i in (1, 2))
    want, own, _ = ref._sparse(q, k, v, hp, jnp.float32, None, None)
    comp = sparse_attention.compress_keys(CFG, k)
    ck, cv = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    for lo, hi in zip(cuts, cuts[1:]):
        qpos = jnp.arange(lo, hi)[None]
        out, idx, counts = sparse_attention.masked(
            CFG, q[:, lo:hi], ck, cv, comp, qpos, jnp.ones_like(qpos))
        np.testing.assert_allclose(out, want[:, lo:hi].reshape(1, hi - lo, -1), atol=TOL)
        past = np.arange(lo, hi) + 1 > CFG.sparse_dense_len
        np.testing.assert_array_equal(np.asarray(idx)[0][past], np.asarray(own)[0, lo:hi][past])
        assert int(counts[3]) == past.sum()


def test_query_blocks_change_nothing():
    """``masked`` over 128 queries at once and in two blocks of 64."""
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 16))
    k, v = (jax.random.normal(ks[i], (1, 2, 128, 16)) for i in (1, 2))
    comp = sparse_attention.compress_keys(CFG, k.transpose(0, 2, 1, 3))
    qpos = jnp.arange(128)[None]
    one = sparse_attention.masked(CFG, q, k, v, comp, qpos, jnp.ones_like(qpos), q_block=128)
    two = sparse_attention.masked(CFG, q, k, v, comp, qpos, jnp.ones_like(qpos), q_block=64)
    np.testing.assert_allclose(one[0], two[0], atol=1e-6)
    np.testing.assert_array_equal(one[1], two[1])
    np.testing.assert_array_equal(one[2], two[2])


@pytest.mark.parametrize("s", [12, 40, 96])
def test_forward_is_the_reference(params, weights, s):
    """(f): the full forward pass, under and over the dense length."""
    w, hp = weights
    tokens = np.random.default_rng(s).integers(0, CFG.vocab_size, (2, s))
    got = jax.jit(lambda t: forward(params, t, CFG))(jnp.asarray(tokens))
    assert np.max(np.abs(got - jax.jit(lambda t: ref.forward(w, t, hp))(tokens))) < TOL


def test_the_programs_decays_are_the_references(params, weights):
    _, hp = weights
    for i in (1, 2):
        np.testing.assert_allclose(params["layers"][i]["log_decay"][0],
                                   ref.log_decay(hp, hp["layer_indices"][i]), rtol=1e-6)
    assert CFG.residual_scale == pytest.approx(1.4 / 8 ** 0.5)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_control_moves_the_reference(weights, fault):
    w, hp = weights
    tokens = np.random.default_rng(5).integers(0, CFG.vocab_size, (1, 96))
    run = jax.jit(lambda t, fault: ref.forward(w, t, hp, fault=fault), static_argnums=1)
    moved = np.max(np.abs(run(tokens, fault) - run(tokens, None)))
    assert moved > 100 * TOL


@pytest.mark.parametrize("kwargs,word", [
    ({"spec_k": 2}, "cannot step back"),
    ({"prefix_cache_tokens": 64}, "no snapshot of a state"),
    ({"kv_dtype": "int8"}, "no quantized form"),
    ({"tp": 2}, "no partition rule"),
])
def test_the_engine_refuses_by_name(params, kwargs, word):
    """(g): each feature the three caches do not carry says which cache
    kind stops it."""
    with pytest.raises(ValueError, match=word):
        _engine(params, **kwargs)


def test_export_import_generate_and_training_refuse_by_name(params):
    eng = _engine(params)
    eng.prefill(0, _request([1, 2, 3]))
    with pytest.raises(ValueError, match="per-slot state"):
        eng.export_kv(0)
    with pytest.raises(ValueError, match="per-slot state"):
        eng.import_kv(1, _request([1, 2, 3]), None)
    with pytest.raises(ValueError, match="serving engine only"):
        generate(params, jnp.asarray([[1, 2, 3]]), CFG, max_new_tokens=2)
    with pytest.raises(ValueError, match="takes whole sequences"):
        forward(params, jnp.ones((1, 8), jnp.int32), CFG, attn_mask=jnp.ones((1, 8), jnp.int32))
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

    with pytest.raises(ValueError, match="no backward pass"):
        Diloco(CFG, DilocoConfig(num_workers=1), build_mesh(MeshConfig(diloco=1)))


@pytest.mark.parametrize("change,word", [
    ({"sparse_kernel_stride": 3}, "must divide"),
    ({"sparse_dense_len": 10}, "may not overlap"),
    ({"num_experts": 4, "moe_dispatch": "ragged"}, "beside expert layers"),
    ({"layer_types": ("sparse_attention", "ring_attention", "linear_attention",
                      "sparse_attention")}, "layer_types must be of"),
])
def test_the_configuration_refuses_what_it_cannot_run(change, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(CFG, **change)


def test_attn_stats_count_what_the_shapes_say(params):
    """(h): 41 prompt tokens in chunks of 16, then 20 ticks, through 2
    sparse and 2 lightning layers, counted by hand."""
    eng = _engine(params)
    eng.prefill(0, _request(list(range(41))))
    for _ in range(20):
        eng.step()
    got = eng.attn_stats()["by_program"]
    # a choosing query at position t attends to block 0 (4 rows), the
    # window's blocks (keys (t + 1 - 8) // 4 * 4 .. t) and 2 chosen blocks
    # of the blocks that lie between (one alone up to 19 keys)
    rows = lambda t: 4 + (t + 1 - (t + 1 - 8) // 4 * 4) + 4 * min(2, (t + 1 - 8) // 4 - 1)
    done = lambda t: (t + 1 - 4) // 2 + 1
    for kind, ts, updates in (("prefill_chunk", range(16, 41), 3), ("decode", range(41, 61), 20)):
        assert got[kind] == {
            "sparse_rows_read": 2 * sum(rows(t) for t in ts),
            "sparse_rows_held": 2 * sum(t + 1 for t in ts),
            "sparse_compressed_rows": 2 * sum(done(t) for t in ts),
            "sparse_queries": 2 * len(ts), "state_updates": 2 * updates}, kind
    assert eng.moe_stats() is None
    kv = eng.kv_stats()
    assert eng.kv_layout == "paged-compressed-state"
    blocks = eng.block_pool.num_blocks
    assert {k: v for k, v in kv["kv_bytes_by_kind"].items() if v} == {
        "sparse_attention": 2 * 2 * blocks * 4 * 2 * 16 * 4,   # layers, k and v, rows, f32
        "compressed_keys": 2 * 3 * (128 + 16) // 2 * 2 * 16 * 4,  # a row a stride of a table
        "linear_attention": 2 * 3 * 4 * 16 * 16 * 4}           # layers x slots x [H, hd, hd]


def test_the_scheduler_and_the_metrics_page_carry_the_counters(params):
    from nanodiloco_tpu.serve import ServeServer

    eng = _engine(params)
    sched = Scheduler(eng, max_queue=4)
    server = ServeServer(sched, None, port=0, host="127.0.0.1")
    ticket = sched.submit(_request(list(range(30)), 4))
    while not ticket.done():
        sched.tick()
    attn = sched.stats()["attn"]
    assert attn["sparse_queries"] == 2 * (14 + 3) and attn["state_updates"] == 2 * (2 + 3)
    page = server.render_metrics()
    assert 'nanodiloco_attn_sparse_rows_read_total{program="decode"}' in page
    assert 'nanodiloco_attn_state_updates_total{program="prefill_chunk"} 4' in page
    server._httpd.server_close()


def test_a_minicpm_sala_checkpoint_is_refused_by_name(tmp_path):
    from nanodiloco_tpu.models import hf_interop

    (tmp_path / "config.json").write_text(json.dumps({"model_type": "minicpm_sala"}))
    with pytest.raises(ValueError, match="minicpm_sala checkpoint.*w_og"):
        hf_interop.from_hf_pretrained(str(tmp_path), LlamaConfig())
