"""Attention kernels: blockwise (flash-style) and ring attention must match
dense attention exactly (up to fp32 reassociation), including under grad."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from nanodiloco_tpu.models.llama import (
    causal_mask,
    dense_attention,
    dense_block_rows,
    dense_score_share,
)
from nanodiloco_tpu.ops.flash_attention import flash_attention
from nanodiloco_tpu.ops.ring_attention import ring_attention


def qkv(key, b=2, s=64, h=4, hd=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (b, s, h, hd)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_flash_matches_dense():
    q, k, v = qkv(jax.random.key(0))
    with jax.default_matmul_precision("highest"):
        dense = dense_attention(q, k, v, None)
        flash = flash_attention(q, k, v, causal=True, block_size=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_flash_single_block_and_noncausal():
    q, k, v = qkv(jax.random.key(1), s=32)
    with jax.default_matmul_precision("highest"):
        # block covering the whole sequence
        out = flash_attention(q, k, v, causal=True, block_size=32)
        dense = dense_attention(q, k, v, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)
        # non-causal: compare against softmax with no mask
        out_nc = flash_attention(q, k, v, causal=False, block_size=8)
        zero_mask = jnp.zeros((1, 1, 32, 32))
        dense_nc = dense_attention(q, k, v, zero_mask)
        np.testing.assert_allclose(np.asarray(out_nc), np.asarray(dense_nc), rtol=2e-5, atol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = qkv(jax.random.key(2), b=1, s=32, h=2, hd=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_size=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, None) ** 2)

    with jax.default_matmul_precision("highest"):
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# dense attention in causal query blocks: block i of bq rows meets keys
# 0..(i+1)*bq only; one block (S <= bq, or S not a multiple) is the
# full-score arithmetic
# ---------------------------------------------------------------------------

def _right_padded(b, s):
    """[B, S] validity with right padding: row 0 ends a third early, the
    last row is whole."""
    valid = np.ones((b, s), np.int32)
    valid[0, s - s // 3:] = 0
    return jnp.asarray(valid)


def _full_scores_attention(q, k, v, valid=None):
    """Attention over all S x S scores, written out: the formula
    ``dense_attention`` had before it ran in blocks."""
    s, hd = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(hd)
    probs = jax.nn.softmax(scores + causal_mask(s, valid), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _score_entries(fn, q, k, v):
    """Score entries a call computes over B*H, read from the jaxpr: the
    output sizes [B, H, rows, keys] of its matmuls that contract over the
    head size (the callers keep that off any block's key count, which
    the other matmul contracts over)."""
    hd = q.shape[3]
    n = 0
    for e in jax.make_jaxpr(fn)(q, k, v).eqns:
        if e.primitive.name == "dot_general":
            (contract, _), _ = e.params["dimension_numbers"]
            if e.invars[0].aval.shape[contract[0]] == hd:
                n += e.outvars[0].aval.shape[2] * e.outvars[0].aval.shape[3]
    return n


@pytest.mark.parametrize("padded", [False, True], ids=["causal", "right_padded"])
@pytest.mark.parametrize("s", [64, 128])
@pytest.mark.parametrize("bq", [16, 32])
def test_blocked_dense_matches_one_block(bq, s, padded):
    """Forward and under grad for q, k and v; with right padding every
    row keeps a valid key (its first), so every row is compared."""
    q, k, v = qkv(jax.random.key(30), s=s, hd=8)
    valid = _right_padded(2, s) if padded else None
    assert dense_block_rows(s, bq) == bq and dense_block_rows(s, s) == s

    def blocked(q, k, v):
        return dense_attention(q, k, v, valid, bq=bq)

    def one_block(q, k, v):
        return dense_attention(q, k, v, valid, bq=s)

    # the share is of what the call computes, not a number beside it
    assert _score_entries(blocked, q, k, v) == dense_score_share(s, bq) * s * s
    assert _score_entries(one_block, q, k, v) == s * s
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(blocked(q, k, v)), np.asarray(one_block(q, k, v)),
            rtol=0, atol=2e-5,
        )
        gb = jax.grad(lambda *a: jnp.sum(blocked(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
        go = jax.grad(lambda *a: jnp.sum(one_block(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-4)


def test_blocked_dense_left_padding_is_finite():
    """Rows before a sequence's first valid key softmax over MASK_VALUE
    alone: uniform over their block's keys, finite, forward and under
    grad; rows with a valid key equal the one-block form's."""
    q, k, v = qkv(jax.random.key(31), s=64)
    valid = jnp.ones((2, 64), jnp.int32).at[0, :24].set(0)
    with jax.default_matmul_precision("highest"):
        out = dense_attention(q, k, v, valid, bq=16)
        ref = dense_attention(q, k, v, valid, bq=64)
        g = jax.grad(
            lambda *a: jnp.sum(dense_attention(*a, valid, bq=16) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    assert all(np.isfinite(np.asarray(x)).all() for x in g)
    has_key = np.asarray(jnp.cumsum(valid, axis=1) > 0)
    np.testing.assert_allclose(
        np.asarray(out)[has_key], np.asarray(ref)[has_key], rtol=0, atol=2e-5
    )


@pytest.mark.parametrize(
    "s,bq", [pytest.param(72, 16, id="not_a_multiple"), pytest.param(32, 32, id="one_block_long"),
             pytest.param(64, None, id="under_the_constant")]
)
def test_dense_one_block_is_the_full_score_formula(s, bq):
    """Where the sequence is no longer than a block or not a whole number
    of them, one block runs: all S x S scores, and the arithmetic of the
    written-out formula, with and without a validity mask."""
    q, k, v = qkv(jax.random.key(32), s=s, hd=8)
    assert dense_block_rows(s, bq) == s and dense_score_share(s, bq) == 1.0
    assert _score_entries(lambda *a: dense_attention(*a, None, bq=bq), q, k, v) == s * s
    for valid in (None, _right_padded(2, s)):
        with jax.default_matmul_precision("highest"):
            out = dense_attention(q, k, v, valid, bq=bq)
            ref = _full_scores_attention(q, k, v, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=1e-6)


def test_dense_block_rule_is_a_function_of_the_sequence():
    """The program's block follows from S alone: the constant up to 16
    blocks, longer blocks beyond (compile time grows with the count)."""
    from nanodiloco_tpu.models.llama import DENSE_BLOCK_Q, DENSE_MAX_BLOCKS

    assert [dense_score_share(2048, b) for b in (128, 256, 512)] == [0.53125, 0.5625, 0.625]
    for s in (512, 1024, 2048, 4096, 8192, 16384):
        rows = dense_block_rows(s)
        assert rows >= DENSE_BLOCK_Q and s % rows == 0
        assert s // rows == min(s // DENSE_BLOCK_Q, DENSE_MAX_BLOCKS)
    assert dense_block_rows(DENSE_BLOCK_Q) == DENSE_BLOCK_Q  # one block
    assert dense_block_rows(3 * DENSE_BLOCK_Q + 8) == 3 * DENSE_BLOCK_Q + 8


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_matches_dense(sp):
    """Global causal attention with the sequence sharded over `sp` devices."""
    b, s, h, hd = 2, 32, 4, 8
    q, k, v = qkv(jax.random.key(3), b=b, s=s, h=h, hd=hd)
    mesh = Mesh(np.asarray(jax.devices()[:sp]).reshape(sp), ("sp",))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    with jax.default_matmul_precision("highest"):
        out = ring(q, k, v)
        dense = dense_attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # ~28 s of tracing; ring-grad coverage also comes from
# tests/test_sp_training.py's training-path parity (run all: pytest -m "")
def test_ring_gradients_match_dense():
    b, s, h, hd = 1, 16, 2, 8
    q, k, v = qkv(jax.random.key(4), b=b, s=s, h=h, hd=hd)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("sp",))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    with jax.default_matmul_precision("highest"):
        gr = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(dense_attention(q, k, v, None) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_fully_masked_rows_no_nan():
    """A sequence whose first tokens are padding must not NaN the loss
    (the causal_mask MASK_VALUE guard)."""
    from nanodiloco_tpu.models import LlamaConfig, causal_lm_loss, init_params

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_attention_heads=4, num_hidden_layers=2)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    # left-padded row: query position 0 has zero visible valid keys
    mask = jnp.ones((2, 16), jnp.int32).at[0, :8].set(0)
    loss, aux = causal_lm_loss(params, tokens, cfg, loss_mask=mask)
    assert np.isfinite(float(loss))
    g = jax.grad(lambda p: causal_lm_loss(p, tokens, cfg, loss_mask=mask)[0])(params)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


def test_ring_full_model_parity():
    """Full Llama forward with attention_impl='ring', sequence sharded 4-way
    over the sp axis, must match the dense single-device forward (also
    exercises traced position_offset through rope_tables)."""
    from nanodiloco_tpu.models import LlamaConfig, forward, init_params
    from nanodiloco_tpu.parallel import MeshConfig, build_mesh

    cfg_ring = LlamaConfig(vocab_size=128, hidden_size=64, num_attention_heads=4,
                           num_hidden_layers=2, intermediate_size=128,
                           attention_impl="ring")
    cfg_dense = LlamaConfig(**{**cfg_ring.to_dict(), "attention_impl": "dense"})
    mesh = build_mesh(MeshConfig(sp=4))
    params = init_params(jax.random.key(0), cfg_ring)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
    s_loc = 64 // 4

    def inner(params, tok):
        idx = jax.lax.axis_index("sp")
        return forward(params, tok, cfg_ring, sp_axis="sp", position_offset=idx * s_loc)

    ring_fwd = jax.shard_map(inner, mesh=mesh,
                             in_specs=(P(), P(None, "sp")), out_specs=P(None, "sp"))
    with jax.default_matmul_precision("highest"):
        out_ring = ring_fwd(params, tokens)
        out_dense = forward(params, tokens, cfg_dense)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode on the CPU mesh; Mosaic-compiled on TPU)
# ---------------------------------------------------------------------------

def test_pallas_flash_matches_dense():
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    q, k, v = qkv(jax.random.key(10))
    with jax.default_matmul_precision("highest"):
        dense = dense_attention(q, k, v, None)
        out = pallas_flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_pallas_flash_gradients_match_dense():
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    q, k, v = qkv(jax.random.key(11), b=1, s=32, h=2, hd=8)

    def loss_pallas(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, None) ** 2)

    with jax.default_matmul_precision("highest"):
        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_pallas_flash_noncausal_and_uneven_blocks():
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    q, k, v = qkv(jax.random.key(12), s=64)
    with jax.default_matmul_precision("highest"):
        out = pallas_flash_attention(q, k, v, causal=False, block_q=32, block_k=16)
        dense = dense_attention(q, k, v, jnp.zeros((1, 1, 64, 64)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_pallas_flash_under_vmap():
    """The Diloco inner step vmaps the loss over the worker axis; the
    kernel must batch correctly through that transform (incl. grad)."""
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    q, k, v = qkv(jax.random.key(13), b=1, s=32, h=2, hd=8)
    qs, ks, vs = (jnp.stack([x, 2 * x]) for x in (q, k, v))

    gv = jax.vmap(
        jax.grad(
            lambda q, k, v: jnp.sum(
                pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2
            ),
            argnums=(0, 1, 2),
        )
    )
    dense_grad = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, None) ** 2),
        argnums=(0, 1, 2),
    )
    with jax.default_matmul_precision("highest"):
        got = gv(qs, ks, vs)
        want0 = dense_grad(q, k, v)
        want1 = dense_grad(2 * q, 2 * k, 2 * v)
    # both mapped elements must be right — a batching defect that
    # broadcasts element 0 across the worker axis must not pass
    for a, b0, b1 in zip(got, want0, want1):
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b0), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b1), rtol=1e-4, atol=2e-3)


def test_flash_dispatcher_impl_override():
    """flash_attention(impl=...) must route to both implementations and
    they must agree."""
    q, k, v = qkv(jax.random.key(14), s=32)
    with jax.default_matmul_precision("highest"):
        scan = flash_attention(q, k, v, causal=True, block_size=16, impl="scan")
        pallas = flash_attention(q, k, v, causal=True, block_size=16, impl="pallas")
    np.testing.assert_allclose(np.asarray(scan), np.asarray(pallas), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "diloco,fsdp,tp",
    [(4, 1, 1), (2, 2, 1), (2, 2, 2), (1, 4, 2), (1, 1, 4)],
    ids=["diloco4", "diloco2_fsdp2", "diloco2_fsdp2_tp2", "fsdp4_tp2", "tp4_kv_whole"],
)
def test_pallas_flash_under_a_mesh_matches_unsharded(diloco, fsdp, tp):
    """Under an ambient multi-device mesh the dispatcher wraps the kernel
    in a shard_map (Mosaic cannot be partitioned automatically): batch
    over fsdp, heads over tp where they divide (2 KV heads over tp=4 do
    not: whole on every device), the vmapped worker axis over diloco.
    Same values and gradients as the kernel alone, and nothing gathered:
    attention mixes none of those axes."""
    from jax.sharding import NamedSharding

    from nanodiloco_tpu.parallel import MeshConfig, build_mesh

    W = 4
    q, k, v = gqa_qkv(jax.random.key(21), b=4 * W, s=32, h=4, hkv=2, hd=8)
    q, k, v = (x.reshape(W, 4, *x.shape[1:]) for x in (q, k, v))

    def loss(spmd_axis_name):
        attn = jax.vmap(
            lambda q, k, v: flash_attention(q, k, v, block_size=8, impl="pallas"),
            spmd_axis_name=spmd_axis_name,
        )
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    mesh = build_mesh(MeshConfig(diloco=diloco, fsdp=fsdp, tp=tp))
    placed = NamedSharding(mesh, P("diloco", "fsdp"))
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(q, k, v)
        with jax.set_mesh(mesh):
            fn = jax.jit(jax.value_and_grad(
                loss("diloco" if diloco > 1 else None), argnums=(0, 1, 2)
            ))
            args = [jax.device_put(x, placed) for x in (q, k, v)]
            text = fn.lower(*args).compile().as_text()
            got = fn(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    # the one reduction is the scalar loss; no activation is gathered
    assert "all-gather" not in text and "all-to-all" not in text
    assert got[1][0].addressable_shards[0].data.shape[:2] == (W // diloco, 4 // fsdp)


# ---------------------------------------------------------------------------
# GQA: kernels take K/V at Hkv heads, never expanded (VERDICT r1 item 4 —
# expanding before the kernel cost 4x K/V bandwidth at Llama-3-8B's 32q/8kv)
# ---------------------------------------------------------------------------

def gqa_qkv(key, b=2, s=32, h=8, hkv=2, hd=8):
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, hd))
    k = jax.random.normal(kk, (b, s, hkv, hd))
    v = jax.random.normal(kv_, (b, s, hkv, hd))
    return q, k, v


def _dense_gqa(q, k, v):
    g = q.shape[2] // k.shape[2]
    return dense_attention(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), None
    )


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_flash_gqa_matches_dense(impl):
    q, k, v = gqa_qkv(jax.random.key(20))
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=True, block_size=8, impl=impl)
        dense = _dense_gqa(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_flash_gqa_gradients_match_dense(impl):
    """dk/dv must sum over the query heads sharing each KV head."""
    q, k, v = gqa_qkv(jax.random.key(21), b=1, s=16, h=4, hkv=2, hd=8)
    with jax.default_matmul_precision("highest"):
        gf = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, block_size=8, impl=impl) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(_dense_gqa(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
    for a, b in zip(gf, gd):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_ring_gqa_matches_dense():
    """Ring attention with un-expanded K/V: the ppermuted block is the
    small Hkv-head one, and results still match dense GQA."""
    b, s, h, hkv, hd = 2, 32, 4, 2, 8
    q, k, v = gqa_qkv(jax.random.key(22), b=b, s=s, h=h, hkv=hkv, hd=hd)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("sp",))
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    with jax.default_matmul_precision("highest"):
        out = ring(q, k, v)
        dense = _dense_gqa(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_gqa_full_model_flash_matches_dense():
    """Full forward with attention_impl='flash' + GQA (no repeat on the
    kernel path) must match the dense GQA forward."""
    from nanodiloco_tpu.models import LlamaConfig, forward, init_params

    base = dict(vocab_size=64, hidden_size=64, num_attention_heads=8,
                num_key_value_heads=2, num_hidden_layers=2, intermediate_size=128)
    cfg_f = LlamaConfig(**base, attention_impl="flash")
    cfg_d = LlamaConfig(**base, attention_impl="dense")
    params = init_params(jax.random.key(0), cfg_f)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)
    with jax.default_matmul_precision("highest"):
        out_f = forward(params, tokens, cfg_f)
        out_d = forward(params, tokens, cfg_d)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


_GQA_MODEL = dict(vocab_size=64, hidden_size=64, num_attention_heads=8,
                  num_key_value_heads=2, num_hidden_layers=2, intermediate_size=128)


def test_gqa_full_model_blocked_dense_matches_one_block(monkeypatch):
    """causal_lm_loss and its gradient under a padded loss_mask, dense
    attention in four blocks (of 16 rows: what the program does from two
    blocks' tokens on, at a test's size) against one."""
    import nanodiloco_tpu.models.llama as llama
    from nanodiloco_tpu.models import LlamaConfig, causal_lm_loss, init_params

    cfg = LlamaConfig(**_GQA_MODEL)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 64)
    mask = _right_padded(2, 64)

    def loss_and_grad():
        return jax.value_and_grad(
            lambda p: causal_lm_loss(p, tokens, cfg, loss_mask=mask)[0]
        )(params)

    with jax.default_matmul_precision("highest"):
        assert llama.dense_score_share(64) == 1.0
        loss_one, grad_one = loss_and_grad()
        monkeypatch.setattr(llama, "DENSE_BLOCK_Q", 16)
        assert llama.dense_score_share(64) == 0.625
        loss_four, grad_four = loss_and_grad()
    np.testing.assert_allclose(float(loss_four), float(loss_one), rtol=0, atol=2e-5)
    for a, b in zip(jax.tree.leaves(grad_four), jax.tree.leaves(grad_one)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-4)


def test_gqa_full_model_flash_matches_blocked_dense(monkeypatch):
    """test_gqa_full_model_flash_matches_dense above one block: the flash
    scan against dense attention in four blocks, on a mask of ones (the
    kernels are packed-sequence kernels; dense honors the mask)."""
    import nanodiloco_tpu.models.llama as llama
    from nanodiloco_tpu.models import LlamaConfig, forward, init_params

    monkeypatch.setattr(llama, "DENSE_BLOCK_Q", 16)
    assert llama.dense_score_share(64) == 0.625
    cfg_f = LlamaConfig(**_GQA_MODEL, attention_impl="flash")
    cfg_d = LlamaConfig(**_GQA_MODEL, attention_impl="dense")
    params = init_params(jax.random.key(0), cfg_f)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 64)
    ones = jnp.ones((2, 64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        out_f = forward(params, tokens, cfg_f, attn_mask=ones)
        out_d = forward(params, tokens, cfg_d, attn_mask=ones)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_pallas_block_env_knobs(monkeypatch):
    """NANODILOCO_PALLAS_BLOCK_Q/K are read at trace time and reach the
    kernel; numerics must be identical across tile choices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanodiloco_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.key(0), (1, 64, 4, 8), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 64, 2, 8), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 64, 2, 8), jnp.float32)
    base = flash_attention(q, k, v, impl="pallas")

    # spy: equality alone can't prove the knobs reach the kernel (ignored
    # knobs would also produce identical numerics)
    import nanodiloco_tpu.ops.flash_attention as fa
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention as real

    seen = {}

    def spy(q, k, v, causal=True, block_q=128, block_k=128, interpret=None):
        seen.update(block_q=block_q, block_k=block_k)
        return real(q, k, v, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret)

    monkeypatch.setattr(
        "nanodiloco_tpu.ops.pallas.flash_attention.pallas_flash_attention", spy
    )
    monkeypatch.setenv("NANODILOCO_PALLAS_BLOCK_Q", "16")
    monkeypatch.setenv("NANODILOCO_PALLAS_BLOCK_K", "32")
    tuned = fa.flash_attention(q, k, v, impl="pallas")
    assert seen == {"block_q": 16, "block_k": 32}
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(base), atol=1e-5)

    # malformed values fail loudly, not mid-grid-math
    monkeypatch.setenv("NANODILOCO_PALLAS_BLOCK_Q", "abc")
    with __import__("pytest").raises(ValueError, match="positive integer"):
        fa.flash_attention(q, k, v, impl="pallas")
    monkeypatch.setenv("NANODILOCO_PALLAS_BLOCK_Q", "-128")
    with __import__("pytest").raises(ValueError, match="positive integer"):
        fa.flash_attention(q, k, v, impl="pallas")
    # scan path never consults the knobs
    out = fa.flash_attention(q, k, v, impl="scan")
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5)
