"""The fused attention kernel of the training path (ops/splash_attention.py)
against ``dense_attention``, in interpret mode on the CPU, through the
``shard_map`` a partitioned mesh puts it in, and the rule by which
``_attention`` chooses between them, as a table."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from nanodiloco_tpu.models import LlamaConfig
from nanodiloco_tpu.models import llama
from nanodiloco_tpu.models.llama import (
    _attention, attention_paths, dense_attention, fused_attention_applies,
)
from nanodiloco_tpu.ops.splash_attention import TILE, block_sizes, splash_attention, whole_tiles

B, S = 2, 512
# (query heads, KV heads, head size): grouped heads of 128, 32-over-4
# style, and heads of 64 in SmolLM2's groups of 3 (15 over 5)
H, HKV, HD = 4, 2, 128
HEADS = [pytest.param((H, HKV, HD), id="hd128"), pytest.param((6, 2, 64), id="hd64_gqa3")]
PAD = 37                               # the first sequence is left-padded by this
WINDOW = 200
# tiles of 128 so that 512 rows are 4 x 4 of them: skipped, partial and full
SMALL = splash.BlockSizes(
    block_q=128, block_kv=128, block_kv_compute=128, block_q_dkv=128, block_kv_dkv=128,
    block_kv_dkv_compute=128, block_q_dq=128, block_kv_dq=128)


def _inputs(dtype=jnp.float32, heads=(H, HKV, HD)):
    h, hkv, hd = heads
    ks = jax.random.split(jax.random.key(33), 4)
    q = jax.random.normal(ks[0], (B, S, h, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, hkv, hd), dtype)
    w = jax.random.normal(ks[3], (B, S, h, hd), jnp.float32)
    return q, k, v, w


def _valid():
    return jnp.ones((B, S), jnp.int32).at[0, :PAD].set(0)


def _losses(valid, window, w):
    """(fused, dense): a loss over the rows that are real tokens, as the
    training loss masks padding, of the kernel and of the dense blocks."""
    rows = 1.0 if valid is None else valid[:, :, None, None].astype(jnp.float32)

    def fused(q, k, v):
        out = splash_attention(q, k, v, valid, window=window, interpret=True, sizes=SMALL)
        return jnp.sum(out.astype(jnp.float32) * w * rows)

    def dense(q, k, v):
        g = q.shape[2] // k.shape[2]
        out = dense_attention(q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
                              valid, window=window)
        return jnp.sum(out.astype(jnp.float32) * w * rows)

    return fused, dense


@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "left_padded"])
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_fused_kernel_matches_dense_forward_and_gradients(window, masked, heads):
    q, k, v, w = _inputs(heads=heads)
    fused, dense = _losses(_valid() if masked else None, window, w)
    with jax.default_matmul_precision("highest"):
        lf, gf = jax.jit(jax.value_and_grad(fused, (0, 1, 2)))(q, k, v)
        ld, gd = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_a_padding_row_is_finite_and_no_gradient_reaches_a_masked_key(window, heads):
    q, k, v, w = _inputs(heads=heads)
    valid = _valid()
    out = splash_attention(q, k, v, valid, window=window, interpret=True, sizes=SMALL)
    assert bool(jnp.isfinite(out).all())  # rows 0..PAD-1 of sequence 0 see no real key
    fused, _ = _losses(valid, window, w)
    dq, dk, dv = jax.grad(fused, (0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(g).all()) for g in (dq, dk, dv))
    assert float(jnp.abs(dk[0, :PAD]).max()) == 0.0
    assert float(jnp.abs(dv[0, :PAD]).max()) == 0.0
    assert float(jnp.abs(dk[0, PAD:]).max()) > 0.0


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_no_row_is_empty_and_a_padding_row_in_the_loss_has_a_true_gradient(window):
    """A row whose every key is masked breaks the library's backward pass
    (its saved log-sum-exp cannot hold "mask value + log n" in float32,
    so each masked key reads probability 1: the chip showed a gradient
    2.9e6 times off with ONE such row in the loss). The wrapper leaves
    no such row: a padding row sees the padding keys its mask allows,
    itself among them. Held here with every row in the loss, padding
    rows too, against dense blocks under the same mask given
    explicitly: value and all three gradients."""
    q, k, v, w = _inputs()
    valid = _valid()
    i = jnp.arange(S)
    allowed = (i[:, None] >= i[None, :]) & (valid[:, :, None] == valid[:, None, :])
    if window is not None:
        allowed &= i[:, None] - i[None, :] < window
    assert bool(allowed.any(axis=-1).all())
    explicit = jnp.where(allowed, 0.0, llama.MASK_VALUE)[:, None]

    def fused(q, k, v):
        out = splash_attention(q, k, v, valid, window=window, interpret=True, sizes=SMALL)
        return jnp.sum(out * w)

    def dense(q, k, v):
        g = H // HKV
        return jnp.sum(dense_attention(
            q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), explicit) * w)

    with jax.default_matmul_precision("highest"):
        lf, gf = jax.jit(jax.value_and_grad(fused, (0, 1, 2)))(q, k, v)
        ld, gd = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_fused_kernel_in_bf16_is_within_bf16_rounding_of_float32_dense(window):
    """bf16 carries 8 bits: a relative step of 2**-8. The kernel rounds q
    once more (the scale) and its probabilities once before the product
    with v; outputs are means of unit normals, so of the order of 1:
    a few steps of absolute error, as the repo's own kernel is held."""
    q, k, v, _ = _inputs()
    g = H // HKV
    with jax.default_matmul_precision("highest"):
        want = dense_attention(q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
                               _valid(), window=window)
    got = splash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)), _valid(),
                           window=window, interpret=True, sizes=SMALL)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))[0, PAD:]
    assert err.max() < 8 * 2.0**-8
    assert err.mean() < 2.0**-8


def test_tiles_are_a_function_of_the_sequence_alone():
    assert whole_tiles(8192) and whole_tiles(TILE) and not whole_tiles(TILE + 128)
    sizes = block_sizes(8192)
    assert sizes == block_sizes(2 * 8192) and sizes.has_backward_blocks
    with pytest.raises(ValueError, match="whole number of tiles"):
        block_sizes(TILE + 128)


# (platform, S, head_dim, mask_ndim, sp_axis, asked under a partitioned mesh) -> fused?
RULE = [
    pytest.param(("tpu", 8192, 128, 2, False, False), True, id="mellum_on_a_tpu"),
    pytest.param(("tpu", 8192, 128, None, False, False), True, id="mellum_no_mask"),
    pytest.param(("tpu", TILE, 128, 2, False, False), True, id="one_tile"),
    pytest.param(("tpu", 2048, 64, 2, False, False), True, id="smollm2_heads_of_64"),
    pytest.param(("tpu", TILE, 64, None, False, False), True, id="heads_of_64_one_tile"),
    pytest.param(("tpu", 2048, 96, 2, False, False), False, id="heads_of_96"),
    pytest.param(("tpu", 2048, 32, 2, False, False), False, id="heads_of_32"),
    pytest.param(("tpu", 2048, 192, 2, False, False), False, id="heads_of_192"),
    pytest.param(("tpu", 2048, 64, 2, False, True), True, id="heads_of_64_partitioned_mesh"),
    pytest.param(("tpu", 8192, 128, 4, False, False), False, id="explicit_4d_mask"),
    pytest.param(("tpu", 8192 + 128, 128, 2, False, False), False, id="no_whole_tiles"),
    pytest.param(("tpu", TILE // 2, 128, 2, False, False), False, id="under_a_tile"),
    pytest.param(("tpu", 8192, 128, 2, True, False), False, id="sequence_parallel_axis"),
    pytest.param(("tpu", 8192, 128, 2, False, True), True, id="partitioned_mesh"),
    pytest.param(("cpu", 8192, 128, 2, False, False), False, id="mellum_on_the_cpu"),
    pytest.param(("gpu", 8192, 128, None, False, False), False, id="mellum_on_a_gpu"),
    pytest.param(("cpu", 2048, 64, 2, False, False), False, id="smollm2_on_the_cpu"),
]


def _partitioned():
    """Two devices over ``fsdp`` as the ambient mesh: a program the
    compiler partitions."""
    from jax.sharding import Mesh

    return jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("fsdp",)))


@pytest.mark.parametrize("args,fused", RULE)
def test_the_rule_that_chooses_the_kernel(args, fused):
    *args, partitioned = args
    with _partitioned() if partitioned else contextlib.nullcontext():
        assert fused_attention_applies(*args) is fused


MELLUM = LlamaConfig(
    hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
    num_hidden_layers=4, sliding_window=1024,
    layer_types=("sliding_attention",) * 3 + ("full_attention",))
SMOLLM = LlamaConfig(hidden_size=128, num_attention_heads=2, num_hidden_layers=3)


@pytest.mark.parametrize("platform,cfg,s,want", [
    ("tpu", MELLUM, 8192, {"fused": 4, "dense": 0}),
    ("cpu", MELLUM, 8192, {"fused": 0, "dense": 4}),
    ("tpu", SMOLLM, 2048, {"fused": 3, "dense": 0}),
], ids=["mellum_tpu", "mellum_cpu", "heads_of_64_tpu"])
def test_the_count_of_layers_on_each_path(monkeypatch, platform, cfg, s, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert attention_paths(cfg, s) == want
    with _partitioned():
        assert attention_paths(cfg, s) == want


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_attention_hands_the_kernel_unexpanded_heads_the_mask_and_the_window(
        monkeypatch, platform):
    """On a TPU ``_attention`` calls the kernel with K and V at their own
    head count, the [B, S] validity array and the layer's window; on the
    CPU the same call runs dense blocks and never reaches it."""
    import nanodiloco_tpu.ops.splash_attention as ops

    seen = []

    def spy(q, k, v, valid, *, window=None):
        seen.append((q.shape, k.shape, None if valid is None else valid.shape, window))
        return jnp.zeros_like(q)

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(ops, "splash_attention", spy)
    s = 2 * TILE
    q = jax.ShapeDtypeStruct((1, s, 2, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, 1, 128), jnp.bfloat16)
    valid = jax.ShapeDtypeStruct((1, s), jnp.int32)
    out = jax.eval_shape(
        lambda q, k, v, m: _attention(MELLUM, q, k, v, m, None, 1024), q, kv, kv, valid)
    assert out.shape == q.shape
    want = [((1, s, 2, 128), (1, s, 1, 128), (1, s), 1024)] if platform == "tpu" else []
    assert seen == want


def test_inside_a_partitioned_mesh_attention_stays_dense(monkeypatch):
    """Only a bound sequence-parallel axis keeps a layer on dense blocks
    inside a partitioned mesh: without one, ``_attention`` on a TPU hands
    the layer to the kernel, which sits in a ``shard_map`` over the
    mesh's free axes, each device's call over its own rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 2 * TILE, 2, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 2 * TILE, 1, 128), jnp.bfloat16)
    valid = jax.ShapeDtypeStruct((2, 2 * TILE), jnp.int32)

    def primitives(axis_name):
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, m: _attention(MELLUM, q, k, v, m, axis_name, 1024))(q, kv, kv, valid)
        return str(jaxpr)

    with _partitioned():
        fused, dense = primitives(None), primitives("sp")
    assert "shard_map" in fused and "pallas_call" in fused
    assert "bf16[1,2,2048,128]" in fused  # a device's rows: 1 of 2 sequences
    assert "pallas_call" not in dense and "shard_map" not in dense


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "left_padded"])
def test_the_kernel_through_the_mesh_door_matches_one_device(masked):
    """Four DiLoCo workers over a four-device mesh (``MeshConfig(diloco=4)``),
    the worker axis a vmap named ``spmd_axis_name="diloco"`` as the trainer's
    (parallel/diloco.py): the kernel runs in ``kernel_on_mesh``'s
    ``shard_map``, one worker a device, and gives each worker's loss and
    all three gradients as the same call on one device does, with a
    left-padded [B, S] validity array and without; nothing is gathered."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nanodiloco_tpu.parallel import MeshConfig, build_mesh

    W = 4
    q, k, v, w = zip(*(_inputs(heads=(6, 2, 64)) for _ in range(W)))
    # each worker its own inputs
    scale = 1 + jnp.arange(W).reshape(W, 1, 1, 1, 1) / 8
    args = tuple(jnp.stack(x) * scale for x in (q, k, v, w))
    if masked:
        args += (jnp.stack([_valid()] * W),)

    def loss(q, k, v, w, *valid):
        out = splash_attention(q, k, v, *valid, interpret=True, sizes=SMALL)
        rows = valid[0][:, :, None, None].astype(jnp.float32) if valid else 1.0
        return jnp.sum(out.astype(jnp.float32) * w * rows)

    grad = jax.value_and_grad(loss, (0, 1, 2))
    mesh = build_mesh(MeshConfig(diloco=W), devices=jax.devices()[:W])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(grad))(*args)
        placed = [jax.device_put(x, NamedSharding(mesh, P("diloco"))) for x in args]
        with jax.set_mesh(mesh):
            fn = jax.jit(jax.vmap(grad, spmd_axis_name="diloco"))
            lowered = fn.lower(*placed)
            got = fn(*placed)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=2e-5)
    assert "manual_computation" in lowered.as_text()  # the door's shard_map
    text = lowered.compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    assert got[1][0].addressable_shards[0].data.shape[0] == 1  # one worker a device
