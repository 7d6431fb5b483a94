"""Kernels and step programs of the main path, compiled at real widths
for a TPU v5e that is described and not attached.

The TPU's compiler is installed beside jax; it refuses here what the
chip's would refuse — a slice not aligned to the tiling, a kernel that
wants too much fast memory or cannot be partitioned, a program that does
not fit a device's memory — at no chip time. Nothing runs: these tests
say nothing about results or speed. ``chip_smoke.py`` is the chip run.

The topology is described inside a module-scoped fixture of THIS file,
after a test of it has started: only one process may load the TPU's
library, and every xdist worker imports every test file, so describing
it at import, in a ``skipif`` or in ``parametrize`` arguments would let
one worker load it and make the others collect different tests. For the
same reason every test here compiles in its own process, and all such
tests live in this one file.

Code that asks ``jax.default_backend()`` still sees the CPU during
such a compile and takes its CPU branch (``ops/flash_attention.py``'s
dispatch picks the scan), so kernels are called directly and the one
test that needs the dispatch steers it itself.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from nanodiloco_tpu.models import LLAMA3_8B, LlamaConfig

# one v5e chip: 16 GiB of HBM (Google Cloud TPU documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        # or the compiler writes its logs under /tmp
        env.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # whatever the plugin raises where it cannot
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _live_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


# (a) the Pallas flash kernel ------------------------------------------------

@pytest.mark.parametrize(
    "b,s,h,hkv,hd",
    [
        pytest.param(1, 2048, 32, 8, 128, id="llama3_8b_gqa32x8_hd128"),
        pytest.param(8, 1024, 4, 4, 32, id="llama_default_mha4_hd32"),
    ],
)
def test_flash_kernel_compiles_forward_and_backward(one_chip, b, s, h, hkv, hd):
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    def flash(q, k, v):
        return pallas_flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(flash(q, k, v).astype(jnp.float32))

    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.bfloat16, sharding=one_chip)
    fwd = jax.jit(flash).lower(q, kv, kv).compile()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in fwd.as_text()
    # dq and dk/dv are two kernels
    assert bwd.as_text().count("tpu_custom_call") >= 2


def test_cost_analysis_of_a_tpu_program_is_read_from_the_executable(one_chip):
    """``Lowered.cost_analysis()`` answers nothing for a TPU program (here
    as on the chip), which left the trainer without a cost record there;
    ``obs/costs.lowered_cost`` then compiles and asks the executable."""
    from nanodiloco_tpu.obs.costs import lowered_cost

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16, sharding=one_chip)
    cost = lowered_cost(jax.jit(lambda a, b: a @ b).lower(x, x))
    assert cost is not None and cost["flops"] == 2 * 1024**3


# (a') dense attention in causal query blocks, at the benchmark's shape -------

def test_blocked_dense_attention_halves_the_compiled_work(one_chip):
    """The attention of ``smollm2-360m.train.1chip`` (8 x 2048, 15 heads
    over 5 of 64, bf16, value and gradient) compiled in the program's
    blocks and in one: by the executable's own count the blocks do at
    most 0.65 of the one-block form's FLOPs (score area 0.5625) and move
    at most 0.72 of its bytes (the count charges each in-place update of
    the concatenated output and of dK, dV with the whole buffer, a
    quarter of a GB a block that no block saves). The check, without a
    chip, that the mechanism engages at the size that matters and that
    XLA does not pad it back."""
    from nanodiloco_tpu.models.llama import dense_attention, dense_score_share

    b, s, h, hkv, hd = 8, 2048, 15, 5, 64
    assert dense_score_share(s) == 0.5625

    def value_and_grad(bq):
        def loss(q, k, v, valid):
            k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
            return jnp.sum(dense_attention(q, k, v, valid, bq=bq).astype(jnp.float32))

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.bfloat16, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    blocked, one_block = (
        value_and_grad(bq).lower(q, kv, kv, valid).compile().cost_analysis()
        for bq in (None, s)
    )
    assert blocked["flops"] <= 0.65 * one_block["flops"], (blocked["flops"], one_block["flops"])
    assert blocked["bytes accessed"] <= 0.72 * one_block["bytes accessed"], (
        blocked["bytes accessed"], one_block["bytes accessed"]
    )


# (a'') the fused kernel behind _attention, at the benchmark's shape -----------

@pytest.mark.parametrize("b,s,h,hkv,hd,window", [
    pytest.param(2, 8192, 32, 4, 128, None, id="mellum_full_layer"),
    pytest.param(2, 8192, 32, 4, 128, 1024, id="mellum_window_layer"),
    pytest.param(8, 2048, 15, 5, 64, None, id="smollm2"),
])
def test_attention_at_benchmark_shapes_compiles_to_three_kernels_and_no_scores(
        one_chip, monkeypatch, b, s, h, hkv, hd, window):
    """``_attention`` at the shapes of ``mellum2-12b.train.seq8k`` (2 x
    8,192, 32 heads over 4 of 128) and of ``smollm2-360m.train.1chip``
    (8 x 2,048, 15 heads over 5 of 64), bf16, a [B, S] validity array,
    value and gradient, where it sees a TPU (this test steers the one
    question the rule asks of the backend): the program holds three
    Mosaic calls (forward, dq, dk/dv), and no block of score rows, which
    is what ``dense_attention``'s blocks make ([2,32,512,8192] for
    Mellum's full layer, [2,32,512,1535] for a window block,
    [8,15,256,2048] for SmolLM2's last block)."""
    import re

    from nanodiloco_tpu.models.llama import _attention, dense_block_rows

    cfg = LlamaConfig(hidden_size=h * hd, num_attention_heads=h, num_key_value_heads=hkv)
    assert cfg.attention_impl == "dense" and cfg.head_dim == hd
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v, valid):
        return jnp.sum(_attention(cfg, q, k, v, valid, None, window).astype(jnp.float32))

    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, hd), jnp.bfloat16, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, valid).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3
    # every array of the program: none has a dense block's rows of scores
    # as its last two dimensions (a block meets at least its own rows' keys)
    rows = dense_block_rows(s)
    shapes = {tuple(map(int, dims.split(","))) for dims in re.findall(
        r"(?:bf16|f32)\[([0-9,]+)\]", text)}
    scores = [d for d in shapes if len(d) >= 2 and d[-2] == rows and d[-1] >= rows]
    assert not scores, scores
    assert _live_bytes(compiled) < 2 * 1024**3


# (b) the paged serve programs at llama3_8b.json widths ----------------------

@pytest.fixture(scope="module")
def serve_8b_abstract(one_chip):
    """Abstract params, pool and scalar makers for a paged engine at the
    llama3_8b.json widths, depth 2, bf16 weights — the shapes
    ``InferenceEngine`` stages (serve/engine.py)."""
    import os

    from nanodiloco_tpu.models import init_params
    from nanodiloco_tpu.models.generate import init_kv_pool

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = dataclasses.replace(
        LlamaConfig.from_json(os.path.join(root, "configs", "llama3_8b.json")),
        num_hidden_layers=2, param_dtype="bfloat16", remat=False,
    )
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (
        4096, 14336, 128256
    )
    slots, max_len, chunk, block = 8, 2048, 64, 16
    table_blocks = max_len // block + chunk // block
    params = _abstract(
        jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)), one_chip
    )
    pool = _abstract(
        jax.eval_shape(lambda: init_kv_pool(cfg, slots * (max_len // block), block)),
        one_chip,
    )

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return cfg, params, pool, arr, (slots, chunk, table_blocks)


def test_paged_prefill_chunk_compiles_at_8b_widths(serve_8b_abstract):
    from nanodiloco_tpu.models.generate import prefill_chunk_paged_fn

    cfg, params, pool, arr, (_slots, chunk, table_blocks) = serve_8b_abstract
    i32, f32, u32 = np.int32, np.float32, np.uint32
    compiled = prefill_chunk_paged_fn(cfg, None).lower(
        params, pool, arr(i32, table_blocks), arr(i32, 1, chunk),
        arr(i32, 1, chunk), arr(i32), arr(i32), arr(u32, 2),
        arr(f32), arr(i32), arr(f32),
    ).compile()
    assert _live_bytes(compiled) < V5E_HBM_BYTES
    tok, logits, _pool = compiled.output_shardings
    assert tok is not None and logits is not None


def test_paged_decode_tick_compiles_at_8b_widths(serve_8b_abstract):
    from nanodiloco_tpu.models.generate import decode_slots_paged_fn

    cfg, params, pool, arr, (slots, _chunk, table_blocks) = serve_8b_abstract
    i32, f32, u32 = np.int32, np.float32, np.uint32
    compiled = decode_slots_paged_fn(cfg, None).lower(
        params, pool, arr(i32, slots, table_blocks), arr(i32, slots),
        arr(i32, slots), arr(u32, slots, 2), arr(f32, slots),
        arr(i32, slots), arr(f32, slots), arr(i32, slots),
    ).compile()
    assert _live_bytes(compiled) < V5E_HBM_BYTES


# (b') the three-cache serve programs at MiniCPM-SALA's widths ---------------

@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_sparse_and_linear_layers_compile_at_minicpm_sala_widths(one_chip, program):
    """One block-sparse and one linear attention layer at the published
    widths (hidden 4096, 32 heads over 2 KV heads of 128, FFN 16384, the
    published sparse sizes) over 8 slots of 16,384 rows: the tick's
    gather of the chosen blocks, the chunk's masked choice, the state's
    update. The tick's temporaries stay under a gigabyte: rows within
    the dense length read the first rung of the ladder, not the table's
    whole width (at the benchmark's size that fault was 2.6 GB)."""
    from nanodiloco_tpu.models import init_params
    from nanodiloco_tpu.models.generate import (
        decode_slots_mixed_fn,
        init_mixed_serve_cache,
        prefill_chunk_mixed_fn,
    )

    cfg = LlamaConfig(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384, num_hidden_layers=2,
        num_attention_heads=32, num_key_value_heads=2, explicit_head_dim=128, qk_norm=True,
        layer_types=("sparse_attention", "linear_attention"), rope_layers="linear",
        attn_output_gate=True, linear_output_gate=True, linear_output_norm=True,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=256, published_layers=32,
        first_layer_index=9, rms_norm_eps=1e-6, dtype="bfloat16", param_dtype="bfloat16")
    slots, max_len, chunk, block = 8, 16384, 512, 16
    table_blocks = max_len // block + chunk // block
    params = _abstract(jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)), one_chip)
    cache = _abstract(jax.eval_shape(lambda: init_mixed_serve_cache(
        cfg, slots, chunk, slots * (max_len // block), block, table_blocks)), one_chip)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32, u32 = np.int32, np.float32, np.uint32
    if program == "tick":
        compiled = decode_slots_mixed_fn(cfg).lower(
            params, cache, arr(i32, slots, table_blocks), arr(i32, slots), arr(i32, slots),
            arr(u32, slots, 2), arr(f32, slots), arr(i32, slots), arr(f32, slots),
            arr(i32, slots)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    else:
        compiled = prefill_chunk_mixed_fn(cfg).lower(
            params, cache, arr(i32, table_blocks), arr(i32), arr(i32, 1, chunk),
            arr(i32, 1, chunk), arr(i32), arr(i32), arr(u32, 2), arr(f32), arr(i32),
            arr(f32)).compile()
    assert _live_bytes(compiled) < V5E_HBM_BYTES


# (c) one DiLoCo inner step at those widths over the four chips --------------

def _abstract_diloco_state(dl, mesh):
    """The state ``Diloco.init_state`` would make, as shapes carrying the
    shardings of parallel/sharding.py — tests/test_8b.py's construction
    (which compiles for the CPU backend), reused."""
    from test_8b import _init_struct, _sharding_like_params

    from nanodiloco_tpu.parallel.diloco import DilocoState
    from nanodiloco_tpu.parallel.sharding import named

    shapes = jax.eval_shape(lambda rng: _init_struct(dl, rng), jax.random.key(0))
    pstruct = jax.tree.structure(shapes.snapshot)
    wshard, pshard = named(mesh, dl._wspec), named(mesh, dl._pspec)
    shardings = DilocoState(
        params=wshard,
        inner_opt_state=_sharding_like_params(
            shapes.inner_opt_state, pstruct, wshard, mesh
        ),
        snapshot=pshard,
        outer_opt_state=_sharding_like_params(
            shapes.outer_opt_state, pstruct, pshard, mesh
        ),
        inner_step_count=NamedSharding(mesh, P()),
    )
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )


@pytest.fixture(scope="module")
def fsdp4(topo):
    """A one-worker ``fsdp=4`` trainer at the llama3_8b widths, depth 2,
    on a mesh of the described chips, with its abstract inputs."""
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
    from nanodiloco_tpu.parallel.sharding import batch_spec

    mesh = build_mesh(MeshConfig(fsdp=4), devices=topo.devices)
    assert mesh.devices.size == 4
    model = dataclasses.replace(LLAMA3_8B, num_hidden_layers=2)
    dl = Diloco(model, DilocoConfig(num_workers=1, inner_steps=2, grad_accum=1), mesh)
    state = _abstract_diloco_state(dl, mesh)
    tok = jax.ShapeDtypeStruct(
        (1, 1, 8, 2048), np.int32,
        sharding=NamedSharding(mesh, batch_spec(sp=False)),
    )
    return mesh, dl, state, tok


def test_inner_step_on_one_chip_with_heads_of_128_carries_the_fused_kernel(topo, monkeypatch):
    """The plain stack's training step (``lax.scan`` over the layers,
    each a ``jax.checkpoint``, under the worker ``vmap``) on ONE described
    chip, heads of 128 over a sequence of two tiles, where the rule sees
    a TPU: the layer body holds the fused kernel four times (the pass;
    in the backward scan its recompute, dq and dk/dv), and the round's
    own description says so."""
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
    from nanodiloco_tpu.parallel.sharding import batch_spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(diloco=1), devices=topo.devices[:1])
    model = LlamaConfig(
        vocab_size=4096, hidden_size=512, intermediate_size=1024, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, dtype="bfloat16", loss_chunk=512,
        remat=True)
    assert model.head_dim == 128
    dl = Diloco(model, DilocoConfig(num_workers=1, inner_steps=2, grad_accum=1), mesh)
    assert dl.attention_paths(2048) == {"fused": 3, "dense": 0}
    state = _abstract_diloco_state(dl, mesh)
    tok = jax.ShapeDtypeStruct(
        (1, 1, 2, 2048), np.int32, sharding=NamedSharding(mesh, batch_spec(sp=False)))
    compiled = dl._inner_jit.lower(state, tok, tok).compile()
    assert len(_kernel_calls(compiled)) == 4


def test_inner_step_fsdp4_at_8b_widths_fits_v5e(fsdp4):
    """Depth 2 is 1.49 B parameters: at 24 bytes each (f32 master, two
    Adam moments, snapshot, Nesterov momentum, gradients) about 9 GB a
    device over four. The program the trainer dispatches (donating its
    state) must fit a chip and keep the weights fsdp-sharded."""
    mesh, dl, state, tok = fsdp4
    with jax.set_mesh(mesh):
        compiled = dl._inner_jit.lower(state, tok, tok).compile()
    per_device = _live_bytes(compiled)
    assert per_device < V5E_HBM_BYTES, f"{per_device / 1e9:.1f} GB a device"
    # a silently replicated tree would be four times this
    assert per_device > 7e9, f"{per_device / 1e9:.1f} GB a device"
    wq = compiled.input_shardings[0][0].params["layers"]["wq"]
    assert tuple(wq.spec)[:3] == ("diloco", None, "fsdp"), wq.spec
    # LLAMA3_8B asks for flash attention; compiled from a CPU process the
    # dispatch takes the scan, so no kernel is in this program
    assert "tpu_custom_call" not in compiled.as_text()


def _kernel_calls(compiled) -> list[str]:
    return [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def test_flash_kernel_runs_inside_the_fsdp4_step(fsdp4, monkeypatch):
    """With the dispatch steered as it goes on the chip, the same step
    carries the Pallas kernel: ``ops/flash_attention.py`` wraps the call
    in a shard_map over the ambient mesh (Mosaic refuses a kernel in an
    automatically partitioned program), batch over ``fsdp``. It never
    runs the scan or the interpreter unasked."""
    from nanodiloco_tpu.parallel import Diloco

    mesh, dl, state, tok = fsdp4
    # a trainer of its own: jax would hand the shared one's cached trace
    # (dispatch already taken) to a second lowering of the same method
    fresh = Diloco(dl.model_cfg, dl.cfg, mesh)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = fresh._inner_jit.lower(state, tok, tok).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) >= 3  # forward, dq, dk/dv
    # each device's kernel sees its own 2 of the 8 rows x 32 query heads
    assert all("bf16[64,2048,128]" in c for c in calls), calls[0][:300]
    assert _live_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize(
    "workers,fsdp", [pytest.param(4, 1, id="diloco4"), pytest.param(2, 2, id="diloco2_fsdp2")]
)
def test_flash_kernel_stays_on_its_worker_shard(topo, monkeypatch, workers, fsdp):
    """The layouts ``chip_smoke.py --chips 4`` runs, llama_default widths
    with flash attention: the worker axis is a vmap, and the kernel's
    shard_map must shard it over ``diloco`` (``spmd_axis_name``) — every
    device's kernel sees one worker's rows, never all of them gathered."""
    import os

    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
    from nanodiloco_tpu.parallel.sharding import batch_spec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = dataclasses.replace(
        LlamaConfig.from_json(os.path.join(root, "configs", "llama_default.json")),
        dtype="bfloat16", attention_impl="flash",
    )
    mesh = build_mesh(MeshConfig(diloco=workers, fsdp=fsdp), devices=topo.devices)
    dl = Diloco(model, DilocoConfig(num_workers=workers, inner_steps=2, grad_accum=2), mesh)
    state = _abstract_diloco_state(dl, mesh)
    tok = jax.ShapeDtypeStruct(
        (workers, 2, 8, 1024), np.int32,
        sharding=NamedSharding(mesh, batch_spec(sp=False)),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = dl._inner_jit.lower(state, tok, tok).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) >= 3
    rows = (8 // fsdp) * model.num_attention_heads  # one worker's, a device
    assert all(f"bf16[{rows},1024,32]" in c for c in calls), calls[0][:300]


def test_smollm2_over_four_workers_runs_the_kernel_on_each_worker_shard(topo, monkeypatch):
    """``smollm2-360m.diloco.4chip``'s layout, ``MeshConfig(diloco=4)`` over
    the four described chips, at SmolLM2-360M's widths (hidden 960, 15
    heads over 5 of 64, FFN 2560, vocabulary 49,152) and depth 2, where
    the rule sees a TPU: the inner step is partitioned by the compiler,
    and its attention runs the fused kernel in ``kernel_on_mesh``'s
    ``shard_map`` — every Mosaic call over one worker's 8 rows, never the
    32 of all four gathered, and no block of dense score rows left
    ([8,15,256,2048] is the last query block's)."""
    import re

    from nanodiloco_tpu.ops.splash_attention import _kernel, block_sizes
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
    from nanodiloco_tpu.parallel.sharding import batch_spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = LlamaConfig(
        vocab_size=49152, hidden_size=960, intermediate_size=2560, num_attention_heads=15,
        num_key_value_heads=5, num_hidden_layers=2, tie_word_embeddings=True,
        dtype="bfloat16", loss_chunk=512, remat=True)
    assert model.head_dim == 64 and model.attention_impl == "dense"
    mesh = build_mesh(MeshConfig(diloco=4), devices=topo.devices)
    dl = Diloco(model, DilocoConfig(num_workers=4, inner_steps=2, grad_accum=1), mesh)
    assert dl.attention_paths(2048) == {"fused": 2, "dense": 0}
    state = _abstract_diloco_state(dl, mesh)
    tok = jax.ShapeDtypeStruct(
        (4, 1, 8, 2048), np.int32, sharding=NamedSharding(mesh, batch_spec(sp=False)))
    # the kernel's tables are arrays made while the program is traced, on
    # the ambient mesh's devices, which described chips cannot run: made
    # here first, on the host (the chip makes them on its own devices)
    _kernel(2048, 15, None, False, block_sizes(2048))
    with jax.set_mesh(mesh):
        compiled = dl._inner_jit.lower(state, tok, tok).compile()
    calls = _kernel_calls(compiled)
    # the layer body's pass, and in the backward scan its recompute, dq and dk/dv
    assert len(calls) == 4
    assert all("bf16[8,15,2048,64]" in c and "[32," not in c for c in calls), calls[0][:400]
    text = compiled.as_text()
    assert not re.search(r"(?:bf16|f32)\[8,15,256,2048\]", text)
    assert _live_bytes(compiled) < V5E_HBM_BYTES
