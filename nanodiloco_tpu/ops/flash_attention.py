"""Memory-efficient causal attention.

``flash_attention`` is the framework-facing API, dispatching on
hardware:

- On TPU it calls the hand-written Pallas kernel (ops/pallas/
  flash_attention.py) — Mosaic-compiled blockwise online-softmax with
  VMEM-resident accumulators and a custom VJP — or raises when the
  sequence does not divide into the kernel's blocks. It never gives way
  to another path there: a run that asked for the kernel and is not
  getting it must say so. Under a multi-device mesh the call sits in a
  ``shard_map`` (``_pallas_on_mesh``).
- Elsewhere (and under ``impl="scan"``, on any backend) it runs the
  same algorithm as a ``lax.scan`` over key/value blocks with per-block
  rematerialization — O(S * block) live memory instead of O(S^2),
  differentiable through the scan, XLA-fused. The scan form doubles as
  the executable spec the Pallas kernel is tested against.

Causal-only and mask-free by design: the data pipeline packs fixed-length
sequences (data/), so padding masks are not needed on the hot path. Use
``dense_attention`` (models/llama.py) when a padding mask is required.
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from nanodiloco_tpu.ops.online_softmax import block_update, finalize_grouped


def _env_block(name: str) -> int | None:
    """Validated positive-int env knob, or None when unset/empty."""
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be a positive integer, got {raw!r}")
    if v <= 0:
        raise ValueError(f"{name} must be a positive integer, got {raw!r}")
    return v


_POD_BLOCKS: tuple[int, int] | None = None


def _tile_knobs() -> tuple[int, int]:
    """(block_q, block_k) env overrides, 0 = unset.

    Single-process: re-read from the environment at every trace, so the
    in-process tile sweep (scripts/chip_agenda.py phase "pallas") retunes
    without code edits. Multi-process pod: process 0's first read is
    broadcast to every host and cached — per-process env divergence
    would compile different programs per process, and multi-controller
    SPMD answers that with a hang, not an error (round-4 advisor
    finding; same treatment as resolve_run_name)."""
    global _POD_BLOCKS
    import jax

    if jax.process_count() == 1:
        return (
            _env_block("NANODILOCO_PALLAS_BLOCK_Q") or 0,
            _env_block("NANODILOCO_PALLAS_BLOCK_K") or 0,
        )
    if _POD_BLOCKS is None:
        import numpy as np
        from jax.experimental import multihost_utils

        # EVERY process must reach the broadcast — including process 0:
        # env is normally pushed uniformly across a pod, so a malformed
        # value raising on rank 0 while ranks 1..N-1 already wait inside
        # the collective is the exact hang class this broadcast exists
        # to prevent (round-5 review; the guard originally covered only
        # non-zero ranks). A bad value degrades to the auto default (0)
        # pod-wide, with a rank-0 warning instead of a silent swallow.
        def safe(name):
            try:
                return _env_block(name) or 0
            except ValueError as e:
                if jax.process_index() == 0:
                    import sys

                    print(
                        f"[nanodiloco] warning: ignoring malformed {name}"
                        f" ({e}); using auto tile",
                        file=sys.stderr,
                    )
                return 0

        vals = [safe("NANODILOCO_PALLAS_BLOCK_Q"),
                safe("NANODILOCO_PALLAS_BLOCK_K")]
        agreed = np.asarray(
            multihost_utils.broadcast_one_to_all(np.asarray(vals, np.int32))
        )
        _POD_BLOCKS = (int(agreed[0]), int(agreed[1]))
    return _POD_BLOCKS


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_size: int = 512,
    impl: str | None = None,
) -> jax.Array:
    """q: [B, S, H, hd]; k, v: [B, S, Hkv, hd] with H % Hkv == 0 (GQA —
    K/V are NOT pre-expanded; each KV head serves its group of H/Hkv
    query heads in-kernel, so K/V HBM traffic stays at Hkv heads).
    Returns [B, S, H, hd].

    ``impl``: "pallas" | "scan" | None. None takes the backend's path:
    the Pallas kernel on TPU — which raises when the sequence does not
    divide into its blocks; ask for "scan" by name then — and the scan
    everywhere else. The choice reads ``jax.default_backend()``, so a
    program compiled for a described (unattached) TPU takes the scan
    unless it says "pallas".
    """
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"query heads {q.shape[2]} must divide by kv heads {k.shape[2]}"
        )
    if impl not in (None, "pallas", "scan"):
        raise ValueError(f"unknown flash attention impl: {impl!r}")
    # Pallas tile knobs (NANODILOCO_PALLAS_BLOCK_Q/K, default 128x128):
    # read at trace time, so a block-size sweep (scripts/chip_agenda.py
    # phase "pallas") retunes without code edits. Each fresh jit closure
    # (new Diloco / new jit of the caller) picks up the current value;
    # an already-compiled executable keeps the blocks it was traced with.
    # On a pod the values are broadcast from process 0 (_tile_knobs) so
    # every host compiles the same program. Validated so a malformed
    # value fails with a clear message, not mid-grid-math.
    if impl != "scan":
        env_bq, env_bk = _tile_knobs()
        bq = env_bq or min(128, block_size)
        bk = env_bk or min(128, block_size)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    if impl == "pallas":
        return _pallas_on_mesh(q, k, v, causal=causal, block_q=bq, block_k=bk)
    return _flash_attention_scan(q, k, v, causal=causal, block_size=block_size)


def _pallas_on_mesh(q, k, v, **kernel_args) -> jax.Array:
    """The Pallas kernel under the ambient mesh (``jax.set_mesh``).

    Mosaic refuses a kernel inside an automatically partitioned program,
    so on a multi-device mesh the call is a ``shard_map`` over every axis
    not already manual: batch over ``fsdp`` and heads over ``tp`` — the
    layout the batch and the projections arrive in (parallel/sharding.py)
    — each where it divides, else whole on every device. Attention mixes
    neither axis, so the region holds no collective. The DiLoCo worker
    axis reaches here as a vmap named ``spmd_axis_name="diloco"``
    (parallel/diloco.py), which shards the vmapped dimension the same
    way."""
    from nanodiloco_tpu.ops.pallas.flash_attention import pallas_flash_attention

    kernel = partial(pallas_flash_attention, **kernel_args)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or not set(mesh.axis_names) - set(mesh.manual_axes):
        return kernel(q, k, v)

    def over(axis: str, *sizes: int) -> str | None:
        free = axis in mesh.axis_names and axis not in mesh.manual_axes
        return axis if free and all(n % mesh.shape[axis] == 0 for n in sizes) else None

    spec = P(over("fsdp", q.shape[0]), None, over("tp", q.shape[2], k.shape[2]), None)
    return jax.shard_map(
        kernel, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


@partial(jax.jit, static_argnames=("causal", "block_size"))
def _flash_attention_scan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_size: int = 512,
) -> jax.Array:
    """Online-softmax over K/V blocks of ``block_size`` (clamped to S); the
    query axis stays whole — queries are cheap, the S^2 score matrix is
    what must never materialize. GQA runs at Hkv "heads" with each KV
    group's G query heads folded into the query-row axis ([B, Hkv, G*S]
    rows, position-fastest) — K/V are never expanded.
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    blk = min(block_size, s)
    if s % blk:
        raise ValueError(f"seq_len {s} must be divisible by block_size {blk}")
    nblk = s // blk
    scale = 1.0 / math.sqrt(hd)

    # [B, H, S, hd] -> [B, Hkv, G*S, hd]; row r has position r % S
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(b, hkv, g * s, hd)
    kb = jnp.transpose(k, (0, 2, 1, 3)).reshape(b, hkv, nblk, blk, hd)
    vb = jnp.transpose(v, (0, 2, 1, 3)).reshape(b, hkv, nblk, blk, hd)
    kb = jnp.moveaxis(kb, 2, 0)  # [nblk, B, Hkv, blk, hd]
    vb = jnp.moveaxis(vb, 2, 0)

    q_pos = jnp.tile(lax.broadcasted_iota(jnp.int32, (s,), 0), g)  # [G*S]

    def body(carry, blk_in):
        o, l, m, j = carry
        k_j, v_j = blk_in
        scores = (
            jnp.einsum("bhqd,bhkd->bhqk", qt, k_j).astype(jnp.float32) * scale
        )
        if causal:
            k_pos = j * blk + lax.broadcasted_iota(jnp.int32, (blk,), 0)
            allowed = q_pos[:, None] >= k_pos[None, :]  # [G*S, blk]
            scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        o, l, m = block_update(o, l, m, scores, v_j)
        return (o, l, m, j + 1), None

    o0 = jnp.zeros((b, hkv, g * s, hd), jnp.float32)
    l0 = jnp.zeros((b, hkv, g * s), jnp.float32)
    m0 = jnp.full((b, hkv, g * s), -jnp.inf, jnp.float32)
    (o, l, _, _), _ = lax.scan(
        jax.checkpoint(body), (o0, l0, m0, jnp.zeros((), jnp.int32)), (kb, vb)
    )
    return finalize_grouped(o, l, g, q.dtype)
