"""A Mosaic attention kernel's call under the ambient mesh: the one door
both of the repo's kernels pass through (``ops/flash_attention.py``,
``ops/splash_attention.py``)."""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import PartitionSpec as P


def kernel_on_mesh(kernel: Callable[..., jax.Array], q: jax.Array, k: jax.Array,
                   v: jax.Array, *rows: jax.Array) -> jax.Array:
    """``kernel(q, k, v, *rows)`` under the ambient mesh (``jax.set_mesh``):
    q [B, S, H, hd], k and v [B, S, Hkv, hd], each of ``rows`` an array
    over the batch ([B, ...], such as a [B, S] validity array); returns
    q's layout.

    Mosaic refuses a kernel inside an automatically partitioned program,
    so where the mesh spans devices over an axis no enclosing
    ``shard_map`` has made manual, the call is a ``shard_map`` over every
    such axis: batch over ``fsdp`` (``rows`` with it) and heads over
    ``tp`` where both H and Hkv divide — the layout the batch and the
    projections arrive in (parallel/sharding.py) — each where it divides,
    else whole on every device. Attention mixes neither axis, so the
    region holds no collective. The DiLoCo worker axis reaches here as a
    vmap named ``spmd_axis_name="diloco"`` (parallel/diloco.py), which
    shards the vmapped dimension the same way. On one device or inside a
    wholly manual region the kernel is called as it is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or not set(mesh.axis_names) - set(mesh.manual_axes):
        return kernel(q, k, v, *rows)

    def over(axis: str, *sizes: int) -> str | None:
        free = axis in mesh.axis_names and axis not in mesh.manual_axes
        return axis if free and all(n % mesh.shape[axis] == 0 for n in sizes) else None

    batch = over("fsdp", q.shape[0])
    spec = P(batch, None, over("tp", q.shape[2], k.shape[2]), None)
    row_specs = tuple(P(batch, *(None,) * (r.ndim - 1)) for r in rows)
    return jax.shard_map(
        kernel, in_specs=(spec, spec, spec, *row_specs), out_specs=spec, check_vma=False
    )(q, k, v, *rows)
