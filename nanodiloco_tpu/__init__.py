"""nanodiloco_tpu — a TPU-native DiLoCo training framework.

A from-scratch JAX/XLA re-design of the capabilities of NanoDiloco
(reference: /root/reference, a minimal torch implementation of
DiLoCo, arXiv:2311.08105). Every DiLoCo worker is a shard of a
`jax.sharding.Mesh` axis named ``"diloco"``; the outer pseudo-gradient
all-reduce is a mean over that axis compiled into the XLA graph, riding
ICI within a slice and DCN across slices — there is no NCCL, no process
group, no runtime collective library.

Package map (TPU-first, not a port):
- ``models/``   Llama-family decoder as pure pytree functions
                (scan-over-layers, RoPE/RMSNorm/SwiGLU, HF-parity numerics).
- ``ops/``      attention kernels: dense, Pallas flash, ring attention
                (sequence parallelism over an ``"sp"`` mesh axis).
- ``parallel/`` mesh construction, sharding rules (diloco/fsdp/tp/sp axes),
                and the DiLoCo core (jitted inner/outer steps).
- ``training/`` optimizers (optax), train driver, checkpointing (orbax),
                metrics (real outer-sync wall-clock, unlike the reference's
                dead stubs, ref nanodiloco/diloco/diloco.py:23-24,62-64).
- ``data/``     tokenizer + dataset pipeline with deterministic per-worker
                sharding, plus a native C++ tokenshard reader.
"""

__version__ = "0.1.0"

from nanodiloco_tpu.models.config import LlamaConfig  # noqa: F401
from nanodiloco_tpu.parallel.diloco import Diloco, DilocoConfig  # noqa: F401

__all__ = ["LlamaConfig", "Diloco", "DilocoConfig", "__version__"]
