"""Sharding rules: one PartitionSpec per weight name.

Because per-layer weights are stacked on a leading layer axis
(models/llama.py), a single spec shards every layer; the DiLoCo worker
axis, when present, is a further leading axis mapped to ``"diloco"``.

Layout (2D "megatron-style" over fsdp x tp):
- column-parallel producers (wq/wk/wv, w_gate/w_up): input dim on fsdp,
  output dim on tp — the following reduction over the tp-sharded dim is
  a single XLA-inserted all-reduce per block, riding ICI;
- row-parallel consumers (wo, w_down) the transpose;
- embedding: VOCAB axis over fsdp, features replicated — a vocab-sharded
  token gather lowers to SPMD's mask+psum pattern, while feature-sharding
  was measured to trigger an involuntary full rematerialization of the
  gather output every step (PERF.md round-3 diagnosis);
  the untied lm_head carries the tp-sharded vocab on its matmul side;
  norm scales replicated.

XLA's SPMD partitioner inserts all collectives; nothing here issues one.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nanodiloco_tpu.models.config import LlamaConfig


def param_specs(
    cfg: LlamaConfig, worker_axis: bool = False, pp: bool = False
) -> dict[str, Any]:
    """PartitionSpec pytree matching models.llama.init_params' tree.
    With ``pp`` the stacked LAYER axis shards over the pipeline stages
    (ops/pipeline.py) — embed/head/norms stay replicated across pp."""
    lax0 = "pp" if pp else None  # the leading (layer) axis of layer leaves
    if cfg.mixed:
        return _mixed_param_specs(cfg, worker_axis, pp)
    if cfg.num_experts:
        # MoE: expert axis over ep; per-expert FFN dims over fsdp/tp
        mlp_specs = {
            "router": P(lax0, None, None),
            "w_gate": P(lax0, "ep", "fsdp", "tp"),
            "w_up": P(lax0, "ep", "fsdp", "tp"),
            "w_down": P(lax0, "ep", "tp", "fsdp"),
        }
    else:
        mlp_specs = {
            "w_gate": P(lax0, "fsdp", "tp"),
            "w_up": P(lax0, "fsdp", "tp"),
            "w_down": P(lax0, "tp", "fsdp"),
        }
    specs = {
        # VOCAB axis over fsdp (measured, round 3): with the FEATURE axis
        # sharded instead, the partitioner all-gathers the table and then
        # cannot reshard the gather output (batch-over-fsdp from the token
        # indices -> feature-over-fsdp for the wq/w_gate matmuls) without
        # an "[SPMD] Involuntary full rematerialization" — replicating
        # [W, B, S, D] every step on the fsdp x tp and ep x fsdp meshes
        # (MULTICHIP_r02 tail). A vocab-sharded gather lowers to SPMD's
        # mask+psum pattern and every dryrun mesh compiles warning-free
        # with identical losses.
        "embed": P("fsdp", None),
        "final_norm": P(),
        "layers": {
            "attn_norm": P(lax0, None),
            "wq": P(lax0, "fsdp", "tp"),
            "wk": P(lax0, "fsdp", "tp"),
            "wv": P(lax0, "fsdp", "tp"),
            "wo": P(lax0, "tp", "fsdp"),
            "mlp_norm": P(lax0, None),
            **mlp_specs,
        },
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    if worker_axis:
        specs = jax.tree.map(
            lambda s: P("diloco", *s), specs, is_leaf=lambda x: isinstance(x, P)
        )
    return specs


def _mixed_param_specs(cfg: LlamaConfig, worker_axis: bool, pp: bool) -> dict[str, Any]:
    """Specs for a mixed configuration's tree (``lead_layers`` unstacked,
    ``layers`` stacked over the periods, models/llama.py): the dense
    rules by name, the held
    experts' leading axis left whole (``ragged_dot`` dispatch needs
    replicated experts: ``ep > 1`` is refused where the mesh is built),
    the gate's bias and the q/k norms replicated. No pipeline: the
    stages scan one kind of layer."""
    if pp:
        raise ValueError(
            "pipeline parallelism (pp > 1) does not carry a mixed layer stack: "
            "ops/pipeline.py scans one kind of layer")
    attn = {
        "attn_norm": P(None, None), "mlp_norm": P(None, None),
        "wq": P(None, "fsdp", "tp"), "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"), "wo": P(None, "tp", "fsdp"),
    }
    if cfg.qk_norm:
        attn.update(q_norm=P(None, None), k_norm=P(None, None))
    dense = {**attn, "w_gate": P(None, "fsdp", "tp"), "w_up": P(None, "fsdp", "tp"),
             "w_down": P(None, "tp", "fsdp")}
    sparse = {**attn, "router": P(None, None, None),
              "w_gate": P(None, None, "fsdp", "tp"), "w_up": P(None, None, "fsdp", "tp"),
              "w_down": P(None, None, "tp", "fsdp")}
    if cfg.scoring_func == "sigmoid":
        sparse["router_bias"] = P(None, None)
    if cfg.num_shared_experts:
        sparse.update(shared_gate=P(None, "fsdp", "tp"), shared_up=P(None, "fsdp", "tp"),
                      shared_down=P(None, "tp", "fsdp"))
    from nanodiloco_tpu.models.llama import layer_plan

    plan = layer_plan(cfg)
    unstacked = lambda g: {k: P(*v[1:]) for k, v in g.items()}
    specs: dict[str, Any] = {
        "embed": P("fsdp", None), "final_norm": P(),
        "lead_layers": tuple(unstacked(sparse if plan.kinds[i][1] else dense)
                             for i in range(plan.lead)),
        "layers": tuple(dict(sparse if plan.kinds[plan.lead + j][1] else dense)
                        for j in range(plan.period if plan.periods else 0)),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    if worker_axis:
        specs = jax.tree.map(
            lambda s: P("diloco", *s), specs, is_leaf=lambda x: isinstance(x, P)
        )
    return specs


def kv_arena_leaf_spec(ndim: int) -> P:
    """Per-leaf spec for one member of the serve KV arena pytree: the
    5-d k/v pool ``[L, num_blocks, block_size, Hkv, hd]`` shards on the
    KV-HEAD axis over ``tp`` (attention is head-parallel, so each shard
    holds its own heads' K/V rows and never reads another shard's);
    every lower-rank member (the int8 per-row scales ``[L, nb, bs]``,
    which carry no head axis) is replicated. Everything host-side
    (block tables, free list, refcounts) stays unsharded — a block id
    names the same physical block on every shard, which is why block
    allocation, copy-on-write prefix sharing, and rejection-rollback
    cursor arithmetic are untouched by tensor parallelism. The ONE
    place this rule lives — the engine's host-side ``device_put`` and
    the compiled programs' ``with_sharding_constraint`` both read it,
    so they cannot drift and force a per-tick resharding transfer."""
    return P(None, None, None, "tp", None) if ndim == 5 else P()


def batch_spec(worker_axis: bool = True, accum_axis: bool = True, sp: bool = False) -> P:
    """Token batches are [W, accum, B, S] (or sub-layouts): workers over
    ``diloco``, per-worker batch over ``fsdp`` (data-parallel inside a
    worker), optionally sequence over ``sp``."""
    dims = []
    if worker_axis:
        dims.append("diloco")
    if accum_axis:
        dims.append(None)
    dims.append("fsdp")
    dims.append("sp" if sp else None)
    return P(*dims)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def constrain(tree: Any, mesh: Mesh, spec_tree: Any) -> Any:
    """with_sharding_constraint over a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        tree,
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
