"""Model configuration.

Mirrors the knobs of the reference's JSON model configs
(ref configs/llama_default.json:1-10 and nanodiloco/main.py:16-27): a
HF-style Llama config with hidden/intermediate sizes, heads, layers,
rms_norm_eps. Extended with the fields a real Llama family needs
(GQA, rope theta, vocab, tying) so the same dataclass scales from the
tiny 128-hidden model to Llama-3-8B-class configs.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any


LAYER_KINDS = ("sliding_attention", "full_attention")
# layer kinds whose cache is no plain K/V rows: block-sparse attention
# (compressed keys a slot beside the paged pool: models/sparse_attention.py)
# and decayed linear attention (a per-slot state and no rows at all:
# models/linear_attention.py). Beside LAYER_KINDS, which stays the set
# a rotary table a layer kind is keyed by
STATE_LAYER_KINDS = ("sparse_attention", "linear_attention")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 128
    intermediate_size: int = 512
    num_hidden_layers: int = 6
    num_attention_heads: int = 4
    num_key_value_heads: int | None = None  # None -> MHA (== num_attention_heads)
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # TPU knobs (no reference analog — compute policy, not architecture):
    dtype: str = "float32"          # activation/compute dtype ("bfloat16" on TPU)
    param_dtype: str = "float32"    # master parameter dtype
    remat: bool = False             # jax.checkpoint each decoder layer
    # What the per-layer checkpoint may SAVE instead of recomputing:
    # "nothing" recomputes the whole layer in backward (min HBM);
    # "dots" saves matmul outputs and recomputes only the cheap
    # elementwise ops (norms, rope, silu) — less recompute where the
    # FLOPs are, at higher activation memory.
    remat_policy: str = "nothing"   # "nothing" | "dots"
    attention_impl: str = "dense"   # "dense" | "flash" | "ring"
    # rows per chunk of the blockwise cross-entropy (ops/fused_ce.py):
    # the full [B, S, V] logits tensor is never materialized. 0 = off.
    # 512 is the tuned TPU default (+38% step throughput on the
    # reference's hidden-128 / vocab-32000 config, bench.py).
    loss_chunk: int = 512
    # Mixture-of-Experts MLP (models/moe.py); 0 = dense (the reference's
    # only mode). Experts shard over the ``ep`` mesh axis.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # 1.25 justified by measurement (scripts/moe_evidence.py "cf",
    # runs/moe_evidence_r5.jsonl): loss flat across cf 1.0-2.0 at the
    # 120-step pylib budget while drops fall 0.34->0.09 — see the
    # models/moe.py design note before trusting this at larger scale
    expert_capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # "tokens_choose": Switch-style top-k experts per token + load-balance
    # aux loss. "experts_choose": each expert picks its top-C tokens
    # (arXiv:2202.09368) — perfectly load-balanced by construction, no
    # aux loss, but token selection sees the whole (batch, sequence) set,
    # so training is not strictly causal and autoregressive decode is
    # unsupported. Both modes size the per-expert capacity as
    # C = ceil(num_experts_per_tok * T / E * capacity_factor) — clamped
    # to T in expert-choice (an expert cannot pick a token twice): there,
    # num_experts_per_tok is the AVERAGE number of experts per token
    # (set 1 for Switch-equivalent compute).
    router_type: str = "tokens_choose"
    # "dense": static one-hot dispatch/combine einsums [T, E, C] with
    # capacity-overflow drops — the XLA-friendly default, right through
    # E<=32 (measured, models/moe.py design note). "ragged": sort
    # token-slot assignments by expert and run exact-sized grouped
    # matmuls (jax.lax.ragged_dot, the Mixtral/megablocks shape) — no
    # capacity, NO dropped tokens, FLOPs exact rather than padded; the
    # large-E regime where the [T, E, C] einsum padding dominates.
    # tokens_choose + replicated experts only (ep=1): the sorted
    # permutation is sequence-global, and sharding experts over ep would
    # need the all-to-all a megablocks-style kernel provides.
    moe_dispatch: str = "dense"
    # -- mixed layer stacks (window and full attention layers, leading
    # dense layers before sparse ones, a bias-corrected gate, a shared
    # expert, the chip's share of the experts). Any of these set makes
    # the configuration ``mixed``: models/llama.py then runs its layers
    # as leading dense layers and scanned periods, models/generate.py
    # keeps two kinds of cache, and the paths that do not carry them
    # refuse the configuration by name. All default to the dense
    # decoder above, whose programs they leave untouched.
    # a head size given apart from hidden_size / num_attention_heads
    # (``head_dim`` in an HF config); None derives it
    explicit_head_dim: int | None = None
    # one of LAYER_KINDS for each layer; None = every layer full
    layer_types: tuple[str, ...] | None = None
    # keys a sliding layer's row i sees: i - window < j <= i
    sliding_window: int | None = None
    # RMSNorm over each head's values of q and k, before RoPE
    qk_norm: bool = False
    # "all": rotary embedding in every layer; "sliding": in sliding
    # layers only (full layers see no position but the causal order)
    rope_layers: str = "all"
    # rotary parameters by layer kind (an HF ``rope_parameters`` whose
    # keys are LAYER_KINDS), frozen to sorted pairs ((kind, ((key,
    # value), ...)), ...) so that the configuration stays hashable:
    # ``rope_for(kind)`` reads it. None: one table from ``rope_theta``
    rope_parameters: tuple | None = None
    # layers [0, first_k_dense_replace) keep the dense SwiGLU of width
    # ``intermediate_size``; the rest are sparse, each expert of width
    # ``moe_intermediate_size`` (None: ``intermediate_size``)
    first_k_dense_replace: int = 0
    moe_intermediate_size: int | None = None
    # SwiGLUs of the expert width that every token passes, summed with
    # the routed experts' output
    num_shared_experts: int = 0
    # "softmax" | "sigmoid" router scores; with "sigmoid" each sparse
    # layer carries a selection bias (``router_bias`` [E] float32) that
    # is added to the scores for the top-k choice and not to the weights
    scoring_func: str = "softmax"
    # weights of the chosen experts normalised to sum to 1 (over ALL k
    # chosen, held on this chip or not), then scaled
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the chip's share of the routed experts as (first, count): the
    # router keeps its ``num_experts`` outputs and its k, the layer
    # holds weights for, and adds the outputs of, experts
    # first..first+count-1 alone. None = all of them
    experts_held: tuple[int, int] | None = None
    # -- block-sparse and linear attention layers (STATE_LAYER_KINDS in
    # ``layer_types``; serving only). A sparse layer's query at position
    # t sees n = t + 1 keys: all of them up to ``sparse_dense_len``,
    # else the first ``sparse_init_blocks`` blocks of ``sparse_block_size``
    # keys, every block that holds one of the last ``sparse_window_size``
    # keys, and the ``sparse_topk`` best of the rest by the scores of
    # compressed keys (means of ``sparse_kernel_size`` keys every
    # ``sparse_kernel_stride``): models/sparse_attention.py
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # a linear layer's head h decays its state by exp(-2^(-e (h + 1) / H)
    # * f_l) a token, e = ``linear_decay_exponent``, f_l = 1 - l / (N - 1)
    # + 1e-5 for PUBLISHED layer index l = ``first_layer_index`` + i of
    # N = ``published_layers`` (None: this stack is the whole model)
    linear_decay_exponent: float = 8.0
    first_layer_index: int = 0
    published_layers: int | None = None
    # sigmoid(W_g h) on the attention output of a sparse (``attn_``) and
    # a linear layer, and an RMSNorm over a linear layer's joined heads
    attn_output_gate: bool = False
    linear_output_gate: bool = False
    linear_output_norm: bool = False
    # muP scalings: the embedding times ``scale_emb``; every residual
    # branch times ``scale_depth`` / sqrt(published layers) (None: 1);
    # the head's input divided by hidden_size / ``dim_model_base``
    scale_emb: float = 1.0
    scale_depth: float | None = None
    dim_model_base: int | None = None

    @property
    def head_dim(self) -> int:
        if self.explicit_head_dim is not None:
            return self.explicit_head_dim
        return self.hidden_size // self.num_attention_heads

    @property
    def mixed(self) -> bool:
        """Whether any mechanism of the mixed layer stack is set."""
        return bool(
            (self.layer_types is not None and "sliding_attention" in self.layer_types)
            or self.qk_norm or self.rope_layers != "all"
            or self.rope_parameters is not None
            or self.first_k_dense_replace or self.num_shared_experts
            or self.scoring_func != "softmax" or not self.norm_topk_prob
            or self.routed_scaling_factor != 1.0
            or self.experts_held is not None
            or self.moe_intermediate_size is not None
            or self.state_layers
            or self.attn_output_gate or self.linear_output_gate
            or self.linear_output_norm or self.scale_emb != 1.0
            or self.scale_depth is not None or self.dim_model_base is not None
        )

    @property
    def state_layers(self) -> bool:
        """Whether any layer is of STATE_LAYER_KINDS."""
        return bool(self.layer_types) and bool(
            set(self.layer_types) & set(STATE_LAYER_KINDS))

    @property
    def residual_scale(self) -> float | None:
        """What every residual branch is multiplied by (None: nothing)."""
        if self.scale_depth is None:
            return None
        return self.scale_depth / (self.published_layers or self.num_hidden_layers) ** 0.5

    @property
    def head_divisor(self) -> float | None:
        """What the head's input is divided by (None: nothing)."""
        if self.dim_model_base is None:
            return None
        return self.hidden_size / self.dim_model_base

    def rotates(self, kind: str) -> bool:
        """Whether a layer of attention kind ``kind`` rotates q and k."""
        return self.rope_layers == "all" or (
            self.rope_layers, kind) in (("sliding", "sliding_attention"),
                                        ("linear", "linear_attention"))

    def linear_log_decay(self, i: int) -> list[float]:
        """log(lambda_h) of linear layer ``i``'s heads: negative, fixed."""
        n = self.published_layers or self.num_hidden_layers
        f = 1.0 - (self.first_layer_index + i) / max(n - 1, 1) + 1e-5
        nh = self.num_attention_heads
        return [-(2.0 ** (-self.linear_decay_exponent * (h + 1) / nh)) * f
                for h in range(nh)]

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the routed experts this chip holds."""
        return self.experts_held or (0, self.num_experts)

    def rope_for(self, kind: str) -> dict[str, Any]:
        """The rotary parameters of a layer of ``kind``: ``rope_type``
        ("default" or "yarn"), ``rope_theta`` and, for YaRN, ``factor``,
        ``original_max_position_embeddings``, ``beta_fast``,
        ``beta_slow`` and ``attention_factor``."""
        if self.rope_parameters is None:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return dict(dict(self.rope_parameters)[kind])

    def layer_kind(self, i: int) -> tuple[str, bool]:
        """(attention kind, sparse feed-forward?) of layer ``i``."""
        kind = self.layer_types[i] if self.layer_types else "full_attention"
        return kind, bool(self.num_experts) and i >= self.first_k_dense_replace

    @property
    def kv_heads(self) -> int:
        if self.num_key_value_heads is None:
            return self.num_attention_heads
        return self.num_key_value_heads

    def __post_init__(self) -> None:
        if self.explicit_head_dim is None and self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide evenly by num_attention_heads")
        if self.mixed:
            self._check_mixed()
        if self.num_key_value_heads is not None and self.num_key_value_heads < 1:
            raise ValueError("num_key_value_heads must be >= 1 (or None for MHA)")
        if self.num_attention_heads % self.kv_heads:
            raise ValueError("num_attention_heads must divide evenly by num_key_value_heads")
        if self.remat_policy not in ("nothing", "dots"):
            raise ValueError(
                f"remat_policy must be 'nothing' or 'dots'; got "
                f"{self.remat_policy!r}"
            )
        if self.router_type not in ("tokens_choose", "experts_choose"):
            raise ValueError(
                f"router_type must be 'tokens_choose' or 'experts_choose'; "
                f"got {self.router_type!r}"
            )
        if self.num_experts and self.num_experts_per_tok > self.num_experts:
            raise ValueError(
                f"num_experts_per_tok ({self.num_experts_per_tok}) cannot "
                f"exceed num_experts ({self.num_experts})"
            )
        if self.moe_dispatch not in ("dense", "ragged"):
            raise ValueError(
                f"moe_dispatch must be 'dense' or 'ragged'; got "
                f"{self.moe_dispatch!r}"
            )
        if self.moe_dispatch == "ragged" and self.router_type != "tokens_choose":
            raise ValueError(
                "moe_dispatch='ragged' supports tokens_choose routing only: "
                "expert-choice selects a FIXED top-C token set per expert, "
                "which is exactly the static shape dense dispatch already "
                "handles without padding waste — ragged's benefit (exact "
                "group sizes) only exists for data-dependent group sizes"
            )

    def _check_mixed(self) -> None:
        """A mixed configuration's own constraints, and the paths that
        do not carry it: each refusal names the feature."""
        n = self.num_hidden_layers
        if self.layer_types is not None:
            if len(self.layer_types) != n:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"num_hidden_layers is {n}")
            bad = set(self.layer_types) - set(LAYER_KINDS) - set(STATE_LAYER_KINDS)
            if bad:
                raise ValueError(f"layer_types must be of {LAYER_KINDS + STATE_LAYER_KINDS}; "
                                 f"got {sorted(bad)}")
            if "sliding_attention" in self.layer_types and not self.sliding_window:
                raise ValueError("sliding_attention layers need sliding_window >= 1")
        if self.rope_layers not in ("all", "sliding", "linear"):
            raise ValueError(
                f"rope_layers must be 'all', 'sliding' or 'linear'; got {self.rope_layers!r}")
        if self.state_layers:
            self._check_state_layers()
        if self.rope_parameters is not None:
            kinds = {kind for kind, _ in self.rope_parameters}
            if kinds != set(LAYER_KINDS):
                raise ValueError(
                    f"rope_parameters by layer kind must name {LAYER_KINDS}; got {sorted(kinds)}")
            for kind in LAYER_KINDS:
                if self.rope_for(kind).get("rope_type", "default") not in ("default", "yarn"):
                    raise ValueError(
                        f"rope_type must be 'default' or 'yarn'; got {self.rope_for(kind)!r}")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring_func must be 'softmax' or 'sigmoid'; got {self.scoring_func!r}")
        if not 0 <= self.first_k_dense_replace <= n:
            raise ValueError("first_k_dense_replace must lie in [0, num_hidden_layers]")
        if self.attention_impl != "dense":
            raise ValueError(
                f"attention_impl={self.attention_impl!r} does not carry a mixed layer "
                "stack (sliding-window layers, per-head q/k norms, layers without "
                "RoPE): the repo's flash and ring kernels know one causal mask and no "
                "padding mask; use 'dense', the default, under which the program picks "
                "a layer's implementation itself (a fused kernel with the layer's "
                "window on a TPU where the shapes allow, query blocks elsewhere: "
                "models/llama.py:fused_attention_applies)")
        if not self.num_experts:
            if (self.num_shared_experts or self.experts_held is not None
                    or self.first_k_dense_replace):
                raise ValueError(
                    "shared experts, experts_held and first_k_dense_replace "
                    "need num_experts > 0")
            return
        if self.router_type != "tokens_choose":
            raise ValueError(
                "router_type='experts_choose' does not carry the sigmoid / "
                "bias-corrected gate, shared experts or a held share of the "
                "experts: use 'tokens_choose'")
        if self.moe_dispatch != "ragged":
            raise ValueError(
                "moe_dispatch='dense' ([T, E, C] capacity dispatch) does not "
                "carry the sigmoid / bias-corrected gate, shared experts or a "
                "held share of the experts: use moe_dispatch='ragged' (no "
                "capacity, no dropped token)")
        first, count = self.held_experts
        if not (0 <= first and 1 <= count and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} must lie inside the "
                f"router's {self.num_experts} experts")

    def _check_state_layers(self) -> None:
        """The geometry a sparse layer's selection is written for, and
        what a stack with a state does not carry."""
        blk, kern, stride = (self.sparse_block_size, self.sparse_kernel_size,
                             self.sparse_kernel_stride)
        if min(blk, kern, stride, self.sparse_topk, self.sparse_window_size) < 1 \
                or self.sparse_init_blocks < 0:
            raise ValueError("the sparse layers' sizes must be positive")
        if blk % stride or kern % stride or kern - stride > blk:
            raise ValueError(
                f"sparse_kernel_stride {stride} must divide sparse_block_size {blk} and "
                f"sparse_kernel_size {kern}, and a compressed key may reach back over "
                "at most one block")
        if self.sparse_dense_len < self.sparse_window_size + blk * (self.sparse_init_blocks + 1):
            raise ValueError(
                "sparse_dense_len must hold the first blocks and the window's apart: "
                "a query past it forces both and they may not overlap")
        if self.num_experts:
            raise ValueError(
                "sparse_attention / linear_attention layers beside expert layers are "
                "not carried: the layers' counters are the attention's or the experts'")
        if self.rope_parameters is not None:
            raise ValueError(
                "sparse_attention / linear_attention layers take one rotary table "
                "(rope_theta), not rope_parameters by layer kind")

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "LlamaConfig":
        """Build from an HF-style config dict, ignoring unknown keys.

        The reference feeds its JSON straight into ``LlamaConfig(**cfg)``
        (ref nanodiloco/main.py:97); we accept the same files, including
        keys we don't model (``architectures``, ``use_cache``). HF's
        ``head_dim`` is ``explicit_head_dim`` here, ``rope_theta`` may
        stand inside ``rope_parameters``, a ``rope_parameters`` keyed by
        layer kind is kept (``rope_for``), and lists become tuples (the
        configuration is a hashable jit-static argument).
        """
        names = {f.name for f in dataclasses.fields(cls)}
        d = dict(d)
        if "head_dim" in d and "explicit_head_dim" not in d:
            d["explicit_head_dim"] = d["head_dim"]
        rope = d.pop("rope_parameters", None) or {}
        if "rope_theta" not in d and "rope_theta" in rope:
            d["rope_theta"] = float(rope["rope_theta"])
        if rope and set(dict(rope)) <= set(LAYER_KINDS):  # a dict, or its frozen pairs
            d["rope_parameters"] = tuple(sorted(
                (kind, tuple(sorted((k, v) for k, v in dict(of).items())))
                for kind, of in dict(rope).items()))
        return cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in d.items() if k in names})

    @classmethod
    def from_json(cls, path: str) -> "LlamaConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def num_params(self) -> int:
        """Exact parameter count (embedding + layers + final norm + head)."""
        d, f, v, l = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_hidden_layers
        hd, nh, nkv = self.head_dim, self.num_attention_heads, self.kv_heads
        attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 2 * d  # q, k, v, o, norms
        if self.qk_norm:
            attn += 2 * hd
        if self.state_layers:
            wide = d * nh * hd
            per_kind = {
                "sparse_attention": attn + (wide if self.attn_output_gate else 0),
                "linear_attention": 4 * wide + 2 * d + (2 * hd if self.qk_norm else 0)
                + (wide if self.linear_output_gate else 0)
                + (nh * hd if self.linear_output_norm else 0)}
            head = 0 if self.tie_word_embeddings else d * v
            return (v * d + sum(per_kind.get(k, attn) for k in self.layer_types)
                    + l * 3 * d * f + d + head)
        if self.num_experts:
            fe = self.expert_width
            held = self.held_experts[1]
            # router (+ selection bias), the experts HELD, the shared ones
            sparse = d * self.num_experts + 3 * (held + self.num_shared_experts) * d * fe
            if self.scoring_func == "sigmoid":
                sparse += self.num_experts
            k = self.first_k_dense_replace
            mlp_all = k * 3 * d * f + (l - k) * sparse
        else:
            mlp_all = l * 3 * d * f  # gate, up, down
        head = 0 if self.tie_word_embeddings else d * v
        return v * d + l * attn + mlp_all + d + head


# The reference's inline default config (ref nanodiloco/main.py:16-27).
TINY_LLAMA = LlamaConfig()

# The "large" variant from the reference's prepare_configs
# (ref scripts/train_modal.py:215-225): hidden 256 x 12 layers.
LARGE_LLAMA = LlamaConfig(
    hidden_size=256, intermediate_size=1024, num_attention_heads=8, num_hidden_layers=12
)

# New capability target (BASELINE.json config 3): Llama-3-8B-class.
# Ships with the memory-lean TPU policy: bf16 compute, per-layer remat,
# blockwise flash attention (dense would materialize [B, H, S, S] scores
# at S up to 8192), GQA-native kernels (32q/8kv never expanded), and
# chunked CE over the 128k vocab.
LLAMA3_8B = LlamaConfig(
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    max_position_embeddings=8192,
    rope_theta=500000.0,
    dtype="bfloat16",
    remat=True,
    attention_impl="flash",
)
