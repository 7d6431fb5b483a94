"""Slot-based continuous-batching engine over the static-shape KV cache.

Orca-style (Yu et al., OSDI'22) iteration-level scheduling on TPU terms:
the engine owns ONE preallocated cache that independent request slots
share. One storage form: a ``[L, num_blocks, block_size, Hkv, hd]``
arena (the block pool) plus a per-slot block table (vLLM's
PagedAttention insight, arXiv:2309.06180, on this repo's static-shape
terms). A request is admitted with exactly ``ceil((prompt + max_new) /
block_size)`` blocks — HBM caps concurrency by tokens RESIDENT, not
slots x worst-case — and its blocks return to the free list the moment
it retires, expires, or cancels. The pool's default size,
``num_slots * ceil(max_len / block_size)`` blocks, admits ``num_slots``
requests of ``max_len`` tokens each; ``kv_pool_blocks`` sizes it to the
HBM a deployment has. ``kv_dtype="int8"`` stores the arena quantized
(per-row scales, quantize on write, dequantize in the attention read)
for ~4x slots per HBM byte vs float32; the float arena stays
bit-identical to solo ``generate()``.

A request's life:

- ``start_prefill(slot, request)`` stages the request into a free slot
  and, when the prefix cache holds the prompt's leading chunks, reuses
  them: it maps the cached chunks' BLOCKS into the slot's table
  copy-on-write (refcount bump, zero device copies) — "copy" never
  happens, because a slot only ever writes past its prefix-hit
  boundary, into blocks it owns exclusively. Admission is
  all-or-nothing: if the pool cannot supply the blocks,
  ``BlocksExhausted`` is raised with nothing allocated and nothing
  counted, and the scheduler leaves the request queued (admission gates
  on free BLOCKS, not just free slots).
- ``prefill_step(slot)`` runs ONE prefill chunk (Sarathi-Serve,
  arXiv:2403.02310: chunked prefill is what keeps a 4k-token prompt
  from freezing every live decode stream between two ticks). The final
  chunk returns the first token; earlier chunks return None. Chunk
  lengths are bucketed to powers of two, so mixed-length traffic
  compiles a BOUNDED program set — not one prefill executable per
  prompt length. Sampling is FUSED into the chunk program: a final
  chunk is one dispatch doing attention+sampling, never
  attention-then-sample.
- every ``step()`` advances ALL decoding slots with a single compiled
  program and returns per-slot token VECTORS (per-slot positions, PRNG
  keys, and sampling params ride as traced arrays; sampling fused into
  the same executable) — admitting a new request or retiring a
  finished one never recompiles and never stops the other streams.
  Without speculation every live slot emits exactly one token; with
  ``spec_k > 0`` host-proposed prompt-lookup drafts
  (serve/speculation.py) are verified by one forward over k+1
  positions per slot and each slot emits its longest accepted prefix
  plus the verified bonus token — 1..k+1 tokens, never zero. Exact
  acceptance (accept a draft iff it equals the token the plain tick
  would have sampled with the same per-step key) keeps EVERY stream —
  greedy and sampled — bit-identical to solo ``generate()``; for the
  deterministic prompt-lookup proposal this rule coincides with
  rejection sampling, so it costs no acceptance either.
- ``release(slot)`` frees the slot (mid-prefill or mid-decode). Nothing
  is zeroed: a retired slot's stale K/V is causally unreachable to the
  next occupant. Every block the slot referenced is deref'd — shared
  prefix blocks survive while the prefix cache (or another slot) still
  holds them; exclusive blocks return to the free list immediately.

Chunking math (why it is exact): K/V at position i depend only on
``tokens[:i+1]``, so writing them chunk-by-chunk produces the same cache
bits as one whole-prompt call; each chunk's queries attend causally over
everything already written, which is the same reduction the one-shot
prefill performs row by row. Every chunk starts at the prompt cursor
(``done``) — a multiple of chunk_size, hence block-aligned — and the
final chunk right-pads up to its power-of-two bucket, passing the last
REAL index into the program: pad K/V land past the prompt, causally
unreachable, then overwritten by decode. (Right-padding, never
re-feeding earlier tokens, is what makes copy-on-write safe: a slot
never writes at positions below its prefix-hit boundary, so shared
blocks are read-only by construction.)

Determinism contract (tested, float pool): a request's token
stream is exactly the stream ``generate()`` produces alone with the
same seed and sampling params — through chunked admission AND through a
prefix-cache hit. The per-request PRNG schedule is replicated on the
host at admission — ``key, k0 = split(key(seed))`` for the first token,
then ``split(key, max_new_tokens - 1)`` for the decode steps (the full
array is materialized up front because ``split(key, n)[i]`` depends on
``n`` on this jax) — and each tick feeds every slot its own next key.
The int8 arena trades that bit-parity for HBM: its contract is logit
tolerance + greedy-token parity (tests/test_kv_paging.py), not bits.

Tensor parallelism (``tp > 1``): params shard by the training
``param_specs`` rules, the arena shards on the KV-HEAD axis
(``parallel/sharding.py::kv_arena_leaf_spec``), and every compiled program
above runs sharded with the final logits replicated before sampling —
the per-step PRNG schedule is unchanged, so a TP stream is bit-identical
to solo ``generate(mesh=...)`` on the same layout. Everything host-side
(block table, free list, refcounts, the prefix cache's chunk registry)
stays UNsharded: a block id names the same physical block on every
shard, so allocation, copy-on-write sharing, and rejection rollback are
degree-independent by construction.

Hot-swap weight deployment (``swap_weights``, fleet/): new params from
the latest training checkpoint replace the serving params atomically —
the KV arenas, block pool, and slot state are untouched (only params
change). Slots tag the weight GENERATION they were admitted under: a
stream in flight at the swap keeps dispatching its own generation's
params (one extra masked dispatch per tick during the transition
window) and finishes bit-identical to solo ``generate()`` on the OLD
weights, while every post-swap admission runs — bit-identically — on
the new ones. The prefix cache is invalidated at the swap: its K/V was
computed under the old params. No recompile: the programs are keyed by
config/shape, and a swap changes neither.

Two kinds of cache (a MIXED configuration, ``cfg.mixed``: window and
full attention layers, a held share of the experts): full layers keep
the paged pool and the block tables, one pool a layer, and every
sliding-window layer holds a ring of ``window + chunk_size`` rows a
slot that no block table addresses (models/generate.py,
``init_mixed_serve_cache``); ``blocks_for`` and the block pool count
the full layers alone, ``kv_stats()`` gives bytes by kind, and the tick
and chunk programs return the expert layer's counters with the tokens
(``moe_stats()``). Carried over: the paged bf16 pool, chunked prefill,
the plain decode tick, release and reuse of a slot (a ring is never
cleared: its mask is rebuilt from the slot's position). NOT carried
over, and refused at construction by name: speculation, the prefix
cache, int8 rows, a serving mesh; ``export_kv`` and ``import_kv``
refuse at the call.

Three kinds of cache (``cfg.state_layers``: block-sparse and linear
attention layers, models/sparse_attention.py, models/linear_attention.py):
a sparse layer keeps its K and V rows in the paged pool behind the block
tables as a full layer does, and beside them its COMPRESSED keys (a mean
of 32 keys every 16, the rows its selector scores) in a per-slot arena
``[slots, max rows / 16, Hkv, hd]`` addressed by slot and row as a ring
is (stale rows lie past what the mask of complete rows lets through); a
linear layer keeps a float32 state ``[slots, heads, hd, hd]`` and no
rows at all. A state has no such mask: the
programs zero a row's state where the row is live at position 0 (the
first chunk of whatever stream takes the slot). The tick and chunk
programs return the attention's counters (``attn_stats()``) and, with
``capture_routing`` set, the blocks every query chose. Refused for this
stack by name, each with the cache kind that stops it: speculation, the
prefix cache, int8 rows, a serving mesh, ``export_kv``/``import_kv``.

Known divergence, inherited from ``generate`` and narrowed here: dense-
dispatch token-choice MoE sizes expert capacity from the tokens in the
call, so a decode tick routes over B slots where ``generate`` routes
over 1, and a prefill chunk routes over its chunk where ``generate``
routes over the whole prompt. With ample capacity (or
``moe_dispatch="ragged"``) routing is per-token independent and
identical; dead slots are masked out of routing entirely (``active``).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.models.generate import (
    decode_slots_mixed_fn,
    decode_slots_paged_fn,
    init_kv_pool,
    init_mixed_serve_cache,
    kv_bytes_per_token,
    mixed_cache_bytes,
    prefill_chunk_mixed_fn,
    prefill_chunk_paged_fn,
    verify_slots_paged_fn,
    view_ladder,
    view_rung,
)
from nanodiloco_tpu.models.llama import layer_counters
from nanodiloco_tpu.models.moe import COUNTERS
from nanodiloco_tpu.obs.devtime import DispatchAccountant
from nanodiloco_tpu.obs.telemetry import Histogram
from nanodiloco_tpu.obs.tracer import trace_span
from nanodiloco_tpu.serve import kvship
from nanodiloco_tpu.serve.block_pool import BlockPool, BlocksExhausted
from nanodiloco_tpu.serve.prefix_cache import PrefixCache
from nanodiloco_tpu.serve.speculation import PromptLookupProposer

__all__ = ["InferenceEngine", "BlocksExhausted"]

# blocks-held-per-request histogram bounds (requests, not seconds —
# powers of two up to a long request's worst case)
_BLOCK_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# emitted-tokens-per-tick histogram bounds (tokens; a spec tick emits
# 1..spec_k+1 per slot)
_SPEC_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16)


def _floor_pow2(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


def _ceil_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


@dataclasses.dataclass
class _Prefill:
    """One slot's in-flight prefill: the staged request plus the cursor
    into its prompt. ``done`` tokens are already in the slot's cache
    (prefix-cache hit + completed chunks); the chunks-remaining count
    lives in the scheduler's ``_Prefilling``, fed by ``start_prefill``'s
    return value."""

    request: object
    ids: list[int]
    done: int            # prompt tokens whose K/V are written


class InferenceEngine:
    """The slot backend the scheduler drives. Not thread-safe: all calls
    must come from one thread (the scheduler's tick loop)."""

    def __init__(
        self,
        params,
        cfg: LlamaConfig,
        *,
        num_slots: int = 4,
        max_len: int = 1024,
        chunk_size: int = 64,
        prefix_cache_tokens: int = 0,
        kv_block_size: int = 16,
        kv_dtype: str | None = None,
        kv_pool_blocks: int | None = None,
        spec_k: int = 0,
        spec_ngram: int = 3,
        tp: int = 1,
    ) -> None:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1; got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2; got {max_len}")
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1; got {chunk_size}")
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1; got {kv_block_size}")
        if cfg.num_experts and cfg.router_type == "experts_choose":
            raise ValueError(
                "expert-choice routing is training-only (see generate()); "
                "use router_type='tokens_choose' for serving"
            )
        if cfg.rope_parameters is not None:
            raise ValueError(
                "the serving programs build one rotary table and this "
                "configuration has one a layer kind (rope_parameters): "
                "served so, its full layers would lose their YaRN"
            )
        if kv_dtype not in (None, "model", "int8"):
            raise ValueError(
                f"kv_dtype must be 'model' or 'int8'; got {kv_dtype!r}"
            )
        self.kv_dtype = None if kv_dtype == "model" else kv_dtype
        self.mixed = cfg.mixed
        if self.mixed:
            # a mixed layer stack's two kinds of cache carry the paged
            # bf16 pool, chunked prefill and the plain tick; the rest
            # refuses here, by name, and takes no silent other path
            # (on, the feature, what of a stack with sparse and linear
            # layers cannot follow it: the cache kind that stops it)
            for on, what, state_why in (
                (spec_k, "speculation (spec_k > 0: the verify programs)",
                 "a linear layer's state cannot step back over a rejected draft (it "
                 "holds no rows to drop), and a sparse layer's compressed keys would "
                 "be completed by tokens that are then taken back"),
                (prefix_cache_tokens, "the prefix cache (prefix_cache_tokens > 0)",
                 "shared blocks carry K and V rows, but the per-slot state and the "
                 "per-slot compressed keys at the end of the shared prefix are held "
                 "nowhere (no snapshot of a state)"),
                (self.kv_dtype == "int8", "kv_dtype='int8' (quantized rows)",
                 "the compressed keys and the float32 state have no quantized form"),
                (int(tp) > 1, "a serving mesh (tp > 1)",
                 "no partition rule is written for the compressed keys or the state"),
            ):
                if on:
                    why = (f"a stack with linear-attention layers: {state_why}"
                           if cfg.state_layers else
                           "a mixed layer stack (window layers in per-slot rings "
                           "beside the paged pool)")
                    raise ValueError(
                        f"{what} is not carried over to {why}: serve this "
                        "configuration with a model-dtype cache, no speculation, "
                        "no prefix cache, tp=1")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0; got {spec_k}")
        # tensor parallelism: shard params (param_specs), the compiled
        # serve programs, and the KV arena (kv_arena_leaf_spec: the KV-head
        # axis) over a tp-axis mesh. Validated LOUDLY here, at boot —
        # a bad degree must be a readable config error, never a shape
        # error out of the first traced program.
        tp = int(tp)
        if tp < 1:
            raise ValueError(f"tp must be >= 1; got {tp}")
        if tp > 1:
            ndev = len(jax.devices())
            if tp > ndev:
                raise ValueError(
                    f"tp={tp} exceeds the {ndev} available "
                    f"device{'s' if ndev != 1 else ''} — the mesh cannot "
                    "be built (use --force-cpu-devices N for virtual "
                    "CPU shards)"
                )
            if cfg.kv_heads % tp:
                raise ValueError(
                    f"tp={tp} does not divide the model's KV-head count "
                    f"({cfg.kv_heads}): the KV arenas shard on the "
                    "KV-head axis, so the degree must divide it evenly"
                )
        self.tp = tp
        if tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh
            from nanodiloco_tpu.parallel.sharding import named, param_specs

            self.mesh = build_mesh(
                MeshConfig(tp=tp), devices=jax.devices()[:tp]
            )
            self._replicated = NamedSharding(self.mesh, PartitionSpec())
            # params resident in their serving layout up front: the
            # first tick must never pay a resharding transfer
            params = jax.device_put(params, named(self.mesh, param_specs(cfg)))
        else:
            self.mesh = None
            self._replicated = None
        self.params = params
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        # chunk lengths are bucketed to powers of two; capping the top
        # bucket at the largest power of two <= max_len keeps every
        # bucketed write inside the slot's gathered view (a bucket can
        # right-pad a final chunk, and dynamic_update_slice would CLAMP
        # an out-of-range write backwards over real positions)
        self.chunk_size = _floor_pow2(min(int(chunk_size), self.max_len))
        self.vocab_size = cfg.vocab_size
        b = self.num_slots
        # block size: a power of two no larger than the chunk size, so
        # every chunk start (a multiple of chunk_size) is block-aligned
        # and shared prefix chunks map to whole blocks
        self.kv_block_size = _floor_pow2(
            min(int(kv_block_size), self.chunk_size)
        )
        bs = self.kv_block_size
        self.max_blocks = -(-self.max_len // bs)   # allocation bound
        # the TABLE is one chunk of sentinel entries wider than any
        # allocation: a right-padded final bucket then always fits the
        # gathered view (done + bucket <= ceil(max_len/cs)*cs < view),
        # so a final chunk never has to start below the cursor, where
        # it would rewrite shared copy-on-write blocks (with OTHER bits
        # in int8: a row read back dequantized is not the fresh row).
        # Pad writes land on the sentinel and drop; pad reads are
        # causally masked.
        self.table_blocks = self.max_blocks + self.chunk_size // bs
        default_blocks = self.num_slots * self.max_blocks
        nb = int(kv_pool_blocks) if kv_pool_blocks else default_blocks
        # a pool SMALLER than one max_len request is legal — it serves
        # short requests and validate() rejects the long ones outright
        # (they could never be admitted)
        self.block_pool = BlockPool(nb, bs)
        if self.mixed:
            # full layers in the pool, sliding layers in rings a chunk
            # wider than the window (generate.py says why)
            self.ring_rows = int(cfg.sliding_window or 0) + self.chunk_size
            if cfg.state_layers and (
                    bs % cfg.sparse_kernel_stride or cfg.sparse_block_size % bs
                    or self.chunk_size % cfg.sparse_kernel_stride):
                raise ValueError(
                    f"a sparse layer's pool needs sparse_kernel_stride "
                    f"{cfg.sparse_kernel_stride} | kv_block_size {bs} | "
                    f"sparse_block_size {cfg.sparse_block_size}, and chunks of "
                    f"whole strides (chunk_size {self.chunk_size})")
            self.pool = init_mixed_serve_cache(
                cfg, self.num_slots, self.ring_rows, nb, bs,
                # a sparse layer's compressed keys: one a stride of the
                # longest stream a table can hold
                self.table_blocks * bs // cfg.sparse_kernel_stride)
            self._chunk_paged = prefill_chunk_mixed_fn(cfg)
            self._decode_paged = decode_slots_mixed_fn(cfg)
        else:
            self.pool = self._shard_kv(
                init_kv_pool(cfg, nb, bs, self.kv_dtype))
            self._chunk_paged = prefill_chunk_paged_fn(
                cfg, self.kv_dtype, self.mesh
            )
            self._decode_paged = decode_slots_paged_fn(
                cfg, self.kv_dtype, self.mesh
            )
        # per-slot block tables; the sentinel nb is out of range: reads
        # clamp to causally-dead garbage, writes drop
        self._tables = np.full((b, self.table_blocks), nb, np.int32)
        # the width a tick's full-attention read took through the tables
        # (generate.py ``view_ladder``): the program picks it from the
        # positions it is handed, and the host names it by the same rule
        # from ``_pos``: a histogram of view rows over the decode and
        # verify dispatches, one bucket a width (``kv_stats()``)
        self._view_ladder = view_ladder(self.table_blocks)
        self.hist_view_rows = Histogram(w * bs for w in self._view_ladder)
        self._slot_blocks: list[list[int]] = [[] for _ in range(b)]
        self.kv_block_evictions = 0
        self.hist_blocks_per_request = Histogram(_BLOCK_BUCKETS)
        self.prefix_cache = (
            PrefixCache(
                int(prefix_cache_tokens), self.chunk_size,
                on_evict=self._evict_prefix_blocks,
            )
            if prefix_cache_tokens else None
        )
        # speculative decoding (spec_k > 0): host-side prompt-lookup
        # drafts (serve/speculation.py) verified by ONE compiled forward
        # over k+1 positions per slot. Draft widths bucket to powers of
        # two, so the verify program set is bounded like the chunk set;
        # a tick with no drafts anywhere falls back to the plain decode
        # program, so adversarial traffic pays only the (host) lookup.
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        if self.spec_k:
            self.speculator = PromptLookupProposer(
                self.spec_k, max_ngram=self.spec_ngram
            )
            self._verify = verify_slots_paged_fn(
                cfg, self.kv_dtype, self.mesh)
        else:
            self.speculator = None
            self._verify = None
        self._spec_ok = [False] * self.num_slots   # per-slot opt-in state
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rejected_tokens = 0
        self.spec_ticks = 0                        # ticks that ran verify
        self.decode_ticks = 0                      # every decode tick
        self.hist_spec_tokens_per_tick = Histogram(_SPEC_BUCKETS)

        # hot-swap weight generations (fleet/): ``swap_weights`` bumps
        # ``deploy_generation`` and stages the new params; slots tag the
        # generation they were ADMITTED under, so during a transition
        # window live streams keep decoding on the weights they started
        # with while new admissions take the new ones — a swap never
        # drops (or silently reweights) an in-flight request.
        self.deploy_generation = 0
        self._params_by_gen: dict[int, object] = {0: self.params}
        self._slot_gen = [0] * b

        self._tokens = np.zeros(b, np.int32)       # next input token per slot
        self._pos = np.zeros(b, np.int32)          # next cache write position
        self._active = np.zeros(b, np.int32)
        self._temp = np.zeros(b, np.float32)
        self._topk = np.zeros(b, np.int32)
        self._topp = np.ones(b, np.float32)
        # per-slot precomputed decode key data [max_new-1, 2] uint32
        self._keys: list[np.ndarray | None] = [None] * b
        self._step_idx = [0] * b
        self._prefills: list[_Prefill | None] = [None] * b
        self._dummy_key = np.asarray(
            jax.random.key_data(jax.random.key(0)), np.uint32
        )
        # debug probe, OFF by default: when ``capture_prefill_logits``
        # is set, each final chunk's logits land here as numpy — the
        # int8 tolerance tests read it. Left off, nothing is copied:
        # a [1, V] device-to-host transfer per admission is real TTFT
        # at production vocab sizes
        self.capture_prefill_logits = False
        self.last_prefill_logits: np.ndarray | None = None
        # a mixed configuration's expert counters, summed over every
        # tick and chunk: [held pairs, held experts hit, all pairs,
        # sparse-layer calls that took the short path] (moe.COUNTERS,
        # moe.sparse_mlp). A second debug probe, OFF by default: with
        # ``capture_routing`` set, the experts each slot's tokens chose
        # land in ``routing_log[slot]`` as [L_sparse, tokens, k] pieces,
        # in position order (the benchmark's check reads them)
        # A stack with sparse and linear layers counts its attention in
        # their place (``layer_counters``: attn_stats()); its
        # ``routing_log`` holds the blocks each query chose, [L_sparse,
        # tokens, Hkv, topk]. A third probe for such a stack: a stream
        # whose prefill ends while ``capture_decode_logits`` is set has
        # every tick's logits [V] appended to ``decode_logits_log[slot]``
        # until its slot is released (the other slots' are not copied);
        # the log stays until the caller clears it
        self._counter_names = layer_counters(cfg) if self.mixed else COUNTERS
        self.moe_counts = {"prefill_chunk": np.zeros(len(self._counter_names), np.int64),
                           "decode": np.zeros(len(self._counter_names), np.int64)}
        self.capture_decode_logits = False
        self.decode_logits_log: dict[int, list[np.ndarray]] = {}
        self._logit_slots: set[int] = set()
        self.capture_routing = False
        self.routing_log: dict[int, list[np.ndarray]] = {}
        # device-resident copies of the slot state that only changes at
        # admit/release (``_stage_dev`` says why)
        self._dev: dict | None = None
        # (kind -> bucket set) of every program shape dispatched, for
        # the layout-qualified compile-count introspection
        self._buckets: dict[str, set[int]] = {}
        # device-time ledger: every dispatch below runs inside one of
        # its fence-timed sections, keyed by the same (kind, bucket,
        # layout) triples as the compile counts (obs/devtime)
        self.accountant = DispatchAccountant()
        # KV block shipping meters (serve/kvship.py): payload bytes,
        # blocks, and wall seconds per direction — the disaggregated
        # fleet's handoff cost counters, surfaced via kvship_stats()
        self.kvship_counts = {
            "export_requests": 0, "import_requests": 0,
            "export_bytes": 0, "import_bytes": 0,
            "export_blocks": 0, "import_blocks": 0,
            "export_seconds": 0.0, "import_seconds": 0.0,
        }

    # -- tensor-parallel plumbing -------------------------------------------

    def _shard_kv(self, kv: dict) -> dict:
        """Commit a KV arena to its serving sharding — the same
        ``kv_arena_leaf_spec`` rule the compiled programs constrain to,
        so the committed layout can never drift from the traced one.
        No-op without a mesh."""
        if self.mesh is None:
            return kv
        from jax.sharding import NamedSharding

        from nanodiloco_tpu.parallel.sharding import kv_arena_leaf_spec

        return {
            name: jax.device_put(
                arr, NamedSharding(self.mesh, kv_arena_leaf_spec(arr.ndim))
            )
            for name, arr in kv.items()
        }

    def _jarr(self, value, dtype=None):
        """Host value -> device array. With a mesh, commit it REPLICATED
        over the tp shards so every program input has an unambiguous
        placement (mixing mesh-committed params with single-device tick
        inputs would make the dispatch placement implementation-defined)."""
        arr = np.asarray(value, dtype) if dtype is not None else np.asarray(value)
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self._replicated)

    # -- hot-swap weight deployment (fleet/) ---------------------------------

    def swap_weights(self, params) -> int:
        """Atomically deploy new params without dropping in-flight
        requests. The new tree must match the serving params leaf for
        leaf (same structure, shapes, dtypes — validated LOUDLY here, at
        the swap, never as a shape error out of the next tick); with a
        mesh it is ``device_put`` into the SAME serving layout boot
        established, so the first post-swap tick never pays a resharding
        transfer. The KV arenas are untouched — only params change — so
        live slots keep their cache rows and finish on the weights they
        were admitted under (their generation's params stay resident
        until the last such slot retires), while every later admission
        runs on the new weights. The prefix cache is INVALIDATED: its
        K/V was computed under the old params, and a post-swap hit
        would splice stale rows into a new-weight stream. Must be
        called from the tick thread (``Scheduler.call_on_tick`` hands a
        swap over from HTTP threads). Returns the new generation."""
        with self.accountant.section("swap", 0, self.kv_layout,
                                     first_is_compile=False):
            return self._swap_weights_inner(params)

    def _swap_weights_inner(self, params) -> int:
        old = jax.tree_util.tree_flatten_with_path(self.params)[0]
        new = jax.tree_util.tree_flatten_with_path(params)[0]
        if [p for p, _ in old] != [p for p, _ in new]:
            raise ValueError(
                "swap_weights: new params tree structure does not match "
                "the serving params (different architecture?)"
            )
        for (path, a), (_, b) in zip(old, new):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                name = "/".join(str(getattr(k, "key", k)) for k in path)
                raise ValueError(
                    f"swap_weights: leaf {name} is "
                    f"{tuple(b.shape)}:{b.dtype} but the serving engine "
                    f"holds {tuple(a.shape)}:{a.dtype} — the checkpoint "
                    "does not fit this engine's compiled programs"
                )
        if self.mesh is not None:
            from nanodiloco_tpu.parallel.sharding import named, param_specs

            params = jax.device_put(
                params, named(self.mesh, param_specs(self.cfg))
            )
        # fence the transfer: the swap section's seconds must cover the
        # H2D upload, not just its dispatch
        jax.block_until_ready(params)
        self.deploy_generation += 1
        self._params_by_gen[self.deploy_generation] = params
        self.params = params
        if self.prefix_cache is not None:
            # cached K/V was computed under the old weights; reusing it
            # would break the bit-parity contract (the cached blocks are
            # deref'd through on_evict, exactly like LRU eviction)
            self.prefix_cache.clear()
        self._prune_param_generations()
        return self.deploy_generation

    def _prune_param_generations(self) -> None:
        """Drop param generations no live (or mid-prefill) slot
        references — an old snapshot stays resident only while a stream
        admitted under it is still running."""
        live = {self.deploy_generation}
        for s in range(self.num_slots):
            if self._active[s] or self._prefills[s] is not None:
                live.add(self._slot_gen[s])
        for g in [g for g in self._params_by_gen if g not in live]:
            del self._params_by_gen[g]

    def _gen_groups(self) -> dict[int, list[int]]:
        """Live slots grouped by the weight generation they were
        admitted under (one group in the steady state)."""
        groups: dict[int, list[int]] = {}
        for s in range(self.num_slots):
            if self._active[s]:
                groups.setdefault(self._slot_gen[s], []).append(s)
        return groups

    # -- request validation (shared with the server's 400 path) -------------

    def blocks_for(self, prompt_tokens: int, max_new_tokens: int) -> int:
        """KV blocks a request occupies for its whole life:
        prompt + completion rows, rounded up to whole blocks. Allocation
        is up-front and exact, so a request admitted never runs out of
        cache mid-decode."""
        # (a sparse layer's compressed keys are held a slot, not a block:
        # the count stands)
        return -(-(prompt_tokens + max_new_tokens) // self.kv_block_size)

    def validate(self, prompt, max_new_tokens: int) -> None:
        """Raises ValueError when a request cannot be served by this
        engine's static shapes (including a pool it could NEVER
        fit — transient block shortage is ``BlocksExhausted`` at
        admission instead, and retryable)."""
        if len(prompt) < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}"
            )
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine's max_len "
                f"({self.max_len})"
            )
        need = self.blocks_for(len(prompt), max_new_tokens)
        if need > self.block_pool.num_blocks:
            raise ValueError(
                f"request needs {need} KV blocks but the pool only "
                f"has {self.block_pool.num_blocks} in total — it can "
                f"never be admitted"
            )
        bad = [t for t in prompt if not 0 <= int(t) < self.vocab_size]
        if bad:
            raise ValueError(
                f"prompt tokens {bad[:4]} outside the model vocabulary "
                f"({self.vocab_size})"
            )

    # -- slot lifecycle ------------------------------------------------------

    def start_prefill(self, slot: int, request) -> int:
        """Stage ``request`` into free slot ``slot``: validate, reuse
        any cached shared-prefix K/V, and return the number of prefill
        chunks still to run (>= 1 — the last prompt token always
        prefills for real, its logits seed the first sample). The
        request's whole block budget is allocated here,
        all-or-nothing: ``BlocksExhausted`` (nothing mutated, nothing
        counted) tells the scheduler to keep the request queued until
        blocks free up."""
        ids = [int(t) for t in request.prompt]
        self.validate(ids, request.max_new_tokens)
        use_cache = self.prefix_cache is not None and getattr(
            request, "prefix_cache", True
        )
        need = self.blocks_for(len(ids), request.max_new_tokens)
        # PEEK the prefix cache first: sizing must precede any
        # side effect so a block-starved admission rolls back to
        # nothing (no counters, no LRU churn, no refs). Under
        # pressure, RECLAIM cache-only blocks by evicting LRU
        # prefixes: cached K/V is a best-effort optimization, and
        # without this path a cache that swallowed the pool would
        # livelock admission forever (no prefill can complete, so
        # insert-side eviction never runs). Each eviction can
        # invalidate the matched chain, so the peek re-walks.
        while True:
            chains = (
                self.prefix_cache.match(ids, record=False)
                if use_cache else []
            )
            shared = [blk for chunk in chains for blk in chunk]
            own_need = need - len(shared)
            if own_need <= self.block_pool.free_blocks:
                break
            if (self.prefix_cache is None
                    or not self.prefix_cache.evict_lru()):
                raise BlocksExhausted(
                    f"request needs {own_need} KV blocks "
                    f"({need} total, {len(shared)} shared) but only "
                    f"{self.block_pool.free_blocks}/"
                    f"{self.block_pool.num_blocks} are free"
                )
        # commit: record the hit/miss for real (same chain —
        # nothing mutated between the peek and this), take the
        # references
        if use_cache:
            chains = self.prefix_cache.match(ids)
        own = self.block_pool.alloc(own_need)
        self.block_pool.ref(shared)
        blocks = shared + own
        self._slot_blocks[slot] = blocks
        row = np.full(self.table_blocks, self.block_pool.num_blocks,
                      np.int32)
        row[: len(blocks)] = blocks
        self._tables[slot] = row
        self._dev = None
        done = len(chains) * self.chunk_size
        # the request is admitted under the CURRENT weights; every chunk
        # and decode tick of its life dispatches this generation's
        # params, even if a hot swap lands mid-stream
        self._slot_gen[slot] = self.deploy_generation
        self._prefills[slot] = _Prefill(request, ids, done)
        return -(-(len(ids) - done) // self.chunk_size)

    def _run_chunk(self, slot: int, chunk, valid, pos: int, last: int,
                   key_data, temp: float, top_k: int, top_p: float):
        """Dispatch one (bucketed) chunk through the compiled chunk
        program; returns (token scalar, logits [1, V])."""
        self._buckets.setdefault("prefill_chunk", set()).add(len(chunk))
        params = self._params_by_gen[self._slot_gen[slot]]
        with trace_span("engine.stage_chunk", slot=slot):
            args = (
                self._jarr([chunk], np.int32), self._jarr(valid),
                self._jarr(pos, np.int32), self._jarr(last, np.int32),
                self._jarr(key_data, np.uint32),
                self._jarr(temp, np.float32), self._jarr(top_k, np.int32),
                self._jarr(top_p, np.float32),
            )
        counts = None
        with self.accountant.section("prefill_chunk", len(chunk),
                                     self.kv_layout), \
                trace_span("engine.prefill_chunk", slot=slot,
                           bucket=len(chunk)):
            if self.mixed:
                tok, logits, self.pool, counts, chosen = self._chunk_paged(
                    params, self.pool, self._jarr(self._tables[slot]),
                    self._jarr(slot, np.int32), *args,
                )
            else:
                tok, logits, self.pool = self._chunk_paged(
                    params, self.pool,
                    self._jarr(self._tables[slot]), *args,
                )
            # fence INSIDE the section: interior chunks have no host
            # consumer (the final chunk's int(tok) is the only natural
            # sync), and an unfenced async dispatch would be timed as
            # free. One output suffices — the chunk is one executable,
            # its buffers materialize together. The counters' copy is
            # asked for before the wait, so that it queues behind the
            # program and does not start at the host's notice of its end
            if counts is not None:
                counts.copy_to_host_async()
            with trace_span("engine.wait"):
                jax.block_until_ready(tok)
            if counts is not None:
                with trace_span("engine.fetch_counts"):
                    self.moe_counts["prefill_chunk"] += np.asarray(counts, np.int64)
                    if self.capture_routing:
                        real = int(np.asarray(valid).sum())
                        self.routing_log.setdefault(slot, []).append(
                            np.asarray(chosen)[:, 0, :real])
        return tok, logits

    def prefill_step(self, slot: int) -> int | None:
        """Run ONE prefill chunk for the staged request in ``slot``.
        Returns None while chunks remain; the final chunk samples (in
        the same executable) and returns the first token, leaving the
        slot live for ``step()``."""
        pf = self._prefills[slot]
        if pf is None:
            raise ValueError(f"slot {slot} has no prefill in flight")
        ids, p = pf.ids, len(pf.ids)
        remaining = p - pf.done
        dummy = (self._dummy_key, 0.0, 0, 1.0)  # interior chunks: unused
        if remaining > self.chunk_size:
            # full interior chunk: exactly chunk_size real tokens
            lo = pf.done
            chunk = ids[lo:lo + self.chunk_size]
            self._run_chunk(
                slot, chunk, np.ones((1, self.chunk_size), np.int32),
                lo, self.chunk_size - 1, *dummy,
            )
            pf.done += self.chunk_size
            return None

        # final chunk, bucketed to a power of two and right-padded: the
        # chunk always starts AT the cursor (never re-feeds earlier
        # positions — which is what makes shared prefix blocks
        # read-only), pads land past the prompt (causally unreachable,
        # then overwritten by decode), and the true last-real index rides
        # into the program as a traced scalar. The padded bucket always
        # fits the gathered view: the table is a chunk wider than any
        # allocation (``__init__`` says why).
        bucket = _ceil_pow2(remaining)
        lo = pf.done
        chunk = ids[lo:] + [0] * (bucket - remaining)
        valid = np.zeros((1, bucket), np.int32)
        valid[0, :remaining] = 1
        last = remaining - 1
        req = pf.request
        temp = float(req.temperature)
        top_k = min(int(req.top_k), self.vocab_size)
        top_p = float(req.top_p)
        # the one-shot generate()'s exact key schedule, replayed per slot
        with trace_span("engine.keys", slot=slot):
            key = jax.random.key(int(req.seed))
            karr = jax.random.split(key)  # karr[0] = rest, karr[1] = k0
            k0 = np.asarray(jax.random.key_data(karr[1]), np.uint32)
        tok, logits = self._run_chunk(
            slot, chunk, valid, lo, last, k0, temp, top_k, top_p,
        )
        tok0 = int(tok)
        if self.capture_prefill_logits:
            self.last_prefill_logits = np.asarray(logits)
        if self.capture_decode_logits:
            self.decode_logits_log[slot] = []
            self._logit_slots.add(slot)
        pf.done = p
        n = int(req.max_new_tokens)
        with trace_span("engine.keys", slot=slot):
            self._keys[slot] = (
                np.asarray(
                    jax.random.key_data(jax.random.split(karr[0], n - 1)),
                    np.uint32)
                if n > 1 else np.zeros((0, 2), np.uint32)
            )
        self._step_idx[slot] = 0
        self._pos[slot] = p
        self._tokens[slot] = tok0
        self._temp[slot] = temp
        self._topk[slot] = top_k
        self._topp[slot] = top_p
        self._active[slot] = 1
        # speculation is per-request opt-in (``GenRequest.speculate``):
        # the proposer only ever sees opted-in slots
        self._spec_ok[slot] = bool(self.spec_k) and bool(
            getattr(req, "speculate", True)
        )
        if self._spec_ok[slot]:
            self.speculator.begin(slot, ids, tok0)
        self._dev = None  # slot state changed: re-stage on the next step

        self._prefills[slot] = None
        if (
            self.prefix_cache is not None
            and getattr(req, "prefix_cache", True)
            # a slot admitted before a hot swap computed these K/V rows
            # under the OLD weights: inserting them into the (cleared,
            # current-generation) cache would hand stale rows to the
            # next same-prefix request — the exact corruption clear()
            # exists to prevent
            and self._slot_gen[slot] == self.deploy_generation
        ):
            # explicit admission: every completed (non-opted-out)
            # prefill offers its whole-chunk prefix; only chunks not
            # already cached are registered
            cs = self.chunk_size
            n_chunks = (p - 1) // cs
            # zero-copy: the cache takes a REFERENCE to the slot's own
            # blocks for each new chunk (bumping their refcount) — the
            # rows never move, and they outlive the slot
            cpb = cs // self.kv_block_size

            def extract(i: int):
                blks = tuple(
                    int(x) for x in
                    self._tables[slot][i * cpb:(i + 1) * cpb]
                )
                self.block_pool.ref(blks)
                return blks

            self.prefix_cache.insert(ids, n_chunks, extract)
        return tok0

    def prefill(self, slot: int, request) -> int:
        """Whole-prompt convenience: stage and run every chunk in one
        call (the parity tests' sequential driver; the scheduler
        interleaves ``prefill_step`` with decode ticks instead)."""
        self.start_prefill(slot, request)
        while True:
            tok = self.prefill_step(slot)
            if tok is not None:
                return tok

    def _stage_dev(self) -> dict:
        """Device-resident slot state that only changes at admit/release
        (uploading the tables every tick would put an H2D copy on the
        per-token path)."""
        if self._dev is None:
            self._dev = {
                "temp": self._jarr(self._temp),
                "topk": self._jarr(self._topk),
                "topp": self._jarr(self._topp),
                "active": self._jarr(self._active),
                "tables": self._jarr(self._tables),
            }
        return self._dev

    def _collect_drafts(self) -> tuple[list[list[int]], int]:
        """Ask the proposer for each live opted-in slot's drafts, capped
        so the tick can never emit past the request's key schedule
        (emitted <= draft_len + 1 <= remaining). Returns (per-slot draft
        lists, max draft length this tick)."""
        drafts: list[list[int]] = [[] for _ in range(self.num_slots)]
        k_tick = 0
        new_tick = getattr(self.speculator, "new_tick", None)
        if new_tick is not None:
            new_tick()
        for s in range(self.num_slots):
            if not self._active[s] or not self._spec_ok[s]:
                continue
            ks = self._keys[s]
            # keys has max_new - 1 entries; position j of the verify
            # window consumes key[step_idx + j], so the last legal draft
            # index is len(keys) - step_idx - 1 (the +1 bonus token then
            # lands exactly on the request's final step)
            cap = min(self.spec_k, len(ks) - self._step_idx[s] - 1)
            if cap <= 0:
                continue
            d = list(self.speculator.propose(s, cap))[:cap]
            if d:
                drafts[s] = [int(t) for t in d]
                k_tick = max(k_tick, len(d))
        return drafts, k_tick

    def step(self) -> list[list[int]]:
        """Advance every live slot 1..spec_k+1 tokens (one compiled
        tick, sampling fused in). Returns per-slot emitted-token lists
        (empty for inactive slots). Without speculation — or on a tick
        where no slot has a draft — every live slot emits exactly one
        token via the plain decode program; with drafts in flight, ONE
        verify dispatch covers every slot and each emits its accepted
        prefix plus the verified bonus token (never zero: all-reject
        still makes one token of forward progress)."""
        b = self.num_slots
        if self.spec_k:
            with trace_span("engine.draft"):
                drafts, k_tick = self._collect_drafts()
        else:
            drafts, k_tick = [[] for _ in range(b)], 0
        self.decode_ticks += 1
        if k_tick == 0:
            return self._step_plain()
        return self._step_verify(drafts, k_tick)

    def _gen_dispatches(self, dev) -> list[tuple[object, list[int], object]]:
        """(params, slots, active array) per weight generation with a
        live slot. The steady state — every live slot on one generation
        — is ONE dispatch reusing the cached device-resident mask, the
        exact pre-hot-swap behavior. During a swap's transition window
        there is one masked dispatch per generation: each stream runs
        under the weights it was admitted with, and the dispatches
        compose because inactive slots' cache writes are masked/dropped
        (the PR-6 fix) and routing masks dead slots out entirely."""
        groups = self._gen_groups()
        if len(groups) <= 1:
            slots = next(iter(groups.values())) if groups else []
            gen = next(iter(groups)) if groups else self.deploy_generation
            return [(self._params_by_gen[gen], slots, dev["active"])]
        out = []
        for gen in sorted(groups):
            act = np.zeros(self.num_slots, np.int32)
            act[groups[gen]] = 1
            out.append((self._params_by_gen[gen], groups[gen],
                        self._jarr(act)))
        return out

    def _tally_view(self, slots: list[int], t: int) -> None:
        """Count one dispatch over ``slots`` at ``t`` positions a slot
        under the view width its program takes."""
        bs = self.kv_block_size
        need = int(self._pos[slots].max()) + t if slots else 0
        rung = int(view_rung(self._view_ladder, need, bs))
        self.hist_view_rows.observe(self._view_ladder[rung] * bs)

    def _step_plain(self) -> list[list[int]]:
        b = self.num_slots
        with trace_span("engine.stage"):
            keys_now = np.empty((b, 2), np.uint32)
            for s in range(b):
                ks = self._keys[s]
                if (self._active[s] and ks is not None
                        and self._step_idx[s] < len(ks)):
                    keys_now[s] = ks[self._step_idx[s]]
                else:
                    keys_now[s] = self._dummy_key
            self._buckets.setdefault("decode", set()).add(1)
            dev = self._stage_dev()
            tokens = self._jarr(self._tokens)
            pos = self._jarr(self._pos)
            keys = self._jarr(keys_now)
            dispatches = self._gen_dispatches(dev)
        out: list[list[int]] = [[] for _ in range(b)]
        for params, slots, active in dispatches:
            self._tally_view(slots, 1)
            with self.accountant.section("decode", 1, self.kv_layout):
                counts = chosen = None
                with trace_span("engine.decode_dispatch"):
                    if self.mixed:
                        nxt, self.pool, counts, chosen, *logits = self._decode_paged(
                            params, self.pool, dev["tables"],
                            tokens, pos, keys,
                            dev["temp"], dev["topk"], dev["topp"], active,
                        )
                    else:
                        nxt, self.pool = self._decode_paged(
                            params, self.pool, dev["tables"],
                            tokens, pos, keys,
                            dev["temp"], dev["topk"], dev["topp"], active,
                        )
                # the host fetch below is the tick's natural fence;
                # inside the section so the measured seconds cover the
                # program, not just its dispatch. ``engine.wait`` holds
                # the program's run and the host's notice of its end; the
                # tokens' copy is asked for first, so that it queues
                # behind the program and does not start at that notice
                with trace_span("engine.fetch_tokens"):
                    nxt.copy_to_host_async()
                    with trace_span("engine.wait"):
                        jax.block_until_ready(nxt)
                    nxt = np.asarray(nxt)
                    if counts is not None:
                        with trace_span("engine.fetch_counts"):
                            self.moe_counts["decode"] += np.asarray(counts, np.int64)
                            if self.capture_routing:
                                chosen = np.asarray(chosen)
                                for s in slots:
                                    self.routing_log.setdefault(s, []).append(
                                        chosen[:, s])
                            for s in self._logit_slots:
                                self.decode_logits_log[s].append(
                                    np.asarray(logits[0][s]))
            with trace_span("engine.advance"):
                for s in slots:
                    self._pos[s] += 1
                    self._step_idx[s] += 1
                    self._tokens[s] = nxt[s]
                    tok = int(nxt[s])
                    if self._spec_ok[s]:
                        self.speculator.observe(s, [tok])
                    out[s] = [tok]
        return out

    def _step_verify(self, drafts: list[list[int]], k_tick: int) -> list[list[int]]:
        """One speculative tick: verify up to ``k_tick`` drafts per slot
        (bucketed to a power of two — bounded verify-program set) in a
        single forward over k+1 positions, emit each slot's longest
        accepted prefix + bonus token, and advance cursors by the
        emission count. Rejected positions' K/V rows sit PAST the
        advanced cursor inside the slot's own allocation (or dropped at
        the table sentinel) and are rewritten by a later tick before any
        query can reach them — rollback is cursor arithmetic, with
        nothing to free and nothing leakable."""
        b = self.num_slots
        bucket = min(_ceil_pow2(k_tick), self.spec_k)
        t = bucket + 1
        with trace_span("engine.stage"):
            tokens = np.zeros((b, t), np.int32)
            tokens[:, 0] = self._tokens
            dlen = np.zeros(b, np.int32)
            keys_now = np.empty((b, t, 2), np.uint32)
            keys_now[:] = self._dummy_key
            for s in range(b):
                d = drafts[s][:bucket]
                if d:
                    tokens[s, 1:1 + len(d)] = d
                    dlen[s] = len(d)
                ks = self._keys[s]
                if self._active[s] and ks is not None:
                    lo = self._step_idx[s]
                    n = min(t, len(ks) - lo)
                    if n > 0:
                        keys_now[s, :n] = ks[lo:lo + n]
            self._buckets.setdefault("verify", set()).add(t)
            dev = self._stage_dev()
            jtokens = self._jarr(tokens)
            jpos = self._jarr(self._pos)
            jdlen = self._jarr(dlen)
            jkeys = self._jarr(keys_now)
            dispatches = self._gen_dispatches(dev)
        out: list[list[int]] = [[] for _ in range(b)]
        for params, slots, active in dispatches:
            self._tally_view(slots, t)
            with self.accountant.section("verify", t, self.kv_layout):
                with trace_span("engine.decode_dispatch"):
                    sampled, counts, self.pool = self._verify(
                        params, self.pool, dev["tables"],
                        jtokens, jpos, jdlen, jkeys,
                        dev["temp"], dev["topk"], dev["topp"], active,
                    )
                with trace_span("engine.fetch_tokens"):
                    sampled.copy_to_host_async()
                    with trace_span("engine.wait"):
                        jax.block_until_ready(sampled)
                    sampled = np.asarray(sampled)
                    counts = np.asarray(counts)
            with trace_span("engine.advance"):
                for s in slots:
                    c = int(counts[s])
                    emitted = [int(v) for v in sampled[s, :c]]
                    self._pos[s] += c
                    self._step_idx[s] += c
                    self._tokens[s] = emitted[-1]
                    proposed = int(dlen[s])
                    accepted = c - 1
                    self.spec_draft_tokens += proposed
                    self.spec_accepted_tokens += accepted
                    self.spec_rejected_tokens += proposed - accepted
                    if proposed:
                        # drafting slots only: a no-draft neighbour riding
                        # the verify tick emits 1 by construction, and
                        # counting it would make the gated tokens-per-tick
                        # economics measure batch composition instead of
                        # speculation quality
                        self.hist_spec_tokens_per_tick.observe(c)
                    if self._spec_ok[s]:
                        if proposed:
                            self.speculator.feedback(s, proposed, accepted)
                        self.speculator.observe(s, emitted)
                    out[s] = emitted
        self.spec_ticks += 1
        return out

    def warm_spec(self) -> int:
        """Compile every verify-program bucket before traffic arrives
        (spec_k buckets the draft width to powers of two; each bucket
        is one executable). Drives a throwaway greedy request through
        slot 0 with a scripted proposer that walks the bucket widths,
        then releases it — nothing observable leaks (no prefix-cache
        insert, blocks returned). Requires an idle engine (call at
        startup, before the tick loop owns the slots). Returns the
        number of buckets warmed; no-op without speculation."""
        if not self.spec_k:
            return 0
        if any(self._active) or any(p is not None for p in self._prefills):
            raise RuntimeError("warm_spec needs an idle engine")
        # widest first: the cap arithmetic (len(keys) - step_idx - 1)
        # shrinks as the throwaway stream advances, so the width that
        # needs the most headroom goes while headroom is maximal
        widths = sorted({
            min(_ceil_pow2(k), self.spec_k)
            for k in range(1, self.spec_k + 1)
        }, reverse=True)

        class _Ramp:
            """Proposes exactly ``self.k`` junk drafts per tick."""

            def __init__(self, vocab: int) -> None:
                self.k = 0
                self.tok = vocab - 1

            def begin(self, *a):
                pass

            def release(self, *a):
                pass

            def propose(self, slot, cap):
                return [self.tok] * min(self.k, cap)

            def observe(self, *a):
                pass

            def feedback(self, *a):
                pass

        from nanodiloco_tpu.serve.scheduler import GenRequest

        prompt_len = min(8, self.max_len // 2)
        req = GenRequest(
            prompt=(1,) * prompt_len,
            max_new_tokens=max(2, min(
                (self.spec_k + 2) * len(widths), self.max_len - prompt_len,
            )),
            prefix_cache=False,
        )
        saved = self.speculator
        ramp = _Ramp(self.vocab_size)
        self.speculator = ramp
        try:
            self.prefill(0, req)
            self._spec_ok[0] = True
            for w in widths:
                ramp.k = w
                self.step()
        finally:
            self.speculator = saved
            self.release(0)
            # the ramp's ticks are warmup, not traffic: /metrics must
            # never report them. Device seconds follow the same rule;
            # COMPILE seconds stay — warmup is exactly when the verify
            # buckets compile, and that budget line is the point
            self.reset_spec_stats()
            self.accountant.reset_device_seconds()
        return len(widths)

    def reset_spec_stats(self) -> None:
        """Zero the speculation counters and histogram — warmup traffic
        (warm_spec's ramp, a bench's compile-warming request) must not
        leak into a measured window or the gauges."""
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rejected_tokens = 0
        self.spec_ticks = 0
        self.decode_ticks = 0
        self.hist_spec_tokens_per_tick = Histogram(_SPEC_BUCKETS)

    def moe_stats(self) -> dict | None:
        """The expert layer's counters over the engine's life (None for
        a configuration without sparse layers of a mixed stack):
        token-expert pairs routed to experts held here, held experts
        (summed over layers, ticks and chunks) that at least one token
        chose, all pairs (k a token a layer), and the sparse-layer calls
        whose grouped products took the short path (a tick or a chunk
        makes one call a sparse layer; always 0 where all experts are
        held); the sums, and ``by_program`` the same for chunks and
        ticks apart."""
        if not (self.mixed and self.cfg.num_experts):
            return None
        return self._counter_stats()

    def _counter_stats(self) -> dict:
        names = self._counter_names
        by = {kind: dict(zip(names, (int(x) for x in c)))
              for kind, c in self.moe_counts.items()}
        return {**{n: sum(c[n] for c in by.values()) for n in names},
                "by_program": by}

    def attn_stats(self) -> dict | None:
        """The sparse and linear layers' counters over the engine's life
        (None for a stack without them), summed over layers, ticks and
        chunks, over queries past ``sparse_dense_len`` alone: K/V rows
        the chosen blocks held (``sparse_rows_read``, a KV group's
        mean), rows the streams held (``sparse_rows_held``: what full
        attention would have read), compressed rows scored, queries
        that chose, and state updates (live rows x linear layers a
        call); ``by_program`` the same for chunks and ticks apart."""
        if not (self.mixed and self.cfg.state_layers):
            return None
        return self._counter_stats()

    def release(self, slot: int) -> None:
        self._active[slot] = 0
        self._keys[slot] = None
        self._pos[slot] = 0
        self._tokens[slot] = 0
        # reset sampling params too: _sample_slots' batch-level cond
        # fast paths (all-greedy -> argmax only; no top-k/p -> no vocab
        # sorts) test jnp.any over the WHOLE row set, and one retired
        # sampled request's stale temperature would otherwise pin every
        # later all-greedy tick onto the slow branch
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        self._prefills[slot] = None
        self._logit_slots.discard(slot)
        if self._spec_ok[slot]:
            self.speculator.release(slot)
        self._spec_ok[slot] = False
        blocks = self._slot_blocks[slot]
        if blocks:
            self.hist_blocks_per_request.observe(len(blocks))
            self.block_pool.deref(blocks)
        self._slot_blocks[slot] = []
        self._tables[slot] = self.block_pool.num_blocks
        self._dev = None
        # a retiring slot may have been the last reference to a
        # pre-swap weight generation — release the old snapshot
        self._prune_param_generations()

    def _evict_prefix_blocks(self, blocks) -> None:
        """Prefix-cache LRU eviction hook: drop the cache's
        references; blocks still mapped into a live slot survive until
        that slot releases them."""
        self.block_pool.deref(blocks)
        self.kv_block_evictions += len(blocks)

    # -- KV block shipping (serve/kvship.py; fleet/disagg.py) ----------------

    def export_kv(self, slot: int) -> dict:
        """Export ``slot``'s written KV rows for shipping to another
        replica (the disaggregated prefill->decode handoff). Returns the
        layout-invariant raw pieces — ``k``/``v`` as ``[L, pos, Hkv,
        hd]`` host arrays in the ARENA's storage dtype (plus
        ``ks``/``vs`` per-row f32 scales from an int8 arena), the
        fingerprint fields, and the cache cursor ``pos`` — which the
        server packs into the wire doc together with the cursor the
        scheduler owns (emitted tokens, request spec). Only blocks
        actually written travel: the export gathers the used blocks
        device-side and transfers those, never the slot's whole
        allocation. Read-only: the slot stays live (release is the
        scheduler's call, after the export is in hand)."""
        if self.mixed and self.cfg.state_layers:
            raise ValueError(
                "export_kv is not carried over to a stack with linear-attention "
                "layers: the wire format ships K and V blocks, and a per-slot "
                "state and the per-slot compressed keys are neither")
        if self.mixed:
            raise ValueError(
                "export_kv is not carried over to a mixed layer stack: a "
                "window layer's ring is no block the wire format knows")
        if not self._active[slot]:
            raise ValueError(f"slot {slot} has no live stream to export")
        t0 = time.perf_counter()
        pos = int(self._pos[slot])
        bs = self.kv_block_size
        nb = -(-pos // bs)
        blocks = self._slot_blocks[slot][:nb]
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        k = np.asarray(self.pool["k"][:, idx])
        v = np.asarray(self.pool["v"][:, idx])
        layers = k.shape[0]
        k = k.reshape(layers, nb * bs, *k.shape[3:])[:, :pos]
        v = v.reshape(layers, nb * bs, *v.shape[3:])[:, :pos]
        ks = vs = None
        if self.kv_dtype == "int8":
            ks = np.asarray(self.pool["ks"][:, idx]).reshape(
                layers, nb * bs)[:, :pos].astype(np.float32)
            vs = np.asarray(self.pool["vs"][:, idx]).reshape(
                layers, nb * bs)[:, :pos].astype(np.float32)
        out = {
            "config": kvship.config_fingerprint(self.cfg),
            "generation": int(self._slot_gen[slot]),
            "wire_dtype": "int8" if ks is not None else str(k.dtype),
            "pos": pos,
            "k": k, "v": v, "ks": ks, "vs": vs,
        }
        nbytes = k.nbytes + v.nbytes
        if ks is not None:
            nbytes += ks.nbytes + vs.nbytes
        c = self.kvship_counts
        c["export_requests"] += 1
        c["export_bytes"] += int(nbytes)
        c["export_blocks"] += nb
        c["export_seconds"] += time.perf_counter() - t0
        return out

    def _convert_wire(self, shipped):
        """Wire rows -> this arena's storage form per the kvship dtype
        rules: verbatim when bit-parity is preservable, requantize
        (amax/127) into an int8 arena, dequantize out of an int8 wire;
        an fp wire into a DIFFERENT fp arena dtype is a loud
        ``ShipMismatchError`` — never a silent cast."""
        wire_int8 = shipped.wire_dtype == "int8"
        if self.kv_dtype == "int8":
            if wire_int8:
                return shipped.k, shipped.v, shipped.ks, shipped.vs
            qk, sk = kvship.quantize_rows(shipped.k)
            qv, sv = kvship.quantize_rows(shipped.v)
            return qk, qv, sk, sv
        cdt = np.asarray(jnp.zeros((), self.cfg.dtype)).dtype
        if wire_int8:
            return (kvship.dequantize_rows(shipped.k, shipped.ks, cdt),
                    kvship.dequantize_rows(shipped.v, shipped.vs, cdt),
                    None, None)
        if np.dtype(shipped.k.dtype) != cdt:
            raise kvship.ShipMismatchError(
                f"fp wire dtype {shipped.wire_dtype} does not match "
                f"this arena's {cdt} — casting fp bits across dtypes "
                "would silently break the bit-parity contract"
            )
        return shipped.k, shipped.v, None, None

    def import_kv(self, slot: int, request, shipped) -> None:
        """Import a shipped stream into free slot ``slot`` and resume it
        mid-request. Validates the fingerprint first (``ShipMismatch
        Error`` — the server's 409 — on an architecture or weight-
        generation mismatch: shipped rows from other weights would be
        silent garbage), re-blocks the rows into this engine's own pool
        geometry, converts dtypes per the kvship rules, then replicates
        ``prefill_step``'s activation tail exactly: the PRNG schedule is
        rebuilt from the request seed (no key material travels), the
        step cursor from the emitted-token count — so the next decode
        tick is bit-identical to the tick the exporting replica would
        have run. The prefix cache is NOT populated from shipped rows
        (a requantized payload would hand non-parity rows to unrelated
        local requests)."""
        if self.mixed and self.cfg.state_layers:
            raise ValueError(
                "import_kv is not carried over to a stack with linear-attention "
                "layers: the wire format ships K and V blocks, and a per-slot "
                "state and the per-slot compressed keys are neither")
        if self.mixed:
            raise ValueError(
                "import_kv is not carried over to a mixed layer stack: a "
                "window layer's ring is no block the wire format knows")
        if self._active[slot] or self._prefills[slot] is not None:
            raise ValueError(f"slot {slot} is busy")
        t0 = time.perf_counter()
        fp = kvship.config_fingerprint(self.cfg)
        if shipped.config != fp:
            raise kvship.ShipMismatchError(
                f"config fingerprint {shipped.config} does not match "
                f"this engine ({fp}) — different architecture/config"
            )
        if int(shipped.generation) != self.deploy_generation:
            raise kvship.ShipMismatchError(
                f"weight generation {shipped.generation} does not match "
                f"this replica's deploy generation "
                f"{self.deploy_generation} — resuming across weight "
                "generations would mix caches from different params"
            )
        ids = [int(t) for t in request.prompt]
        self.validate(ids, request.max_new_tokens)
        emitted = [int(t) for t in shipped.emitted]
        if len(ids) != shipped.prompt_len:
            raise kvship.ShipFormatError(
                f"request prompt has {len(ids)} tokens but the payload "
                f"was exported for prompt_len={shipped.prompt_len}"
            )
        if len(emitted) > int(request.max_new_tokens):
            raise kvship.ShipFormatError(
                f"{len(emitted)} emitted tokens exceed the request's "
                f"max_new_tokens={request.max_new_tokens}"
            )
        bad = [t for t in emitted if not 0 <= t < self.vocab_size]
        if bad:
            raise kvship.ShipFormatError(
                f"emitted tokens {bad[:4]} outside the model vocabulary "
                f"({self.vocab_size})"
            )
        pos = int(shipped.pos)
        layers, _nb, _bs, heads, hd = self.pool["k"].shape
        if tuple(shipped.k.shape) != (layers, pos, heads, hd):
            raise kvship.ShipMismatchError(
                f"payload rows are {tuple(shipped.k.shape)} but this "
                f"engine expects [{layers}, {pos}, {heads}, {hd}]"
            )
        k, v, ks, vs = self._convert_wire(shipped)
        # re-block the rows into this engine's pool geometry: the
        # request's FULL block budget is allocated all-or-nothing at
        # refcount 1 (``BlocksExhausted`` stays the retryable admission
        # signal, and ``release`` derefs exactly like a local admission —
        # refcount conservation needs no new path), the written rows land
        # in the leading blocks, and the trailing blocks hold the
        # decode-to-come
        bs = self.kv_block_size
        need = self.blocks_for(len(ids), request.max_new_tokens)
        own = self.block_pool.alloc(need)
        try:
            nb = -(-pos // bs)

            def blockify(rows):
                pad = np.zeros((layers, nb * bs, heads, hd), rows.dtype)
                pad[:, :pos] = rows
                return pad.reshape(layers, nb, bs, heads, hd)

            idx = jnp.asarray(np.asarray(own[:nb], np.int32))
            self.pool["k"] = self.pool["k"].at[:, idx].set(
                jnp.asarray(blockify(k), self.pool["k"].dtype))
            self.pool["v"] = self.pool["v"].at[:, idx].set(
                jnp.asarray(blockify(v), self.pool["v"].dtype))
            if ks is not None:

                def blockify_s(sc):
                    pad = np.zeros((layers, nb * bs), np.float32)
                    pad[:, :pos] = sc
                    return pad.reshape(layers, nb, bs)

                self.pool["ks"] = self.pool["ks"].at[:, idx].set(
                    jnp.asarray(blockify_s(ks)))
                self.pool["vs"] = self.pool["vs"].at[:, idx].set(
                    jnp.asarray(blockify_s(vs)))
            self.pool = self._shard_kv(self.pool)
            row = np.full(self.table_blocks, self.block_pool.num_blocks,
                          np.int32)
            row[:need] = own
            self._tables[slot] = row
            self._slot_blocks[slot] = own
        except BaseException:
            # a failed scatter must not leak the allocation (zero-leak
            # under mid-ship failure is part of the ship contract)
            self.block_pool.deref(own)
            raise
        # prefill_step's activation tail, replicated: the one-shot
        # generate()'s key schedule from the request seed, the cursors
        # from the shipped emission count
        req = request
        temp = float(req.temperature)
        top_k = min(int(req.top_k), self.vocab_size)
        top_p = float(req.top_p)
        key = jax.random.key(int(req.seed))
        karr = jax.random.split(key)
        n = int(req.max_new_tokens)
        self._keys[slot] = (
            np.asarray(jax.random.key_data(jax.random.split(karr[0], n - 1)),
                       np.uint32)
            if n > 1 else np.zeros((0, 2), np.uint32)
        )
        self._step_idx[slot] = len(emitted) - 1
        self._pos[slot] = pos
        self._tokens[slot] = emitted[-1]
        self._temp[slot] = temp
        self._topk[slot] = top_k
        self._topp[slot] = top_p
        self._active[slot] = 1
        self._slot_gen[slot] = self.deploy_generation
        self._spec_ok[slot] = bool(self.spec_k) and bool(
            getattr(req, "speculate", True)
        )
        if self._spec_ok[slot]:
            # the proposer's context is (prompt, emitted...) — replayed
            # here it reaches the exporter's exact state, and exact
            # acceptance keeps the stream bit-identical regardless of
            # what it proposes
            self.speculator.begin(slot, ids, emitted[0])
            if len(emitted) > 1:
                self.speculator.observe(slot, emitted[1:])
        self._dev = None
        self._prefills[slot] = None
        nbytes = shipped.k.nbytes + shipped.v.nbytes
        if shipped.ks is not None:
            nbytes += shipped.ks.nbytes + shipped.vs.nbytes
        c = self.kvship_counts
        c["import_requests"] += 1
        c["import_bytes"] += int(nbytes)
        c["import_blocks"] += nb
        c["import_seconds"] += time.perf_counter() - t0

    def kvship_stats(self) -> dict | None:
        """KV shipping meters for /metrics and the stats JSONL (None
        until the first ship touches this engine, so non-disaggregated
        replicas' outputs are unchanged). Flat scalars by design: the
        stats JSONL's nested-dict filter and ``summarize_run`` consume
        them directly."""
        c = self.kvship_counts
        if not (c["export_requests"] or c["import_requests"]):
            return None
        out = dict(c)
        out["export_seconds"] = round(out["export_seconds"], 6)
        out["import_seconds"] = round(out["import_seconds"], 6)
        return out

    # -- observability -------------------------------------------------------

    def prefix_stats(self) -> dict | None:
        """Prefix-cache counters for the serve gauges (None when the
        cache is disabled)."""
        return None if self.prefix_cache is None else self.prefix_cache.stats()

    def kv_stats(self) -> dict:
        """Block-pool gauges for /metrics and the stats JSONL.
        ``kv_bytes`` is the arena's true HBM footprint;
        ``hist_blocks_per_request`` is the blocks-held distribution
        observed at release."""
        ps = self.block_pool.stats()
        if self.mixed:
            # by kind: the pool's blocks are the full layers' alone, a
            # sliding layer holds ``ring_rows`` rows a slot
            by_kind = mixed_cache_bytes(self.cfg, self.pool)
            kinds = [k for k, _ in (self.cfg.layer_kind(i)
                                    for i in range(self.cfg.num_hidden_layers))]
            extra = {
                "kv_bytes": int(sum(by_kind.values())),
                "kv_bytes_by_kind": {k: int(v) for k, v in by_kind.items()},
                # the compressed keys are the sparse layers' second cache
                "layers_by_kind": {k: kinds.count(
                    "sparse_attention" if k == "compressed_keys" else k) for k in by_kind},
                "ring_rows_per_slot": self.ring_rows,
            }
        else:
            extra = {"kv_bytes": int(
                self.block_pool.num_blocks * self.kv_block_size
                * kv_bytes_per_token(self.cfg, self.kv_dtype)
            )}
        # the full-attention read's view over the decode and verify
        # dispatches so far: mean rows a slot, their share of the table's
        # rows, dispatches by view rows (the widths taken alone)
        views = self.hist_view_rows.snapshot()
        mean = views["sum"] / views["count"] if views["count"] else None
        below, by_view = 0, {}
        for rows, cum in views["buckets"][:-1]:
            if cum > below:
                by_view[str(int(rows))] = cum - below
            below = cum
        out = {
            **ps,
            "kv_dtype": self.kv_dtype or str(self.cfg.dtype),
            "block_evictions": self.kv_block_evictions,
            "view_rows_mean": mean,
            "view_share": (None if mean is None else
                           mean / (self.table_blocks * self.kv_block_size)),
            "ticks_by_view": by_view,
            "hist_view_rows": views,
            **extra,
            "hist_blocks_per_request": self.hist_blocks_per_request.snapshot(),
        }
        if self.tp > 1:
            # per-shard breakdown: the host pool is global (a block id
            # names the same physical block on every shard — each shard
            # holds that block's rows for ITS KV heads), so every shard
            # reports the same free count here; the per-shard family
            # exists so a fleet scraper has one shape whether shards
            # share a pool (this engine) or own one each (a future
            # disaggregated deployment)
            out["tp_degree"] = self.tp
            out["blocks_free_per_shard"] = {
                str(s): ps["blocks_free"] for s in range(self.tp)
            }
        return out

    def devtime_stats(self) -> dict:
        """Per-program device/compile-second ledgers for /metrics and
        the stats JSONL — the accountant is always armed (host-side
        perf_counter sections; observation-only)."""
        return self.accountant.snapshot()

    def blocks_held(self, slot: int) -> int:
        """KV blocks currently mapped into ``slot``. The scheduler's
        ``kv_block_seconds`` attribution reads this at admission."""
        return len(self._slot_blocks[slot])

    def spec_stats(self) -> dict | None:
        """Speculative-decoding counters for /metrics and the stats
        JSONL (None with speculation off). ``acceptance_rate`` is
        accepted/drafted over the engine's whole life;
        ``tokens_per_tick_mean`` averages emitted tokens over
        SPECULATIVE ticks (the histogram carries the distribution)."""
        if not self.spec_k:
            return None
        drafted = self.spec_draft_tokens
        hist = self.hist_spec_tokens_per_tick.snapshot()
        return {
            "spec_k": self.spec_k,
            "spec_ngram": self.spec_ngram,
            "draft_tokens": drafted,
            "accepted_tokens": self.spec_accepted_tokens,
            "rejected_tokens": self.spec_rejected_tokens,
            "acceptance_rate": (
                round(self.spec_accepted_tokens / drafted, 4)
                if drafted else None
            ),
            "spec_ticks": self.spec_ticks,
            "decode_ticks": self.decode_ticks,
            "tokens_per_tick_mean": (
                round(hist["sum"] / hist["count"], 4)
                if hist["count"] else None
            ),
            "hist_tokens_per_tick": hist,
        }

    @property
    def device(self) -> dict:
        """The devices the weights sit on, as JAX names them: where this
        engine's programs run. On /healthz, so that a replica that came
        up on the CPU because its accelerator could not be initialized
        says so."""
        devs = sorted(jax.tree.leaves(self.params)[0].devices(), key=lambda d: d.id)
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    @property
    def kv_layout(self) -> str:
        """The engine's program layout tag: what the cache stores plus
        the tensor-parallel degree when sharded — the string every
        ``compile_counts`` key carries."""
        if self.kv_dtype == "int8":
            base = "paged-int8"
        elif self.mixed and self.cfg.state_layers:
            # sparse layers paged with compressed keys, linear layers' states
            base = "paged-compressed-state"
        elif self.mixed:
            base = "paged-rings"  # full layers paged, sliding layers in rings
        else:
            base = "paged"
        return base if self.tp == 1 else f"{base}-tp{self.tp}"

    def compile_counts(self) -> dict:
        """Compiled-executable counts per program, keyed by
        ``kind:layout`` — the bounded-compile contract is testable, not
        folklore: chunk programs are capped by the power-of-two bucket
        set, decode by 1 (sampling is fused into chunk and decode, so
        there is no separate sample program to count; a prefix hit maps
        blocks by reference, so there is no copy program either).

        Keys are LAYOUT-QUALIFIED (``prefill_chunk:paged-int8-tp2``,
        not ``prefill_chunk``): a flat kind key let a per-layout pin
        silently read another layout's count. ``buckets`` records the
        (kind -> program shape) set actually dispatched, so a pin can
        assert the exact (kind, bucket, layout) triples too."""
        def size(fn):
            try:
                return fn._cache_size()
            except Exception:  # pragma: no cover - older/newer jit internals
                return None

        layout = self.kv_layout
        out: dict = {
            "layout": layout,
            "tp_degree": self.tp,
            "buckets": {k: sorted(v) for k, v in sorted(self._buckets.items())},
            f"prefill_chunk:{layout}": size(self._chunk_paged),
            f"decode:{layout}": size(self._decode_paged),
        }
        if self._verify is not None:
            out[f"verify:{layout}"] = size(self._verify)
        return out
