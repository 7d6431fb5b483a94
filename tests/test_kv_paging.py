"""Paged block KV cache (nanodiloco_tpu/serve/block_pool + the paged
engine mode): allocator policy units, copy-on-write prefix block
refcounts, release on cancel/expiry mid-flight, block-aware admission
(no leak, no partial allocation), the int8 KV accuracy contract
(logit tolerance + greedy-token parity vs the fp engine and solo
``generate()`` across chunk-boundary prompt lengths), the compile-count
bound re-pinned under paging, and the block-pool observability keys
(scheduler stats -> /metrics names -> summarize_run)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, generate, init_params
from nanodiloco_tpu.serve import (
    BlockPool,
    BlocksExhausted,
    GenRequest,
    InferenceEngine,
    Scheduler,
)

CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


# -- allocator policy (model-free) -------------------------------------------


def test_pool_alloc_is_all_or_nothing():
    pool = BlockPool(4, 8)
    got = pool.alloc(3)
    assert len(got) == 3 and pool.free_blocks == 1
    free_before = pool.free_blocks
    with pytest.raises(BlocksExhausted):
        pool.alloc(2)
    # the failed alloc mutated NOTHING — no partial allocation to leak
    assert pool.free_blocks == free_before
    assert pool.used_blocks == 3
    pool.deref(got)
    assert pool.free_blocks == 4


def test_pool_fragmentation_free_reuse():
    """Blocks are interchangeable: any interleaving of allocs and frees
    leaves the pool able to satisfy any request that fits the free
    count — there is no fragmentation state to get wrong."""
    pool = BlockPool(8, 4)
    a = pool.alloc(3)
    b = pool.alloc(3)
    pool.deref(a)          # free the FIRST allocation: a "hole"
    c = pool.alloc(5)      # larger than either previous allocation
    assert len(c) == 5 and pool.free_blocks == 0
    assert sorted(b + c) == sorted(set(b + c))  # no double-handout
    pool.deref(b)
    pool.deref(c)
    assert pool.free_blocks == 8
    assert pool.stats()["total_allocated"] == 11
    assert pool.stats()["total_freed"] == 11


def test_pool_refcounts_shared_blocks():
    pool = BlockPool(4, 8)
    blocks = pool.alloc(2)
    pool.ref(blocks)                       # second holder
    assert pool.deref(blocks) == 0         # first deref: still held
    assert pool.free_blocks == 2
    assert pool.deref(blocks) == 2         # second deref: freed
    assert pool.free_blocks == 4
    with pytest.raises(ValueError, match="not allocated"):
        pool.deref(blocks)                 # double-free is loud
    with pytest.raises(ValueError, match="not allocated"):
        pool.ref(blocks)                   # so is reffing a dead block


def test_pool_validates():
    with pytest.raises(ValueError):
        BlockPool(0, 8)
    with pytest.raises(ValueError):
        BlockPool(8, 0)
    with pytest.raises(ValueError):
        BlockPool(4, 8).alloc(-1)


# -- copy-on-write prefix block refcounts (real engine) ----------------------


def _drain(sched, tickets, n=200):
    for _ in range(n):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            return
    raise AssertionError("requests did not finish")


def test_cow_prefix_blocks_shared_not_copied(params):
    """A prefix hit maps the CACHED chunks' blocks into the new slot's
    table by refcount — the hit allocates only the suffix blocks — and
    a shared block outlives the slot that created it (the cache still
    references it) but is freed once evicted AND released."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, prefix_cache_tokens=8,
                          kv_block_size=4)
    sched = Scheduler(eng)
    prefix = (5, 9, 2, 11, 3, 8, 1, 7)     # exactly two chunks/blocks
    ta = sched.submit(GenRequest(prompt=prefix + (4, 6), max_new_tokens=2,
                                 seed=1))
    _drain(sched, [ta])
    # A released its slot; the cache alone holds its two prefix blocks
    assert eng.block_pool.used_blocks == 2
    cached = [b for chunk in eng.prefix_cache._blocks.values()
              for b in chunk]
    assert len(cached) == 2
    assert all(eng.block_pool.refcount(b) == 1 for b in cached)

    free_before = eng.block_pool.free_blocks
    # admit B against the engine directly so the shared state is
    # observable mid-flight (a scheduler tick would run the whole
    # 2-token request to completion inside one call)
    chunks = eng.start_prefill(0, GenRequest(prompt=prefix + (2, 10),
                                             max_new_tokens=2, seed=2))
    # B needs ceil(12/4)=3 blocks but only ONE is newly allocated: the
    # two prefix blocks are shared (refcount 2), not copied — and both
    # cached chunks count as already written (one suffix chunk left)
    assert chunks == 1
    assert eng.block_pool.free_blocks == free_before - 1
    assert all(eng.block_pool.refcount(b) == 2 for b in cached)
    eng.release(0)
    assert all(eng.block_pool.refcount(b) == 1 for b in cached)
    assert eng.block_pool.free_blocks == free_before

    # capacity 8 tokens = 2 chunks: a DIFFERENT prompt's insert evicts
    # the LRU chunk; eviction derefs, and with no slot holding them the
    # evicted blocks return to the free list
    tc = sched.submit(GenRequest(prompt=(90, 91, 92, 93, 94, 95, 96, 97, 98),
                                 max_new_tokens=2, seed=3))
    _drain(sched, [tc])
    assert eng.kv_block_evictions >= 1
    assert eng.block_pool.used_blocks == 2  # the new prompt's 2 chunks
    stats = eng.kv_stats()
    assert stats["block_evictions"] == eng.kv_block_evictions
    assert stats["blocks_used"] == 2


def test_release_on_cancel_mid_prefill_frees_blocks(params):
    """A request cancelled between two prefill chunks releases its
    whole block allocation — mid-flight retirement must not leak."""
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                          chunk_size=4, kv_block_size=4)
    sched = Scheduler(eng)
    t = sched.submit(GenRequest(prompt=tuple(range(1, 14)),
                                max_new_tokens=4, seed=0))
    sched.tick()   # admit + first chunk
    assert eng.block_pool.used_blocks > 0
    t.cancel()
    sched.tick()   # cancellation sweep releases the slot
    assert t.done() and t.result["finish_reason"] == "cancelled"
    assert eng.block_pool.used_blocks == 0
    assert eng.block_pool.free_blocks == eng.block_pool.num_blocks


def test_expiry_mid_prefill_frees_blocks(params):
    clock = {"t": 0.0}
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                          chunk_size=4, kv_block_size=4)
    sched = Scheduler(eng, clock=lambda: clock["t"])
    t = sched.submit(GenRequest(prompt=tuple(range(1, 14)),
                                max_new_tokens=4, seed=0, deadline_s=1.0))
    sched.tick()
    assert eng.block_pool.used_blocks > 0
    clock["t"] = 5.0   # the deadline passes between chunks
    sched.tick()
    assert t.done() and t.result["finish_reason"] == "deadline"
    assert eng.block_pool.used_blocks == 0


# -- block-aware admission (the QueueFull/no-blocks fix) ---------------------


def test_admission_gates_on_blocks_and_rolls_back(params):
    """THE regression test: with a pool that can hold one live request,
    a second request stays QUEUED (never errored, nothing leaked — the
    free count is untouched by every failed attempt), is admitted the
    moment the first retires, and both streams bit-match their solo
    runs. The stall is accounted under no_blocks, not no_slot."""
    eng = InferenceEngine(params, CFG, num_slots=3, max_len=32,
                          chunk_size=4, kv_block_size=4, kv_pool_blocks=8)
    sched = Scheduler(eng)
    reqs = [
        GenRequest(prompt=tuple(range(1, 21)), max_new_tokens=8, seed=1),
        GenRequest(prompt=tuple(range(2, 22)), max_new_tokens=8, seed=2),
    ]  # 28 tokens -> 7 of the 8 blocks each: strictly one at a time
    with jax.default_matmul_precision("highest"):
        t1, t2 = (sched.submit(r) for r in reqs)
        free_floor = eng.block_pool.num_blocks
        for _ in range(60):
            sched.tick()
            free_floor = min(free_floor, eng.block_pool.free_blocks)
            if t1.done() and t2.done():
                break
        refs = [
            np.asarray(generate(
                params, jnp.asarray([r.prompt], jnp.int32), CFG,
                r.max_new_tokens, key=jax.random.key(r.seed),
            )[0]).tolist()
            for r in reqs
        ]
    assert t1.result["tokens"] == refs[0]
    assert t2.result["tokens"] == refs[1]
    assert free_floor == 1          # never two requests' blocks at once
    s = sched.stats()
    assert s["admission_blocked_no_blocks"] > 0
    assert s["admission_blocked_no_slot"] == 0
    assert s["errors"] == 0 and s["served"] == 2
    assert eng.block_pool.free_blocks == eng.block_pool.num_blocks


def test_admission_reclaims_cache_only_blocks_under_pressure(params):
    """Livelock regression: blocks held ONLY by the prefix cache are
    reclaimable — a request that cannot fit beside the cached prefixes
    evicts LRU entries (freeing their blocks) and admits, instead of
    raising BlocksExhausted forever (insert-side eviction needs a
    prefill to COMPLETE, which a starved pool never allows)."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=24,
                          chunk_size=4, prefix_cache_tokens=16,
                          kv_block_size=4, kv_pool_blocks=4)
    sched = Scheduler(eng)
    # this request caches 2 whole chunks at completion: the pool is
    # then half-held by the cache alone
    t1 = sched.submit(GenRequest(prompt=(5, 9, 2, 11, 3, 8, 1, 7, 4),
                                 max_new_tokens=2, seed=1))
    _drain(sched, [t1])
    assert eng.block_pool.used_blocks == 2  # cache-only references
    # an UNRELATED request needing 3 of the 4 blocks: must evict a
    # cached prefix to fit, not starve
    t2 = sched.submit(GenRequest(prompt=(90, 91, 92, 93, 94, 95, 96, 97, 98),
                                 max_new_tokens=2, seed=2))
    _drain(sched, [t2])
    assert t2.result["finish_reason"] == "length"
    assert eng.kv_block_evictions >= 1
    assert eng.prefix_cache.stats()["evictions"] >= 1


def test_request_that_can_never_fit_is_rejected_loudly(params):
    """A prompt the POOL can never hold (even empty) is a ValueError at
    validation — an error-finish, not an eternal queue squat — and the
    free count is untouched."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, kv_block_size=4, kv_pool_blocks=4)
    with pytest.raises(ValueError, match="never"):
        eng.validate([1] * 18, 4)   # 22 tokens -> 6 blocks > 4 total
    sched = Scheduler(eng)
    t = sched.submit(GenRequest(prompt=tuple(range(1, 19)),
                                max_new_tokens=4, seed=0))
    sched.tick()
    assert t.done() and t.result["finish_reason"] == "error"
    assert "never" in t.result["error"]
    assert eng.block_pool.free_blocks == eng.block_pool.num_blocks


@pytest.mark.parametrize("bad", [0, -1])
def test_block_size_below_one_is_refused_at_construction(params, bad):
    """The pool is the engine's only cache: there is no block size that
    means "no blocks", and the refusal names the parameter."""
    with pytest.raises(ValueError, match="kv_block_size"):
        InferenceEngine(params, CFG, num_slots=1, max_len=16,
                        kv_block_size=bad)


def test_default_pool_holds_every_slot_at_max_len(params):
    """An engine built with no KV argument sizes its pool at
    ``num_slots * ceil(max_len / block)`` blocks: every slot can hold a
    request of ``max_len`` tokens at once, whatever the others hold —
    admission is then bound by slots, never by blocks."""
    eng = InferenceEngine(params, CFG, num_slots=3, max_len=24)
    assert eng.kv_block_size == 16  # the default, under the chunk's cap
    assert eng.block_pool.num_blocks == 3 * 2  # ceil(24 / 16) a slot
    sched = Scheduler(eng)
    tickets = [
        sched.submit(GenRequest(prompt=tuple(range(1 + i, 21 + i)),
                                max_new_tokens=4, seed=i))
        for i in range(3)
    ]  # 20 + 4 = max_len tokens each
    sched.tick()  # all three admitted in one tick: none waits for blocks
    assert sched.stats()["admission_blocked_no_blocks"] == 0
    assert [eng.blocks_held(s) for s in range(3)] == [2, 2, 2]
    assert eng.kv_stats()["blocks_free"] == 0
    _drain(sched, tickets)
    assert all(t.result["finish_reason"] == "length" for t in tickets)
    assert eng.kv_stats()["blocks_free"] == eng.block_pool.num_blocks


def test_scheduler_keeps_slo_order_while_block_starved():
    """Model-free: a fake backend that refuses blocks keeps the peeked
    request AT ITS QUEUE POSITION (head-of-line — a later, smaller
    request must not leapfrog the SLO order), and admission resumes
    where it stopped."""

    class Fake:
        num_slots = 2

        def __init__(self):
            self.blocks_ok = False
            self.admitted = []

        def kv_stats(self):
            return {"blocks_free": 0, "num_blocks": 8}

        def start_prefill(self, slot, request):
            if not self.blocks_ok:
                raise BlocksExhausted("no blocks")
            self.admitted.append(request.seed)
            return 1

        def prefill_step(self, slot):
            return 1

        def step(self):
            return [2] * self.num_slots

        def release(self, slot):
            pass

    backend = Fake()
    sched = Scheduler(backend)
    first = sched.submit(GenRequest(prompt=(1,), max_new_tokens=1, seed=10))
    sched.submit(GenRequest(prompt=(2,), max_new_tokens=1, seed=11))
    sched.tick()
    sched.tick()
    assert backend.admitted == [] and sched.queue_depth() == 2
    assert not first.done()
    assert sched.stats()["admission_blocked_no_blocks"] == 2
    backend.blocks_ok = True
    sched.tick()
    assert backend.admitted == [10, 11]  # original submit order held


def test_queue_full_message_names_block_saturation():
    class Fake:
        num_slots = 1

        def kv_stats(self):
            return {"blocks_free": 0, "num_blocks": 16}

        def start_prefill(self, slot, request):
            raise BlocksExhausted("no blocks")

        def prefill_step(self, slot):
            return 1

        def step(self):
            return [2]

        def release(self, slot):
            pass

    sched = Scheduler(Fake(), max_queue=1)
    sched.submit(GenRequest(prompt=(1,), max_new_tokens=1, seed=0))
    from nanodiloco_tpu.serve import QueueFull

    with pytest.raises(QueueFull, match=r"KV blocks 0/16 free"):
        sched.submit(GenRequest(prompt=(2,), max_new_tokens=1, seed=1))


# -- int8 accuracy contract ---------------------------------------------------


def test_int8_kv_greedy_parity_and_logit_tolerance(params):
    """The int8 contract, gated like the smoke baseline: across the
    chunk-boundary prompt lengths (3/4/5/8/13), greedy streams from the
    paged-int8 engine match solo fp ``generate()`` token for token, and
    the first-token logits stay within a small tolerance of the
    fp-paged engine's (whose logits are bit-identical to generate's)."""
    lens = [3, 4, 5, 8, 13]
    reqs = [
        GenRequest(
            prompt=tuple((7 * i + 3 * j) % 50 + 1 for j in range(n)),
            max_new_tokens=4, seed=40 + i,  # temperature 0 = greedy
        )
        for i, n in enumerate(lens)
    ]
    logits = {}
    streams = {}
    with jax.default_matmul_precision("highest"):
        for mode, kv_dtype in (("fp", "model"), ("int8", "int8")):
            eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                                  chunk_size=4, kv_block_size=4,
                                  kv_dtype=kv_dtype)
            eng.capture_prefill_logits = True  # the tolerance probe
            logits[mode], streams[mode] = [], []
            for req in reqs:
                eng.prefill(0, req)
                logits[mode].append(np.array(eng.last_prefill_logits))
                toks = [int(eng._tokens[0])]
                for _ in range(req.max_new_tokens - 1):
                    toks.extend(eng.step()[0])
                streams[mode].append(toks)
                eng.release(0)
        refs = [
            np.asarray(generate(
                params, jnp.asarray([r.prompt], jnp.int32), CFG,
                r.max_new_tokens,
            )[0]).tolist()
            for r in reqs
        ]
    for n, fp_s, i8_s, ref in zip(lens, streams["fp"], streams["int8"], refs):
        assert fp_s == ref, f"fp-paged diverged at prompt len {n}"
        assert i8_s == ref, f"int8 greedy diverged at prompt len {n}"
    for n, lf, li in zip(lens, logits["fp"], logits["int8"]):
        err = float(np.max(np.abs(lf - li)))
        span = float(np.max(lf) - np.min(lf))
        assert err <= 0.05 * max(span, 1e-6), (
            f"int8 first-token logits off by {err} (span {span}) at "
            f"prompt len {n}"
        )


def test_int8_tp2_greedy_parity_across_layouts(params):
    """The int8 contract on a tensor-parallel mesh: greedy paged-int8
    streams from a tp=2 engine match the tp=1 paged-int8 engine AND
    solo fp ``generate()`` token for token (per-row quantization is
    amax/127 — max is exactly associative, so the int8 bits are
    layout-invariant; only the fp matmul reassociation moves, and
    greedy argmax absorbs it at this scale like the float tp tests)."""
    lens = [3, 5, 8]
    reqs = [
        GenRequest(
            prompt=tuple((7 * i + 3 * j) % 50 + 1 for j in range(n)),
            max_new_tokens=4, seed=40 + i,
        )
        for i, n in enumerate(lens)
    ]
    streams = {}
    with jax.default_matmul_precision("highest"):
        for tp in (1, 2):
            eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                                  chunk_size=4, kv_block_size=4,
                                  kv_dtype="int8", tp=tp)
            streams[tp] = []
            for req in reqs:
                eng.prefill(0, req)
                toks = [int(eng._tokens[0])]
                for _ in range(req.max_new_tokens - 1):
                    toks.extend(eng.step()[0])
                streams[tp].append(toks)
                eng.release(0)
        refs = [
            np.asarray(generate(
                params, jnp.asarray([r.prompt], jnp.int32), CFG,
                r.max_new_tokens,
            )[0]).tolist()
            for r in reqs
        ]
    for n, s1, s2, ref in zip(lens, streams[1], streams[2], refs):
        assert s2 == s1 == ref, f"int8 tp2 diverged at prompt len {n}"


def test_bucket_overflow_corner_never_rewrites_shared_blocks(params):
    """The re-feed corner, closed: with max_len NOT a multiple of the
    final bucket (done=16, remaining=5 -> bucket 8 pokes past a 22-row
    view), the widened paged table keeps the right-pad path in range —
    no re-feed below the prefix boundary. fp-paged stays bit-identical
    to solo generate() at the corner shape, and in int8 mode a request
    whose admission hits the cached prefix leaves the shared blocks'
    BITS untouched (a re-feed would rewrite them non-identically: its
    recompute reads earlier rows dequantized)."""
    corner = dict(num_slots=1, max_len=22, chunk_size=16)
    prompt = tuple((11 * j + 5) % 50 + 1 for j in range(21))
    with jax.default_matmul_precision("highest"):
        # fp parity at the corner shape (paged vs solo)
        eng = InferenceEngine(params, CFG, kv_block_size=2, **corner)
        eng.prefill(0, GenRequest(prompt=prompt, max_new_tokens=1, seed=0))
        toks = [int(eng._tokens[0])]
        ref = np.asarray(generate(
            params, jnp.asarray([prompt], jnp.int32), CFG, 1,
        )[0]).tolist()
        assert toks == ref

        # int8 shared-block immutability through the corner admission
        eng8 = InferenceEngine(params, CFG, kv_block_size=2,
                               prefix_cache_tokens=32, kv_dtype="int8",
                               **corner)
        sched = Scheduler(eng8)
        t1 = sched.submit(GenRequest(prompt=prompt, max_new_tokens=1,
                                     seed=1))
        _drain(sched, [t1])
        shared = sorted({b for chunk in eng8.prefix_cache._blocks.values()
                         for b in chunk})
        assert shared  # the 21-token prompt cached its first chunk
        before = np.asarray(eng8.pool["k"][:, shared]).copy()
        t2 = sched.submit(GenRequest(prompt=prompt, max_new_tokens=1,
                                     seed=2))
        _drain(sched, [t2])
        after = np.asarray(eng8.pool["k"][:, shared])
        assert (before == after).all()
        assert eng8.prefix_cache.stats()["hits"] >= 1


# -- compile-count bound under paging ----------------------------------------


def test_compile_count_bounded_under_paging():
    """The recompile-trap pin, paged edition: mixed-length admissions
    compile paged chunk programs only for the power-of-two bucket set
    and exactly one paged decode program — block tables, positions, and
    sampling params all ride as traced arrays."""
    cfg2 = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=64,
    )
    params2 = init_params(jax.random.key(1), cfg2)
    eng = InferenceEngine(params2, cfg2, num_slots=2, max_len=64,
                          chunk_size=8, prefix_cache_tokens=64,
                          kv_block_size=8)
    sched = Scheduler(eng)
    lens = [1, 2, 3, 5, 7, 8, 9, 12, 15, 17, 23, 31]
    tickets = [
        sched.submit(GenRequest(prompt=tuple((i + j) % 60 for j in range(n)),
                                max_new_tokens=2, seed=i))
        for i, n in enumerate(lens)
    ]
    for _ in range(200):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            break
    assert all(t.done() for t in tickets)
    counts = eng.compile_counts()
    assert counts["layout"] == "paged"
    if counts["prefill_chunk:paged"] is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    # 12 distinct prompt lengths -> at most the 4 bucket lengths
    # {1, 2, 4, 8}; admitting/retiring never recompiled the tick
    assert 1 <= counts["prefill_chunk:paged"] <= 4
    assert counts["decode:paged"] == 1
    # no copy program exists: prefix sharing is by block reference,
    # zero device copies
    assert not any(k.startswith(("extract", "insert")) for k in counts)


# -- observability keys -------------------------------------------------------


def test_kv_stats_blocks_held_histogram(params):
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, kv_block_size=4)
    sched = Scheduler(eng)
    t = sched.submit(GenRequest(prompt=(1, 2, 3, 4, 5), max_new_tokens=3,
                                seed=0))
    _drain(sched, [t])
    kv = eng.kv_stats()
    hist = kv["hist_blocks_per_request"]
    assert hist["count"] == 1
    assert hist["sum"] == 2.0   # 8 tokens -> 2 blocks of 4
    assert kv["blocks_free"] == kv["num_blocks"]


def test_summarize_run_tolerates_old_and_new_serve_records(tmp_path):
    from nanodiloco_tpu.training.metrics import summarize_run

    new = tmp_path / "new.jsonl"
    new.write_text(json.dumps({
        "serve_stats": True, "served": 3, "tokens_out": 12,
        "admission_blocked_no_slot": 1, "admission_blocked_no_blocks": 4,
        "kv_pool": {"blocks_free": 10, "blocks_used": 6,
                    "block_evictions": 2, "block_size": 16,
                    "num_blocks": 16},
    }) + "\n")
    s = summarize_run(str(new))
    assert s["kv_blocks_free"] == 10 and s["kv_blocks_used"] == 6
    assert s["kv_block_evictions"] == 2 and s["kv_block_size"] == 16
    assert s["serve_admission_blocked_no_blocks"] == 4

    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({
        "serve_stats": True, "served": 2, "tokens_out": 8,
    }) + "\n")
    s2 = summarize_run(str(old))
    assert s2["serve_served"] == 2
    assert "kv_blocks_free" not in s2
    assert "serve_admission_blocked_no_blocks" not in s2
