"""Speculative decoding (serve/speculation + the verify programs +
the engine/scheduler multi-token tick contract).

The load-bearing contract: with speculation enabled, EVERY stream —
greedy and sampled, at either block size, whatever the proposer does — is
bit-identical to solo ``generate()``, because acceptance is exact
(a draft survives iff it equals the token the plain tick would have
sampled with the same per-step key; for a deterministic proposal this
IS rejection sampling). Speculation may only change how many ticks a
stream takes, never its tokens. The suite drives three proposers
through the real engine: the prompt-lookup proposer, an ORACLE that
always proposes the true continuation (pins the full-accept path and
the tick-count win), and an adversarial JUNK proposer whose drafts are
wrong (pins all-reject forward progress, rollback, and zero block
leakage)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, generate, init_params
from nanodiloco_tpu.serve import GenRequest, InferenceEngine, Scheduler
from nanodiloco_tpu.serve.speculation import PromptLookupProposer

CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)

KV_MODES = [
    pytest.param({}, id="default"),   # blocks of 16 rows, clamped to the chunk
    pytest.param({"kv_block_size": 4}, id="bs4"),
]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _reference(params, req: GenRequest):
    out = generate(
        params, jnp.asarray([req.prompt], jnp.int32), CFG,
        req.max_new_tokens, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, key=jax.random.key(req.seed),
        stop_token=req.stop_token,
    )
    row = np.asarray(out[0]).tolist()
    if req.stop_token is not None and req.stop_token in row:
        row = row[: row.index(req.stop_token) + 1]
    return row


class OracleProposer:
    """Proposes the request's TRUE continuation (from its solo stream):
    every draft accepts, so each tick emits k+1 tokens — the upper
    bound the tick-count assertion pins."""

    def __init__(self, streams: dict[int, list[int]]) -> None:
        self.streams = streams
        self._emitted: dict[int, int] = {}

    def begin(self, slot, prompt_ids, first_token):
        self._emitted[slot] = 1

    def release(self, slot):
        self._emitted.pop(slot, None)

    def propose(self, slot, cap):
        e = self._emitted[slot]
        return self.streams[slot][e:e + cap]

    def observe(self, slot, emitted):
        self._emitted[slot] += len(emitted)

    def feedback(self, slot, proposed, accepted):
        pass


class JunkProposer:
    """Adversarial: always proposes ``cap`` copies of one (almost
    always wrong) token — near-total rejection, maximal rollback."""

    def __init__(self, token: int) -> None:
        self.token = int(token)

    def begin(self, slot, prompt_ids, first_token):
        pass

    def release(self, slot):
        pass

    def propose(self, slot, cap):
        return [self.token] * cap

    def observe(self, slot, emitted):
        pass

    def feedback(self, slot, proposed, accepted):
        pass


def _drain(sched, tickets, limit=80):
    for _ in range(limit):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            return
    raise AssertionError("scheduler did not drain")


# -- the proposer alone (no jax) ---------------------------------------------


def _ramp_to_max(p, slot):
    """Walk the adaptive budget up to max_k with full-accept feedback
    (fresh streams open at START_K, not max_k)."""
    for _ in range(p.max_k):
        p.feedback(slot, proposed=1, accepted=1)


def test_proposer_matches_longest_ngram_continuation():
    p = PromptLookupProposer(max_k=4, max_ngram=3)
    #       0  1  2  3  4  5  6  7
    p.begin(0, [5, 9, 2, 7, 1, 5, 9], 2)  # ctx tail ...5 9 2
    # tail 3-gram (5, 9, 2) occurred at positions 0-2 -> continuation
    # starts at 3: [7, 1, 5, 9, 2] cycled to k; a fresh stream opens at
    # START_K drafts
    assert p.propose(0, 4) == [7, 1]
    _ramp_to_max(p, 0)
    assert p.propose(0, 4) == [7, 1, 5, 9]
    assert p.propose(0, 2) == [7, 1]


def test_proposer_backs_off_to_shorter_ngrams_then_nothing():
    p = PromptLookupProposer(max_k=4, max_ngram=3)
    p.begin(0, [1, 2, 3, 4], 2)  # tail ...4, 2; "4 2" and "3 4 2" unseen
    _ramp_to_max(p, 0)
    # 1-gram tail [2] seen at position 1 -> continuation [3, 4, 2]
    # cycled out to k
    assert p.propose(0, 4) == [3, 4, 2, 3]
    p.begin(1, [1, 2, 3], 4)  # tail 4: never seen before -> no drafts
    assert p.propose(1, 4) == []


def test_proposer_cycles_short_periodic_continuation():
    """A greedy loop of period 2: the tail matches 2 back, leaving only
    2 known continuation tokens — cycling extends the draft to the full
    k, which is exactly what the looping stream will emit."""
    p = PromptLookupProposer(max_k=6, max_ngram=3)
    p.begin(0, [9, 9, 9, 7, 8, 7, 8, 7], 8)  # ...7 8 7 8
    _ramp_to_max(p, 0)
    assert p.propose(0, 6) == [7, 8, 7, 8, 7, 8]


def test_proposer_observe_extends_context_and_index():
    p = PromptLookupProposer(max_k=4, max_ngram=2)
    p.begin(0, [10, 11], 12)
    assert p.propose(0, 4) == []          # nothing repeats yet
    p.observe(0, [10, 11, 12])            # output repeats the opening
    # tail 2-gram (11, 12) first occurred ending at position 2 ->
    # continuation from there ([10, 11, 12]), capped at START_K until
    # acceptance feedback ramps the budget
    assert p.propose(0, 3) == [10, 11]
    _ramp_to_max(p, 0)
    assert p.propose(0, 3) == [10, 11, 12]


def test_proposer_ema_floor_suppresses_and_probe_recovers():
    """Gating: sustained rejection sinks the acceptance EMA below the
    floor and the slot stops proposing — except one cheap 1-draft probe
    per shared PROBE_PERIOD ticks; accepted probes raise the EMA back
    over the floor and full drafting resumes."""
    p = PromptLookupProposer(max_k=4, max_ngram=2)
    p.begin(0, [7, 8, 7, 8, 7], 8)           # periodic: always a match
    assert len(p.propose(0, 4)) == p.START_K
    for _ in range(4):                        # EMA 1 -> .7 -> .49 -> .34...
        p.feedback(0, proposed=4, accepted=0)
    assert p._ema[0] < p.ACCEPT_FLOOR
    probes = 0
    for _ in range(2 * p.PROBE_PERIOD):
        p.new_tick()
        d = p.propose(0, 4)
        assert len(d) <= 1                    # probe drafts only
        probes += bool(d)
    assert probes == 2                        # exactly one per period
    # two accepted probes lift the EMA back over the floor
    p.feedback(0, proposed=1, accepted=1)
    p.feedback(0, proposed=1, accepted=1)
    assert p._ema[0] >= p.ACCEPT_FLOOR
    p.new_tick()
    # drafting resumed; k regrows from the backoff floor (1 -> 3 after
    # two full-accept ticks), not instantly back to max
    assert len(p.propose(0, 4)) == 3


def test_proposer_adaptive_k_feedback():
    p = PromptLookupProposer(max_k=8, max_ngram=2)
    p.begin(0, [1, 2, 1, 2, 1], 2)
    assert p.current_k(0) == p.START_K    # ramp-up start, not max_k
    for _ in range(8):
        p.feedback(0, proposed=2, accepted=2)
    assert p.current_k(0) == 8            # full accepts walk up to max
    p.feedback(0, proposed=8, accepted=0)
    assert p.current_k(0) == 4            # zero-accept halves
    p.feedback(0, proposed=4, accepted=0)
    p.feedback(0, proposed=2, accepted=0)
    p.feedback(0, proposed=1, accepted=0)
    assert p.current_k(0) == 1            # floor 1, never 0
    p.feedback(0, proposed=1, accepted=1)
    assert p.current_k(0) == 2            # full accept grows again
    p.feedback(0, proposed=2, accepted=1)
    assert p.current_k(0) == 2            # partial holds steady
    p.release(0)
    assert p.current_k(0) == 0 and p.propose(0, 4) == []


# -- greedy + sampled bit-parity, block size x proposer -----------------------


SPEC_MODES = [
    pytest.param("off", id="spec-off"),
    pytest.param("lookup", id="spec-lookup"),
    pytest.param("junk", id="spec-adversarial"),
]


@pytest.mark.parametrize("kv", KV_MODES)
@pytest.mark.parametrize("spec", SPEC_MODES)
def test_streams_bit_match_solo_generate(params, kv, spec):
    """THE acceptance test, spec edition: overlapping greedy AND
    sampled requests through an engine with speculation {off, real
    prompt-lookup, adversarial all-reject} produce token streams
    bit-identical to solo generate() — speculation may change tick
    counts, never tokens."""
    spec_kw = {} if spec == "off" else {"spec_k": 4}
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          **kv, **spec_kw)
    if spec == "junk":
        eng.speculator = JunkProposer(CFG.vocab_size - 1)
    sched = Scheduler(eng)
    reqs = [
        GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=10, seed=0),
        GenRequest(prompt=(7, 1, 4), max_new_tokens=8,
                   temperature=0.8, top_k=20, seed=7),
        GenRequest(prompt=(1, 2, 3, 4), max_new_tokens=6,
                   temperature=0.7, top_p=0.9, seed=3),
    ]
    with jax.default_matmul_precision("highest"):
        tickets = [sched.submit(reqs[0])]
        sched.tick()
        tickets.append(sched.submit(reqs[1]))
        sched.tick()
        tickets.append(sched.submit(reqs[2]))
        _drain(sched, tickets)
        refs = [_reference(params, r) for r in reqs]
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["finish_reason"] == "length"
        assert ticket.result["tokens"] == ref
    if spec == "junk":
        ss = eng.spec_stats()
        assert ss["rejected_tokens"] > 0  # the adversary really fired


@pytest.mark.parametrize("kv", KV_MODES)
def test_oracle_full_acceptance_compresses_ticks(params, kv):
    """With a proposer that always guesses right, a greedy max_new=12
    stream finishes in ~ceil(11/(k+1)) speculative ticks instead of 11
    plain ones, the stream still bit-matches solo generate(), and the
    accept counters are exact."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=12, seed=0)
    with jax.default_matmul_precision("highest"):
        ref = _reference(params, req)
        eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                              spec_k=4, **kv)
        eng.speculator = OracleProposer({0: ref})
        sched = Scheduler(eng)
        ticket = sched.submit(req)
        _drain(sched, [ticket])
    assert ticket.result["tokens"] == ref
    ss = eng.spec_stats()
    assert ss["accepted_tokens"] == ss["draft_tokens"] > 0
    assert ss["rejected_tokens"] == 0
    # 11 decode tokens at up to 5/tick: 3 verify ticks (4+1 emitted
    # each, capped by the key schedule at the end)
    assert ss["decode_ticks"] <= 4
    assert ss["tokens_per_tick_mean"] > 2.0


def test_all_reject_still_makes_progress_every_tick(params):
    """Adversarial floor: with every draft rejected, each tick still
    emits exactly one verified token per live slot (never zero forward
    progress), so the stream takes the same tick count as spec-off."""
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=32, spec_k=4)
    eng.speculator = JunkProposer(CFG.vocab_size - 1)
    req = GenRequest(prompt=(5, 9, 2), max_new_tokens=8, seed=0)
    with jax.default_matmul_precision("highest"):
        ref = _reference(params, req)
        tok0 = eng.prefill(0, req)
        toks = [tok0]
        ticks = 0
        while len(toks) < req.max_new_tokens:
            out = eng.step()
            ticks += 1
            assert len(out[0]) >= 1, "a tick emitted zero tokens"
            toks.extend(out[0])
    assert toks == ref
    assert ticks == req.max_new_tokens - 1  # exactly 1 token per tick


def test_int8_paged_spec_greedy_parity(params):
    """The int8 arena's greedy-token contract holds through the verify
    path too: spec-on paged-int8 greedy streams match solo fp
    generate() token for token (logit tolerance is pinned elsewhere)."""
    reqs = [
        GenRequest(prompt=tuple((7 * i + 3 * j) % 50 + 1
                                for j in range(n)),
                   max_new_tokens=6, seed=40 + i)
        for i, n in enumerate([3, 5, 8])
    ]
    with jax.default_matmul_precision("highest"):
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                              chunk_size=4, kv_block_size=4,
                              kv_dtype="int8", spec_k=4)
        sched = Scheduler(eng)
        tickets = [sched.submit(r) for r in reqs]
        _drain(sched, tickets)
        refs = [_reference(params, r) for r in reqs]
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["tokens"] == ref


@pytest.mark.parametrize("kv", KV_MODES)
def test_tp2_spec_streams_bit_match_sharded_generate(params, kv):
    """Speculation on a tensor-parallel mesh: greedy AND sampled
    spec-on streams through a tp=2 engine are bit-identical to solo
    ``generate(mesh=...)`` on the SAME layout — the verify program's
    sampling runs on replicated logits with the plain tick's exact
    per-step key schedule, so sharding changes neither acceptance nor
    tokens."""
    from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          spec_k=4, tp=2, **kv)
    sched = Scheduler(eng)
    reqs = [
        GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=10, seed=0),
        GenRequest(prompt=(7, 1, 4), max_new_tokens=8,
                   temperature=0.8, top_k=20, seed=7),
    ]
    with jax.default_matmul_precision("highest"):
        tickets = [sched.submit(r) for r in reqs]
        _drain(sched, tickets)
        refs = []
        for r in reqs:
            out = generate(
                params, jnp.asarray([r.prompt], jnp.int32), CFG,
                r.max_new_tokens, temperature=r.temperature,
                top_k=r.top_k, top_p=r.top_p,
                key=jax.random.key(r.seed), mesh=mesh,
            )
            refs.append(np.asarray(out[0]).tolist())
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["tokens"] == ref
    assert "tp2" in eng.compile_counts()["layout"]


def test_stop_token_inside_a_draft_window_truncates(params):
    """A verify window can sail past EOS: the scheduler must scan the
    emitted vector in order, finish AT the stop token, and never leak
    post-stop tokens into the result."""
    with jax.default_matmul_precision("highest"):
        free = np.asarray(generate(
            params, jnp.asarray([[5, 9, 2]], jnp.int32), CFG, 10
        )[0]).tolist()
        stop = free[4]  # emitted at the fifth step
        req = GenRequest(prompt=(5, 9, 2), max_new_tokens=10, seed=0,
                         stop_token=stop)
        ref = _reference(params, req)
        eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                              spec_k=4)
        eng.speculator = OracleProposer({0: free})
        sched = Scheduler(eng)
        ticket = sched.submit(req)
        _drain(sched, [ticket])
    assert ticket.result["finish_reason"] == "stop"
    assert ticket.result["tokens"] == ref
    assert ticket.result["tokens"][-1] == stop


def test_per_request_opt_out(params):
    """``speculate=False`` keeps a request on the plain one-token path
    even on a spec-enabled engine (and the proposer never sees it);
    an opted-in neighbour still speculates in the same batch."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, spec_k=4)
    sched = Scheduler(eng)
    r_out = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8, seed=0,
                       speculate=False)
    r_in = GenRequest(prompt=(7, 1, 4), max_new_tokens=8, seed=1)
    with jax.default_matmul_precision("highest"):
        t1, t2 = sched.submit(r_out), sched.submit(r_in)
        sched.tick()
        slots = {s for s in range(2) if eng._active[s]}
        opted = {s for s in slots if eng._spec_ok[s]}
        assert len(opted) <= 1  # the opt-out slot never registered
        _drain(sched, [t1, t2])
        refs = [_reference(params, r) for r in (r_out, r_in)]
    assert t1.result["tokens"] == refs[0]
    assert t2.result["tokens"] == refs[1]


# -- rollback + block accounting ---------------------------------------------


def test_rejected_drafts_leak_no_blocks(params):
    """The PR-9 audit, spec edition: streams with heavy rejection
    (adversarial proposer) over a paged pool, including a mid-stream
    cancel, release EVERY block — free list back to full, all
    refcounts zero. Rollback is cursor arithmetic inside the slot's
    own up-front allocation, so there is nothing allocable to leak,
    and this pins it."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, kv_block_size=4, spec_k=4)
    eng.speculator = JunkProposer(CFG.vocab_size - 1)
    sched = Scheduler(eng)
    with jax.default_matmul_precision("highest"):
        tickets = [
            sched.submit(GenRequest(prompt=(5, 9, 2, 11, 3),
                                    max_new_tokens=8, seed=0)),
            sched.submit(GenRequest(prompt=(7, 1, 4), max_new_tokens=10,
                                    temperature=0.8, top_k=20, seed=7)),
            sched.submit(GenRequest(prompt=(1, 2, 3), max_new_tokens=9,
                                    seed=3)),
        ]
        sched.tick()
        sched.tick()
        tickets[1].cancel()  # mid-stream retirement with drafts in flight
        _drain(sched, tickets)
    kv = eng.kv_stats()
    assert kv["blocks_free"] == kv["num_blocks"], "spec path leaked blocks"
    assert all(eng.block_pool.refcount(b) == 0
               for b in range(eng.block_pool.num_blocks))
    assert eng.spec_stats()["rejected_tokens"] > 0


# -- compile-count pin --------------------------------------------------------


def test_compile_count_pinned_with_speculation():
    """Speculation must not reopen the PR-4 recompile trap: across
    mixed draft lengths the verify program compiles once per
    power-of-two draft-width bucket (<= log2(spec_k)+1), the decode
    tick stays at one executable, and chunk programs stay bucket-
    bounded. Dedicated config so the jit caches start empty."""
    cfg2 = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=64,
    )
    params2 = init_params(jax.random.key(1), cfg2)
    eng = InferenceEngine(params2, cfg2, num_slots=2, max_len=64,
                          chunk_size=8, spec_k=4)

    class Varying:
        """Forces every draft length 1..4 to appear (bucket widths 1,
        2, 4 -> T in {2, 3, 5})."""

        def __init__(self):
            self.n = 0

        def begin(self, *a):
            pass

        def release(self, *a):
            pass

        def propose(self, slot, cap):
            self.n += 1
            return [1] * max(1, min(cap, self.n % 4 + 1))

        def observe(self, *a):
            pass

        def feedback(self, *a):
            pass

    eng.speculator = Varying()
    sched = Scheduler(eng)
    tickets = [
        sched.submit(GenRequest(
            prompt=tuple((i + j) % 60 for j in range(n)),
            max_new_tokens=8, seed=i,
        ))
        for i, n in enumerate([1, 3, 7, 8, 12, 17])
    ]
    for _ in range(200):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            break
    assert all(t.done() for t in tickets)
    counts = eng.compile_counts()
    if counts["verify:paged"] is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    assert 1 <= counts["verify:paged"] <= 3   # T buckets {2, 3, 5}
    assert counts["decode:paged"] == 1
    assert 1 <= counts["prefill_chunk:paged"] <= 4
    # every dispatched verify width was a bucketed T in {2, 3, 5}
    assert set(counts["buckets"].get("verify", [])) <= {2, 3, 5}


def test_warm_spec_compiles_buckets_and_leaves_no_trace(params):
    """``warm_spec`` (serve CLI / bench boot): compiles every verify
    bucket up front, then leaves NOTHING observable — zero spec
    counters, all blocks free, slot 0 idle — so warmup never pollutes
    /metrics or a measured window. Dedicated config: the verify jit is
    lru-cached per config, so the shared CFG's cache already holds
    entries from the parity tests."""
    cfg3 = LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=64,
    )
    eng = InferenceEngine(init_params(jax.random.key(2), cfg3), cfg3,
                          num_slots=2, max_len=32, chunk_size=4,
                          kv_block_size=4, spec_k=4)
    warmed = eng.warm_spec()
    assert warmed == 3  # widths {1, 2, 4}
    counts = eng.compile_counts()
    if counts["verify:paged"] is not None:
        assert counts["verify:paged"] == 3
    ss = eng.spec_stats()
    assert ss["draft_tokens"] == 0 and ss["spec_ticks"] == 0
    assert ss["hist_tokens_per_tick"]["count"] == 0
    kv = eng.kv_stats()
    assert kv["blocks_free"] == kv["num_blocks"]
    assert not any(eng._active)


# -- scheduler multi-token contract + decode-rate accounting -----------------


class VectorBackend:
    """Fake backend emitting scripted multi-token VECTORS per tick —
    the contract a speculative engine presents to the scheduler."""

    num_slots = 1

    def __init__(self, vectors):
        self.vectors = list(vectors)
        self.i = 0

    def start_prefill(self, slot, request):
        return 1

    def prefill_step(self, slot):
        return 100

    def step(self):
        out = self.vectors[min(self.i, len(self.vectors) - 1)]
        self.i += 1
        return [list(out)]

    def release(self, slot):
        pass


def test_decode_rate_counts_emitted_tokens_not_ticks():
    """THE decode-rate satellite pin: two ticks emitting 3+2 tokens
    must count 5 decode tokens (the old ticks x slots arithmetic says
    2 — latently wrong at 1 token/tick, badly wrong under
    speculation). The rate is tokens per decode-second."""

    class SteppingClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 0.5
            return self.t

    backend = VectorBackend([[101, 102, 103], [104, 105]])
    sched = Scheduler(backend, clock=SteppingClock())
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=6, seed=0))
    for _ in range(4):
        sched.tick()
    assert t1.done() and t1.result["tokens"] == [100, 101, 102, 103, 104, 105]
    s = sched.stats()
    assert s["decode_tokens"] == 5            # emitted, not 2 ticks
    # each observation advances the injected clock 0.5 s; two decode
    # ticks were timed -> 1.0 s -> 5 tokens / 1 s
    assert s["decode_tokens_per_sec"] == pytest.approx(5.0)


def test_stop_and_length_scan_within_vector():
    """Multi-token retirement: the stop token lands mid-vector (finish
    'stop', post-stop tokens dropped) and the length bound lands
    mid-vector (finish 'length', overflow dropped)."""
    b1 = VectorBackend([[101, 99, 103]])
    s1 = Scheduler(b1)
    t1 = s1.submit(GenRequest(prompt=(5,), max_new_tokens=8, seed=0,
                              stop_token=99))
    s1.tick()
    s1.tick()
    assert t1.done() and t1.result["finish_reason"] == "stop"
    assert t1.result["tokens"] == [100, 101, 99]

    b2 = VectorBackend([[101, 102, 103, 104]])
    s2 = Scheduler(b2)
    t2 = s2.submit(GenRequest(prompt=(5,), max_new_tokens=3, seed=0))
    s2.tick()
    s2.tick()
    assert t2.done() and t2.result["finish_reason"] == "length"
    assert t2.result["tokens"] == [100, 101, 102]
    assert s2.stats()["decode_tokens"] == 2  # the overflow token dropped


# -- observability plumbing ---------------------------------------------------


def test_spec_stats_reach_scheduler_and_metrics(params):
    """spec_stats flow scheduler.stats() -> /metrics families; an
    engine without speculation exposes nothing."""
    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve import ServeServer

    eng = InferenceEngine(params, CFG, num_slots=1, max_len=32, spec_k=4)
    eng.speculator = JunkProposer(CFG.vocab_size - 1)
    srv = ServeServer(Scheduler(eng), port=0, host="127.0.0.1")
    try:
        sched = srv._scheduler
        t1 = sched.submit(GenRequest(prompt=(5, 9, 2), max_new_tokens=6,
                                     seed=0))
        with jax.default_matmul_precision("highest"):
            _drain(sched, [t1])
        s = sched.stats()
        assert s["spec"]["rejected_tokens"] > 0
        m = parse_metrics_text(srv.render_metrics())
        assert m["nanodiloco_spec_draft_tokens_total"] > 0
        assert m["nanodiloco_spec_rejected_total"] > 0
        assert "nanodiloco_spec_acceptance_rate" in m
        assert m["nanodiloco_spec_tokens_per_tick_count"] > 0
    finally:
        # never .start()ed (the scheduler is driven directly, and
        # render_metrics needs no socket) — stop() would block in
        # shutdown() waiting for a serve_forever that never ran
        srv._httpd.server_close()
    # spec-off engines: no spec key, no families
    eng0 = InferenceEngine(params, CFG, num_slots=1, max_len=32)
    assert eng0.spec_stats() is None
    assert "spec" not in Scheduler(eng0).stats()


def test_summarize_run_tolerates_old_and_new_serve_records(tmp_path):
    """serve_stats records WITH a spec block summarize to spec_* keys;
    records from older builds (no spec key) summarize exactly as
    before — no Keyerror, no spurious keys."""
    from nanodiloco_tpu.training.metrics import summarize_run

    new = tmp_path / "new.jsonl"
    new.write_text(json.dumps({
        "serve_stats": True, "served": 4, "tokens_out": 64,
        "decode_tokens": 60, "decode_tokens_per_sec": 50.0,
        "spec": {"spec_k": 4, "draft_tokens": 30, "accepted_tokens": 21,
                 "rejected_tokens": 9, "acceptance_rate": 0.7,
                 "tokens_per_tick_mean": 2.4, "spec_ticks": 12},
    }) + "\n")
    s = summarize_run(str(new))
    assert s["spec_draft_tokens"] == 30
    assert s["spec_accepted_tokens"] == 21
    assert s["spec_acceptance_rate"] == 0.7
    assert s["spec_tokens_per_tick"] == 2.4

    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({
        "serve_stats": True, "served": 2, "tokens_out": 10,
        "decode_tokens_per_sec": 12.0,
    }) + "\n")
    s2 = summarize_run(str(old))
    assert s2["decode_tokens_per_sec"] == 12.0
    assert not any(k.startswith("spec_") for k in s2)
