"""Serving integration tests (nanodiloco_tpu/serve): continuous-batching
bit-parity against sequential ``generate()`` — run over two geometries
of the block pool (the float pool must reproduce every stream
bit-identically through block tables, chunk scatter, and copy-on-write
prefix sharing) — and the HTTP server over a
REAL socket (POST /v1/generate, /healthz, serve gauges on /metrics)."""

import dataclasses
import importlib
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, generate, init_params
from nanodiloco_tpu.models.generate import view_ladder, view_rung
from nanodiloco_tpu.obs.telemetry import parse_metrics_text
from nanodiloco_tpu.serve import (
    GenRequest,
    InferenceEngine,
    Scheduler,
    ServeServer,
    http_get,
    http_post_json,
)

# the module: ``nanodiloco_tpu.models.generate`` as an attribute is the function
generate_module = importlib.import_module("nanodiloco_tpu.models.generate")

CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)

# the parity suite runs over two pool geometries (float arena): the
# default block size (16, clamped to the chunk size: the block at the
# chunk's cap) and blocks of 4 rows — both must stay bit-identical
# through block gather/scatter and copy-on-write prefix sharing
KV_MODES = [
    pytest.param({}, id="default"),
    pytest.param({"kv_block_size": 4}, id="bs4"),
]

# THE acceptance test additionally runs on a tensor-parallel mesh
# (params + KV arenas sharded over 2 virtual CPU devices): a TP stream
# must be bit-identical to solo generate() on the SAME layout
# (generate(mesh=...)) — across layouts only greedy token-identity can
# hold, because the tp psums reassociate float reductions
KV_TP_MODES = KV_MODES + [
    pytest.param({"tp": 2}, id="default-tp2"),
    pytest.param({"kv_block_size": 4, "tp": 2}, id="bs4-tp2"),
]


def _tp_mesh(tp: int):
    from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _reference(params, req: GenRequest, tp: int = 1):
    """The request run ALONE through the one-shot generate() — the
    stream the engine must reproduce bit-identically. ``tp > 1`` runs
    the solo reference on the same tensor-parallel layout the engine
    under test shards over."""
    out = generate(
        params, jnp.asarray([req.prompt], jnp.int32), CFG,
        req.max_new_tokens, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, key=jax.random.key(req.seed),
        stop_token=req.stop_token,
        mesh=_tp_mesh(tp) if tp > 1 else None,
    )
    row = np.asarray(out[0]).tolist()
    if req.stop_token is not None and req.stop_token in row:
        row = row[: row.index(req.stop_token) + 1]  # engine stops AT eos
    return row


# -- continuous-batching correctness ----------------------------------------


@pytest.mark.parametrize("kv", KV_TP_MODES)
def test_overlapping_requests_bit_match_sequential_generate(params, kv):
    """THE acceptance test: requests admitted mid-stream, decoded
    together in one batch, and retired at different times produce token
    ids bit-identical to running each alone through generate() with the
    same seed and sampling params — on the tp modes, through a sharded
    mesh against the same-layout solo run."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
    sched = Scheduler(eng)
    reqs = [
        GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8,
                   temperature=0.8, top_k=20, seed=7),
        GenRequest(prompt=(7, 1, 4), max_new_tokens=6,
                   temperature=0.7, top_p=0.9, seed=3),
        GenRequest(prompt=(1, 2, 3, 4), max_new_tokens=5, seed=0),  # greedy
    ]
    with jax.default_matmul_precision("highest"):
        tickets = [sched.submit(reqs[0])]
        sched.tick()                      # A alone for two ticks
        sched.tick()
        tickets.append(sched.submit(reqs[1]))
        sched.tick()                      # B joins A mid-stream
        tickets.append(sched.submit(reqs[2]))
        for _ in range(20):               # C refills the first freed slot
            if sched.tick() == 0 and all(t.done() for t in tickets):
                break
        refs = [_reference(params, r, tp=kv.get("tp", 1)) for r in reqs]
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["finish_reason"] == "length"
        assert ticket.result["tokens"] == ref
    s = sched.stats()
    assert s["served"] == 3 and s["slots_busy"] == 0
    assert s["tp_degree"] == kv.get("tp", 1)


@pytest.mark.parametrize("kv", KV_MODES)
def test_three_requests_two_slots_refill_parity(params, kv):
    """More requests than slots: the third request decodes in a slot
    another request just vacated (stale cache rows under it) and still
    bit-matches its solo run."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=24, **kv)
    sched = Scheduler(eng)
    reqs = [
        GenRequest(prompt=(5, 9), max_new_tokens=3, temperature=0.9,
                   top_k=10, seed=11),
        GenRequest(prompt=(8, 8, 8, 8), max_new_tokens=7, temperature=0.6,
                   seed=12),
        GenRequest(prompt=(3, 1, 4, 1, 5), max_new_tokens=4,
                   temperature=0.8, top_p=0.8, seed=13),
    ]
    with jax.default_matmul_precision("highest"):
        tickets = [sched.submit(r) for r in reqs]
        for _ in range(20):
            if sched.tick() == 0 and all(t.done() for t in tickets):
                break
        refs = [_reference(params, r) for r in reqs]
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["tokens"] == ref


def test_stop_token_retires_slot_and_matches_generate(params):
    """EOS retirement parity: pick a stop token the greedy run actually
    emits; the engine's stream must end AT it, matching the solo run's
    stream up to and including the stop."""
    with jax.default_matmul_precision("highest"):
        free = np.asarray(generate(
            params, jnp.asarray([[5, 9, 2]], jnp.int32), CFG, 8
        )[0]).tolist()
        stop = free[2]  # emitted at the third step
        req = GenRequest(prompt=(5, 9, 2), max_new_tokens=8, seed=0,
                         stop_token=stop)
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=32)
        sched = Scheduler(eng)
        ticket = sched.submit(req)
        for _ in range(12):
            if sched.tick() == 0 and ticket.done():
                break
        ref = _reference(params, req)
    assert ticket.result["finish_reason"] == "stop"
    assert ticket.result["tokens"][-1] == stop
    assert ticket.result["tokens"] == ref


@pytest.mark.parametrize("kv", KV_MODES)
def test_chunked_prefill_boundary_parity(params, kv):
    """Chunk-boundary bit-parity: with chunk_size=4, prompts whose
    lengths straddle every boundary case (< chunk, == chunk, chunk+1,
    several chunks, several+1) admit OVERLAPPING through the chunked
    path — interior chunks, a bucketed final chunk, and the right-padded
    single-chunk case all land — and every stream is bit-identical to
    its solo generate() run."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, **kv)
    sched = Scheduler(eng)
    # 6 and 7 exercise the right-padded multi-chunk final bucket (an
    # interior chunk followed by a 2- or 4-bucket with trailing pad)
    lens = [3, 4, 5, 6, 7, 8, 13]
    reqs = [
        GenRequest(
            prompt=tuple((7 * i + 3 * j) % 50 + 1 for j in range(n)),
            max_new_tokens=4, temperature=0.8, top_k=12, seed=40 + i,
        )
        for i, n in enumerate(lens)
    ]
    with jax.default_matmul_precision("highest"):
        tickets = [sched.submit(r) for r in reqs]
        for _ in range(120):
            if sched.tick() == 0 and all(t.done() for t in tickets):
                break
        refs = [_reference(params, r) for r in reqs]
    for ticket, ref in zip(tickets, refs):
        assert ticket.result["finish_reason"] == "length"
        assert ticket.result["tokens"] == ref
    # every prompt ran exactly ceil(n/4) chunks (no cache, no retries)
    assert sched.stats()["prefill_chunks_total"] == sum(
        -(-n // 4) for n in lens
    )


@pytest.mark.parametrize("kv", KV_MODES + [
    # default-tp2: a prefix hit maps blocks of a SHARDED arena through
    # the host-keyed cache — the one tp path the acceptance matrix
    # doesn't already cross
    pytest.param({"tp": 2}, id="default-tp2"),
])
def test_prefix_cache_hit_parity_and_counters(params, kv):
    """Cached-prefix admission bit-parity: requests B and D share A's
    chunk-aligned prefix — their admission maps A's cached blocks into
    their tables and prefills only the suffix — and C opts out. All four streams are
    bit-identical to solo generate(); the counters prove B and D
    genuinely reused cached chunks (D's whole prompt IS the prefix, so
    the reuse is capped one chunk short: the last token must prefill
    for real to seed the first sample)."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, prefix_cache_tokens=64, **kv)
    sched = Scheduler(eng)
    prefix = (5, 9, 2, 11, 3, 8, 1, 7)  # exactly two whole chunks
    reqs = [
        GenRequest(prompt=prefix + (4, 6), max_new_tokens=4,
                   temperature=0.7, top_k=16, seed=3),
        GenRequest(prompt=prefix + (2, 10, 12), max_new_tokens=5,
                   temperature=0.9, top_p=0.9, seed=8),
        GenRequest(prompt=prefix + (1,), max_new_tokens=3, seed=5,
                   prefix_cache=False),
        GenRequest(prompt=prefix, max_new_tokens=4, temperature=0.6,
                   seed=21),
    ]
    with jax.default_matmul_precision("highest"):
        ta = sched.submit(reqs[0])
        for _ in range(20):  # A completes and populates the cache
            if sched.tick() == 0 and ta.done():
                break
        others = [sched.submit(r) for r in reqs[1:]]
        for _ in range(40):
            if sched.tick() == 0 and all(t.done() for t in others):
                break
        refs = [_reference(params, r, tp=kv.get("tp", 1)) for r in reqs]
    for ticket, ref in zip([ta, *others], refs):
        assert ticket.result["tokens"] == ref
    ps = eng.prefix_stats()
    # A missed; B hit 2 chunks (8 tokens); C opted out (no lookup at
    # all); D hit but capped at 1 chunk (4 tokens)
    assert ps["hits"] == 2 and ps["misses"] == 1
    assert ps["hit_tokens"] == 8 + 4
    assert ps["insertions"] >= 2
    assert sched.stats()["prefix_cache"]["hits"] == 2


def test_compile_count_bounded_across_mixed_lengths():
    """The recompile-trap pin: mixed-length admissions compile chunk
    programs only for the power-of-two bucket set (<= log2(chunk)+1),
    NOT one executable per prompt length, exactly one decode program,
    and no copy program for the prefix cache (a hit maps blocks). Uses its own config so the jit caches under count
    start empty."""
    cfg2 = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=64,
    )
    params2 = init_params(jax.random.key(1), cfg2)
    eng = InferenceEngine(params2, cfg2, num_slots=2, max_len=64,
                          chunk_size=8, prefix_cache_tokens=64)
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
    # {1, 2, 4, 8} ever compile (the PR-4 path compiled 12); sampling
    # is fused into the chunk and decode programs, so there is no
    # separate sample executable at all
    assert 1 <= counts["prefill_chunk:paged"] <= 4
    assert counts["decode:paged"] == 1
    assert not any(k.startswith(("extract", "insert")) for k in counts)
    # the dispatched program-shape ledger: every chunk bucket a power
    # of two <= 8, the decode tick always T=1
    assert set(counts["buckets"]["prefill_chunk"]) <= {1, 2, 4, 8}
    assert counts["buckets"]["decode"] == [1]


def test_engine_validates_impossible_requests(params):
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.validate([1] * 10, 10)
    with pytest.raises(ValueError, match="at least one token"):
        eng.validate([], 4)
    with pytest.raises(ValueError, match="vocabulary"):
        eng.validate([CFG.vocab_size + 5], 4)


# -- tensor-parallel serving --------------------------------------------------


def test_tp_greedy_token_identical_across_layouts(params):
    """Cross-layout greedy token-identity: the same greedy requests
    through tp2, tp4, and tp2 with blocks of 4 rows produce the same
    token ids as unsharded solo generate(). (Bit-parity of SAMPLED
    streams only holds within one layout — the tp psums reassociate
    float reductions — which is exactly what the same-layout acceptance
    test above pins.)"""
    reqs = [
        GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=6, seed=0),
        GenRequest(prompt=(7, 1, 4), max_new_tokens=5, seed=1),
    ]
    with jax.default_matmul_precision("highest"):
        refs = [_reference(params, r) for r in reqs]  # unsharded solo
        for kv in ({"tp": 2}, {"tp": 4}, {"tp": 2, "kv_block_size": 4}):
            eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
            sched = Scheduler(eng)
            tickets = [sched.submit(r) for r in reqs]
            for _ in range(20):
                if sched.tick() == 0 and all(t.done() for t in tickets):
                    break
            for ticket, ref in zip(tickets, refs):
                assert ticket.result["tokens"] == ref, kv


def test_tp_validation_is_a_loud_boot_error(params):
    """A bad --tp degree must fail at engine CONSTRUCTION with a
    readable config error — never as a shape error out of the first
    traced program: tp not dividing the KV-head count (CFG has 4), and
    tp exceeding the device count (the harness pins 8 virtual CPUs)."""
    with pytest.raises(ValueError, match="KV-head"):
        InferenceEngine(params, CFG, num_slots=1, max_len=16, tp=3)
    with pytest.raises(ValueError, match="devices"):
        InferenceEngine(params, CFG, num_slots=1, max_len=16, tp=16)
    with pytest.raises(ValueError, match="tp"):
        InferenceEngine(params, CFG, num_slots=1, max_len=16, tp=0)
    # the serve CLI carries the flag end to end
    from nanodiloco_tpu.cli import build_serve_parser

    args = build_serve_parser().parse_args(
        ["--checkpoint-dir", "x", "--tp", "2"]
    )
    assert args.tp == 2


def test_compile_counts_keyed_by_layout():
    """The introspection-conflation regression pin: compile counts are
    keyed (kind, layout) — with ``buckets`` carrying the dispatched
    (kind, bucket) shapes — so a per-layout compile pin can NEVER
    silently read another layout's program set (the old flat
    ``prefill_chunk`` key reported float and int8 counts identically
    named). Dedicated config — distinct VALUES too, not just a fresh
    object: LlamaConfig hashes by value, so a config equal to another
    test's would share its lru-cached jits and absorb its compiles."""
    cfgc = LlamaConfig(
        vocab_size=80, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=64,
    )
    paramsc = init_params(jax.random.key(3), cfgc)

    def drive(eng):
        sched = Scheduler(eng)
        tickets = [
            sched.submit(GenRequest(prompt=tuple((i + j) % 60
                                                 for j in range(n)),
                                    max_new_tokens=2, seed=i))
            for i, n in enumerate([3, 8])
        ]
        for _ in range(40):
            if sched.tick() == 0 and all(t.done() for t in tickets):
                break
        assert all(t.done() for t in tickets)

    quant = InferenceEngine(paramsc, cfgc, num_slots=2, max_len=32,
                            chunk_size=8, kv_dtype="int8")
    paged = InferenceEngine(paramsc, cfgc, num_slots=2, max_len=32,
                            chunk_size=8, kv_block_size=8)
    drive(quant)
    drive(paged)
    qc, pc = quant.compile_counts(), paged.compile_counts()
    assert qc["layout"] == "paged-int8" and pc["layout"] == "paged"
    # each layout's counts live ONLY under its own keys
    assert "prefill_chunk:paged-int8" in qc and "prefill_chunk:paged" not in qc
    assert "prefill_chunk:paged" in pc and "prefill_chunk:paged-int8" not in pc
    # no layout has a copy program: a prefix hit maps blocks
    assert not any(k.startswith(("extract", "insert")) for k in (*qc, *pc))
    # the dispatched shapes: prompts of 3 and 8 -> chunk buckets {4, 8}
    # in both layouts, decode always T=1
    assert qc["buckets"]["prefill_chunk"] == [4, 8]
    assert pc["buckets"]["prefill_chunk"] == [4, 8]
    assert qc["buckets"]["decode"] == pc["buckets"]["decode"] == [1]
    # a tp engine's keys are further qualified by the degree
    tp = InferenceEngine(paramsc, cfgc, num_slots=1, max_len=32,
                         chunk_size=8, tp=2)
    assert tp.compile_counts()["layout"] == "paged-tp2"
    assert "prefill_chunk:paged-tp2" in tp.compile_counts()


def test_tp_metrics_and_stats_jsonl_flow(params, tmp_path):
    """The TP observability contract over a real socket: a paged tp=2
    server reports ``nanodiloco_serve_tp_degree`` and the per-shard
    ``nanodiloco_kv_blocks_free_per_shard`` family on /metrics, and the
    same keys ride ``serve_stats`` JSONL -> summarize_run (older
    JSONLs without them summarize unchanged)."""
    from nanodiloco_tpu.training.metrics import summarize_run

    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          kv_block_size=4, tp=2)
    srv = ServeServer(
        Scheduler(eng), port=0, host="127.0.0.1", request_timeout_s=120.0,
    ).start()
    try:
        code, out = _post(srv.port, {"token_ids": [5, 9, 2],
                                     "max_new_tokens": 2, "stop": False})
        assert code == 200, out
        code, body = _get(srv.port, "/metrics")
        assert code == 200
        m = parse_metrics_text(body)
        assert m["nanodiloco_serve_tp_degree"] == 2
        assert m['nanodiloco_kv_blocks_free_per_shard{shard="0"}'] == \
            m['nanodiloco_kv_blocks_free_per_shard{shard="1"}'] == \
            m["nanodiloco_kv_blocks_free"]
        stats = srv._scheduler.stats()
    finally:
        srv.stop()
    new = tmp_path / "new.jsonl"
    new.write_text(json.dumps({
        "serve_stats": True, "served": stats["served"],
        "tp_degree": stats["tp_degree"],
        "kv_pool": {"blocks_free": 16, "blocks_used": 0,
                    "num_blocks": 16, "block_size": 4,
                    "view_share": stats["kv_pool"]["view_share"],
                    "view_rows_mean": stats["kv_pool"]["view_rows_mean"],
                    "blocks_free_per_shard": {"0": 16, "1": 16}},
    }) + "\n")
    s = summarize_run(str(new))
    assert s["serve_tp_degree"] == 2
    # one tick of a 4-row stream through a table of 16 blocks of 4 rows
    assert s["kv_view_rows_mean"] == 8 and s["kv_view_share"] == 8 / 64
    assert s["kv_blocks_free_per_shard"] == {"0": 16, "1": 16}
    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({"serve_stats": True, "served": 1}) + "\n")
    s2 = summarize_run(str(old))
    assert "serve_tp_degree" not in s2 and "kv_view_share" not in s2
    assert "kv_blocks_free_per_shard" not in s2


# -- the ladder of view widths (models/generate.py ``view_ladder``) ----------


@pytest.mark.parametrize("mb", [1, 2, 7, 17, 18, 144, 544])
def test_view_ladder_takes_the_narrowest_width_that_holds_the_rows(mb):
    """For every count of blocks a call can need, the width taken is the
    smallest of the ladder that holds it; the top width is the table's;
    the program's traced rule is the host's."""
    ladder = view_ladder(mb)
    assert ladder[-1] == mb and list(ladder) == sorted(set(ladder))
    assert 1 <= len(ladder) <= generate_module.VIEW_STEPS
    assert set(ladder) == {-(-mb * i // 8) for i in range(1, 9)}
    bs = 4
    needs = np.arange(0, mb * bs + 2 * bs)          # past the table too
    got = [ladder[int(view_rung(ladder, int(n), bs))] for n in needs]
    want = [min((w for w in ladder if w * bs >= n), default=mb) for n in needs]
    assert got == want
    traced = jax.jit(jax.vmap(lambda n: view_rung(ladder, n, bs) + 0))(
        jnp.asarray(needs, jnp.int32))
    assert [ladder[int(i)] for i in traced] == want


class _JunkProposer:
    """Always proposes ``cap`` copies of one token: every tick is a
    verify tick of k + 1 positions a slot, nearly every draft rejected."""

    def begin(self, slot, prompt_ids, first_token): pass
    def release(self, slot): pass
    def propose(self, slot, cap): return [1] * cap
    def observe(self, slot, emitted): pass
    def feedback(self, slot, proposed, accepted): pass


# max_len 64 in blocks and chunks of 4: a table of 17 blocks, read at
# 12, 20, 28, 36, 44, 52, 60 or 68 rows
VIEW_ENGINES = {
    "paged": {},
    "paged-int8": {"kv_dtype": "int8"},
    "paged-tp2": {"tp": 2},
    "verify": {"spec_k": 3},
}
VIEW_STREAMS = {
    # the short stream (4-11 rows) ticks alone at 12 rows while the long
    # one's prompt is in chunks, then beside its 42-50 rows at 44 and 52
    "short_beside_long": [(41, 10, 0.8), (3, 9, 0.7)],
    # rows 10..34 of one stream: its ticks pass four widths mid-decode
    "crosses_widths_mid_decode": [(9, 26, 0.9)],
}


def _view_requests(streams):
    return [
        GenRequest(prompt=tuple((5 * i + 3 * j) % 50 + 1 for j in range(n)),
                   max_new_tokens=new, temperature=temp, top_k=12, seed=60 + i)
        for i, (n, new, temp) in enumerate(VIEW_STREAMS[streams])
    ]


def _serve_views(params, cfg, reqs, **kw):
    eng = InferenceEngine(params, cfg, num_slots=2, max_len=64, chunk_size=4,
                          kv_block_size=4, **kw)
    if eng.spec_k:
        eng.speculator = _JunkProposer()
    sched = Scheduler(eng)
    tickets = [sched.submit(r) for r in reqs]
    for _ in range(300):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            break
    return eng, [t.result["tokens"] for t in tickets]


@pytest.mark.parametrize("streams", sorted(VIEW_STREAMS))
@pytest.mark.parametrize("engine", sorted(VIEW_ENGINES))
def test_streams_hold_through_the_view_widths(params, monkeypatch, engine, streams):
    """A tick reads each slot's K/V through its block table at the
    narrowest width of the ladder that holds every live row: a short
    stream beside a long neighbour, and a stream whose rows pass widths
    mid-decode, are bit-identical to solo ``generate()``; int8 rows to
    the same engine reading every table at its whole width."""
    kw = VIEW_ENGINES[engine]
    reqs = _view_requests(streams)
    with jax.default_matmul_precision("highest"):
        eng, got = _serve_views(params, CFG, reqs, **kw)
        if engine == "paged-int8":
            # an equal configuration that differs in a field nothing
            # here reads: programs of its own, traced with one width
            whole = dataclasses.replace(CFG, max_position_embeddings=65)
            monkeypatch.setattr(generate_module, "view_ladder", lambda mb: (mb,))
            _, want = _serve_views(params, whole, reqs, **kw)
        else:
            want = [_reference(params, r, tp=kw.get("tp", 1)) for r in reqs]
    assert got == want
    taken = sorted(int(r) for r in eng.kv_stats()["ticks_by_view"])
    if engine == "verify":
        assert eng.spec_ticks > 0 and len(taken) >= 2  # rows pos + 4 a tick
    else:
        assert taken == {"short_beside_long": [12, 44, 52],
                         "crosses_widths_mid_decode": [12, 20, 28, 36]}[streams]


@pytest.mark.parametrize("engine", ["paged", "paged-int8", "verify"])
def test_every_view_width_is_one_tick_program(engine):
    """One stream grows through all eight widths of its table: one
    decode executable (and one verify executable a draft width), the
    chunk buckets it dispatched and no other."""
    cfg2 = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_hidden_layers=1,
        max_position_embeddings=66,  # programs of its own
    )
    params2 = init_params(jax.random.key(1), cfg2)
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=59, seed=2)
    eng, (out,) = _serve_views(params2, cfg2, [req], **VIEW_ENGINES[engine])
    assert len(out) == 59
    kv = eng.kv_stats()
    assert [int(r) for r in kv["ticks_by_view"]] == [w * 4 for w in view_ladder(17)]
    counts = eng.compile_counts()
    layout = counts["layout"]
    if counts[f"decode:{layout}"] is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    assert counts[f"prefill_chunk:{layout}"] == len(counts["buckets"]["prefill_chunk"]) == 2
    if engine == "verify":
        assert counts[f"verify:{layout}"] == len(counts["buckets"]["verify"])
        assert counts[f"decode:{layout}"] <= 1
    else:
        assert counts[f"decode:{layout}"] == 1


@pytest.mark.parametrize("prompt_len,new", [(61, 3), (57, 7)])
def test_a_stream_at_the_top_of_its_allocation_fits_its_view(params, prompt_len, new):
    """The last rows a slot can hold (max_len 64: 16 of the table's 17
    blocks): the final chunk is right-padded to its bucket there and the
    ticks after it read the table's top width."""
    req = GenRequest(prompt=tuple((3 * j) % 50 + 1 for j in range(prompt_len)),
                     max_new_tokens=new, temperature=0.8, top_k=12, seed=9)
    with jax.default_matmul_precision("highest"):
        eng, (got,) = _serve_views(params, CFG, [req])
        assert got == _reference(params, req)
    assert max(int(r) for r in eng.kv_stats()["ticks_by_view"]) == 68


def test_kv_stats_view_share_is_what_the_positions_imply(params):
    """A scripted run: two slots prefilled to 5 and 30 rows, ticked
    together, the long one released, the short one ticked on. The
    tally names each tick's width from the longest live slot's rows."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=64, chunk_size=4,
                          kv_block_size=4)
    assert eng.kv_stats()["view_share"] is None
    assert eng.kv_stats()["ticks_by_view"] == {}
    eng.prefill(0, GenRequest(prompt=tuple(range(1, 6)), max_new_tokens=30))
    eng.prefill(1, GenRequest(prompt=tuple(range(1, 31)), max_new_tokens=30))
    rows = []
    for tick in range(20):
        if tick == 8:
            eng.release(1)
        live = [5 + tick] + ([30 + tick] if tick < 8 else [])
        need = max(live) + 1                  # the row this tick writes
        rows.append(min(w * 4 for w in view_ladder(17) if w * 4 >= need))
        eng.step()
    kv = Scheduler(eng).stats()["kv_pool"]
    assert kv["ticks_by_view"] == {
        str(r): rows.count(r) for r in sorted(set(rows))}
    assert kv["view_rows_mean"] == sum(rows) / 20
    assert kv["view_share"] == sum(rows) / (20 * 68)
    assert kv["hist_view_rows"]["count"] == 20
    assert [b for b, _ in kv["hist_view_rows"]["buckets"][:-1]] == [
        w * 4 for w in view_ladder(17)]
    assert rows[0] == 36 and rows[7] == 44 and rows[8] == 20 and rows[-1] == 28


# -- the HTTP server over a real socket --------------------------------------


def _post(port: int, doc: dict, timeout: float = 60.0):
    return http_post_json(
        f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
    )


def _get(port: int, path: str, timeout: float = 10.0):
    return http_get(f"http://127.0.0.1:{port}{path}", timeout=timeout)


def test_generate_endpoint_over_real_socket(params):
    """POST /v1/generate on a tiny config: two overlapping requests from
    concurrent client threads both succeed, the same seed is
    deterministic, serve gauges land on /metrics, /healthz is 200."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32)
    srv = ServeServer(
        Scheduler(eng), port=0, host="127.0.0.1", request_timeout_s=120.0,
    ).start()
    try:
        doc = {"token_ids": [5, 9, 2, 11], "max_new_tokens": 6,
               "temperature": 0.8, "top_k": 20, "seed": 7, "stop": False}
        results: dict[int, tuple] = {}

        def client(i, seed):
            # client 0 supplies its own correlation id; the others get
            # scheduler-assigned ones
            extra = {"request_id": "client-0-xyz"} if i == 0 else {}
            results[i] = _post(srv.port, {**doc, **extra, "seed": seed})

        threads = [threading.Thread(target=client, args=(i, s))
                   for i, s in enumerate((7, 7, 21))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for code, out in results.values():
            assert code == 200, out
            assert out["finish_reason"] == "length"
            assert len(out["token_ids"]) == 6
            assert all(0 <= t < CFG.vocab_size for t in out["token_ids"])
            assert out["timing"]["ttft_s"] > 0
        # same seed -> same stream, different seed -> (here) different
        assert results[0][1]["token_ids"] == results[1][1]["token_ids"]
        assert results[0][1]["token_ids"] != results[2][1]["token_ids"]
        # request ids: the client-supplied one is echoed verbatim; the
        # others carry distinct scheduler-assigned ids — the join key
        # across the response, serve spans, and histograms
        assert results[0][1]["request_id"] == "client-0-xyz"
        auto_ids = {results[i][1]["request_id"] for i in (1, 2)}
        assert len(auto_ids) == 2
        assert all(rid.startswith("req-") for rid in auto_ids)

        code, body = _get(srv.port, "/metrics")
        assert code == 200
        m = parse_metrics_text(body)
        assert m['nanodiloco_serve_requests_total{outcome="served"}'] == 3
        assert m["nanodiloco_serve_slots_total"] == 2
        assert m["nanodiloco_serve_queue_depth"] == 0
        assert m["nanodiloco_serve_ttft_seconds"] > 0
        assert m["nanodiloco_serve_decode_tokens_per_sec"] > 0
        assert m["nanodiloco_serve_tokens_total"] >= 18
        # the ticks' view through the block tables: every dispatch counted
        # at its width, the share what the histogram's mean implies
        kv = srv._scheduler.stats()["kv_pool"]
        assert m["nanodiloco_kv_view_rows_count"] == kv["hist_view_rows"]["count"] > 0
        assert m["nanodiloco_kv_view_share"] == pytest.approx(kv["view_share"])
        assert 0 < kv["view_share"] <= 1
        assert body.rstrip().endswith("# EOF")
        # the TTFT histogram: 3 served requests, cumulative buckets
        # monotone and capped by the +Inf bucket == _count
        assert m["nanodiloco_serve_ttft_histogram_seconds_count"] == 3
        assert m["nanodiloco_serve_ttft_histogram_seconds_sum"] > 0
        bucket_lines = [
            (k, v) for k, v in m.items()
            if k.startswith("nanodiloco_serve_ttft_histogram_seconds_bucket")
        ]
        assert bucket_lines, body
        cums = [v for _, v in sorted(
            bucket_lines,
            key=lambda kv: float("inf") if '+Inf' in kv[0]
            else float(kv[0].split('le="')[1].rstrip('"}')),
        )]
        assert cums == sorted(cums) and cums[-1] == 3
        assert m['nanodiloco_serve_ttft_histogram_seconds_bucket{le="+Inf"}'] == 3
        assert m["nanodiloco_serve_queue_wait_seconds_count"] == 3
        assert m["nanodiloco_serve_decode_tick_seconds_count"] > 0

        code, body = _get(srv.port, "/healthz")
        assert code == 200
        doc = json.loads(body)
        assert doc["healthy"] and doc["served"] == 3
        # where the engine's weights sit, as JAX names it: a replica that
        # fell back to the CPU must say so (chip_smoke.py reads this)
        assert doc["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}

        code, _ = _get(srv.port, "/nope")
        assert code == 404
    finally:
        srv.stop()


def test_server_rejects_bad_requests_with_400(params):
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=16)
    srv = ServeServer(Scheduler(eng), port=0, host="127.0.0.1").start()
    try:
        for bad in (
            {},                                            # no prompt at all
            {"prompt": "hi"},                              # no tokenizer
            {"token_ids": []},                             # empty
            {"token_ids": [1], "max_new_tokens": 0},       # zero tokens
            {"token_ids": [1], "max_new_tokens": None},    # null -> TypeError
            {"token_ids": [1], "temperature": "hot"},      # wrong type
            {"token_ids": [1], "temperature": -1.0},
            {"token_ids": [1], "top_p": 0.0},
            {"token_ids": [1] * 15, "max_new_tokens": 10},  # > max_len
            {"token_ids": [CFG.vocab_size + 1]},           # out of vocab
            {"token_ids": [1], "request_id": ""},          # empty id
            {"token_ids": [1], "request_id": 7},           # non-string id
            {"token_ids": [1], "request_id": "x" * 200},   # oversized id
            {"token_ids": [1], "speculate": "yes"},        # non-bool opt-out
        ):
            code, out = _post(srv.port, bad)
            assert code == 400, (bad, out)
            assert "error" in out
    finally:
        srv.stop()


def test_queue_full_returns_429():
    """Backpressure over the wire: a gated fake backend holds the only
    slot busy; with max_queue=1 the second waiting request is answered
    429 while the first eventually completes."""

    class GatedBackend:
        num_slots = 1

        def __init__(self):
            self.gate = threading.Event()
            self.seed = None

        def start_prefill(self, slot, request):
            self._staged = request.seed
            return 1

        def prefill_step(self, slot):
            self.seed = self._staged
            return 1

        def step(self):
            self.gate.wait(30)  # hold the slot until the test opens it
            return [2]

        def release(self, slot):
            self.seed = None

    backend = GatedBackend()
    srv = ServeServer(
        Scheduler(backend, max_queue=1), port=0, host="127.0.0.1",
        request_timeout_s=60.0,
    ).start()
    try:
        codes: dict[int, int] = {}

        def client(i):
            codes[i], _ = _post(
                srv.port,
                {"token_ids": [1], "max_new_tokens": 2, "seed": i},
            )

        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        # wait until request 0 occupies the slot (its prefill ran)
        for _ in range(500):
            if backend.seed is not None:
                break
            threading.Event().wait(0.01)
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        # wait until request 1 is queued, then overflow with request 2
        for _ in range(500):
            if json.loads(_get(srv.port, "/healthz")[1])["queue_depth"] >= 1:
                break
            threading.Event().wait(0.01)
        code2, out2 = _post(
            srv.port, {"token_ids": [1], "max_new_tokens": 2, "seed": 2}
        )
        assert code2 == 429, out2
        assert "full" in out2["error"]
        backend.gate.set()
        t0.join(timeout=60)
        t1.join(timeout=60)
        assert codes[0] == 200 and codes[1] == 200
        m = parse_metrics_text(_get(srv.port, "/metrics")[1])
        assert m['nanodiloco_serve_requests_total{outcome="rejected"}'] >= 1
    finally:
        backend.gate.set()
        srv.stop()


def test_healthz_flips_503_when_the_loop_dies():
    class DoomedBackend:
        num_slots = 1

        def start_prefill(self, slot, request):
            return 1

        def prefill_step(self, slot):
            return 1

        def step(self):
            raise RuntimeError("device lost")

        def release(self, slot):
            pass

    srv = ServeServer(
        Scheduler(DoomedBackend()), port=0, host="127.0.0.1",
        request_timeout_s=2.0,  # the doomed request can never resolve
    ).start()
    try:
        assert _get(srv.port, "/healthz")[0] == 200
        # a request whose decode step explodes kills the loop thread
        code, out = _post(
            srv.port,
            {"token_ids": [1], "max_new_tokens": 3, "seed": 0},
            timeout=30,
        )
        assert code == 504  # the ticket never resolves
        for _ in range(500):
            if _get(srv.port, "/healthz")[0] == 503:
                break
            threading.Event().wait(0.01)
        code, body = _get(srv.port, "/healthz")
        assert code == 503
        assert "device lost" in json.loads(body).get("error", "")
    finally:
        srv.stop()
