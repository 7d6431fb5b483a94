"""KV block shipping tests (serve/kvship + engine export/import + the
/admin endpoints): the disaggregated handoff's parity and safety
contract.

- ROUND-TRIP BIT-PARITY: a stream prefilled on one engine, parked,
  exported through ``kvship.pack`` -> ``unpack`` (the real wire bytes),
  and resumed on a SECOND engine is bit-identical to solo
  ``generate()`` — across pool geometries (different block sizes, tp
  degrees) because the wire format is layout-invariant.
- REFCOUNT CONSERVATION: imported blocks are freed on retire and on
  mid-stream cancel, and a failure mid-import leaks nothing
  (all-or-nothing).
- FINGERPRINT 4xx MATRIX over a real socket: wrong config hash -> 409,
  wrong weight generation -> 409, truncated/malformed payload -> 400 —
  loud refusals, never silent garbage in the importer's cache.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, generate, init_params
from nanodiloco_tpu.obs.telemetry import parse_metrics_text
from nanodiloco_tpu.serve import (
    GenRequest,
    InferenceEngine,
    Scheduler,
    ServeServer,
    http_get,
    http_post_json,
)
from nanodiloco_tpu.serve import kvship

CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _reference(params, req: GenRequest):
    out = generate(
        params, jnp.asarray([req.prompt], jnp.int32), CFG,
        req.max_new_tokens, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, key=jax.random.key(req.seed),
    )
    return np.asarray(out[0]).tolist()


def _drain(sched, tickets, limit=60):
    for _ in range(limit):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            return
    raise AssertionError("scheduler did not drain")


def _park(params, req: GenRequest, rid: str, **kv):
    """Prefill-only admission: the ticket finishes at the first token
    with finish_reason='prefilled' and the slot parks for export."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
    sched = Scheduler(eng)
    ticket = sched.submit(dataclasses.replace(
        req, prefill_only=True, request_id=rid))
    for _ in range(20):
        sched.tick()
        if ticket.done():
            break
    assert ticket.result["finish_reason"] == "prefilled"
    assert len(ticket.result["tokens"]) == 1
    return eng, sched, ticket


def _ship(sched, rid: str, req: GenRequest):
    """Export the parked slot and cross the REAL wire format: pack to
    the JSON doc, then unpack — every base64/cursor check runs."""
    raw, parked = sched.export_parked(rid)
    shipped = kvship.ShippedKV(
        config=raw["config"], generation=raw["generation"],
        wire_dtype=raw["wire_dtype"], prompt_len=len(parked.request.prompt),
        pos=raw["pos"], step_idx=len(parked.tokens) - 1,
        emitted=list(parked.tokens), k=raw["k"], v=raw["v"],
        ks=raw.get("ks"), vs=raw.get("vs"),
        request={"token_ids": [int(t) for t in req.prompt],
                 "max_new_tokens": int(req.max_new_tokens),
                 "seed": int(req.seed), "request_id": rid, "stop": False},
    )
    return kvship.unpack(kvship.pack(shipped))


def _resume(params, req: GenRequest, shipped, **kv):
    """Import into a fresh engine and decode the stream to completion."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
    sched = Scheduler(eng)
    ticket = sched.admit_import(
        dataclasses.replace(req, prefill_only=False), shipped)
    _drain(sched, (ticket,))
    return eng, sched, ticket


# -- round-trip bit-parity across pool geometries -----------------------------


@pytest.mark.parametrize("src,dst", [
    pytest.param({}, {}, id="default-to-default"),   # blocks of 16 rows
    pytest.param({}, {"kv_block_size": 4}, id="default-to-bs4"),
    pytest.param({"kv_block_size": 4}, {}, id="bs4-to-default"),
    pytest.param({"kv_block_size": 4}, {"kv_block_size": 8},
                 id="paged4-to-paged8"),
])
def test_roundtrip_parity_across_geometries(params, src, dst):
    """THE ship acceptance: a SAMPLED stream prefilled under one pool
    geometry and resumed under another is bit-identical to running it
    alone through generate() — the wire's [L, pos, Hkv, hd] rows are
    re-blocked into the importer's own geometry without losing a bit,
    and the seed-derived PRNG schedule rebuilds the exact sampler
    state (no key material travels)."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8,
                     temperature=0.8, top_k=20, seed=7)
    with jax.default_matmul_precision("highest"):
        _, sa, ta = _park(params, req, "ship-a", **src)
        shipped = _ship(sa, "ship-a", req)
        assert shipped.emitted == ta.result["tokens"]
        _, _, tb = _resume(params, req, shipped, **dst)
        ref = _reference(params, req)
    assert tb.result["finish_reason"] == "length"
    assert tb.result["tokens"] == ref


def test_roundtrip_parity_across_tp_degrees(params):
    """Layout invariance across tensor-parallel degrees: a GREEDY
    stream prefilled on a tp=2 paged engine resumes on a tp=1 engine
    with the same token ids as unsharded solo generate() (cross-layout
    only token-identity can hold — the tp psums reassociate float
    reductions, which is why this leg is greedy)."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=6, seed=0)
    with jax.default_matmul_precision("highest"):
        _, sa, _ = _park(params, req, "ship-tp", kv_block_size=4, tp=2)
        shipped = _ship(sa, "ship-tp", req)
        _, _, tb = _resume(params, req, shipped, kv_block_size=4)
        ref = _reference(params, req)
    assert tb.result["tokens"] == ref


def test_int8_roundtrip_bit_exact_vs_monolithic_int8(params):
    """An int8 arena ships its stored int8 rows + f32 scales VERBATIM:
    the disaggregated stream reads exactly the bits a monolithic int8
    engine would have read locally, so the two streams are
    bit-identical (the quantization error is identical, not merely
    similar)."""
    kv = {"kv_block_size": 4, "kv_dtype": "int8"}
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8,
                     temperature=0.8, top_k=20, seed=7)
    with jax.default_matmul_precision("highest"):
        # monolithic int8 reference
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
        sm = Scheduler(eng)
        tm = sm.submit(req)
        _drain(sm, (tm,))
        # disaggregated int8 -> int8
        _, sa, _ = _park(params, req, "ship-q", **kv)
        shipped = _ship(sa, "ship-q", req)
        assert shipped.wire_dtype == "int8"
        assert shipped.ks is not None and shipped.vs is not None
        _, _, tb = _resume(params, req, shipped, **kv)
    assert tb.result["tokens"] == tm.result["tokens"]


def test_roundtrip_parity_with_speculation(params):
    """Speculation survives the ship: a SAMPLED stream resumed on a
    spec-enabled decode engine (the importer replays the emitted prefix
    into its speculator — no draft state crosses the wire) stays
    bit-identical to solo generate(), because rejection sampling
    preserves the target distribution exactly and the PRNG schedule is
    position-keyed."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3, 9, 2), max_new_tokens=8,
                     temperature=0.8, top_k=20, seed=7)
    with jax.default_matmul_precision("highest"):
        _, sa, _ = _park(params, req, "ship-sp", kv_block_size=4)
        shipped = _ship(sa, "ship-sp", req)
        _, _, tb = _resume(params, req, shipped,
                           kv_block_size=4, spec_k=2)
        ref = _reference(params, req)
    assert tb.result["tokens"] == ref


def test_cross_dtype_requantize_and_dequantize(params):
    """Cross-dtype imports trade bit-parity for compatibility the same
    way the int8 arena itself does: an fp wire requantizes into an int8
    arena, an int8 wire dequantizes into an fp arena — both complete
    the stream (emitted tokens travel verbatim either way). An fp wire
    into a DIFFERENT fp dtype is refused loudly: silently casting the
    bits would be the quiet-garbage failure the fingerprint exists to
    prevent."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=6, seed=0)
    with jax.default_matmul_precision("highest"):
        # fp wire -> int8 arena (requantize on import)
        _, sa, ta = _park(params, req, "ship-f", kv_block_size=4)
        fp_wire = _ship(sa, "ship-f", req)
        _, _, tb = _resume(params, req, fp_wire,
                           kv_block_size=4, kv_dtype="int8")
        assert tb.result["tokens"][0] == ta.result["tokens"][0]
        assert len(tb.result["tokens"]) == req.max_new_tokens
        assert all(0 <= t < CFG.vocab_size for t in tb.result["tokens"])
        # int8 wire -> fp arena (dequantize on import)
        _, sq, tq = _park(params, req, "ship-g",
                          kv_block_size=4, kv_dtype="int8")
        q_wire = _ship(sq, "ship-g", req)
        _, _, td = _resume(params, req, q_wire, kv_block_size=4)
        assert td.result["tokens"][0] == tq.result["tokens"][0]
        assert len(td.result["tokens"]) == req.max_new_tokens
        # fp wire -> mismatched fp arena dtype: loud refusal
        eng = InferenceEngine(params, CFG, num_slots=1, max_len=32,
                              kv_block_size=4)
        bad = dataclasses.replace(
            fp_wire, wire_dtype="float16",
            k=fp_wire.k.astype(np.float16), v=fp_wire.v.astype(np.float16),
        )
        with pytest.raises(kvship.ShipMismatchError, match="dtype"):
            eng.import_kv(0, req, bad)


# -- refcount conservation ----------------------------------------------------


def test_refcount_conservation_export_and_retire(params):
    """Zero leak on the happy path: the exporter's blocks are freed the
    moment the export is in hand (the parked slot releases), and the
    importer's all-or-nothing allocation is fully derefed when the
    resumed stream retires. Both pools return exactly to baseline."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8, seed=0)
    with jax.default_matmul_precision("highest"):
        eng_a, sa, _ = _park(params, req, "ship-rc", kv_block_size=4)
        free_a = eng_a.kv_stats()["blocks_free"]
        shipped = _ship(sa, "ship-rc", req)
        assert eng_a.kv_stats()["blocks_free"] > free_a  # park released
        eng_b = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                                kv_block_size=4)
        sb = Scheduler(eng_b)
        base_b = eng_b.kv_stats()["blocks_free"]
        ticket = sb.admit_import(req, shipped)
        held = eng_b.kv_stats()["blocks_free"]
        assert held < base_b  # the import holds real blocks
        _drain(sb, (ticket,))
    assert eng_b.kv_stats()["blocks_free"] == base_b
    c = eng_b.kvship_stats()
    assert c["import_requests"] == 1 and c["import_blocks"] > 0
    assert eng_a.kvship_stats()["export_requests"] == 1


def test_import_cancel_frees_blocks_mid_stream(params):
    """Mid-ship cancel: an imported stream cancelled partway through
    decode derefs its whole allocation at retirement — an abandoned
    handoff must not leak the decode replica's KV blocks."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=16, seed=0)
    with jax.default_matmul_precision("highest"):
        _, sa, _ = _park(params, req, "ship-cx", kv_block_size=4)
        shipped = _ship(sa, "ship-cx", req)
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                              kv_block_size=4)
        sched = Scheduler(eng)
        base = eng.kv_stats()["blocks_free"]
        ticket = sched.admit_import(req, shipped)
        sched.tick()  # one decode step: the stream is genuinely live
        assert not ticket.done()
        ticket.cancel()
        for _ in range(10):
            if sched.tick() == 0 and ticket.done():
                break
    assert ticket.result["finish_reason"] == "cancelled"
    assert eng.kv_stats()["blocks_free"] == base


def test_failed_import_scatter_leaks_nothing(params):
    """All-or-nothing under failure: a raise AFTER the block allocation
    (mid-scatter) derefs the whole allocation on the way out — the pool
    is bit-for-bit back at baseline, and the slot stays free."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=8, seed=0)
    with jax.default_matmul_precision("highest"):
        _, sa, _ = _park(params, req, "ship-fx", kv_block_size=4)
        shipped = _ship(sa, "ship-fx", req)
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                              kv_block_size=4)
    base = eng.kv_stats()["blocks_free"]
    eng.pool["k"] = None  # the scatter will blow up after alloc
    with pytest.raises(Exception):
        eng.import_kv(0, req, shipped)
    assert eng.kv_stats()["blocks_free"] == base
    assert not eng._active[0]


# -- the fingerprint 4xx matrix over a real socket ----------------------------


def test_ship_4xx_matrix_over_real_socket(params):
    """The /admin/kv/export + /admin/kv/import wire contract: a parked
    stream exports exactly once (then 404), a tampered config hash or
    weight generation is a 409 (the pairing is wrong), a truncated or
    structurally broken payload is a 400 (the bytes are wrong) — and
    the UNTOUCHED payload still imports cleanly afterwards, finishing
    bit-identical to solo generate()."""
    req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=6, seed=0)
    exporter = ServeServer(
        Scheduler(InferenceEngine(params, CFG, num_slots=2, max_len=32,
                                  kv_block_size=4)),
        port=0, host="127.0.0.1", role="prefill",
        request_timeout_s=120.0).start()
    importer = ServeServer(
        Scheduler(InferenceEngine(params, CFG, num_slots=2, max_len=32,
                                  kv_block_size=4)),
        port=0, host="127.0.0.1", role="decode",
        request_timeout_s=120.0).start()

    def post(srv, path, doc, timeout=120.0):
        return http_post_json(
            f"http://127.0.0.1:{srv.port}{path}", doc, timeout=timeout)

    try:
        with jax.default_matmul_precision("highest"):
            ref = _reference(params, req)
        code, out = post(exporter, "/v1/generate", {
            "token_ids": list(req.prompt), "max_new_tokens": 6,
            "stop": False, "request_id": "wire-1", "prefill_only": True,
        })
        assert code == 200 and out["finish_reason"] == "prefilled", out
        assert out["token_ids"] == ref[:1]

        code, _ = post(exporter, "/admin/kv/export", {"request_id": "nope"})
        assert code == 404
        code, doc = post(exporter, "/admin/kv/export",
                         {"request_id": "wire-1"})
        assert code == 200, doc
        # exactly once: the slot was freed with the export
        code, _ = post(exporter, "/admin/kv/export", {"request_id": "wire-1"})
        assert code == 404

        # 409: wrong architecture fingerprint
        code, out = post(importer, "/admin/kv/import",
                         {**doc, "config": "deadbeefdeadbeef"})
        assert code == 409 and "fingerprint" in out["error"], out
        # 409: wrong weight deploy generation
        code, out = post(importer, "/admin/kv/import",
                         {**doc, "generation": 7})
        assert code == 409 and "generation" in out["error"], out
        # 400: truncated payload (valid base64, wrong byte count)
        cut = doc["k"][: (len(doc["k"]) // 8) * 4]
        code, out = post(importer, "/admin/kv/import", {**doc, "k": cut})
        assert code == 400 and "truncated" in out["error"], out
        # 400: broken base64
        code, out = post(importer, "/admin/kv/import",
                         {**doc, "v": "!!not-base64!!"})
        assert code == 400, out
        # 400: inconsistent resume cursor
        code, out = post(importer, "/admin/kv/import",
                         {**doc, "pos": doc["pos"] + 1})
        assert code == 400 and "cursor" in out["error"], out
        # 400: structurally missing field
        code, out = post(importer, "/admin/kv/import",
                         {k: v for k, v in doc.items() if k != "emitted"})
        assert code == 400, out

        # none of the refusals touched the importer's pool or counters
        code, body = http_get(
            f"http://127.0.0.1:{importer.port}/metrics", timeout=10)
        assert "nanodiloco_kv_ship" not in body

        # the untouched payload still lands: resumed stream, solo parity
        code, out = post(importer, "/admin/kv/import", doc)
        assert code == 200, out
        assert out["finish_reason"] == "length"
        assert out["token_ids"] == ref
        assert out["request_id"] == "wire-1"

        em = parse_metrics_text(http_get(
            f"http://127.0.0.1:{exporter.port}/metrics", timeout=10)[1])
        im = parse_metrics_text(http_get(
            f"http://127.0.0.1:{importer.port}/metrics", timeout=10)[1])
        assert em['nanodiloco_kv_ship_requests_total{direction="export"}'] == 1
        assert em['nanodiloco_kv_ship_bytes_total{direction="export"}'] > 0
        assert em['nanodiloco_serve_role{role="prefill"}'] == 1
        assert em["nanodiloco_serve_slots_parked"] == 0
        assert im['nanodiloco_kv_ship_requests_total{direction="import"}'] == 1
        assert im['nanodiloco_kv_ship_blocks_total{direction="import"}'] > 0
        assert im['nanodiloco_serve_role{role="decode"}'] == 1
        # the tier rides the health body for the router's probe
        hz = json.loads(http_get(
            f"http://127.0.0.1:{exporter.port}/healthz", timeout=10)[1])
        assert hz["role"] == "prefill"
    finally:
        exporter.stop()
        importer.stop()
