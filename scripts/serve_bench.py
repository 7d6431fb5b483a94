"""Closed-loop load generator for the continuous-batching server.

N client threads each drive M sequential requests (closed loop: a
client's next request waits for its previous answer) with mixed prompt
lengths against an in-process ``ServeServer`` over a REAL socket, then
report TTFT p50/p95 and aggregate decode tokens/s — the serving twin of
``bench.py``'s training numbers, emitted as one ``BENCH_SERVE`` JSON
line on stdout.

Workloads:
- ``uniform`` (default): every client cycles through ``--prompt-lens``
  with unique random prompts — the PR-4 throughput shape.
- ``capacity``: the paged-KV economics sweep. At a FIXED KV HBM budget
  (``--kv-hbm-budget-mb``) it sizes three engines — ``dense`` (the
  default pool: a worst-case ``max_len`` of blocks reserved for every
  slot, so slots bind), paged-fp, and paged-int8 (pools sized to the
  budget with more slots than fit, so blocks bind) — admits identical
  requests
  (``--capacity-prompt-len`` + ``--max-new-tokens`` tokens) until
  admission refuses, then measures aggregate decode tok/s with every
  admitted slot live. The admission count is MEASURED (the engine
  really holds that many concurrent requests in that much cache), and
  ``kv_hbm_bytes_per_token`` = allocated KV bytes / resident real
  tokens at capacity. Headline keys ``max_concurrent_slots`` /
  ``kv_hbm_bytes_per_token`` are the paged-int8 numbers and gate in
  ``report compare`` (both directions: slots must not drop, bytes per
  token must not grow).
- ``mixed``: the interference + shared-prefix scenario the chunked-
  prefill/prefix-cache engine exists for. ``--long-clients`` clients
  stream ``--long-prompt-len``-token prompts (unique content, prefix
  cache opted OUT so they cannot evict the shared prefix) while the
  short clients all open with the same ``--shared-prefix-len``-token
  system prefix plus a unique tail. Short arrivals are OPEN-LOOP (one
  every ``--short-interval-s``, regardless of completions): a closed
  loop self-synchronizes away from the stall — a short's next request
  is only submitted after its previous answer, and answers cannot
  arrive while a monolithic prefill holds the tick loop, so closed-loop
  shorts systematically land right AFTER the stall window and report
  flattering TTFTs (PERF.md measurement rules). The record splits TTFT
  by class: ``short_ttft_p95_s`` is the headline — with whole-prompt
  prefill a long admission stalls every short stream's first token;
  with chunked prefill it must stay bounded — and the prefix-cache
  counters show the shared prefix being computed once, not per request.

- ``surge``: the traffic-surge / predictive-autoscaling scenario the
  observability plane's ACTION loop exists for. An in-process fleet
  (``FleetRouter`` over real-socket replicas) starts at
  ``--surge-initial-replicas`` while an embedded collector +
  ``CapacityModel`` + ``Autoscaler`` watch it; mixed-class open-loop
  traffic (priority 0 and ``--surge-low-priority``) ramps past one
  replica's capacity, the queue-depth trend forecasts slot exhaustion,
  and the autoscaler must scale out BEFORE the surge peaks, shed the
  low class (terminal ``{"shed": true}`` 429s) if the fleet hits
  ``--surge-max-replicas`` while still pressed, then drain back down
  after the ramp. Gated keys: ``fleet_goodput_fraction`` (every
  replica-second accounted, scale transitions included),
  ``shed_total`` (BOTH directions: far more sheds = overload handling
  regressed, none = admission control broke), and ``class0_ttft_p95_s``
  (the SLO shedding exists to protect).

- ``chaos``: the committed fault drill (``fleet/chaos.py`` DRILL_PLAN —
  latency, slow-drip, mid-response reset, 500 burst, garbage JSON,
  flapped healthz, blackhole, hard replica kill) against a 3-replica
  fleet whose router<->replica wire runs through ``ChaosProxy``s.
  Clients fire greedy bursts with ``timeout_s=T``; the gate — asserted
  in-bench and via ``report compare`` against
  ``bench_serve_chaos_baseline.json`` — is ZERO dropped in-flight
  streams, every surviving stream bit-identical to solo ``generate()``,
  no client past T + one hedge delay, and ``chaos_goodput_fraction``
  holding (``chaos_dropped_streams`` gates both ways, shed-style).

- ``disagg``: the disaggregated prefill/decode comparison
  (``serve/kvship.py`` + ``fleet/disagg.py``). Two fleets of EQUAL
  device count run the same mixed long-prompt + chatty traffic: a
  tiered fleet (1 prefill + ``--disagg-decode-replicas`` decode
  replicas behind a ``DisaggRouter`` — every stream prefills on the
  prefill tier, its KV ships over the wire, and decode resumes on the
  decode tier) and a monolithic control (same replica count, all
  ``role=both``). Gated keys: ``disagg_ttft_p95_s`` (tiered
  chatty-class first-token latency, end-to-end through the handoff),
  ``disagg_decode_tokens_per_sec`` (decode-tier token rate — the
  number long-prompt interference erodes on a monolithic fleet), and
  ``kv_ship_bytes_per_request`` (both directions: a heavier ship
  bloated the wire format, a far lighter one stopped carrying the
  cache). The monolithic control's numbers and the interference ratio
  ride along in every record.

- ``repetitive``: the speculative-decoding sweep. Four legs on the same
  build: templated GREEDY prompts (pattern x reps + unique tail — the
  few-shot/templated shape where prompt-lookup speculation shines,
  because greedy continuations self-repeat) served spec-on and
  spec-off, then adversarial unique-random-token prompts at sampling
  temperature (no n-gram structure — lookup proposes nothing and the
  engine falls back to plain ticks) served spec-on and spec-off.
  Headline gated keys: ``spec_speedup`` (client tokens/s on vs off,
  the >= 1.5x contract), ``spec_acceptance_rate`` and
  ``spec_tokens_per_tick`` (the draft economics), and
  ``spec_adversarial_ratio`` (on/off where lookup CANNOT work — must
  stay ~1.0; reported alongside the flattering number on purpose,
  PERF.md honest-measurement rules).

``--tp N`` shards every engine the bench builds over an N-device
tensor-parallel mesh (``--force-cpu-devices N`` for virtual CPU shards
on a dev box); all records carry ``tp_degree``, and the capacity
workload additionally emits per-layout ``tp_*_decode_tokens_per_sec``
keys gated by ``report compare`` — on CPU these are an absolute parity
bar (TP-record vs TP-record), never a speedup claim (PERF.md).

By default the model is a random-init tiny Llama (shape knobs below) so
the bench runs anywhere, CPU included; ``--checkpoint-dir`` serves a
real trained checkpoint instead. Examples:

    python scripts/serve_bench.py                      # tiny, defaults
    python scripts/serve_bench.py --clients 16 --slots 8 --max-new-tokens 64
    python scripts/serve_bench.py --workload mixed     # interference bench
    python scripts/serve_bench.py --workload mixed --chunk-size 256
                                   # ~unchunked: one bucket swallows all

The committed CPU record lives in ``bench_serve_baseline.json``;
``python -m nanodiloco_tpu report compare bench_serve_baseline.json
out.json`` gates a candidate run against it (TTFT keys regress on
``--max-latency-increase``, throughput on ``--max-tps-drop``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="serve this trained checkpoint; default: a "
                        "random-init tiny model (throughput-shaped, "
                        "content-free)")
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--workload",
                   choices=("uniform", "mixed", "capacity", "repetitive",
                            "surge", "chaos", "disagg"),
                   default="uniform",
                   help="uniform: every client cycles --prompt-lens; "
                        "mixed: long-prompt interference + shared-prefix "
                        "short traffic; capacity: fixed-HBM-budget sweep "
                        "over dense/paged-fp/paged-int8 KV; repetitive: "
                        "the speculative-decoding sweep — templated "
                        "greedy traffic where prompt-lookup shines AND "
                        "an adversarial random-token leg where it "
                        "cannot, each measured spec-on vs spec-off on "
                        "the same build (see module docstring); surge: "
                        "mixed-class open-loop ramp against an "
                        "autoscaled in-process fleet — forecast-driven "
                        "scale-out, class-aware shedding, scale-in "
                        "after the ramp; chaos: the committed fault "
                        "schedule (fleet/chaos.py DRILL_PLAN) against a "
                        "3-replica fleet behind chaos proxies — gates "
                        "zero dropped streams, bit-parity of every "
                        "surviving stream, and goodput under chaos; "
                        "disagg: tiered prefill/decode fleet vs a "
                        "monolithic fleet of EQUAL device count under "
                        "mixed long-prompt + chatty traffic — gates the "
                        "tiered fleet's TTFT, its decode-tier "
                        "throughput, and the KV ship weight per handoff")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--chunk-size", type=int, default=64,
                   help="engine prefill chunk size (bucketed to powers "
                        "of two; >= --max-len approximates the unchunked "
                        "whole-prompt engine)")
    p.add_argument("--prefix-cache-tokens", type=int, default=4096,
                   help="shared-prefix KV cache capacity in tokens; 0 "
                        "disables")
    p.add_argument("--clients", type=int, default=8,
                   help="concurrent closed-loop (short) client threads")
    p.add_argument("--requests-per-client", type=int, default=4)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--prompt-lens", type=str, default="8,24,64",
                   help="comma-separated prompt lengths, cycled across "
                        "requests (mixed prefill shapes; in --workload "
                        "mixed these are the short clients' TAIL lengths "
                        "after the shared prefix)")
    p.add_argument("--long-clients", type=int, default=1,
                   help="[mixed] clients streaming long prompts")
    p.add_argument("--short-interval-s", type=float, default=0.4,
                   help="[mixed] open-loop short-request arrival spacing "
                        "in seconds (shorts fire on this schedule no "
                        "matter what's in flight — the only honest way "
                        "to observe prefill interference)")
    p.add_argument("--long-prompt-len", type=int, default=160,
                   help="[mixed] long-prompt length in tokens")
    p.add_argument("--shared-prefix-len", type=int, default=64,
                   help="[mixed] shared system-prefix length prepended "
                        "to every short request (the prefix cache is "
                        "chunk-granular: a prefix shorter than one "
                        "chunk never caches, so keep this >= "
                        "--chunk-size)")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree: shard every engine the "
                        "bench builds over this many devices (must "
                        "divide the model's KV-head count); the record "
                        "carries tp_degree, and the capacity workload "
                        "additionally emits per-layout "
                        "tp_*_decode_tokens_per_sec keys gated by "
                        "report compare")
    p.add_argument("--force-cpu-devices", type=int, default=None,
                   metavar="N",
                   help="bench on N virtual CPU devices (the TP record "
                        "on a laptop/CI box; same mechanism as the "
                        "serve CLI flag)")
    # KV pool knobs (any workload) + the capacity sweep's shape
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="token rows in one block of the KV pool (the "
                        "capacity workload ignores this and uses "
                        "--capacity-block-size)")
    p.add_argument("--kv-dtype", choices=("model", "int8"), default="model",
                   help="KV storage dtype")
    p.add_argument("--kv-pool-blocks", type=int, default=None,
                   help="pool size in blocks (default: every slot at "
                        "max_len)")
    p.add_argument("--kv-hbm-budget-mb", type=float, default=2.0,
                   help="[capacity] fixed KV HBM budget each mode must "
                        "live inside")
    p.add_argument("--capacity-block-size", type=int, default=16,
                   help="[capacity] block size for the paged modes")
    p.add_argument("--capacity-prompt-len", type=int, default=64,
                   help="[capacity] prompt length of every admitted "
                        "request (completion length is "
                        "--max-new-tokens)")
    p.add_argument("--capacity-decode-ticks", type=int, default=12,
                   help="[capacity] timed decode ticks per mode (after "
                        "one warmup tick)")
    # the surge workload's fleet + traffic shape
    p.add_argument("--surge-initial-replicas", type=int, default=1,
                   help="[surge] replicas at start (and the autoscaler "
                        "floor it drains back to)")
    p.add_argument("--surge-max-replicas", type=int, default=2,
                   help="[surge] autoscaler ceiling; shedding only "
                        "starts once the fleet is pinned here")
    p.add_argument("--surge-low-priority", type=int, default=3,
                   help="[surge] the sheddable class interleaved with "
                        "class-0 traffic (must be > 0)")
    p.add_argument("--surge-phase-requests", type=str, default="8,80,8",
                   help="[surge] arrivals per phase: base,peak,cooldown")
    p.add_argument("--surge-base-interval-s", type=float, default=0.5,
                   help="[surge] open-loop arrival spacing in the base "
                        "and cooldown phases")
    p.add_argument("--surge-peak-interval-s", type=float, default=0.04,
                   help="[surge] arrival spacing during the surge — "
                        "must exceed one replica's capacity (the "
                        "committed CPU baseline runs --slots 2 "
                        "--max-new-tokens 48 so the tiny model "
                        "actually saturates)")
    # the disagg workload's tiered-vs-monolithic comparison shape
    p.add_argument("--disagg-decode-replicas", type=int, default=1,
                   help="[disagg] decode-tier replicas behind the "
                        "tiered router (the tiered fleet is 1 prefill "
                        "+ this many decode; the monolithic control "
                        "fleet is the SAME total replica count, all "
                        "role=both — equal device count by "
                        "construction)")
    p.add_argument("--chaos-plan", type=str, default=None,
                   help="[chaos] JSON fault-plan path (fleet/chaos.py "
                        "format); default: the committed DRILL_PLAN — "
                        "one fault of every kind against r0/r1/r2")
    p.add_argument("--chaos-requests", type=int, default=48,
                   help="[chaos] total requests, fired in concurrent "
                        "bursts of 3 so every replica accrues the "
                        "ordinals its scheduled faults key on")
    p.add_argument("--chaos-prompt-len", type=int, default=24,
                   help="[chaos] one prompt length for every request "
                        "(one compiled shape, so the bit-parity replay "
                        "against solo generate() compiles once)")
    p.add_argument("--chaos-timeout-s", type=float, default=20.0,
                   help="[chaos] client timeout_s=T on every request; "
                        "the gate asserts no client ever waits past "
                        "T + one hedge delay")
    p.add_argument("--chaos-hedge-after-s", type=float, default=2.0,
                   help="[chaos] fixed router hedge delay — above the "
                        "tiny model's normal latency so only genuinely "
                        "stuck attempts (blackhole) hedge")
    # speculative decoding (any workload; the repetitive workload's
    # spec-on legs use these, its spec-off legs force 0)
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative drafts verified per slot per tick "
                        "(default: 4 for the repetitive workload's "
                        "spec-on legs, 0 — speculation off — for every "
                        "other workload)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="longest prompt-lookup n-gram")
    p.add_argument("--repetitive-pattern-len", type=int, default=16,
                   help="[repetitive] template pattern length; each "
                        "prompt is the pattern repeated "
                        "--repetitive-reps times + a unique 4-token "
                        "tail (few-shot shape)")
    p.add_argument("--repetitive-reps", type=int, default=3,
                   help="[repetitive] template repetitions per prompt")
    # tiny-model shape knobs (ignored with --checkpoint-dir)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=2048)
    return p


def _pct(sorted_vals: list[float], p: float) -> float | None:
    """Nearest-rank percentile — the ONE shared implementation the
    serve scheduler's gauges also use."""
    from nanodiloco_tpu.obs.telemetry import nearest_rank_percentile

    return nearest_rank_percentile(sorted_vals, p)


def _device_seconds_per_token(results: list[dict]) -> float | None:
    """Attributed device-seconds per completed token, from the
    responses' ``timing`` blocks — the over-the-wire side of the
    accountant's ledger. None when the server predates attribution or
    nothing completed."""
    dev_s = 0.0
    tokens = 0
    for r in results:
        t = r.get("timing") or {}
        dev_s += (t.get("prefill_device_s") or 0.0)
        dev_s += (t.get("decode_device_s") or 0.0)
        tokens += int(r.get("completion_tokens") or 0)
    if not tokens or dev_s <= 0:
        return None
    return round(dev_s / tokens, 8)


def _capacity_mode(args, cfg, params, mode: str, budget_bytes: int) -> dict:
    """Size ONE engine variant to the fixed KV HBM budget, admit
    identical requests until admission refuses (slots exhausted for
    ``dense``, whose pool reserves max_len for every slot; blocks
    exhausted for the others — both MEASURED, not computed),
    then time decode ticks with every admitted slot live."""
    from nanodiloco_tpu.models.generate import kv_bytes_per_token
    from nanodiloco_tpu.serve import (
        BlocksExhausted,
        GenRequest,
        InferenceEngine,
    )

    prompt_len = int(args.capacity_prompt_len)
    new_tokens = int(args.max_new_tokens)
    req_tokens = prompt_len + new_tokens
    max_len = min(args.max_len, cfg.max_position_embeddings)
    if req_tokens > max_len:
        raise SystemExit(
            f"--capacity-prompt-len {prompt_len} + --max-new-tokens "
            f"{new_tokens} exceeds max_len {max_len}"
        )
    bs = int(args.capacity_block_size)
    if mode == "dense":
        per_slot = max_len * kv_bytes_per_token(cfg)
        slots = max(1, int(budget_bytes // per_slot))
        eng = InferenceEngine(
            params, cfg, num_slots=slots, max_len=max_len,
            chunk_size=args.chunk_size, kv_block_size=bs, tp=args.tp,
        )
    else:
        kv_dtype = "int8" if mode == "paged-int8" else "model"
        tok_bytes = kv_bytes_per_token(
            cfg, None if kv_dtype == "model" else kv_dtype
        )
        nb = max(1, int(budget_bytes // (bs * tok_bytes)))
        blocks_per_req = -(-req_tokens // bs)
        # one MORE slot than the pool can hold, so the binding limit is
        # provably blocks, not the slot count
        slots = max(1, min(nb // blocks_per_req + 1, 512))
        eng = InferenceEngine(
            params, cfg, num_slots=slots, max_len=max_len,
            chunk_size=args.chunk_size, kv_block_size=bs,
            kv_dtype=kv_dtype, kv_pool_blocks=nb, tp=args.tp,
        )
    kv_bytes = int(eng.kv_stats()["kv_bytes"])
    rng = __import__("random").Random(args.seed)
    admitted = 0
    for slot in range(eng.num_slots):
        prompt = tuple(rng.randrange(cfg.vocab_size)
                       for _ in range(prompt_len))
        req = GenRequest(prompt=prompt, max_new_tokens=new_tokens,
                         temperature=float(args.temperature),
                         top_k=int(args.top_k), seed=slot)
        try:
            eng.prefill(slot, req)
        except (BlocksExhausted, ValueError):
            break
        admitted += 1
    slot_bound = mode != "dense" and admitted == eng.num_slots
    if slot_bound:
        # the paged number must be BLOCK-bound to mean anything: hitting
        # the engine's slot count (the 512 safety cap, or a rounding
        # corner) silently understates capacity — say so loudly
        print(
            f"# WARNING: {mode} admitted == engine slots ({admitted}); "
            "the measurement is slot-bound, not block-bound — raise the "
            "slot cap or shrink --kv-hbm-budget-mb",
            file=sys.stderr, flush=True,
        )
    eng.step()  # warmup: compile the decode tick outside the window
    # stay inside each request's exact block allocation: after the
    # warmup tick, only max_new - 2 more decode steps write at
    # positions the admission budget covers — timing past that would
    # measure attention over sentinel-clamped garbage rows, not the
    # steady state the record claims
    avail = max(1, int(args.max_new_tokens) - 2)
    ticks = min(max(1, int(args.capacity_decode_ticks)), avail)
    if ticks < int(args.capacity_decode_ticks):
        print(
            f"# note: decode window clamped to {ticks} ticks to stay "
            "inside the per-request KV allocation (raise "
            "--max-new-tokens for a longer window)",
            file=sys.stderr, flush=True,
        )
    # device-second cost over the SAME measured window: the engine's
    # dispatch accountant (obs/devtime) as a snapshot delta, so warmup
    # and compile seconds stay out of the per-token number
    dev0 = eng.accountant.total_device_seconds()
    t0 = time.monotonic()
    for _ in range(ticks):
        eng.step()
    dt = time.monotonic() - t0
    dev_s = eng.accountant.total_device_seconds() - dev0
    window_tokens = admitted * ticks
    return {
        "mode": mode,
        "max_concurrent_slots": admitted,
        **({"slot_bound": True} if slot_bound else {}),
        "engine_slots": eng.num_slots,
        "kv_bytes": kv_bytes,
        "kv_hbm_bytes_per_token": (
            round(kv_bytes / (admitted * req_tokens), 1) if admitted else None
        ),
        "decode_tokens_per_sec": round(admitted * ticks / dt, 1) if dt else None,
        "device_seconds_per_token": (
            round(dev_s / window_tokens, 8)
            if window_tokens and dev_s > 0 else None
        ),
        "kv_pool_blocks": eng.block_pool.num_blocks,
        "kv_block_size": eng.kv_block_size,
    }


def run_capacity(args, cfg, params, jax) -> None:
    """The fixed-HBM capacity sweep: dense vs paged-fp vs paged-int8 at
    one budget, one ``BENCH_SERVE`` record. Headline gated keys are the
    paged-int8 numbers; every mode's breakdown rides under
    ``capacity_modes``."""
    budget_bytes = int(args.kv_hbm_budget_mb * 2**20)
    modes = {}
    for mode in ("dense", "paged-fp", "paged-int8"):
        modes[mode] = _capacity_mode(args, cfg, params, mode, budget_bytes)
        print(f"# {mode}: {modes[mode]}", file=sys.stderr, flush=True)
    int8 = modes["paged-int8"]
    dense = modes["dense"]
    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": f"random-init llama (hidden {cfg.hidden_size} x "
                 f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})",
        "workload": "capacity",
        "tp_degree": args.tp,
        "kv_hbm_budget_mb": args.kv_hbm_budget_mb,
        "capacity_prompt_len": args.capacity_prompt_len,
        "max_new_tokens": args.max_new_tokens,
        "capacity_block_size": args.capacity_block_size,
        "capacity_modes": modes,
        # the gated contract: paged-int8 at the fixed budget
        "max_concurrent_slots": int8["max_concurrent_slots"],
        "kv_hbm_bytes_per_token": int8["kv_hbm_bytes_per_token"],
        # device-second cost per decoded token at capacity (paged-int8
        # headline, accountant snapshot delta over the timed window) —
        # gated BOTH directions in report compare: costlier tokens are
        # a regression, and a wildly cheaper number means the window
        # stopped measuring what it claims
        "device_seconds_per_token": int8.get("device_seconds_per_token"),
        "capacity_ratio_int8_vs_dense": (
            round(int8["max_concurrent_slots"]
                  / dense["max_concurrent_slots"], 2)
            if dense["max_concurrent_slots"] else None
        ),
        "capacity_ratio_fp_vs_dense": (
            round(modes["paged-fp"]["max_concurrent_slots"]
                  / dense["max_concurrent_slots"], 2)
            if dense["max_concurrent_slots"] else None
        ),
    }
    if args.tp > 1:
        # the gated TP contract (see _COMPARE_METRICS): per-layout
        # decode throughput ON the mesh — compared TP-record vs
        # TP-record, an absolute parity bar on CPU virtual devices (the
        # chip sitting pins the actual speedup/HBM headroom)
        rec["tp_dense_decode_tokens_per_sec"] = dense["decode_tokens_per_sec"]
        rec["tp_paged_fp_decode_tokens_per_sec"] = (
            modes["paged-fp"]["decode_tokens_per_sec"]
        )
        rec["tp_paged_int8_decode_tokens_per_sec"] = (
            int8["decode_tokens_per_sec"]
        )
        # headline alias of the paged-int8 number (the PR-9 convention:
        # the record leads with its best layout); informational only —
        # the gate reads the per-layout keys above
        rec["tp_decode_tokens_per_sec"] = int8["decode_tokens_per_sec"]
    print(json.dumps(rec), flush=True)


def _spec_leg(args, cfg, params, *, spec_k: int, adversarial: bool,
              seed: int) -> dict:
    """One repetitive-workload leg: a fresh engine (speculation on or
    off) behind a real socket, closed-loop clients, client-side AND
    engine-side decode throughput. Repetitive legs send GREEDY
    templated prompts (pattern x reps + unique tail — few-shot shape;
    greedy output self-repeats, which is exactly what prompt-lookup
    predicts); adversarial legs send unique random-token prompts at
    --temperature, where n-gram lookup finds nothing and the engine
    must fall back to plain one-token ticks."""
    import random
    import threading as _threading

    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    engine = InferenceEngine(
        params, cfg, num_slots=args.slots,
        max_len=min(args.max_len, cfg.max_position_embeddings),
        chunk_size=args.chunk_size,
        prefix_cache_tokens=args.prefix_cache_tokens,
        kv_block_size=args.kv_block_size, kv_dtype=args.kv_dtype,
        kv_pool_blocks=args.kv_pool_blocks,
        spec_k=spec_k, spec_ngram=args.spec_ngram, tp=args.tp,
    )
    # every verify bucket compiles BEFORE the window: the adaptive-k
    # ramp reaches buckets data-dependently, and a 0.5 s compile landing
    # mid-window would swamp the ~3 ms ticks being measured
    engine.warm_spec()
    server = ServeServer(
        Scheduler(engine, max_queue=args.max_queue),
        port=0, host="127.0.0.1", max_new_tokens_cap=args.max_new_tokens,
    ).start()

    def post(doc):
        return http_post_json(
            f"http://127.0.0.1:{server.port}/v1/generate", doc
        )

    rng = random.Random(seed)
    pattern = [rng.randrange(cfg.vocab_size)
               for _ in range(args.repetitive_pattern_len)]
    docs = []
    for c in range(args.clients):
        for r in range(args.requests_per_client):
            if adversarial:
                ids = [rng.randrange(cfg.vocab_size) for _ in range(
                    args.repetitive_pattern_len * args.repetitive_reps + 4
                )]
                temp, top_k = args.temperature, args.top_k
            else:
                ids = pattern * args.repetitive_reps + [
                    rng.randrange(cfg.vocab_size) for _ in range(4)
                ]
                temp, top_k = 0.0, 0
            docs.append((c, {
                "token_ids": ids, "max_new_tokens": args.max_new_tokens,
                "temperature": temp, "top_k": top_k,
                "seed": seed + c * 1000 + r, "stop": False,
            }))
    # warmup outside the window: compile every prefill bucket + the
    # decode tick + (spec legs) the verify buckets the adaptive-k ramp
    # walks through — a long greedy repetitive request climbs them all
    warm = {
        "token_ids": pattern * args.repetitive_reps + [1, 2, 3, 4],
        "max_new_tokens": args.max_new_tokens, "temperature": 0.0,
        "seed": 999_999, "stop": False, "prefix_cache": False,
    }
    code, out = post(warm)
    if code != 200:
        server.stop()
        raise SystemExit(
            f"repetitive warmup failed with {code}: {out.get('error')}"
        )
    # the warmup request's ticks must not leak into the measured
    # window: spec counters reset outright, cumulative scheduler decode
    # stats subtracted as a baseline snapshot below
    engine.reset_spec_stats()
    s0 = server._scheduler.stats()
    results, errors = [], []
    lock = _threading.Lock()

    def client(cid):
        for c, doc in docs:
            if c != cid:
                continue
            code, out = post(doc)
            with lock:
                (results if code == 200 else errors).append(out)

    threads = [_threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    stats = server._scheduler.stats()
    server.stop()
    completion = sum(r["completion_tokens"] for r in results)
    ttft = sorted(r["timing"]["ttft_s"] for r in results)
    decode_tokens = stats["decode_tokens"] - s0["decode_tokens"]
    decode_s = stats["decode_s"] - s0["decode_s"]
    return {
        "requests": len(results),
        "errors": len(errors),
        "wall_s": round(wall, 3),
        "client_tokens_per_sec": round(completion / wall, 1) if wall else None,
        "decode_tokens_per_sec": (
            round(decode_tokens / decode_s, 1) if decode_s > 0 else None
        ),
        "ttft_p50_s": round(_pct(ttft, 0.50), 4) if ttft else None,
        "spec": stats.get("spec"),
    }


def run_repetitive(args, cfg, params, jax) -> None:
    """The speculative-decoding sweep: repetitive (templated, greedy)
    and adversarial (random-token, sampled) traffic, each served
    spec-on and spec-off on the SAME build — one ``BENCH_SERVE`` record
    whose gated keys are the speedup where lookup works, the
    acceptance/emission economics, and the adversarial ratio proving
    the fallback costs (almost) nothing."""
    legs = {}
    for name, spec_k, adversarial in (
        ("repetitive_spec_on", args.spec_k, False),
        ("repetitive_spec_off", 0, False),
        ("adversarial_spec_on", args.spec_k, True),
        ("adversarial_spec_off", 0, True),
    ):
        legs[name] = _spec_leg(
            args, cfg, params, spec_k=spec_k, adversarial=adversarial,
            seed=args.seed,
        )
        print(f"# {name}: {legs[name]}", file=sys.stderr, flush=True)
    on, off = legs["repetitive_spec_on"], legs["repetitive_spec_off"]
    aon, aoff = legs["adversarial_spec_on"], legs["adversarial_spec_off"]
    spec = on.get("spec") or {}
    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": f"random-init llama (hidden {cfg.hidden_size} x "
                 f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})",
        "workload": "repetitive",
        "tp_degree": args.tp,
        "slots": args.slots,
        "clients": args.clients,
        "requests_per_client": args.requests_per_client,
        "max_new_tokens": args.max_new_tokens,
        "spec_k": args.spec_k,
        "spec_ngram": args.spec_ngram,
        "kv_block_size": args.kv_block_size,
        "legs": legs,
        # the gated speculation contract (see _COMPARE_METRICS):
        # client-visible decode throughput with speculation on, its
        # ratio to the same build with speculation off, the
        # draft-accept economics, and the adversarial fallback ratio
        "decode_tokens_per_sec": on["decode_tokens_per_sec"],
        "client_tokens_per_sec": on["client_tokens_per_sec"],
        "spec_off_client_tokens_per_sec": off["client_tokens_per_sec"],
        "spec_speedup": (
            round(on["client_tokens_per_sec"] / off["client_tokens_per_sec"], 3)
            if on["client_tokens_per_sec"] and off["client_tokens_per_sec"]
            else None
        ),
        "spec_acceptance_rate": spec.get("acceptance_rate"),
        "spec_tokens_per_tick": spec.get("tokens_per_tick_mean"),
        "adversarial_client_tokens_per_sec": aon["client_tokens_per_sec"],
        "adversarial_spec_off_client_tokens_per_sec": (
            aoff["client_tokens_per_sec"]
        ),
        "spec_adversarial_ratio": (
            round(aon["client_tokens_per_sec"] / aoff["client_tokens_per_sec"], 3)
            if aon["client_tokens_per_sec"] and aoff["client_tokens_per_sec"]
            else None
        ),
    }
    print(json.dumps(rec), flush=True)


class _InProcessProvider:
    """A ReplicaProvider whose replicas are in-process ``ServeServer``s
    sharing the bench's params — the surge workload's provider (the CLI
    and the chip drill use real subprocesses via
    ``ProcessReplicaProvider``; a bench must not pay a fresh Python +
    jax import per scale-out). ``make_server`` builds, WARMS (compiles
    outside the traffic window), and starts one server."""

    def __init__(self, make_server) -> None:
        self._make = make_server
        self._servers: dict = {}
        self._seq = 0

    def launch(self):
        from nanodiloco_tpu.fleet import Replica

        self._seq += 1
        name = f"auto{self._seq}"
        srv = self._make()
        self._servers[name] = srv
        return Replica(name=name, url=f"http://127.0.0.1:{srv.port}")

    def retire(self, name: str) -> None:
        srv = self._servers.pop(name, None)
        if srv is not None:
            srv.stop()

    def preempted(self) -> list:
        return []  # in-process replicas cannot be reclaimed

    def stop_all(self) -> None:
        for name in list(self._servers):
            self.retire(name)


def run_surge(args, cfg, params, jax) -> None:
    """The closed observe->forecast->act loop under a traffic surge:
    open-loop mixed-class arrivals ramp past one replica's capacity, the
    capacity model forecasts queue/slot exhaustion from the collector's
    series (never point gauges), the autoscaler grows the fleet through
    the router's scaling_up discipline, sheds the low class once pinned
    at max, and drains back down after the ramp — one ``BENCH_SERVE``
    record whose gated keys are ``fleet_goodput_fraction``,
    ``shed_total``, and ``class0_ttft_p95_s``."""
    from nanodiloco_tpu.fleet import FleetRouter, Replica
    from nanodiloco_tpu.fleet.autoscaler import Autoscaler
    from nanodiloco_tpu.obs.collector import Collector
    from nanodiloco_tpu.obs.forecast import CapacityModel
    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    if args.surge_low_priority < 1:
        raise SystemExit("--surge-low-priority must be >= 1 (class 0 is "
                         "the protected class)")
    lens = [int(x) for x in args.prompt_lens.split(",") if x]
    phase_counts = [int(x) for x in args.surge_phase_requests.split(",")]
    if len(phase_counts) != 3:
        raise SystemExit("--surge-phase-requests must be base,peak,cooldown")

    def make_server() -> ServeServer:
        engine = InferenceEngine(
            params, cfg, num_slots=args.slots,
            max_len=min(args.max_len, cfg.max_position_embeddings),
            chunk_size=args.chunk_size,
            prefix_cache_tokens=args.prefix_cache_tokens,
            kv_block_size=args.kv_block_size, kv_dtype=args.kv_dtype,
            kv_pool_blocks=args.kv_pool_blocks, tp=args.tp,
        )
        srv = ServeServer(
            Scheduler(engine, max_queue=args.max_queue),
            port=0, host="127.0.0.1",
            max_new_tokens_cap=args.max_new_tokens,
        ).start()
        # compile every prefill bucket + the decode tick BEFORE the
        # replica joins the router: a mid-surge scale-out must add
        # capacity, not a compile stall that poisons class-0 TTFT
        for n, p_len in enumerate(sorted(set(lens))):
            code, out = http_post_json(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                {"token_ids": [(i * 7 + 3) % cfg.vocab_size
                               for i in range(p_len)],
                 "max_new_tokens": 2, "temperature": args.temperature,
                 "top_k": args.top_k, "seed": 10_000 + n, "stop": False,
                 "prefix_cache": False},
            )
            if code != 200:
                srv.stop()
                raise SystemExit(
                    f"surge warmup (prompt_len={p_len}) failed with "
                    f"{code}: {out.get('error')}"
                )
        return srv

    provider = _InProcessProvider(make_server)
    seed_servers = [make_server()
                    for _ in range(args.surge_initial_replicas)]
    replicas = [Replica(name=f"r{i}", url=f"http://127.0.0.1:{s.port}")
                for i, s in enumerate(seed_servers)]
    router = FleetRouter(
        replicas, port=0, host="127.0.0.1",
        health_interval_s=0.2, quiet=True,
    ).start()
    collector = Collector([(r.name, r.url) for r in replicas],
                          interval_s=0.25)
    model = CapacityModel(collector.store, window_s=20.0,
                          min_horizon_s=1.5)
    scaler = Autoscaler(
        router, model, provider,
        min_replicas=args.surge_initial_replicas,
        max_replicas=args.surge_max_replicas,
        interval_s=0.25, cooldown_s=3.0, max_step=1,
        hysteresis_ticks=2, scale_out_horizon_s=30.0,
        scale_in_idle_ticks=6, shed_horizon_s=20.0,
    )
    stop = threading.Event()

    def control_loop() -> None:
        while not stop.is_set():
            targets = []
            for n in router.replica_names():
                try:
                    targets.append((n, router.url_of(n)))
                except KeyError:
                    continue  # removed between calls
            try:
                if targets:
                    collector.set_targets(targets)
                    collector.scrape_once()
                scaler.tick()
            except Exception:
                pass  # one bad pass must not kill the loop
            stop.wait(scaler.interval_s)

    ctrl = threading.Thread(target=control_loop, daemon=True,
                            name="surge-autoscale")
    ctrl.start()

    results: list[dict] = []
    shed: list[dict] = []
    errors: list[tuple[int, dict]] = []
    lock = threading.Lock()
    rng = __import__("random").Random(args.seed)

    def fire(i: int, prio: int) -> None:
        p_len = lens[i % len(lens)]
        code, out = http_post_json(
            f"http://127.0.0.1:{router.port}/v1/generate",
            {"token_ids": [rng.randrange(cfg.vocab_size)
                           for _ in range(p_len)],
             "max_new_tokens": args.max_new_tokens,
             "temperature": args.temperature, "top_k": args.top_k,
             "seed": i, "stop": False, "priority": prio},
            timeout=120.0,
        )
        with lock:
            if code == 200:
                out["_priority"] = prio
                results.append(out)
            elif code == 429 and isinstance(out, dict) and out.get("shed"):
                shed.append(out)
            else:
                errors.append((code, out))

    # open-loop arrivals (a closed loop would self-throttle away from
    # the very overload being measured), class 0 and the low class
    # interleaved so both see every phase
    workers: list[threading.Thread] = []
    t0 = time.monotonic()
    i = 0
    for count, interval in zip(
        phase_counts,
        (args.surge_base_interval_s, args.surge_peak_interval_s,
         args.surge_base_interval_s),
    ):
        phase_start = time.monotonic()
        for k in range(count):
            due = phase_start + k * interval
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            prio = 0 if i % 2 == 0 else args.surge_low_priority
            w = threading.Thread(target=fire, args=(i, prio))
            w.start()
            workers.append(w)
            i += 1
    for w in workers:
        w.join()
    traffic_wall = time.monotonic() - t0

    # let the loop scale back in (drain discipline + idle-tick
    # hysteresis) before the books close — bounded, not open-ended
    settle_deadline = time.monotonic() + 30.0
    while time.monotonic() < settle_deadline:
        s = router.fleet_stats()
        if (s["replicas_serving"] <= args.surge_initial_replicas
                and s["replicas_scaling_up"] == 0):
            break
        time.sleep(0.25)
    stop.set()
    ctrl.join(timeout=10)
    fleet = router.fleet_stats()
    router.stop()
    provider.stop_all()
    for s in seed_servers:
        s.stop()

    def ttfts(prio=None):
        return sorted(
            r["timing"]["ttft_s"] for r in results
            if prio is None or r["_priority"] == prio
        )

    class0, low = ttfts(0), ttfts(args.surge_low_priority)
    events = fleet.get("events", {})
    shed_by_class = fleet.get("shed_by_class", {})
    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": f"random-init llama (hidden {cfg.hidden_size} x "
                 f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})",
        "workload": "surge",
        "tp_degree": args.tp,
        "slots": args.slots,
        "surge_initial_replicas": args.surge_initial_replicas,
        "surge_max_replicas": args.surge_max_replicas,
        "surge_low_priority": args.surge_low_priority,
        "surge_phase_requests": phase_counts,
        "max_new_tokens": args.max_new_tokens,
        "traffic_wall_s": round(traffic_wall, 3),
        "requests": len(results),
        "rejected_or_failed": len(errors),
        # the gated surge contract: capacity availability with every
        # scale-transition second accounted, the admission-control
        # evidence (both directions), and the protected class's latency
        "fleet_goodput_fraction": fleet.get("fleet_goodput_fraction"),
        "shed_total": sum(shed_by_class.values()) if shed_by_class
                      else len(shed),
        "class0_ttft_p95_s": (
            round(_pct(class0, 0.95), 4) if class0 else None
        ),
        "class0_requests": len(class0),
        "low_class_ttft_p95_s": (
            round(_pct(low, 0.95), 4) if low else None
        ),
        "shed_by_class": shed_by_class,
        "shed_responses_seen": len(shed),
        # device-second cost per completed token OVER THE WIRE: summed
        # from each response's attribution timing block — the same
        # ledger the engine accountant keeps, arriving via the client
        # path (reconciliation is pinned by test; gated both ways)
        "device_seconds_per_token": _device_seconds_per_token(results),
        "scale_up_events": events.get("scale_up", 0),
        "scale_down_events": events.get("scale_down", 0),
        "preempt_resume_events": events.get("preempt_resume", 0),
        "seconds_by_state": fleet.get("seconds_by_state"),
        "replicas_departed": fleet.get("replicas_departed"),
    }
    print(f"# surge fleet: {json.dumps(fleet.get('seconds_by_state'))} "
          f"events={json.dumps(events)}", file=sys.stderr, flush=True)
    print(json.dumps(rec), flush=True)


def run_chaos(args, cfg, params, jax) -> None:
    """The committed fault drill against a 3-replica fleet behind chaos
    proxies: every byte of router<->replica traffic crosses the chaos
    wire while clients (clean wire, ``timeout_s=T``) fire greedy
    requests in concurrent bursts. Gates, asserted in-bench AND via the
    ``BENCH_SERVE`` record in ``report compare``: ZERO dropped
    in-flight streams (a client transport error is a drop — honest
    5xx/503 JSON answers are not), every surviving 200 stream
    bit-identical to solo ``generate()`` on the same backend, no client
    waiting past T + one hedge delay, and ``chaos_goodput_fraction``
    (200s over requests sent) holding against the committed baseline."""
    from nanodiloco_tpu.fleet import FleetRouter, Replica
    from nanodiloco_tpu.fleet.chaos import DRILL_PLAN, ChaosPlan, proxy_fleet
    from nanodiloco_tpu.models.generate import generate
    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    plan = (ChaosPlan.load(args.chaos_plan) if args.chaos_plan
            else ChaosPlan.from_dict(DRILL_PLAN))
    p_len = args.chaos_prompt_len
    timeout_s = args.chaos_timeout_s

    def make_server() -> ServeServer:
        engine = InferenceEngine(
            params, cfg, num_slots=args.slots,
            max_len=min(args.max_len, cfg.max_position_embeddings),
            chunk_size=args.chunk_size,
            prefix_cache_tokens=args.prefix_cache_tokens,
            kv_block_size=args.kv_block_size, kv_dtype=args.kv_dtype,
            kv_pool_blocks=args.kv_pool_blocks, tp=args.tp,
        )
        srv = ServeServer(
            Scheduler(engine, max_queue=args.max_queue),
            port=0, host="127.0.0.1",
            max_new_tokens_cap=args.max_new_tokens,
        ).start()
        # compile the one prompt bucket + decode BEFORE chaos starts:
        # warmup goes straight to the replica, so it consumes no proxy
        # ordinal and cannot eat a scheduled fault
        code, out = http_post_json(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            {"token_ids": [(i * 7 + 3) % cfg.vocab_size
                           for i in range(p_len)],
             "max_new_tokens": args.max_new_tokens, "temperature": 0.0,
             "top_k": 0, "seed": 0, "stop": False, "prefix_cache": False},
        )
        if code != 200:
            srv.stop()
            raise SystemExit(
                f"chaos warmup failed with {code}: {out.get('error')}"
            )
        return srv

    servers = {f"r{i}": make_server() for i in range(3)}

    def on_kill(name: str) -> None:
        # a hard replica death WITH streams in flight: the server stops
        # mid-decode, every later forward to it aborts on the wire
        srv = servers.get(name)
        if srv is not None:
            srv.stop()

    replicas = [Replica(name=n, url=f"http://127.0.0.1:{s.port}")
                for n, s in servers.items()]
    proxied, proxies = proxy_fleet(replicas, plan, on_kill=on_kill)
    router = FleetRouter(
        proxied, port=0, host="127.0.0.1",
        health_interval_s=0.2, probe_timeout_s=1.0, quiet=True,
        request_timeout_s=60.0,
        hedge_after_s=args.chaos_hedge_after_s,
        retry_budget_min=10.0, retry_budget_cap=20.0,
        breaker_window=8, breaker_min_samples=3,
        breaker_failure_rate=0.5, breaker_open_s=1.5,
    ).start()

    rng = __import__("random").Random(args.seed)
    prompts = [[rng.randrange(cfg.vocab_size) for _ in range(p_len)]
               for _ in range(args.chaos_requests)]
    results: dict[int, tuple[int, dict, float]] = {}
    dropped: list[tuple[int, str]] = []
    lock = threading.Lock()

    def fire(i: int) -> None:
        t0 = time.monotonic()
        try:
            code, out = http_post_json(
                f"http://127.0.0.1:{router.port}/v1/generate",
                {"token_ids": prompts[i],
                 "max_new_tokens": args.max_new_tokens,
                 "temperature": 0.0, "top_k": 0, "seed": i,
                 "stop": False, "prefix_cache": False, "priority": 0,
                 "timeout_s": timeout_s},
                timeout=timeout_s + args.chaos_hedge_after_s + 10.0,
            )
            with lock:
                results[i] = (code, out, time.monotonic() - t0)
        except Exception as e:  # a transport failure IS a dropped stream
            with lock:
                dropped.append((i, f"{type(e).__name__}: {e}"))

    # concurrent bursts of 3 (one per replica-sized slice of the fleet):
    # least-loaded routing spreads each burst, so every proxy accrues
    # the per-target request ordinals its scheduled faults key on
    t0 = time.monotonic()
    for base in range(0, args.chaos_requests, 3):
        burst = [threading.Thread(target=fire, args=(i,))
                 for i in range(base, min(base + 3, args.chaos_requests))]
        for w in burst:
            w.start()
        for w in burst:
            w.join()
    traffic_wall = time.monotonic() - t0

    fleet = router.fleet_stats()
    router.stop()
    for p in proxies:
        p.stop()
    for srv in servers.values():
        try:
            srv.stop()  # the killed replica is already down; harmless
        except Exception:
            pass

    # bit-parity replay: every surviving 200 stream against solo
    # generate() on the same backend — one prompt shape, so the whole
    # replay reuses ONE compiled program. A deadline-shortened stream
    # (finish_reason expired/cancelled) must still be a PREFIX of the
    # solo stream: partial, but never wrong.
    import numpy as np

    survivors = [(i, out) for i, (code, out, _) in sorted(results.items())
                 if code == 200]
    parity_failures = []
    for i, out in survivors:
        served = [int(t) for t in out.get("token_ids", [])]
        solo = generate(
            params, jax.numpy.asarray([prompts[i]], dtype="int32"),
            cfg, args.max_new_tokens, temperature=0.0,
        )
        solo_list = [int(t) for t in np.asarray(solo)[0][: len(served)]]
        if served != solo_list or not served:
            parity_failures.append(i)
    latencies = sorted(lat for _, (_, _, lat) in results.items())
    max_lat = latencies[-1] if latencies else 0.0
    ok = sum(1 for code, _, _ in results.values() if code == 200)
    sent = args.chaos_requests
    counts = plan.counts()

    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": f"random-init llama (hidden {cfg.hidden_size} x "
                 f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})",
        "workload": "chaos",
        "tp_degree": args.tp,
        "slots": args.slots,
        "requests": sent,
        "max_new_tokens": args.max_new_tokens,
        "timeout_s": timeout_s,
        "hedge_after_s": args.chaos_hedge_after_s,
        "traffic_wall_s": round(traffic_wall, 3),
        # the gated chaos contract: drops gate BOTH WAYS (shed-style),
        # goodput is a share with the absolute band
        "chaos_dropped_streams": len(dropped),
        "chaos_goodput_fraction": round(ok / sent, 6) if sent else None,
        "chaos_parity_streams": len(survivors),
        "chaos_parity_failures": len(parity_failures),
        "chaos_injected_total": sum(counts.values()),
        "chaos_injected_by_kind": counts,
        "max_client_latency_s": round(max_lat, 3),
        "latency_p95_s": (round(_pct(latencies, 0.95), 4)
                          if latencies else None),
        "hedges": fleet.get("hedges"),
        "hedge_wins": fleet.get("hedge_wins"),
        "retries": fleet.get("retries"),
        "retry_budget_exhausted": fleet.get("retry_budget_exhausted"),
        "deadline_expired": fleet.get("deadline_expired"),
        "breaker_opens": fleet.get("breaker_opens"),
        "fleet_events": fleet.get("events", {}),
        "seconds_by_state": fleet.get("seconds_by_state"),
    }
    print(f"# chaos fleet: injected={json.dumps(counts)} "
          f"events={json.dumps(fleet.get('events', {}))} "
          f"dropped={len(dropped)} parity_failures={parity_failures}",
          file=sys.stderr, flush=True)
    print(json.dumps(rec), flush=True)

    failures = []
    if dropped:
        failures.append(f"{len(dropped)} dropped in-flight streams "
                        f"(client transport errors): {dropped[:5]}")
    if parity_failures:
        failures.append(f"{len(parity_failures)} surviving streams "
                        f"diverged from solo generate(): "
                        f"{parity_failures[:5]}")
    bound = timeout_s + args.chaos_hedge_after_s + 2.0
    if max_lat > bound:
        failures.append(f"client latency {max_lat:.2f}s exceeds "
                        f"timeout_s + hedge + slack = {bound:.2f}s")
    if failures:
        raise SystemExit("chaos gate FAILED:\n  - " + "\n  - ".join(failures))


def _disagg_leg(args, cfg, params, *, tiered: bool) -> dict:
    """One fleet build + mixed-traffic run: ``tiered`` = 1 prefill +
    ``--disagg-decode-replicas`` decode replicas behind a
    ``DisaggRouter``; the control is the SAME total replica count, all
    ``role=both``, behind a plain ``FleetRouter`` — equal device count
    by construction, so the delta is the disaggregation, not extra
    hardware. Long prompts run closed-loop, chatty shorts OPEN-LOOP
    (the only honest way to observe prefill interference — a closed
    loop self-synchronizes away from the stall, PERF.md)."""
    from nanodiloco_tpu.fleet import DisaggRouter, FleetRouter, Replica
    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    lens = [int(x) for x in args.prompt_lens.split(",") if x]
    warm_lens = sorted(set(lens) | {args.long_prompt_len})

    def make_server(role: str) -> ServeServer:
        engine = InferenceEngine(
            params, cfg, num_slots=args.slots,
            max_len=min(args.max_len, cfg.max_position_embeddings),
            chunk_size=args.chunk_size,
            prefix_cache_tokens=args.prefix_cache_tokens,
            kv_block_size=args.kv_block_size, kv_dtype=args.kv_dtype,
            kv_pool_blocks=args.kv_pool_blocks, tp=args.tp,
        )
        srv = ServeServer(
            Scheduler(engine, max_queue=args.max_queue),
            port=0, host="127.0.0.1",
            max_new_tokens_cap=args.max_new_tokens,
            role=role,
        ).start()
        # compile every prompt bucket + the decode tick straight at the
        # replica, outside the timed window (decode replicas too: the
        # fallback path re-prefills there, and a compile stall inside
        # the window would corrupt the comparison)
        for n, p_len in enumerate(warm_lens):
            code, out = http_post_json(
                f"http://127.0.0.1:{srv.port}/v1/generate",
                {"token_ids": [(i * 7 + 3) % cfg.vocab_size
                               for i in range(p_len)],
                 "max_new_tokens": 2, "temperature": args.temperature,
                 "top_k": args.top_k, "seed": 90_000 + n, "stop": False,
                 "prefix_cache": False},
            )
            if code != 200:
                srv.stop()
                raise SystemExit(
                    f"disagg warmup (prompt_len={p_len}) failed with "
                    f"{code}: {out.get('error')}"
                )
        return srv

    n_dec = int(args.disagg_decode_replicas)
    roles = ((["prefill"] + ["decode"] * n_dec) if tiered
             else ["both"] * (1 + n_dec))
    servers = [make_server(r) for r in roles]
    replicas = [Replica(name=f"r{i}", url=f"http://127.0.0.1:{s.port}")
                for i, s in enumerate(servers)]
    router_cls = DisaggRouter if tiered else FleetRouter
    router = router_cls(
        replicas, port=0, host="127.0.0.1",
        health_interval_s=0.2, quiet=True,
    ).start()
    # wait for the health loop to see every replica ready (and, tiered,
    # to learn the roles) — otherwise the first arrivals take the
    # monolithic fallback and the handoff count lies
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if len(router.tier_capacity_names(None)) == len(replicas):
            break
        time.sleep(0.1)
    else:
        raise SystemExit("disagg fleet never became ready")

    results: list[dict] = []
    errors: list[tuple[int, dict]] = []
    lock = threading.Lock()
    rng = __import__("random").Random(args.seed)

    def run_request(doc: dict, cls: str) -> None:
        code, out = http_post_json(
            f"http://127.0.0.1:{router.port}/v1/generate", doc,
            timeout=180.0,
        )
        with lock:
            if code == 200:
                out["_class"] = cls
                results.append(out)
            else:
                errors.append((code, out))

    t_start = time.monotonic()

    def short_client(cid: int) -> None:
        workers = []
        for r in range(args.requests_per_client):
            p_len = lens[(cid + r) % len(lens)]
            doc = {
                "token_ids": [rng.randrange(cfg.vocab_size)
                              for _ in range(p_len)],
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": cid * 1000 + r, "stop": False,
                "prefix_cache": False,
            }
            due = t_start + (cid + r * args.clients) * args.short_interval_s
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            w = threading.Thread(target=run_request, args=(doc, "short"))
            w.start()
            workers.append(w)
        for w in workers:
            w.join()

    def long_client(cid: int) -> None:
        for r in range(args.requests_per_client):
            run_request({
                "token_ids": [rng.randrange(cfg.vocab_size)
                              for _ in range(args.long_prompt_len)],
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": 500_000 + cid * 1000 + r, "stop": False,
                "prefix_cache": False,
            }, "long")

    threads = ([threading.Thread(target=short_client, args=(c,))
                for c in range(args.clients)]
               + [threading.Thread(target=long_client, args=(c,))
                  for c in range(args.long_clients)])
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.monotonic() - t0

    fleet = router.fleet_stats()
    # decode-tier device economics: tokens per second OF DECODE WORK on
    # the replicas that serve decode (tiered: the decode tier;
    # monolithic: everyone) — the number long-prompt interference
    # erodes, because a prefill chunk interleaved into the tick loop
    # stretches every live stream's inter-token time
    decode_tokens = 0
    decode_s = 0.0
    for srv, role in zip(servers, roles):
        if role in ("decode", "both"):
            s = srv._scheduler.stats()
            decode_tokens += s.get("decode_tokens") or 0
            decode_s += s.get("decode_s") or 0.0
    router.stop()
    for srv in servers:
        srv.stop()

    def ttft(r: dict) -> float:
        # a handoff stream's honest end-to-end first-token latency is
        # the router's receipt->prefill-reply span; the decode
        # replica's own timing only covers the resumed tail
        return r.get("handoff_ttft_s") or r["timing"]["ttft_s"]

    short_ttfts = sorted(ttft(r) for r in results if r["_class"] == "short")
    long_ttfts = sorted(ttft(r) for r in results if r["_class"] == "long")
    all_ttfts = sorted(ttft(r) for r in results)
    disagg = fleet.get("disagg") or {}
    # per-phase TTFT waterfall (tiered leg only: the phases exist only
    # on handoff responses) — where a handed-off request's first-token
    # latency went: queue on the prefill tier, prefill compute, the
    # ship window (export + decode pick), and import admission overhead
    phase_stats: dict = {}
    for ph in ("queue_s", "prefill_s", "ship_s", "decode_admission_s"):
        vals = sorted(
            r["handoff_phases"][ph] for r in results
            if isinstance(r.get("handoff_phases"), dict)
            and isinstance(r["handoff_phases"].get(ph), (int, float))
        )
        if vals:
            key = ph[:-2]  # strip the _s unit suffix off the phase name
            phase_stats[f"{key}_p50_s"] = round(_pct(vals, 0.50), 6)
            phase_stats[f"{key}_p95_s"] = round(_pct(vals, 0.95), 6)
    return {
        "replicas": len(replicas),
        "roles": roles,
        "requests": len(results),
        "rejected_or_failed": len(errors),
        "wall_s": round(wall_s, 3),
        "ttft_p95_s": round(_pct(all_ttfts, 0.95), 4) if all_ttfts else None,
        "short_ttft_p95_s": (
            round(_pct(short_ttfts, 0.95), 4) if short_ttfts else None
        ),
        "long_ttft_p50_s": (
            round(_pct(long_ttfts, 0.50), 4) if long_ttfts else None
        ),
        "decode_tokens": decode_tokens,
        "decode_s": round(decode_s, 4),
        "decode_tokens_per_sec": (
            round(decode_tokens / decode_s, 1) if decode_s > 0 else None
        ),
        "completion_tokens": sum(r["completion_tokens"] for r in results),
        "handoffs": disagg.get("handoffs", 0),
        "handoff_fallbacks": disagg.get("fallbacks", 0),
        "fallbacks_by_reason": disagg.get("fallbacks_by_reason"),
        "ship_bytes": disagg.get("ship_bytes", 0),
        "handoff_seconds_sum": disagg.get("handoff_seconds_sum"),
        "ttft_phases": phase_stats or None,
    }


def run_disagg(args, cfg, params, jax) -> None:
    """Tiered vs monolithic at EQUAL device count under the same mixed
    long-prompt + chatty traffic, one ``BENCH_SERVE`` record. Gated
    keys: ``disagg_ttft_p95_s`` (the tiered fleet's chatty-class
    first-token latency), ``disagg_decode_tokens_per_sec`` (decode-tier
    token rate — what the split exists to protect from long-prompt
    interference), and ``kv_ship_bytes_per_request`` (ship weight per
    handoff, both directions: bloat OR a payload that stopped carrying
    the cache). The monolithic control's numbers ride along so the
    interference ratio is visible in every record."""
    tiered = _disagg_leg(args, cfg, params, tiered=True)
    if not tiered["handoffs"]:
        raise SystemExit(
            "disagg bench invalid: the tiered leg completed zero "
            "handoffs — every request fell back to the monolithic path"
        )
    mono = _disagg_leg(args, cfg, params, tiered=False)
    ship_per_req = (round(tiered["ship_bytes"] / tiered["handoffs"], 1)
                    if tiered["handoffs"] else None)
    d_tps, m_tps = (tiered["decode_tokens_per_sec"],
                    mono["decode_tokens_per_sec"])
    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": f"random-init llama (hidden {cfg.hidden_size} x "
                 f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})",
        "workload": "disagg",
        "tp_degree": args.tp,
        "slots": args.slots,
        "kv_block_size": args.kv_block_size,
        "kv_dtype": args.kv_dtype,
        "disagg_decode_replicas": args.disagg_decode_replicas,
        "clients": args.clients,
        "long_clients": args.long_clients,
        "long_prompt_len": args.long_prompt_len,
        "short_interval_s": args.short_interval_s,
        "max_new_tokens": args.max_new_tokens,
        # the gated disagg contract
        "disagg_ttft_p95_s": tiered["short_ttft_p95_s"],
        "disagg_decode_tokens_per_sec": d_tps,
        "kv_ship_bytes_per_request": ship_per_req,
        # the per-phase TTFT waterfall, flattened into gated keys: the
        # compare gate catches a regression in WHICH hop ate the
        # latency, not just that p95 moved
        **{f"disagg_phase_{k}": v
           for k, v in (tiered.get("ttft_phases") or {}).items()},
        # the monolithic control at the same device count, and the
        # headline ratio the split is FOR (>= 1 means the decode tier
        # really is shielded from long-prompt admissions)
        "mono_ttft_p95_s": mono["short_ttft_p95_s"],
        "mono_decode_tokens_per_sec": m_tps,
        "disagg_interference_ratio": (
            round(d_tps / m_tps, 4) if d_tps and m_tps else None
        ),
        "handoffs": tiered["handoffs"],
        "handoff_fallbacks": tiered["handoff_fallbacks"],
        "handoff_seconds_sum": tiered["handoff_seconds_sum"],
        "tiered": tiered,
        "monolithic": mono,
    }
    print(
        f"# disagg tiered: {tiered['requests']} ok, "
        f"{tiered['handoffs']} handoffs, "
        f"{tiered['handoff_fallbacks']} fallbacks, decode "
        f"{d_tps} tok/s | mono: {mono['requests']} ok, decode "
        f"{m_tps} tok/s",
        file=sys.stderr, flush=True,
    )
    print(json.dumps(rec), flush=True)
    if tiered["rejected_or_failed"] or mono["rejected_or_failed"]:
        raise SystemExit(
            f"disagg gate FAILED: {tiered['rejected_or_failed']} tiered "
            f"+ {mono['rejected_or_failed']} monolithic requests "
            "errored — a handoff failure must degrade to a fallback, "
            "never an error"
        )


def main() -> None:
    args = build_parser().parse_args()
    from nanodiloco_tpu.utils import (
        enable_compile_cache,
        force_virtual_cpu_devices,
        require_accelerator,
    )

    if args.force_cpu_devices:
        force_virtual_cpu_devices(args.force_cpu_devices)
    enable_compile_cache()
    # a measurement entry point: no accelerator and no CPU asked for by
    # name is an error, not a CPU number under a device metric's name
    require_accelerator("serve_bench.py")
    import jax

    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    if args.checkpoint_dir:
        from nanodiloco_tpu.cli import _load_checkpoint_snapshot

        cfg, _sidecar, params = _load_checkpoint_snapshot(
            args.checkpoint_dir, args.step
        )
    else:
        from nanodiloco_tpu.models import LlamaConfig, init_params

        cfg = LlamaConfig(
            vocab_size=args.vocab, hidden_size=args.hidden,
            intermediate_size=2 * args.hidden,
            num_attention_heads=args.heads, num_hidden_layers=args.layers,
            max_position_embeddings=args.max_len,
        )
        params = init_params(jax.random.key(args.seed), cfg)

    if args.workload == "capacity":
        run_capacity(args, cfg, params, jax)
        return
    if args.workload == "surge":
        run_surge(args, cfg, params, jax)
        return
    if args.workload == "chaos":
        run_chaos(args, cfg, params, jax)
        return
    if args.workload == "disagg":
        run_disagg(args, cfg, params, jax)
        return
    if args.workload == "repetitive":
        if args.spec_k is None:
            args.spec_k = 4
        run_repetitive(args, cfg, params, jax)
        return

    engine = InferenceEngine(
        params, cfg, num_slots=args.slots,
        max_len=min(args.max_len, cfg.max_position_embeddings),
        chunk_size=args.chunk_size,
        prefix_cache_tokens=args.prefix_cache_tokens,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
        kv_pool_blocks=args.kv_pool_blocks,
        spec_k=args.spec_k or 0,
        spec_ngram=args.spec_ngram,
        tp=args.tp,
    )
    engine.warm_spec()  # no-op unless --spec-k was passed
    server = ServeServer(
        Scheduler(engine, max_queue=args.max_queue),
        port=0, host="127.0.0.1", max_new_tokens_cap=args.max_new_tokens,
    ).start()
    lens = [int(x) for x in args.prompt_lens.split(",") if x]
    rng = __import__("random").Random(args.seed)
    mixed = args.workload == "mixed"
    shared_prefix = (
        [rng.randrange(cfg.vocab_size) for _ in range(args.shared_prefix_len)]
        if mixed else []
    )

    def post(doc: dict) -> tuple[int, dict]:
        return http_post_json(
            f"http://127.0.0.1:{server.port}/v1/generate", doc
        )

    # warmup: compile the decode tick + every prefill chunk bucket the
    # run will touch, outside the timed window. Chunked prefill bounds
    # the bucket set, but a failed warmup would still silently move
    # compilation INTO the timed window and corrupt the TTFT
    # percentiles, so it is a hard error. Warmup prompts are unique
    # random content: the shared prefix stays COLD until the window.
    warm_lens = set(len(shared_prefix) + p for p in lens) | set(lens)
    if mixed:
        warm_lens.add(args.long_prompt_len)
    warm_new = min(2, args.max_new_tokens)
    for n, p_len in enumerate(sorted(warm_lens)):
        code, out = post({
            "token_ids": [(i * 7 + 3) % cfg.vocab_size for i in range(p_len)],
            "max_new_tokens": warm_new, "temperature": args.temperature,
            "top_k": args.top_k, "seed": 10_000 + n, "stop": False,
            "prefix_cache": False,
        })
        if code != 200:
            server.stop()
            raise SystemExit(
                f"warmup request (prompt_len={p_len}) failed with "
                f"{code}: {out.get('error')} — fix --prompt-lens/"
                f"--max-new-tokens/--max-len before benchmarking"
            )

    results: list[dict] = []
    errors: list[tuple[int, dict]] = []
    lock = threading.Lock()

    def run_request(doc: dict, cls: str) -> None:
        code, out = post(doc)
        with lock:
            if code == 200:
                out["_class"] = cls
                results.append(out)
            else:
                errors.append((code, out))

    t_start = time.monotonic()

    def short_client(cid: int) -> None:
        workers = []
        for r in range(args.requests_per_client):
            tail_len = lens[(cid + r) % len(lens)]
            tail = [rng.randrange(cfg.vocab_size) for _ in range(tail_len)]
            doc = {
                "token_ids": shared_prefix + tail,
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": cid * 1000 + r, "stop": False,
            }
            if mixed:
                # open-loop: fire on the global arrival schedule (client
                # arrivals interleaved) whether or not earlier requests
                # answered — each in-flight request gets its own thread
                due = t_start + (cid + r * args.clients) * args.short_interval_s
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                w = threading.Thread(target=run_request, args=(doc, "short"))
                w.start()
                workers.append(w)
            else:
                run_request(doc, "short")
        for w in workers:
            w.join()

    def long_client(cid: int) -> None:
        for r in range(args.requests_per_client):
            ids = [rng.randrange(cfg.vocab_size)
                   for _ in range(args.long_prompt_len)]
            run_request({
                "token_ids": ids, "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": 500_000 + cid * 1000 + r, "stop": False,
                # unique content: caching it would only churn the shared
                # prefix out — the per-request opt-out exists for this
                "prefix_cache": False,
            }, "long")

    threads = [threading.Thread(target=short_client, args=(c,))
               for c in range(args.clients)]
    if mixed:
        threads += [threading.Thread(target=long_client, args=(c,))
                    for c in range(args.long_clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.monotonic() - t0

    stats = server._scheduler.stats()
    server.stop()

    def ttfts(cls=None):
        return sorted(
            r["timing"]["ttft_s"] for r in results
            if cls is None or r["_class"] == cls
        )

    all_ttft = ttfts()
    completion = sum(r["completion_tokens"] for r in results)
    rec = {
        "metric": "BENCH_SERVE",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "model": (
            args.checkpoint_dir
            or f"random-init llama (hidden {cfg.hidden_size} x "
               f"{cfg.num_hidden_layers}L, vocab {cfg.vocab_size})"
        ),
        "workload": args.workload,
        "tp_degree": args.tp,
        "slots": args.slots,
        "chunk_size": engine.chunk_size,
        "kv_block_size": engine.kv_block_size,
        "kv_dtype": args.kv_dtype,
        "prefix_cache_tokens": args.prefix_cache_tokens,
        "clients": args.clients,
        "requests": len(results),
        "rejected_or_failed": len(errors),
        "prompt_lens": lens,
        "max_new_tokens": args.max_new_tokens,
        "wall_s": round(wall_s, 3),
        "requests_per_sec": round(len(results) / wall_s, 3) if wall_s else None,
        "ttft_p50_s": round(_pct(all_ttft, 0.50), 4) if all_ttft else None,
        "ttft_p95_s": round(_pct(all_ttft, 0.95), 4) if all_ttft else None,
        "completion_tokens": completion,
        "client_tokens_per_sec": (
            round(completion / wall_s, 1) if wall_s else None
        ),
        "decode_tokens_per_sec": (
            round(stats["decode_tokens_per_sec"], 1)
            if stats["decode_tokens_per_sec"] else None
        ),
        "prefill_chunks": stats.get("prefill_chunks_total"),
    }
    if mixed:
        short, long_ = ttfts("short"), ttfts("long")
        rec.update({
            "long_clients": args.long_clients,
            "long_prompt_len": args.long_prompt_len,
            "shared_prefix_len": args.shared_prefix_len,
            "short_interval_s": args.short_interval_s,
            "short_requests": len(short),
            "short_ttft_p50_s": (
                round(_pct(short, 0.50), 4) if short else None
            ),
            "short_ttft_p95_s": (
                round(_pct(short, 0.95), 4) if short else None
            ),
            "long_ttft_p50_s": (
                round(_pct(long_, 0.50), 4) if long_ else None
            ),
        })
    pc = stats.get("prefix_cache")
    if pc:
        rec.update({
            "prefix_hits": pc["hits"],
            "prefix_misses": pc["misses"],
            "prefix_hit_tokens": pc["hit_tokens"],
        })
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
